"""Timing + characterization primitives — the CUDA Event API analogue.

The paper replaces Rodinia's system-time measurement with CUDA events for
accurate kernel timing. JAX dispatch is asynchronous, so this module
offers **two timing modes** over a monotonic clock:

- **sync mode** (``time_fn`` with ``window=1``, the default): warm up,
  then ``jax.block_until_ready`` around every measured call. Each sample
  is one full host round trip — dispatch, device execution, and the
  host's completion wakeup — which is the comparable, conservative number
  every prior record carries (``us_per_call``). For small level-0/1
  kernels it measures host dispatch latency as much as kernel time:
  exactly the async-runtime pitfall the K80→A100 lineage study warns
  about.
- **windowed mode** (``time_fn`` with ``window=K``): dispatch a window of
  K calls back to back, riding JAX's async dispatch, and synchronize
  *once per window* on **all** K outputs (blocking only on the last
  output could under-measure if the runtime completes computations out
  of order). Host dispatch of call *i+1* overlaps device execution of
  call *i*, so the per-call quotient (``us_per_call_windowed``)
  approaches true device throughput; ``sync − windowed`` is the measured
  per-call dispatch + sync overhead the sync mode folds into its number.

Both modes assume device-resident inputs: ``commit_args`` pre-commits
host-side arguments (numpy arrays, python scalars) with ``device_put``
*once, before the loop*, so per-call H2D transfer never pollutes either
number. Host-transfer benchmarks (``no_jit`` meta) opt out — staging cost
is what they measure.

Layering (post staged-engine refactor): this module holds the *primitives*
— ``time_fn`` for an already-compiled callable, ``characterize_compiled``
for the static analysis of a compiled executable, and small constructors
for the result dataclasses. The staged path that compiles each workload
exactly once (or restores it from the disk executable cache without any
compilation) and feeds the same executable to the timer, the roofline
characterization, and the serve stage lives in ``core/engine.py``;
``time_workload`` / ``compile_workload`` remain as standalone one-shot
conveniences (each compiles on its own — use the engine for suite runs).
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Any, Callable, Sequence

import jax

from repro.core.metrics import (
    RooflineTerms,
    collective_bytes_from_hlo,
    cost_analysis_dict,
    roofline_terms,
)
from repro.core.registry import Workload

__all__ = [
    "TimingResult",
    "CompiledInfo",
    "commit_args",
    "time_workload",
    "compile_workload",
    "time_fn",
    "timing_from_stats",
    "characterize_compiled",
    "empty_compiled_info",
]


@dataclasses.dataclass(frozen=True)
class TimingResult:
    name: str
    us_per_call: float
    us_stdev: float
    iters: int
    achieved_gflops: float  # from the workload's analytic FLOP count
    achieved_gbps: float  # from the workload's analytic byte count
    # Windowed-mode companion numbers (None when only sync mode ran):
    # per-call time with K calls in flight per sync, the window size K,
    # and the derived per-call dispatch+sync overhead (sync − windowed,
    # clamped at 0 — noise can put windowed above sync).
    us_per_call_windowed: float | None = None
    timing_window: int | None = None
    timer_dispatch_us: float | None = None

    def csv(self) -> str:
        return (
            f"{self.name},{self.us_per_call:.2f},"
            f"gflops={self.achieved_gflops:.2f};gbps={self.achieved_gbps:.2f}"
        )


@dataclasses.dataclass(frozen=True)
class CompiledInfo:
    name: str
    cost: dict[str, float]
    memory: dict[str, float]
    roofline: RooflineTerms
    hlo_collectives_bytes: float


def commit_args(args: Sequence[Any]) -> tuple:
    """Pre-commit host-side argument leaves to the device, once.

    Leaves that are already ``jax.Array`` (including placed/sharded
    arrays) pass through untouched; numpy arrays and python scalars are
    ``device_put`` and blocked on, so a timing loop over the result never
    pays per-call H2D transfer. Abstract leaves (``jax.ShapeDtypeStruct``
    in dry-run flows) also pass through unchanged.
    """

    def commit(leaf: Any) -> Any:
        if isinstance(leaf, (jax.Array, jax.ShapeDtypeStruct)):
            return leaf
        return jax.block_until_ready(jax.device_put(leaf))

    return tuple(jax.tree_util.tree_map(commit, tuple(args)))


def time_fn(
    fn: Callable[..., Any],
    args: Sequence[Any],
    *,
    iters: int = 10,
    warmup: int = 3,
    window: int = 1,
) -> tuple[float, float]:
    """Return (mean_us, stdev_us) per call for an already-compiled callable.

    ``window=1`` is sync mode: synchronize after every call. ``window=K``
    is windowed mode: each of ``iters`` samples dispatches K calls and
    synchronizes once on all K outputs; the sample is the per-call
    quotient. Callers wanting device-resident inputs should pass args
    through :func:`commit_args` first (the engine and one-shot paths do).
    """
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(iters):
        if window == 1:
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            samples.append((time.perf_counter() - t0) * 1e6)
        else:
            t0 = time.perf_counter()
            outs = [fn(*args) for _ in range(window)]
            jax.block_until_ready(outs)
            samples.append((time.perf_counter() - t0) * 1e6 / window)
    mean = statistics.fmean(samples)
    stdev = statistics.stdev(samples) if len(samples) > 1 else 0.0
    return mean, stdev


def timing_from_stats(
    workload: Workload,
    *,
    mean_us: float,
    stdev_us: float,
    iters: int,
    backward: bool = False,
    windowed_us: float | None = None,
    window: int | None = None,
) -> TimingResult:
    """Fold measured wall time with the workload's analytic FLOP/byte counts.

    ``windowed_us`` / ``window`` attach the windowed-mode companion number
    when both modes ran; the derived dispatch overhead is computed here so
    every consumer sees the same clamping convention.
    """
    flops = workload.flops_bwd if backward else workload.flops
    sec = mean_us / 1e6
    return TimingResult(
        name=workload.name + (".bwd" if backward else ""),
        us_per_call=mean_us,
        us_stdev=stdev_us,
        iters=iters,
        achieved_gflops=(flops / sec / 1e9) if (flops and sec > 0) else 0.0,
        achieved_gbps=(workload.bytes_moved / sec / 1e9)
        if (workload.bytes_moved and sec > 0)
        else 0.0,
        us_per_call_windowed=windowed_us,
        timing_window=window if windowed_us is not None else None,
        timer_dispatch_us=(
            max(mean_us - windowed_us, 0.0) if windowed_us is not None else None
        ),
    )


def time_workload(
    workload: Workload,
    *,
    iters: int = 10,
    warmup: int = 3,
    seed: int = 0,
    backward: bool = False,
    window: int = 1,
) -> TimingResult:
    """Compile + validate + time one workload (forward or backward pass).

    Inputs are pre-committed to the device (``commit_args``) before the
    timing loop so standalone timings, like engine runs, never include
    per-call host transfer — except for ``no_jit`` host-transfer
    workloads, whose staging path is the measurement. ``window=K`` adds a
    windowed measurement alongside the sync one.
    """
    args = workload.make_inputs(seed)
    fn = workload.fn_bwd if backward else workload.fn
    if backward and fn is None:
        raise ValueError(f"workload {workload.name!r} has no backward pass")
    no_jit = bool(workload.meta.get("no_jit"))
    # Host-transfer benchmarks (BusSpeed*) measure the un-jitted staging path.
    jitted = fn if no_jit else jax.jit(fn)
    if not no_jit:
        args = commit_args(args)
    out = jax.block_until_ready(jitted(*args))
    if not backward and workload.validate is not None:
        workload.validate(out, args)
    mean, stdev = time_fn(jitted, args, iters=iters, warmup=warmup)
    windowed_us = None
    if window > 1 and not no_jit:
        windowed_us, _ = time_fn(jitted, args, iters=iters, warmup=0, window=window)
    return timing_from_stats(
        workload, mean_us=mean, stdev_us=stdev, iters=iters, backward=backward,
        windowed_us=windowed_us, window=window,
    )


def _memory_analysis_dict(compiled: Any) -> dict[str, float]:
    ma = compiled.memory_analysis()
    out: dict[str, float] = {}
    for key in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "alias_size_in_bytes",
        "host_argument_size_in_bytes",
        "host_output_size_in_bytes",
        "host_temp_size_in_bytes",
    ):
        if hasattr(ma, key):
            out[key] = float(getattr(ma, key))
    return out


def characterize_compiled(compiled: Any, name: str) -> CompiledInfo:
    """Static cost/memory/roofline analysis of a compiled executable."""
    cost = cost_analysis_dict(compiled)
    coll = collective_bytes_from_hlo(compiled.as_text())
    return CompiledInfo(
        name=name,
        cost=cost,
        memory=_memory_analysis_dict(compiled),
        roofline=roofline_terms(cost, collective_bytes=coll),
        hlo_collectives_bytes=coll,
    )


def empty_compiled_info(name: str) -> CompiledInfo:
    """Placeholder for workloads with no device program (``no_jit`` meta)."""
    return CompiledInfo(
        name=name,
        cost={},
        memory={},
        roofline=roofline_terms({}, collective_bytes=0.0),
        hlo_collectives_bytes=0.0,
    )


def compile_workload(
    workload: Workload,
    *,
    seed: int = 0,
    backward: bool = False,
    abstract_args: Sequence[Any] | None = None,
) -> CompiledInfo:
    """Lower + compile, returning static cost/memory/roofline analysis.

    ``abstract_args`` lets callers pass ShapeDtypeStructs (dry-run path: no
    allocation); otherwise concrete inputs are built from ``seed`` and
    pre-committed to the device (``commit_args`` passes abstract leaves
    through untouched).
    """
    args = abstract_args if abstract_args is not None else workload.make_inputs(seed)
    fn = workload.fn_bwd if backward else workload.fn
    if backward and fn is None:
        raise ValueError(f"workload {workload.name!r} has no backward pass")
    name = workload.name + (".bwd" if backward else "")
    if workload.meta.get("no_jit"):
        # Host-transfer workloads have no device program to analyse.
        return empty_compiled_info(name)
    compiled = jax.jit(fn).lower(*commit_args(args)).compile()
    return characterize_compiled(compiled, name)
