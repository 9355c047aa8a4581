"""Staged execution engine: build → place → [tune] → compile → measure →
characterize → report.

The imperative half of the plan/engine split (``core/plan.py`` holds the
declarative half). For every selected benchmark the engine runs the stages:

- **build**: instantiate the workload from the spec at the plan's preset
  (plus Rodinia-style overrides) and materialize its inputs.
- **place**: realize the plan's :class:`~repro.core.plan.Placement` on a
  data mesh (``runtime/sharding``): ``replicate`` device_puts every input
  on all devices; ``shard`` partitions inputs along the workload's
  declared ``batch_dims`` (non-batchable workloads fall back to replicate
  and the record says so). Single-device runs pre-commit host-side inputs
  with ``harness.commit_args`` — one ``device_put`` before any loop, so
  neither the timer nor the serve stage ever pays per-call H2D transfer
  (``no_jit`` host-transfer workloads opt out: staging *is* their
  measurement).
- **tune** (only for ``impl="pallas"`` plans with ``tune=True``): sweep
  the declared kernel's ``tune_space()`` block/grid candidates, compiling
  each through the same cache and timing it with the windowed timer; the
  winner's params join the compile-cache key and persist in the HLO disk
  cache next to the executable, so a warm ``--tune`` run restores the
  winner and performs **zero trials and zero compiles**.
- **compile**: lower + compile through an in-process cache keyed on
  ``(name, preset, overrides, backward, backend, devices, placement,
  impl, tuned-params)`` so each workload is compiled **exactly once per
  (pass, placement, implementation)** — the sharded and replicated (and
  xla and pallas) lowerings are distinct executables, and the same
  executable feeds both the timer and the static analysis. The plan's
  ``impl`` axis resolves per workload (a pallas plan falls back to xla
  for workloads with no declared ``pallas_kernel``, recorded in
  ``impl_fallback``) and is realized by tracing under
  ``kernels.ops.force_impl`` — the kernel-vs-oracle choice is baked into
  the lowering, not dispatched per call.
- **measure**: validate the first output, then time the compiled
  executable (``harness.time_fn``) in sync mode (``us_per_call``, the
  comparable number) and — when ``plan.timing_window > 1`` — in windowed
  mode (``us_per_call_windowed``: K calls in flight per synchronization,
  riding async dispatch; the difference is the derived per-call dispatch
  overhead).
- **characterize**: static cost/memory/roofline analysis of the cached
  executable, computed once and memoized alongside it.
- **serve** (only when the plan carries a
  :class:`~repro.core.plan.ServeSpec`): run the *same cached executable*
  under generated load through ``repro.serve`` — open-loop arrivals at a
  target QPS or closed-loop at fixed concurrency, dispatched across N
  lanes by the spec's client (``single``: every lane issued from this
  thread; ``threaded``: one issuing thread per lane with per-lane
  deterministic sub-schedules and dispatch-overhead accounting) — and
  fold latency percentiles / achieved QPS / truncation honesty into the
  record.
  With ``colocate``, the workload is additionally served against a
  partner benchmark on split lanes and both rows carry their p50
  slowdown vs the isolated baseline. With a ``ServeSpec.mix`` of
  weighted :class:`~repro.core.plan.ShapeBucket`\\ s, arrivals are
  stamped with seeded bucket labels (or replayed from a saved JSONL
  trace) and the stage precompiles one vmapped executable per
  (bucket, batch-width) through the ordinary compile cache *and* the
  disk cache — warm runs restore every bucket with zero XLA compiles —
  then routes per bucket (``loop``/``lanes``/``batched``) or coalesces
  compatible requests under a latency budget (``dynamic``,
  ``serve/batcher.py``), recording occupancy / padding waste /
  per-bucket percentiles. Outside a mix, serving never compiles
  anything the measure stage didn't already put in the cache (the
  partner's own entry aside), and a sharded plan serves the sharded
  lowering.
- **report**: a :class:`BenchmarkRecord` carrying ``devices`` /
  ``placement`` / ``scaling_efficiency`` (plus the serve columns above),
  streamed to the JSONL writer as it is produced.

``run()`` iterates ``plan.device_sweep`` (ascending), re-running the
selection at each device count against the shared cache; multi-device rows
carry ``scaling_efficiency`` — speedup over the same run's 1-device row,
divided by the device count.

Failures are isolated per benchmark: an exception in any stage yields an
``status="error"`` record naming the stage and the suite keeps going.

Adding a stage = add an ``_stage_name`` method, call it in ``_run_pass``
between its neighbours, and extend the record (see ROADMAP.md §Execution
engine).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Any, Callable

import jax

from repro.core.harness import (
    CompiledInfo,
    characterize_compiled,
    commit_args,
    empty_compiled_info,
    time_fn,
    timing_from_stats,
)
from repro.core.hlocache import HloDiskCache
from repro.core.plan import ExecutionPlan, Placement, PlanError, ServeSpec
from repro.core.registry import BenchmarkSpec, Workload, get_benchmark
from repro.core.results import (
    BenchmarkRecord,
    JsonlReportWriter,
    RunMetadata,
    write_report,
)
from repro.obs import NULL_TRACER, NullTracer, Tracer, use_tracer

__all__ = [
    "CompileCache", "Engine", "RunResult", "SweepStat", "enable_compile_cache",
]

# (name, preset, frozen-overrides, backward, backend, devices, placement,
#  impl, frozen-tuned-params). Mixed-shape serving appends ("vmap", width)
# for batch widths > 1 — a bucket's width-1 program at the plan's own
# preset/overrides shares the measure stage's key (and its executable).
CacheKey = tuple[str, int, tuple, bool, str, int, str, str, tuple]


@dataclasses.dataclass
class _CacheEntry:
    executable: Callable[..., Any]
    info: CompiledInfo | None = None  # memoized by the characterize stage


class CompileCache:
    """In-process compiled-executable cache with hit/miss counters."""

    def __init__(self) -> None:
        self._entries: dict[CacheKey, _CacheEntry] = {}
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    def peek(self, key: CacheKey) -> _CacheEntry | None:
        """Lookup without counting a hit (callers count on actual use)."""
        return self._entries.get(key)

    def lookup(self, key: CacheKey, build: Callable[[], _CacheEntry]) -> _CacheEntry:
        entry = self._entries.get(key)
        if entry is not None:
            self.hits += 1
            return entry
        # Count the miss only after a successful build so a failing compile
        # retried later is not double-counted as two compilations.
        entry = build()
        self.misses += 1
        self._entries[key] = entry
        return entry

    def clear(self) -> None:
        self._entries.clear()


@dataclasses.dataclass(frozen=True)
class SweepStat:
    """Cache traffic of one device-sweep step (scaling-run diagnostics)."""

    devices: int
    misses: int
    hits: int


@dataclasses.dataclass
class RunResult:
    records: list[BenchmarkRecord]
    metadata: RunMetadata
    cache: CompileCache
    sweep_stats: list[SweepStat] = dataclasses.field(default_factory=list)

    @property
    def ok_records(self) -> list[BenchmarkRecord]:
        return [r for r in self.records if r.status == "ok"]

    @property
    def error_records(self) -> list[BenchmarkRecord]:
        return [r for r in self.records if r.status != "ok"]


class Engine:
    """Executes plans. Holds the compile cache, so a long-lived engine

    (e.g. the module-level one behind ``run_suite``) reuses executables
    across runs, sections, and figure drivers within one process.
    """

    def __init__(
        self,
        cache: CompileCache | None = None,
        cache_dir: str | None = None,
        tracer: Tracer | NullTracer | None = None,
    ) -> None:
        self.cache = cache if cache is not None else CompileCache()
        # Optional cross-process persistence of serialized executables —
        # warm entries skip retracing and XLA compilation. None =
        # in-process only. JAX's own persistent compilation cache is
        # placed by the entry points (enable_compile_cache), never here.
        self.disk_cache = HloDiskCache(cache_dir) if cache_dir else None
        # Structured tracing (repro.obs): every _stage_* becomes a span,
        # serve completions and batch executions become retrospective
        # events, and counter totals land in the final RunMetadata.
        # Default NULL_TRACER: falsy, no-op spans, swallowed counters —
        # the disabled cost at a guarded call site is one attribute read.
        self.tracer = tracer if tracer is not None else NULL_TRACER

    # -- stages ------------------------------------------------------------

    def _resolve_impl(
        self, workload: Workload, plan: ExecutionPlan, backward: bool
    ) -> tuple[str, str | None]:
        """The *effective* implementation for one (workload, pass):
        ``(impl, fallback_reason)``. A pallas plan degrades to xla — with
        the reason recorded, never silently — for workloads that declare
        no Pallas variant, for host-transfer (no_jit) workloads, and for
        backward passes (the hand-written kernels are forward programs;
        differentiating through ``pallas_call`` is not the measured path).
        """
        if plan.impl != "pallas":
            return "xla", None
        if workload.meta.get("no_jit"):
            return "xla", "no_jit"
        if workload.pallas_kernel is None:
            return "xla", "no_pallas_variant"
        from repro.kernels import ops as kernel_ops

        if workload.pallas_kernel not in kernel_ops.PALLAS_OPS:
            raise ValueError(
                f"workload {workload.name!r} declares pallas_kernel="
                f"{workload.pallas_kernel!r}, not a known op: "
                f"{sorted(kernel_ops.PALLAS_OPS)}"
            )
        if backward:
            return "xla", "backward_pass"
        return "pallas", None

    def _stage_build(
        self, spec: BenchmarkSpec, plan: ExecutionPlan, preset: int
    ) -> tuple[Workload, tuple]:
        workload = spec.build_preset(preset, **plan.overrides_for(spec.name))
        return workload, workload.make_inputs(plan.seed)

    def _resolve_placement(
        self, workload: Workload, args: tuple, requested: Placement
    ) -> Placement:
        """The *effective* placement, from shapes alone (no transfers):
        shard requests degrade to replicate for workloads that opt out of
        ``batch_dims`` (or whose dims don't divide), and no_jit host-
        transfer workloads always run — and are recorded — on one device."""
        if workload.meta.get("no_jit"):
            return Placement(devices=1, mode="replicate")
        if requested.devices == 1:
            return Placement(devices=1, mode="replicate")
        if requested.mode == "shard":
            from repro.runtime.sharding import shard_applies

            if shard_applies(args, workload, requested.devices):
                return requested
        return Placement(devices=requested.devices, mode="replicate")

    def _stage_place(
        self, workload: Workload, args: tuple, requested: Placement
    ) -> tuple[tuple, Placement]:
        """Put inputs where the placement says; the effective placement
        joins the compile-cache key. Single-device placement means
        committing host-side inputs once (numpy arrays from make_inputs
        would otherwise pay H2D on *every* timed and served call)."""
        placement = self._resolve_placement(workload, args, requested)
        if placement.devices == 1:
            if not workload.meta.get("no_jit"):
                args = commit_args(args)
            return args, placement
        from repro.runtime.sharding import data_mesh, place_args

        mesh = data_mesh(placement.devices)
        placed, mode = place_args(args, workload, mesh, placement.mode)
        assert mode == placement.mode, (mode, placement)
        return placed, placement

    def _impl_context(
        self, workload: Workload, impl: str, tuned_params: dict | None
    ):
        """The forced-dispatch context tracing must run under.

        Workloads that declare a ``pallas_kernel`` are *pinned* both ways:
        ``impl="pallas"`` forces the kernel (with the tuned block params
        merged in), ``impl="xla"`` forces the jnp reference — so an xla
        row on a TPU host is really the lax lowering, not ``mode="auto"``
        silently picking the kernel. Undeclared workloads trace untouched.
        """
        if workload.pallas_kernel is None:
            return contextlib.nullcontext()
        from repro.kernels import ops as kernel_ops

        mode = "pallas" if impl == "pallas" else "ref"
        return kernel_ops.force_impl(
            mode, workload.pallas_kernel, **(tuned_params or {})
        )

    def _stage_compile(
        self,
        spec: BenchmarkSpec,
        workload: Workload,
        args: tuple,
        plan: ExecutionPlan,
        preset: int,
        backward: bool,
        placement: Placement,
        impl: str = "xla",
        tuned_params: dict | None = None,
    ) -> _CacheEntry:
        fn = workload.fn_bwd if backward else workload.fn
        if backward and fn is None:
            raise ValueError(f"workload {workload.name!r} has no backward pass")
        key = self._bucket_key(
            spec, preset, plan.overrides_for(spec.name), placement, impl,
            tuned_params, 1, backward=backward,
        )

        def build() -> _CacheEntry:
            if workload.meta.get("no_jit"):
                # Host-transfer workloads time the un-jitted staging path and
                # have no device program to analyse.
                return _CacheEntry(
                    executable=fn,
                    info=empty_compiled_info(_pass_name(workload, backward)),
                )
            # Disk cache: a warm entry skips the retrace and the XLA
            # compile; a cold or failed one falls through.
            return self._compile_through_caches(
                key, workload, fn, args,
                pass_name=_pass_name(workload, backward),
                impl=impl,
                tuned_params=tuned_params,
                use_disk=self.disk_cache is not None,
            )

        return self.cache.lookup(key, build)

    def _compile_through_caches(
        self,
        key: CacheKey,
        workload: Workload,
        fn: Callable[..., Any],
        args: tuple,
        *,
        pass_name: str,
        impl: str,
        tuned_params: dict | None,
        use_disk: bool,
    ) -> _CacheEntry:
        """Lower + compile one program through the disk cache: a warm
        entry skips the retrace and the XLA compile. Shared by the
        measure-path compile stage and the mixed-shape serve stage's
        per-(bucket, width) executables, so every bucket persists and
        restores exactly like a measure executable."""
        if use_disk:
            loaded = self.disk_cache.load(key, args)
            if loaded is not None:
                executable, info = loaded
                return _CacheEntry(executable=executable, info=info)
        # The impl choice is a trace-time decision: force_impl is
        # consulted by the kernel ops as fn traces, so the selected
        # implementation (and its tuned blocks) is baked into this
        # lowering — execution later needs no context.
        with self._impl_context(workload, impl, tuned_params):
            lowered = jax.jit(fn).lower(*args)
        compiled = lowered.compile()
        if use_disk:
            self.disk_cache.store(key, compiled, pass_name)
        return _CacheEntry(executable=compiled)

    def _stage_tune(
        self,
        spec: BenchmarkSpec,
        workload: Workload,
        args: tuple,
        plan: ExecutionPlan,
        preset: int,
        backward: bool,
        placement: Placement,
        impl: str,
    ) -> tuple[dict | None, int | None, float | None]:
        """Sweep the kernel's ``tune_space()`` -> (winner, trials, wall µs).

        Runs between place and compile, only for effective-pallas passes of
        tuning plans; every other pass returns ``(None, None, None)`` and
        costs nothing. Candidates compile through the ordinary cache under
        their full key — the winner's later compile stage is a guaranteed
        hit — and are timed with the windowed timer (small kernels are
        dispatch-bound; sync-mode timing would tune the host, not the
        block shape). Ties keep the earliest candidate, so a fixed seed
        and a deterministic timer give a deterministic winner. The winner
        persists in the disk cache under the *base* key (params excluded —
        the lookup must not need the answer), making a warm run's sweep
        zero trials: restored, not re-timed.
        """
        if impl != "pallas" or not plan.tune:
            return None, None, None
        from repro.kernels import ops as kernel_ops

        space = kernel_ops.tune_space(workload.pallas_kernel)
        if not space:
            space = ({},)
        if len(space) == 1:
            # Nothing to sweep (kernels without block params): the single
            # candidate wins by default, at zero trials.
            return dict(space[0]), 0, 0.0
        base_key = self._bucket_key(
            spec, preset, plan.overrides_for(spec.name), placement, impl,
            None, 1, backward=backward,
        )
        use_disk = self.disk_cache is not None and placement.devices == 1
        if use_disk:
            won = self.disk_cache.load_tuned(base_key)
            if won is not None:
                return won, 0, 0.0
        best_us: float | None = None
        best: dict = {}
        trials = 0
        # tune_trials_us is the *sum of the per-candidate trial spans* —
        # each trial's wall time is measured once (c0/c1 below), added to
        # the total, and emitted as a trace event from the same pair, so
        # the record's number and the trace can never disagree.
        trials_us = 0.0
        tracer = self.tracer
        for cand in space:
            c0 = time.perf_counter()
            entry = self._stage_compile(
                spec, workload, args, plan, preset, backward, placement,
                impl, dict(cand),
            )
            mean_us = self._time_tune_trial(entry, args, plan)
            c1 = time.perf_counter()
            trials_us += (c1 - c0) * 1e6
            trials += 1
            if tracer.enabled:
                tracer.event(
                    "tune.trial", t_start=c0, t_end=c1, track="engine",
                    bench=spec.name, params=dict(cand), mean_us=mean_us,
                )
                tracer.counters.inc("tune.trials")
            if best_us is None or mean_us < best_us:
                best_us, best = mean_us, dict(cand)
        if use_disk:
            self.disk_cache.store_tuned(base_key, best, trials, trials_us)
        return best, trials, trials_us

    def _time_tune_trial(
        self, entry: _CacheEntry, args: tuple, plan: ExecutionPlan
    ) -> float:
        """One candidate's figure of merit (mean µs/call, windowed).
        A seam: tests monkeypatch this to pin the sweep's timing."""
        mean_us, _ = time_fn(
            entry.executable,
            args,
            iters=min(plan.iters, 3),  # a sweep trial, not the measurement
            warmup=1,
            window=plan.timing_window,
        )
        return mean_us

    def _stage_measure(
        self,
        workload: Workload,
        entry: _CacheEntry,
        args: tuple,
        plan: ExecutionPlan,
        backward: bool,
    ):
        out = jax.block_until_ready(entry.executable(*args))
        if not backward and workload.validate is not None:
            workload.validate(out, args)
        mean, stdev = time_fn(
            entry.executable, args, iters=plan.iters, warmup=plan.warmup
        )
        windowed_us = None
        window = plan.timing_window
        if window > 1 and not workload.meta.get("no_jit"):
            # Windowed mode rides async dispatch; the sync loop above
            # already warmed the executable, so no second warmup. no_jit
            # host-transfer workloads run synchronously by construction —
            # a windowed number for them would be the sync number with
            # extra noise, so their windowed columns stay empty.
            windowed_us, _ = time_fn(
                entry.executable, args, iters=plan.iters, warmup=0, window=window
            )
        return timing_from_stats(
            workload, mean_us=mean, stdev_us=stdev, iters=plan.iters,
            backward=backward, windowed_us=windowed_us, window=window,
        )

    def _stage_characterize(
        self, workload: Workload, entry: _CacheEntry, backward: bool
    ) -> CompiledInfo:
        if entry.info is None:
            entry.info = characterize_compiled(
                entry.executable, _pass_name(workload, backward)
            )
        return entry.info

    # -- serving -----------------------------------------------------------

    def _trace_completions(self, completions) -> None:
        """Retrospective per-request trace events, one per completion,
        attributed to its dispatch lane (``serve`` track, one tid per
        lane). Emitted *after* the serving run from timestamps the lanes
        already recorded — the serve hot path is never instrumented."""
        tracer = self.tracer
        if not tracer.enabled:
            return
        for c in completions:
            attrs = {"index": c.index, "warmup": c.warmup}
            if c.bucket is not None:
                attrs["bucket"] = c.bucket
            tracer.event(
                "request", t_start=c.t_submit, t_end=c.t_done,
                track="serve", tid=f"lane {c.lane}", **attrs,
            )
        tracer.counters.inc("serve.requests", len(completions))

    def _trace_batches(self, report) -> None:
        """Retrospective per-batch events from a ``BatchReport``: one
        span per dispatched device program on the ``batcher`` track (one
        tid per bucket queue), plus the flush/expiry/padding counters."""
        tracer = self.tracer
        if not tracer.enabled:
            return
        counters = tracer.counters
        for b in report.batches:
            tracer.event(
                f"batch[{b.width}]", t_start=b.t_dispatch, t_end=b.t_done,
                track="batcher", tid=f"queue {b.bucket}",
                width=b.width, filled=b.filled, cause=b.cause,
            )
            counters.inc("batcher.flushes")
            if b.cause == "expired":
                counters.inc("batcher.budget_expiries")
            counters.inc("batcher.dispatched_slots", b.width)
            counters.inc("batcher.padded_slots", b.width - b.filled)

    def _serve_call(self, call, serve: ServeSpec, seed: int):
        """One isolated serving run of an already-compiled callable.

        Selects the host issue architecture the spec asked for: the
        ``single`` client dispatches every lane from this thread; the
        ``threaded`` client gives each lane its own issuing thread fed
        from a per-lane deterministic sub-schedule, and its per-request
        dispatch overhead lands in the stats. Open-loop stats carry the
        schedule's ``truncated`` flag so a request-capped run never
        reports the full target as its offered load.
        """
        from repro.serve.client import (
            run_closed_loop_threaded,
            run_open_loop_threaded,
        )
        from repro.serve.lanes import run_closed_loop, run_open_loop
        from repro.serve.latency import stats_from_completions
        from repro.serve.loadgen import open_loop_lane_schedules, open_loop_schedule

        # Fill the whole pipeline (every in-flight slot, not just one per
        # lane) before measuring, like time_fn's warmup: early requests
        # submitted into an empty window see less queueing than steady
        # state and would bias the percentiles low.
        warmup = max(serve.concurrency, serve.lanes, 2)
        if serve.mode == "open":
            if serve.client == "threaded":
                lane_schedules = open_loop_lane_schedules(
                    qps=serve.qps,
                    duration_s=serve.duration_s,
                    n_lanes=serve.lanes,
                    seed=seed,
                    warmup=warmup,
                )
                result = run_open_loop_threaded(
                    call, lane_schedules, concurrency=serve.concurrency
                )
                self._trace_completions(result.completions)
                return stats_from_completions(
                    result.completions,
                    offered_qps=serve.qps,
                    slo_us=serve.slo_us,
                    truncated=any(s.truncated for s in lane_schedules),
                    dispatch_overhead_us=result.dispatch_overhead_us,
                    n_lanes=serve.lanes,
                )
            schedule = open_loop_schedule(
                qps=serve.qps,
                duration_s=serve.duration_s,
                seed=seed,
                warmup=warmup,
            )
            completions = run_open_loop(
                call, schedule, n_lanes=serve.lanes, concurrency=serve.concurrency
            )
            self._trace_completions(completions)
            return stats_from_completions(
                completions,
                offered_qps=serve.qps,
                slo_us=serve.slo_us,
                truncated=schedule.truncated,
                n_lanes=serve.lanes,
            )
        if serve.client == "threaded":
            result = run_closed_loop_threaded(
                call,
                concurrency=serve.concurrency,
                n_lanes=serve.lanes,
                duration_s=serve.duration_s,
                warmup=warmup,
            )
            self._trace_completions(result.completions)
            return stats_from_completions(
                result.completions,
                slo_us=serve.slo_us,
                dispatch_overhead_us=result.dispatch_overhead_us,
                n_lanes=serve.lanes,
            )
        completions = run_closed_loop(
            call,
            concurrency=serve.concurrency,
            n_lanes=serve.lanes,
            duration_s=serve.duration_s,
            warmup=warmup,
        )
        self._trace_completions(completions)
        return stats_from_completions(
            completions, slo_us=serve.slo_us, n_lanes=serve.lanes
        )

    def _bucket_key(
        self,
        spec: BenchmarkSpec,
        bucket_preset: int,
        merged_overrides: dict,
        placement: Placement,
        impl: str,
        tuned_params: dict | None,
        width: int,
        backward: bool = False,
    ) -> tuple:
        """The compile-cache key, for every program the engine compiles:
        the measure stage's (width 1, either pass) and each (shape bucket,
        batch width) serve executable. Width 1 uses the ordinary key
        shape, so a bucket at the plan's own preset/overrides *shares the
        measure stage's executable*; wider programs append ("vmap", width)."""
        base = (
            spec.name,
            bucket_preset,
            tuple(sorted(merged_overrides.items())),
            backward,
            jax.default_backend(),
            placement.devices,
            placement.mode,
            impl,
            tuple(sorted((tuned_params or {}).items())),
        )
        return base if width == 1 else base + ("vmap", width)

    def _build_bucket_calls(
        self,
        spec: BenchmarkSpec,
        plan: ExecutionPlan,
        preset: int,
        placement: Placement,
        impl: str,
        tuned_params: dict | None,
    ) -> dict[str, dict[int, Callable[[], Any]]]:
        """Precompile one executable per (shape bucket, batch width).

        Every program goes through the in-process CompileCache AND the
        disk executable cache under a bucket-specific key, so a warm run
        restores the whole table with zero XLA compiles. Batch member j
        gets inputs from ``make_inputs(seed + j)`` — a width-w program
        computes w *distinct* requests, stacked on a new leading axis and
        committed to the device once. Each executable is run once here
        (pipeline warmup), so first-execution overhead never lands in a
        served request's latency.
        """
        import numpy as np

        from repro.serve.batcher import bucket_widths

        serve = plan.serve
        widths = bucket_widths(serve.dispatch, serve.max_batch)
        calls: dict[str, dict[int, Callable[[], Any]]] = {}
        for bucket in serve.buckets(preset):
            bp = (
                bucket.preset
                if bucket.preset in spec.presets
                else min(spec.presets)
            )
            merged = {
                **plan.overrides_for(spec.name),
                **dict(bucket.overrides),
            }
            workload = spec.build_preset(bp, **merged)
            if workload.meta.get("no_jit"):
                raise ValueError(
                    f"mixed-shape serving needs a jittable workload; "
                    f"{workload.name!r} is no_jit (host-transfer)"
                )
            instances = [
                workload.make_inputs(plan.seed + j) for j in range(max(widths))
            ]
            per_width: dict[int, Callable[[], Any]] = {}
            for width in widths:
                if width == 1:
                    fn, wargs = workload.fn, instances[0]
                else:
                    fn = jax.vmap(workload.fn)
                    wargs = jax.tree_util.tree_map(
                        lambda *xs: np.stack(xs), *instances[:width]
                    )
                wargs = commit_args(wargs)
                key = self._bucket_key(
                    spec, bp, merged, placement, impl, tuned_params, width
                )
                entry = self.cache.lookup(
                    key,
                    lambda key=key, wl=workload, fn=fn, a=wargs, w=width: (
                        self._compile_through_caches(
                            key, wl, fn, a,
                            pass_name=f"{wl.name}.serve[{w}]",
                            impl=impl,
                            tuned_params=tuned_params,
                            use_disk=self.disk_cache is not None,
                        )
                    ),
                )
                call = lambda e=entry, a=wargs: e.executable(*a)  # noqa: E731
                jax.block_until_ready(call())  # warm: allocs, first dispatch
                per_width[width] = call
            calls[bucket.label] = per_width
        return calls

    def _mixed_schedule(self, serve: ServeSpec, seed: int, bucket_labels):
        """The mixed-shape request stream: load ``serve.trace`` verbatim
        when the file exists (the trace IS the load — qps/mix knobs are
        ignored on replay), else generate seeded Poisson arrivals, sample
        each request's bucket from the mix, and save to ``serve.trace``
        if one was named — so the next run (any dispatch policy) replays
        this exact stream."""
        from repro.serve.loadgen import (
            load_trace,
            open_loop_schedule,
            sample_mix,
            save_trace,
        )

        warmup = max(serve.concurrency, serve.max_batch, serve.lanes, 2)
        if serve.trace is not None and os.path.exists(serve.trace):
            schedule = load_trace(serve.trace)
            unknown = {r.bucket for r in schedule} - set(bucket_labels)
            if unknown:
                raise ValueError(
                    f"trace {serve.trace!r} names buckets {sorted(map(str, unknown))} "
                    f"absent from this run's mix {sorted(bucket_labels)}"
                )
            return schedule
        schedule = open_loop_schedule(
            qps=serve.qps,
            duration_s=serve.duration_s,
            seed=seed,
            warmup=warmup,
        )
        schedule = sample_mix(
            schedule,
            {b.label: b.weight for b in serve.buckets(0)}
            if serve.mix is not None
            else {label: 1.0 for label in bucket_labels},
            seed=seed,
        )
        if serve.trace is not None:
            save_trace(schedule, serve.trace)
        return schedule

    def _serve_mixed(
        self,
        spec: BenchmarkSpec,
        plan: ExecutionPlan,
        preset: int,
        placement: Placement,
        impl: str,
        tuned_params: dict | None,
    ):
        """The continuous-batching serve path: per-bucket executables
        (every (bucket, width) through both compile caches), a mixed-shape
        schedule (generated or replayed from a trace), and the spec's
        dispatch policy from ``repro.serve.batcher``. Stats carry batch
        occupancy, padding waste, and per-bucket latency percentiles."""
        from repro.serve.batcher import (
            serve_dynamic,
            serve_fixed_batched,
            serve_mixed_lanes,
            serve_mixed_loop,
        )
        from repro.serve.latency import stats_from_completions

        serve = plan.serve
        calls = self._build_bucket_calls(
            spec, plan, preset, placement, impl, tuned_params
        )
        schedule = self._mixed_schedule(serve, plan.seed, set(calls))
        if serve.dispatch == "loop":
            report = serve_mixed_loop(calls, schedule)
        elif serve.dispatch == "lanes":
            report = serve_mixed_lanes(
                calls, schedule,
                n_lanes=serve.lanes, concurrency=serve.concurrency,
            )
        elif serve.dispatch == "batched":
            report = serve_fixed_batched(
                calls, schedule,
                batch=serve.max_batch, concurrency=serve.concurrency,
            )
        else:
            report = serve_dynamic(
                calls, schedule,
                budget_s=serve.batch_budget_us / 1e6,
                concurrency=serve.concurrency,
            )
        self._trace_completions(report.completions)
        self._trace_batches(report)
        return stats_from_completions(
            report.completions,
            # A replayed trace's offered load is the trace's, not the
            # spec's qps knob (which replay ignores).
            offered_qps=(
                schedule.offered_qps
                if schedule.offered_qps is not None
                else serve.qps
            ),
            slo_us=serve.slo_us,
            truncated=schedule.truncated,
            n_lanes=serve.lanes if serve.dispatch == "lanes" else 1,
            batch_occupancy=report.occupancy,
            padding_waste=report.padding_waste,
            n_batches=len(report.batches),
        )

    def _stage_serve(
        self,
        spec: BenchmarkSpec,
        entry: _CacheEntry,
        args: tuple,
        plan: ExecutionPlan,
        preset: int,
        placement: Placement,
        impl: str = "xla",
        tuned_params: dict | None = None,
    ) -> tuple[Any, str | None, float | None, list[BenchmarkRecord]]:
        """Serve the measured executable under the plan's ServeSpec.

        Returns ``(stats, colocate, slowdown, partner_records)``. Without
        co-location this reuses the cache entry the measure stage compiled
        — zero new compilations. With ``colocate``, the partner benchmark
        is built/placed/compiled through the same cache and both tenants
        are served isolated then together (``serve.interference``); the
        partner's colocated row is returned for the report. A mixed-shape
        spec (``serve.is_mixed``) routes through ``_serve_mixed`` instead:
        per-bucket vmapped executables and the batcher dispatch policies.
        """
        serve = plan.serve
        if serve.is_mixed:
            stats = self._serve_mixed(
                spec, plan, preset, placement, impl, tuned_params
            )
            return stats, None, None, []
        call = lambda: entry.executable(*args)  # noqa: E731
        if serve.colocate is None:
            return self._serve_call(call, serve, plan.seed), None, None, []

        from repro.serve.interference import measure_colocation

        partner_spec = get_benchmark(serve.colocate)
        p_preset = plan.resolve_preset(partner_spec)
        p_workload, p_args = self._stage_build(partner_spec, plan, p_preset)
        p_args, p_placement = self._stage_place(
            p_workload, p_args, plan.placement_at(placement.devices)
        )
        p_entry = self._stage_compile(
            partner_spec, p_workload, p_args, plan, p_preset, False, p_placement
        )
        p_call = lambda: p_entry.executable(*p_args)  # noqa: E731

        a_name = spec.name
        b_name = serve.colocate if serve.colocate != spec.name else spec.name + "#2"
        result = measure_colocation(
            {a_name: call, b_name: p_call},
            concurrency=serve.concurrency,
            n_lanes=serve.lanes,
            duration_s=serve.duration_s,
            warmup=max(serve.concurrency, serve.lanes, 2),
            slo_us=serve.slo_us,
        )
        partner = BenchmarkRecord.from_serve(
            partner_spec,
            p_preset,
            result.colocated[b_name],
            mode=serve.mode,
            lanes=serve.lanes,
            client=serve.client,
            name=f"{b_name}@{a_name}",
            colocate=a_name,
            slowdown=result.slowdown(b_name),
            devices=p_placement.devices,
            placement=p_placement.mode,
        )
        return (
            result.colocated[a_name],
            b_name,
            result.slowdown(a_name),
            [partner],
        )

    def characterize(
        self,
        spec: BenchmarkSpec,
        plan: ExecutionPlan,
        *,
        backward: bool = False,
        workload: Workload | None = None,
    ) -> CompiledInfo:
        """Compile (through the cache) + characterize, without timing.

        For characterization-only consumers (Table II, dry-run style flows):
        shares executables with full runs of the same plan parameters. A
        warm cache with memoized analysis returns without building the
        workload or its inputs; pass ``workload`` to reuse one already built.

        Uses the plan placement at ``plan.devices`` (not the sweep): the
        cache key needs the effective placement, which for a shard request
        depends on the workload's ``batch_dims`` and input shapes — so a
        shard-mode lookup builds the workload (shapes only, no transfers)
        to resolve the key; inputs are placed on devices only on a miss.
        Likewise the plan's ``impl`` resolves per workload, so a pallas
        lookup also builds the workload first. Characterization always
        analyses the kernel's *default* blocks (``plan.tune`` is a timing
        concern; the static analysis does not sweep).
        """
        preset = plan.resolve_preset(spec)
        requested = plan.placement_at(plan.devices)
        if requested.mode == "replicate" and plan.impl == "xla":
            # Effective placement/impl == requested without building the
            # workload (xla is every workload's fallback).
            cached = self.cache.peek(
                self._bucket_key(
                    spec, preset, plan.overrides_for(spec.name), requested,
                    "xla", None, 1, backward=backward,
                )
            )
            if cached is not None and cached.info is not None:
                self.cache.hits += 1
                return cached.info
        if workload is None:
            workload = spec.build_preset(preset, **plan.overrides_for(spec.name))
        impl, _ = self._resolve_impl(workload, plan, backward)
        args = workload.make_inputs(plan.seed)
        placement = self._resolve_placement(workload, args, requested)
        cached = self.cache.peek(
            self._bucket_key(
                spec, preset, plan.overrides_for(spec.name), placement, impl,
                None, 1, backward=backward,
            )
        )
        if cached is not None and cached.info is not None:
            self.cache.hits += 1
            return cached.info
        # Characterize-only flows still emit stage spans (no-ops under
        # NULL_TRACER) so traced dry runs account for where time went.
        timings: dict[str, float] = {}
        with self._timed_stage("place", timings, bench=spec.name):
            args, placement = self._stage_place(workload, args, requested)
        with self._timed_stage("compile", timings, bench=spec.name):
            entry = self._stage_compile(
                spec, workload, args, plan, preset, backward, placement, impl
            )
        with self._timed_stage("characterize", timings, bench=spec.name):
            return self._stage_characterize(workload, entry, backward)

    def outputs(self, spec: BenchmarkSpec, plan: ExecutionPlan, devices: int):
        """One forward execution of ``spec`` at the plan's preset, placed
        on ``devices`` as the plan's placement says, through the compile
        cache (a sweep that already ran this point compiles nothing).
        Returns the outputs on the host — what a cross-placement agreement
        check compares."""
        preset = plan.resolve_preset(spec)
        timings: dict[str, float] = {}
        with self._timed_stage("build", timings, bench=spec.name):
            workload, args = self._stage_build(spec, plan, preset)
        with self._timed_stage("place", timings, bench=spec.name):
            args, placement = self._stage_place(
                workload, args, plan.placement_at(devices)
            )
        impl, _ = self._resolve_impl(workload, plan, False)
        with self._timed_stage("compile", timings, bench=spec.name):
            entry = self._stage_compile(
                spec, workload, args, plan, preset, False, placement, impl
            )
        return jax.device_get(entry.executable(*args))

    # -- orchestration -----------------------------------------------------

    def run(
        self,
        plan: ExecutionPlan,
        *,
        report_path: str | None = None,
        jsonl_path: str | None = None,
        verbose: bool = False,
    ) -> RunResult:
        specs = plan.select()
        available = jax.device_count()
        want = max(plan.device_sweep)
        if want > available:
            raise PlanError(
                f"plan requests {want} devices but only "
                f"{available} available"
            )
        if plan.serve is not None and plan.serve.colocate is not None:
            try:
                get_benchmark(plan.serve.colocate)
            except KeyError as e:
                raise PlanError(str(e)) from None
        if plan.serve is not None and plan.serve.is_mixed and want > 1:
            raise PlanError(
                "mixed-shape serving (mix/trace/batcher dispatch) is "
                f"single-device; the plan sweeps up to {want} devices"
            )
        metadata = RunMetadata.capture(
            preset=plan.preset,
            devices=plan.devices,
            placement=plan.placement.mode,
            device_sweep=plan.device_sweep,
            serve=plan.serve,
            timing_window=plan.timing_window,
            impl=plan.impl,
            tune=plan.tune,
        )
        writer = JsonlReportWriter(jsonl_path, metadata) if jsonl_path else None
        records: list[BenchmarkRecord] = []
        sweep_stats: list[SweepStat] = []
        # 1-device us_per_call per row name: the scaling baseline. The sweep
        # is sorted ascending, so baselines exist before multi-device rows
        # stream out.
        baseline_us: dict[str, float] = {}

        def emit(rec: BenchmarkRecord) -> None:
            if rec.status == "ok":
                if rec.devices == 1:
                    baseline_us[rec.name] = rec.us_per_call
                elif rec.name in baseline_us and rec.us_per_call > 0:
                    rec.scaling_efficiency = (
                        baseline_us[rec.name] / rec.us_per_call / rec.devices
                    )
            records.append(rec)
            if writer is not None:
                writer.write(rec)
            if verbose:
                print(rec.csv(), flush=True)

        if verbose:
            print(BenchmarkRecord.csv_header(), flush=True)
        try:
            # The engine's tracer becomes the ambient one for the run, so
            # the serve layer (lane workers, batcher) reaches it without
            # a parameter threaded through every client signature.
            with use_tracer(self.tracer):
                for devices in plan.device_sweep:
                    misses0, hits0 = self.cache.misses, self.cache.hits
                    for spec in specs:
                        for rec in self._run_benchmark(spec, plan, devices):
                            emit(rec)
                    sweep_stats.append(
                        SweepStat(
                            devices=devices,
                            misses=self.cache.misses - misses0,
                            hits=self.cache.hits - hits0,
                        )
                    )
        finally:
            metadata = self._final_metadata(metadata)
            if writer is not None:
                writer.write_meta(metadata)
                writer.close()
        if verbose and self.disk_cache is not None:
            # A disk cache that never hits is otherwise invisible: say what
            # it did, and why any warm load fell back to retracing.
            print(f"# {self.disk_cache.summary()}", flush=True)
        if report_path:
            write_report(records, report_path)
        return RunResult(
            records=records,
            metadata=metadata,
            cache=self.cache,
            sweep_stats=sweep_stats,
        )

    def _final_metadata(self, metadata: RunMetadata) -> RunMetadata:
        """End-of-run observability stamped into the (frozen) metadata:
        the disk cache's counter totals whenever a --cache-dir was in
        play — committed reports must show whether the run was warm,
        which `verbose` stdout alone cannot — and the obs counter
        snapshot (cache totals folded in under a ``cache.`` prefix) when
        tracing was on."""
        cache_stats = (
            self.disk_cache.counter_dict()
            if self.disk_cache is not None
            else None
        )
        if self.tracer.enabled and cache_stats:
            for k, v in cache_stats.items():
                # set, not inc: the disk cache accumulates across runs of
                # a long-lived engine; incrementing would double-count.
                self.tracer.counters.set(f"cache.{k}", v)
        counters = (
            self.tracer.counters.snapshot() if self.tracer.enabled else None
        )
        if cache_stats is None and counters is None:
            return metadata
        return dataclasses.replace(
            metadata, cache_stats=cache_stats, counters=counters
        )

    @contextlib.contextmanager
    def _timed_stage(self, name: str, timings: dict, **attrs: Any):
        """One engine stage = one tracer span + one ``stage_timings_us``
        entry, from a single perf_counter pair. The timing lands even
        when the stage raises, so error records still say where the time
        went. The dict entry is always written (tracing on or off):
        per-stage wall time is a record column, not just a trace row."""
        t0 = time.perf_counter()
        try:
            with self.tracer.span(name, **attrs):
                yield
        finally:
            timings[name] = (time.perf_counter() - t0) * 1e6

    def _run_benchmark(
        self, spec: BenchmarkSpec, plan: ExecutionPlan, devices: int
    ) -> list[BenchmarkRecord]:
        preset = plan.resolve_preset(spec)
        requested = plan.placement_at(devices)
        # Build/place run once per benchmark and their timings are copied
        # into every pass's stage_timings_us (the passes share the work).
        base_timings: dict[str, float] = {}
        try:
            with self._timed_stage(
                "build", base_timings, bench=spec.name, devices=devices
            ):
                workload, args = self._stage_build(spec, plan, preset)
        except Exception as e:  # noqa: BLE001 — fault isolation is the contract
            rec = BenchmarkRecord.from_error(
                spec, preset, stage="build", error=_err_text(e),
                devices=devices, placement=requested.mode,
            )
            rec.stage_timings_us = dict(base_timings)
            return [rec]
        try:
            with self._timed_stage(
                "place", base_timings, bench=spec.name, devices=devices
            ):
                args, placement = self._stage_place(workload, args, requested)
        except Exception as e:  # noqa: BLE001 — fault isolation is the contract
            rec = BenchmarkRecord.from_error(
                spec, preset, stage="place", error=_err_text(e),
                devices=devices, placement=requested.mode,
            )
            rec.stage_timings_us = dict(base_timings)
            return [rec]
        out: list[BenchmarkRecord] = []
        for backward in plan.passes(workload):
            out.extend(
                self._run_pass(
                    spec, workload, args, plan, preset, backward, placement,
                    base_timings,
                )
            )
        return out

    def _run_pass(
        self,
        spec: BenchmarkSpec,
        workload: Workload,
        args: tuple,
        plan: ExecutionPlan,
        preset: int,
        backward: bool,
        placement: Placement,
        base_timings: dict[str, float] | None = None,
    ) -> list[BenchmarkRecord]:
        stage = "tune"
        impl, impl_fallback = "xla", None
        # Per-stage wall microseconds for this pass (schema v8). Stages
        # run back to back, so the dict's sum tracks the pass's wall time
        # by construction; the _timed_stage helper fills it whether or
        # not tracing is on, and keeps filling it when a stage raises, so
        # error records carry the partial breakdown too.
        timings: dict[str, float] = dict(base_timings or {})
        span_attrs = dict(bench=_pass_name(workload, backward))
        try:
            impl, impl_fallback = self._resolve_impl(workload, plan, backward)
            span_attrs["impl"] = impl
            with self._timed_stage("tune", timings, **span_attrs):
                tuned_params, tune_trials, tune_trials_us = self._stage_tune(
                    spec, workload, args, plan, preset, backward, placement,
                    impl,
                )
            stage = "compile"
            with self._timed_stage("compile", timings, **span_attrs):
                entry = self._stage_compile(
                    spec, workload, args, plan, preset, backward, placement,
                    impl, tuned_params,
                )
            stage = "measure"
            with self._timed_stage("measure", timings, **span_attrs):
                timing = self._stage_measure(
                    workload, entry, args, plan, backward
                )
            stage = "characterize"
            with self._timed_stage("characterize", timings, **span_attrs):
                info = self._stage_characterize(workload, entry, backward)
            rec = BenchmarkRecord.from_measurement(
                spec, preset, timing, info,
                devices=placement.devices, placement=placement.mode,
                impl=impl,
                # Explicit interpret flag: a pallas row on a non-TPU host
                # ran the kernel interpreted — a dispatch study, never a
                # compiled-kernel number. None (not False) on xla rows.
                impl_interpret=(
                    jax.default_backend() != "tpu" if impl == "pallas" else None
                ),
                impl_fallback=impl_fallback,
                tuned_params=tuned_params,
                tune_trials=tune_trials,
                tune_trials_us=tune_trials_us,
            )
            rec.stage_timings_us = timings
            extra: list[BenchmarkRecord] = []
            # Serving measures request-level concurrency of the forward
            # pass; backward rows keep their isolation-mode semantics.
            if plan.serve is not None and not backward:
                stage = "serve"
                with self._timed_stage("serve", timings, **span_attrs):
                    stats, colocate, slowdown, extra = self._stage_serve(
                        spec, entry, args, plan, preset, placement,
                        impl, tuned_params,
                    )
                rec.apply_serve(
                    stats,
                    mode=plan.serve.mode,
                    lanes=plan.serve.lanes,
                    client=plan.serve.client,
                    colocate=colocate,
                    slowdown=slowdown,
                    dispatch=plan.serve.dispatch,
                    mix=_mix_label(plan.serve),
                )
            return [rec] + extra
        except Exception as e:  # noqa: BLE001 — fault isolation is the contract
            err = BenchmarkRecord.from_error(
                spec, preset, stage=stage, error=_err_text(e), backward=backward,
                devices=placement.devices, placement=placement.mode,
                impl=impl,
            )
            err.stage_timings_us = timings
            return [err]


# Fixed default for JAX's persistent compilation cache: <checkout>/.jax_cache
# (gitignored). The path is part of the cache's key, so it never moves.
_CHECKOUT_JAX_CACHE = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ),
    ".jax_cache",
)


def enable_compile_cache(cache_dir: str | None = None) -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Called once by each entry point (the suite CLI, ``benchmarks/run.py``,
    ``chip_smoke.py``) before anything compiles — never on import, never
    by :class:`Engine`. The rule: ``JAX_COMPILATION_CACHE_DIR``, when set,
    wins and JAX reads it itself (no other directory is set here); else
    ``<cache_dir>/jax-persistent`` when a ``--cache-dir`` was given; else
    the fixed ``<checkout>/.jax_cache``. The executable cache covers the
    benchmark programs; this one covers everything around them — input
    builders, validators, one-off jnp ops. Configuration errors raise.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = (
            os.path.join(cache_dir, "jax-persistent")
            if cache_dir
            else _CHECKOUT_JAX_CACHE
        )
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _pass_name(workload: Workload, backward: bool) -> str:
    return workload.name + (".bwd" if backward else "")


def _mix_label(serve: ServeSpec) -> str | None:
    """The record's compact mix description: ``label@weight`` per bucket
    (None for non-mixed serve specs)."""
    if not serve.is_mixed:
        return None
    if serve.mix is None:
        return None
    return ",".join(f"{b.label}@{b.weight:g}" for b in serve.mix)


def _err_text(e: BaseException, limit: int = 500) -> str:
    # Collapse whitespace: error records land in one-line CSV/JSONL rows.
    text = " ".join(f"{type(e).__name__}: {e}".split())
    return text if len(text) <= limit else text[: limit - 3] + "..."
