"""Execution plans — the declarative half of the staged engine.

An :class:`ExecutionPlan` is a frozen value object describing *what* to run
(selection by level / name / tag / domain, or an explicit spec list), *at
what size* (SHOC-style preset plus Rodinia-style per-benchmark overrides),
*which passes* (forward, and backward where a workload defines one), *how to
measure* (iters / warmup / seed, plus ``timing_window`` — sync-mode timing
always runs; a window K > 1 additionally measures with K calls in flight
per synchronization, riding async dispatch, so records carry both
``us_per_call`` and ``us_per_call_windowed``), *where* (a :class:`Placement` —
device count plus mode, ``replicate`` or ``shard``, realized through
``runtime/sharding`` helpers; ``device_sweep`` runs the same selection at
several device counts for scaling curves), and *under what load* (an
optional :class:`ServeSpec` — open/closed-loop serving through N dispatch
lanes issued by a single-threaded or thread-per-lane client, with
optional SLO goodput and co-location; realized by the engine's serve
stage via ``repro.serve``).

Plans carry no execution state: the engine (``core/engine.py``) consumes a
plan, owns the compilation cache and the stage sequence, and emits records.
Two engines given equal plans produce comparable runs; the same plan can be
re-run against a warm engine to reuse every compiled executable.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

from repro.core.registry import BenchmarkSpec, Workload, all_benchmarks

__all__ = [
    "ExecutionPlan",
    "Placement",
    "ServeSpec",
    "ShapeBucket",
    "PlanError",
    "PLACEMENT_MODES",
    "SERVE_MODES",
    "SERVE_CLIENTS",
    "SERVE_DISPATCH",
    "IMPLS",
]

PLACEMENT_MODES = ("replicate", "shard")
IMPLS = ("xla", "pallas")
SERVE_MODES = ("open", "closed")
SERVE_CLIENTS = ("single", "threaded")
# How requests map onto device programs. "lanes" is the pre-mix default
# (N dispatch lanes over the measure-stage executable); the other three
# are the mixed-shape paths realized by serve/batcher.py: "loop" is the
# sync-per-request floor, "batched" a fixed-width vmap that waits to fill,
# "dynamic" the continuous batcher that coalesces compatible requests into
# the largest width that fits under the latency budget.
SERVE_DISPATCH = ("lanes", "loop", "batched", "dynamic")


class PlanError(ValueError):
    """A plan or placement that cannot be executed as configured (bad
    selection, unknown mode, more devices than the host offers). CLIs treat
    it as a configuration error — exit 2, no traceback."""


@dataclasses.dataclass(frozen=True)
class Placement:
    """Where a plan runs: how many devices, and what lands on them.

    - ``replicate``: every input is device_put fully replicated across the
      data mesh — all devices do identical work (the pre-placement
      behaviour of the old scalar ``devices`` knob).
    - ``shard``: inputs of workloads that declare ``batch_dims`` are
      partitioned along those dims across the data mesh (data parallelism);
      workloads that opt out (``batch_dims=None``) fall back to replicate,
      and the record says so.

    A placement is part of the engine's compile-cache key: the sharded and
    replicated lowerings of one workload are distinct executables.
    """

    devices: int = 1
    mode: str = "replicate"

    def __post_init__(self) -> None:
        if self.devices < 1:
            raise PlanError(f"placement devices must be >= 1, got {self.devices}")
        if self.mode not in PLACEMENT_MODES:
            raise PlanError(
                f"placement mode must be one of {PLACEMENT_MODES}, got {self.mode!r}"
            )


@dataclasses.dataclass(frozen=True)
class ShapeBucket:
    """One request shape in a serve mix: a preset (plus optional per-param
    overrides on top of it) drawn with probability proportional to
    ``weight``. Buckets are identified everywhere — requests, traces,
    compile-cache keys, per-bucket record columns — by :attr:`label`
    (``p<preset>`` plus ``/param=value`` for each override)."""

    preset: int = 0
    weight: float = 1.0
    overrides: tuple[tuple[str, Any], ...] = ()

    def __post_init__(self) -> None:
        if self.preset < 0:
            raise PlanError(f"mix bucket preset must be >= 0, got {self.preset}")
        if not self.weight > 0:
            raise PlanError(f"mix bucket weight must be > 0, got {self.weight}")
        if not isinstance(self.overrides, tuple):
            object.__setattr__(
                self,
                "overrides",
                tuple(tuple(kv) for kv in self.overrides),
            )
        else:
            object.__setattr__(
                self,
                "overrides",
                tuple(
                    kv if isinstance(kv, tuple) else tuple(kv)
                    for kv in self.overrides
                ),
            )
        for kv in self.overrides:
            if len(kv) != 2 or not isinstance(kv[0], str):
                raise PlanError(
                    f"mix bucket overrides must be (param, value) pairs, "
                    f"got {self.overrides!r}"
                )
            _freeze_value("mix", kv[0], kv[1])

    @property
    def label(self) -> str:
        parts = [f"p{self.preset}"]
        parts += [f"{k}={v}" for k, v in sorted(self.overrides)]
        return "/".join(parts)


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """How to serve the selected workloads under load (``repro.serve``).

    - ``mode="closed"``: keep ``concurrency`` requests in flight across
      ``lanes`` dispatch lanes for ``duration_s`` seconds (throughput-
      oriented; the next request is issued the moment a slot frees).
    - ``mode="open"``: Poisson arrivals at ``qps`` for ``duration_s``
      seconds, deterministic for the plan's seed; ``concurrency`` caps
      total in-flight work under overload.
    - ``client``: the host-side issue architecture. ``single`` dispatches
      every lane from one host thread (the pre-threaded behaviour);
      ``threaded`` gives each lane its own issuing thread fed from a
      deterministic per-lane sub-schedule, so host-side contention between
      lanes becomes part of the measurement (``repro.serve.client``).
    - ``slo_us``: optional latency SLO; rows then carry ``goodput_qps``
      (completions with latency <= the SLO per second — a request at
      exactly the SLO counts as good).
    - ``colocate``: serve every selected workload *paired* with this
      registered benchmark, splitting the lanes between the two tenants,
      and record each tenant's slowdown vs its isolated baseline. A
      closed-loop measurement (open arrivals would conflate queueing with
      interference), so it requires ``mode="closed"``; its dispatch is
      single-threaded by construction (tenants alternate submissions), so
      it requires ``client="single"``.
    - ``mix``: a tuple of :class:`ShapeBucket` — each open-loop request
      draws its shape from this weighted distribution (seeded off the
      plan seed, independently of the arrival draws). The engine then
      precompiles one executable per (bucket, batch width) through both
      compile caches and serves via ``repro.serve.batcher``.
    - ``dispatch``: how requests map onto device programs (one of
      ``SERVE_DISPATCH``). ``lanes`` is the classic N-lane path; ``loop``
      / ``batched`` / ``dynamic`` are the mixed-shape paths — sync
      per-request floor, fixed-width vmap that waits to fill, and the
      continuous batcher that coalesces queued requests of one bucket
      into the largest width that fits under ``batch_budget_us``.
      Padding to a width edge is *measured* (``padding_waste``), never
      hidden.
    - ``trace``: path to a replayable JSONL arrival+shape trace. If the
      file exists it is loaded verbatim (qps/duration/mix draws are
      ignored — the trace IS the load); otherwise the generated schedule
      is saved there, so two runs with different dispatch modes replay
      the identical request stream.
    - ``batch_budget_us`` / ``max_batch``: dynamic-batcher knobs — how
      long the oldest queued request may wait before a partial batch
      dispatches anyway, and the largest vmap width (widths are powers
      of two up to it).
    The engine runs serving as a stage after ``measure``. Dispatch
    ``lanes`` without a mix calls the *same cached executable* the timer
    used — never a recompile (and a sharded plan serves the sharded
    lowering); the mixed-shape paths serve per-bucket executables that
    went through the ordinary CompileCache and the HLO disk cache, so a
    warm run restores every bucket with zero XLA compiles.
    """

    mode: str = "closed"
    qps: float = 0.0
    concurrency: int = 4
    lanes: int = 2
    duration_s: float = 2.0
    colocate: str | None = None
    client: str = "single"
    slo_us: float | None = None
    dispatch: str = "lanes"
    mix: tuple[ShapeBucket, ...] | None = None
    trace: str | None = None
    batch_budget_us: float = 2000.0
    max_batch: int = 8

    def __post_init__(self) -> None:
        if self.mix is not None:
            entries = []
            for entry in self.mix:
                if isinstance(entry, Mapping):  # RunMetadata JSON round-trip
                    known = {f.name for f in dataclasses.fields(ShapeBucket)}
                    entry = ShapeBucket(
                        **{k: v for k, v in entry.items() if k in known}
                    )
                elif not isinstance(entry, ShapeBucket):
                    raise PlanError(
                        f"serve mix entries must be ShapeBucket, got {entry!r}"
                    )
                entries.append(entry)
            if not entries:
                raise PlanError("serve mix must have at least one bucket")
            labels = [e.label for e in entries]
            if len(set(labels)) != len(labels):
                raise PlanError(f"serve mix has duplicate buckets: {labels}")
            object.__setattr__(self, "mix", tuple(entries))
        if self.mode not in SERVE_MODES:
            raise PlanError(
                f"serve mode must be one of {SERVE_MODES}, got {self.mode!r}"
            )
        if self.client not in SERVE_CLIENTS:
            raise PlanError(
                f"serve client must be one of {SERVE_CLIENTS}, got {self.client!r}"
            )
        if self.mode == "open" and self.qps <= 0:
            raise PlanError(f"open-loop serving needs qps > 0, got {self.qps}")
        if self.concurrency < 1:
            raise PlanError(f"serve concurrency must be >= 1, got {self.concurrency}")
        if self.lanes < 1:
            raise PlanError(f"serve lanes must be >= 1, got {self.lanes}")
        if self.duration_s <= 0:
            raise PlanError(f"serve duration_s must be > 0, got {self.duration_s}")
        if self.slo_us is not None and self.slo_us <= 0:
            raise PlanError(f"serve slo_us must be > 0, got {self.slo_us}")
        if self.colocate is not None and self.mode != "closed":
            raise PlanError(
                "co-location is a closed-loop measurement; "
                f"got colocate={self.colocate!r} with mode={self.mode!r}"
            )
        if self.colocate is not None and self.client != "single":
            raise PlanError(
                "co-location dispatch is single-threaded (tenants alternate "
                f"submissions); got colocate={self.colocate!r} with "
                f"client={self.client!r}"
            )
        if self.dispatch not in SERVE_DISPATCH:
            raise PlanError(
                f"serve dispatch must be one of {SERVE_DISPATCH}, "
                f"got {self.dispatch!r}"
            )
        if self.batch_budget_us <= 0:
            raise PlanError(
                f"batch_budget_us must be > 0, got {self.batch_budget_us}"
            )
        if self.max_batch < 1:
            raise PlanError(f"max_batch must be >= 1, got {self.max_batch}")
        mixed = (
            self.mix is not None
            or self.trace is not None
            or self.dispatch != "lanes"
        )
        if mixed and self.mode != "open":
            raise PlanError(
                "mixed-shape serving (mix/trace/dispatch != 'lanes') is "
                f"arrival-driven; it requires mode='open', got {self.mode!r}"
            )
        if mixed and self.client != "single":
            raise PlanError(
                "mixed-shape serving dispatches from one host thread; "
                f"it requires client='single', got {self.client!r}"
            )
        if mixed and self.colocate is not None:
            raise PlanError(
                "mixed-shape serving cannot be combined with colocate "
                f"(got colocate={self.colocate!r})"
            )

    @property
    def is_mixed(self) -> bool:
        """True when serving goes through the mixed-shape/batcher path
        (per-bucket executables) rather than the classic lanes path."""
        return (
            self.mix is not None
            or self.trace is not None
            or self.dispatch != "lanes"
        )

    def buckets(self, default_preset: int) -> tuple[ShapeBucket, ...]:
        """The effective bucket set: the mix, or one bucket at the plan's
        preset when only trace/dispatch selected the mixed path."""
        if self.mix is not None:
            return self.mix
        return (ShapeBucket(preset=default_preset),)


def _freeze_value(name: str, param: str, value: Any) -> Any:
    """Override values feed the engine's compile-cache key: fail fast on
    unhashable ones (lists become tuples) instead of erroring per-benchmark."""
    if isinstance(value, list):
        value = tuple(value)
    try:
        hash(value)
    except TypeError:
        raise ValueError(
            f"override {name}.{param}={value!r} is not hashable; "
            f"use scalars or tuples"
        ) from None
    return value


def _freeze_overrides(
    overrides: Mapping[str, Mapping[str, Any]] | None,
) -> tuple[tuple[str, tuple[tuple[str, Any], ...]], ...]:
    """Canonicalize name->{param: value} into a hashable, sorted tuple."""
    if not overrides:
        return ()
    return tuple(
        (
            name,
            tuple(
                (param, _freeze_value(name, param, value))
                for param, value in sorted(kwargs.items())
            ),
        )
        for name, kwargs in sorted(overrides.items())
    )


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """What to run, at what size, how many passes, on how many devices."""

    levels: tuple[int, ...] = (0, 1, 2)
    names: tuple[str, ...] | None = None
    tags: tuple[str, ...] | None = None
    domains: tuple[str, ...] | None = None
    preset: int = 0
    # Rodinia-style size overrides: benchmark name -> {param: value}, applied
    # on top of the preset by BenchmarkSpec.build_preset.
    overrides: tuple[tuple[str, tuple[tuple[str, Any], ...]], ...] = ()
    include_backward: bool = True
    iters: int = 5
    warmup: int = 2
    seed: int = 0
    # Windowed timing: per measured pass, additionally dispatch `iters`
    # windows of K calls and synchronize once per window (K=1 disables —
    # sync-only, the pre-v5 behaviour). Sync mode always runs; the
    # windowed number amortizes per-call dispatch+sync overhead, which is
    # the paper's async-runtime timing pitfall for small kernels.
    timing_window: int = 4
    # Multi-device placement: a frozen Placement(devices, mode) value object.
    # `devices=N` remains accepted as back-compat sugar for
    # Placement(devices=N, mode="replicate"); after construction
    # `plan.devices` always mirrors `plan.placement.devices`.
    placement: Placement | None = None
    devices: int | None = None
    # Scaling sweep: run the selection once per device count (sorted
    # ascending, deduplicated) under placement.mode, sharing the compile
    # cache across counts. None = just (placement.devices,).
    device_sweep: tuple[int, ...] | None = None
    # Implementation axis: which lowering of each workload to compile and
    # time. "xla" (default) traces the jnp/lax path; "pallas" traces the
    # hand-written kernel for workloads that declare one (pallas_kernel on
    # the Workload — registry.py impl contract), with a recorded fallback
    # to xla otherwise. Part of the compile-cache key, like placement.
    impl: str = "xla"
    # Autotune: sweep each Pallas kernel's tune_space() in a stage between
    # place and compile, timing candidates with the windowed timer; the
    # winner persists in the HLO disk cache so warm runs skip the sweep.
    # No-op for impl="xla" (there is nothing to tune on the lax path).
    tune: bool = False
    # Serve the selection under generated load after measuring it: a frozen
    # ServeSpec (mode/qps/concurrency/lanes/duration/colocate), or None for
    # isolation-only runs (the pre-serve behaviour).
    serve: ServeSpec | None = None
    # Escape hatch for tests and programmatic callers: bypass the registry
    # and run exactly these specs (selection filters are ignored).
    specs: tuple[BenchmarkSpec, ...] | None = None

    def __post_init__(self) -> None:
        # Normalize sequence-ish fields so equal plans compare/hash equal.
        def norm(field: str, value):
            if value is not None and not isinstance(value, tuple):
                object.__setattr__(self, field, tuple(value))

        for f in ("levels", "names", "tags", "domains", "specs"):
            norm(f, getattr(self, f))
        if not isinstance(self.overrides, tuple):
            object.__setattr__(self, "overrides", _freeze_overrides(self.overrides))
        if self.iters < 1:
            raise ValueError(f"iters must be >= 1, got {self.iters}")
        if self.warmup < 0:
            raise ValueError(f"warmup must be >= 0, got {self.warmup}")
        if self.timing_window < 1:
            raise ValueError(
                f"timing_window must be >= 1 (1 = sync-only), "
                f"got {self.timing_window}"
            )
        if self.impl not in IMPLS:
            raise PlanError(f"impl must be one of {IMPLS}, got {self.impl!r}")
        if self.serve is not None and not isinstance(self.serve, ServeSpec):
            raise PlanError(f"serve must be a ServeSpec, got {self.serve!r}")
        self._resolve_placement()

    def _resolve_placement(self) -> None:
        placement = self.placement
        if placement is None:
            devices = 1 if self.devices is None else self.devices
            if devices < 1:
                raise PlanError(f"devices must be >= 1, got {devices}")
            placement = Placement(devices=devices, mode="replicate")
        elif isinstance(placement, int):  # Placement-shaped sugar
            placement = Placement(devices=placement, mode="replicate")
        elif not isinstance(placement, Placement):
            raise PlanError(
                f"placement must be a Placement (or int), got {placement!r}"
            )
        if self.devices is not None and self.devices != placement.devices:
            raise PlanError(
                f"conflicting device counts: devices={self.devices} vs "
                f"placement.devices={placement.devices}; pass one or the other"
            )
        object.__setattr__(self, "placement", placement)
        object.__setattr__(self, "devices", placement.devices)
        sweep = self.device_sweep
        if sweep is None:
            sweep = (placement.devices,)
        else:
            if not isinstance(sweep, tuple):
                sweep = tuple(sweep)
            if not sweep:
                raise PlanError("device_sweep is empty")
            for n in sweep:
                if not isinstance(n, int) or n < 1:
                    raise PlanError(
                        f"device_sweep entries must be ints >= 1, got {sweep}"
                    )
            # Ascending order puts the 1-device baseline first, so sweep
            # records can carry scaling_efficiency as they stream out.
            sweep = tuple(sorted(set(sweep)))
        object.__setattr__(self, "device_sweep", sweep)

    def placement_at(self, devices: int) -> Placement:
        """The effective placement for one sweep step: the plan's mode at
        ``devices`` (sharding over one device degenerates to replicate)."""
        mode = self.placement.mode if devices > 1 else "replicate"
        return Placement(devices=devices, mode=mode)

    # -- selection ---------------------------------------------------------

    def select(self) -> list[BenchmarkSpec]:
        """Resolve the plan's selection against the registry (or ``specs``)."""
        if self.specs is not None:
            if not self.specs:
                raise ValueError("plan.specs is empty")
            return list(self.specs)
        cands = all_benchmarks()
        if self.names is not None:
            known = {s.name for s in cands}
            unknown = sorted(set(self.names) - known)
            if unknown:
                raise ValueError(
                    f"unknown benchmark(s) {unknown}; known: {sorted(known)}"
                )
        selected = [
            s
            for s in cands
            if s.level in self.levels
            and (self.names is None or s.name in self.names)
            and (self.tags is None or set(self.tags) & set(s.tags))
            and (self.domains is None or s.domain in self.domains)
        ]
        if not selected:
            raise ValueError(
                f"no benchmarks match levels={self.levels} names={self.names} "
                f"tags={self.tags} domains={self.domains}"
            )
        return selected

    def resolve_preset(self, spec: BenchmarkSpec) -> int:
        """The plan preset, clamped to the smallest one the spec defines."""
        return self.preset if self.preset in spec.presets else min(spec.presets)

    def overrides_for(self, name: str) -> dict[str, Any]:
        for n, kwargs in self.overrides:
            if n == name:
                return dict(kwargs)
        return {}

    def passes(self, workload: Workload) -> list[bool]:
        """[False] (forward), plus [True] when backward is planned+defined."""
        out = [False]
        if self.include_backward and workload.fn_bwd is not None:
            out.append(True)
        return out
