"""Result records and reports for the suite runner and benchmark drivers.

Two report formats share one record schema:

- **JSON** (``write_report`` / legacy): one array of record objects, written
  atomically at the end of a run — the artifact EXPERIMENTS.md reads.
- **JSONL** (``JsonlReportWriter``): streaming — a ``meta`` line carrying
  run provenance (backend, device count, jax version, schema version)
  followed by one ``record`` line per benchmark, flushed as each finishes,
  so a killed or crashed run still leaves every completed row on disk.

``load_records`` sniffs the format and reads either; ``load_run`` also
returns the :class:`RunMetadata` when the file carries it. Error rows
(per-benchmark fault isolation in the engine) are ordinary records with
``status="error"`` so both formats round-trip them unchanged. A missing,
empty, or unparseable report raises :class:`ReportError` — a one-line
configuration-style error CLI drivers print without a traceback.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import IO, Iterable, Sequence

from repro.core.harness import CompiledInfo, TimingResult
from repro.core.metrics import utilization_scale10
from repro.core.plan import ServeSpec

__all__ = [
    "SCHEMA_VERSION",
    "BenchmarkRecord",
    "RunMetadata",
    "JsonlReportWriter",
    "ReportError",
    "to_csv_lines",
    "write_report",
    "load_records",
    "load_run",
]

# Bump when BenchmarkRecord/RunMetadata fields change incompatibly.
# v2: placement-aware rows — devices / placement / scaling_efficiency.
# v3: serving rows — latency percentiles / achieved QPS / goodput /
#     co-location slowdown; RunMetadata carries the ServeSpec.
# v4: serving-client rows — serve_client (single|threaded), truncation
#     honesty flag, dispatch_overhead_us, per-lane achieved QPS.
# v5: windowed timing — us_per_call_windowed (K calls in flight per
#     synchronization), timing_window, timer_dispatch_us (sync − windowed,
#     the per-call dispatch+sync overhead sync mode folds in); RunMetadata
#     carries the plan's timing_window.
# v6: implementation axis — impl (xla|pallas, the lowering actually timed),
#     impl_interpret (pallas ran in interpret mode — non-TPU hosts; such
#     rows are dispatch studies, not compiled-kernel numbers),
#     impl_fallback (why a pallas plan fell back to xla for this row),
#     tuned_params / tune_trials / tune_trials_us (the autotune stage's
#     winning block config and what the sweep cost); RunMetadata carries
#     the plan's impl and tune flags.
# v7: continuous batching — serve_dispatch (lanes|loop|batched|dynamic, how
#     requests mapped onto device programs), serve_mix (the weighted
#     shape-bucket mix served, "label@weight,..."), batch_occupancy
#     (filled / dispatched batch slots), padding_waste (padded / dispatched
#     slots — padding to a bucket edge is measured, never hidden),
#     serve_batches (device programs dispatched), bucket_latency_us
#     (per-bucket requests + p50/p95/p99 keyed by bucket label); the
#     ServeSpec in RunMetadata carries dispatch/mix/trace/batch knobs.
# v8: observability — stage_timings_us (per-stage wall microseconds for
#     the row: build/place/tune/compile/measure/characterize/serve —
#     always collected, tracing on or off); RunMetadata carries
#     cache_stats (the HloDiskCache counter totals, so committed reports
#     show whether a run was warm) and counters (the obs layer's counter
#     snapshot: cache traffic, tune trials, batcher flushes/expiries/
#     padding, lane submit-block time — None when tracing was off). The
#     JSONL writer re-emits the final metadata as a second meta line at
#     close (load_run is last-meta-wins), so streamed reports carry
#     end-of-run counter totals without giving up streaming.
# v9: two per-process columns of the multi-process load generator.
# v10: the v9 columns left with the multi-process load generator.
SCHEMA_VERSION = 10


class ReportError(ValueError):
    """A report that cannot be read as asked (missing file, empty file,
    no usable records). CLIs print the one-line message and exit nonzero
    instead of dumping a traceback."""


@dataclasses.dataclass
class BenchmarkRecord:
    """One row of suite output: timing + static characterization.

    ``status`` is ``"ok"`` for measured rows and ``"error"`` for rows the
    engine emitted after a per-benchmark failure (``error`` holds the stage
    and exception text; the numeric fields are zeroed). ``devices`` /
    ``placement`` record where the row actually ran (``placement`` is the
    *effective* mode: a sharded plan over a non-batchable workload reads
    ``replicate``); ``scaling_efficiency`` is speedup over the same run's
    1-device row divided by the device count (None when no baseline row
    exists, e.g. single-count runs or a failed baseline).

    The ``serve_*`` / ``latency_*`` / ``*_qps`` columns are populated only
    when the plan carried a :class:`~repro.core.plan.ServeSpec` (schema
    v3): latency percentiles over non-warmup requests, achieved QPS, and —
    for co-located runs — the partner's name and this row's p50 slowdown
    vs its isolated baseline. Schema v4 adds the client-side issue
    accounting: ``serve_client`` (which host issue architecture served the
    row), ``serve_truncated`` (the open-loop schedule hit its request cap,
    so the run offered *less* than ``offered_qps``),
    ``dispatch_overhead_us`` (mean host time per dispatch, threaded
    client), and ``lane_qps`` (per-lane achieved QPS).

    Schema v5 adds the windowed-timing columns: ``us_per_call`` stays the
    sync-mode number (synchronize every call — comparable across all
    schema versions), ``us_per_call_windowed`` is the per-call time with
    ``timing_window`` calls in flight per synchronization (closer to true
    device throughput for dispatch-bound kernels), and
    ``timer_dispatch_us`` is their difference — the measured per-call
    host dispatch + sync overhead.

    Schema v6 adds the implementation axis: ``impl`` is the lowering this
    row actually timed (``xla`` or ``pallas`` — the *effective* choice;
    a pallas plan over a workload with no Pallas variant reads ``xla``
    and ``impl_fallback`` says why). ``impl_interpret=True`` flags pallas
    rows that ran the kernel in interpret mode (non-TPU hosts) so CPU CI
    rows are never mistaken for compiled-kernel numbers. ``tuned_params``
    / ``tune_trials`` / ``tune_trials_us`` report the autotune stage:
    the winning block config, how many candidates were timed (0 = winner
    restored from the disk cache), and the sweep's wall-clock cost.

    Schema v7 adds the continuous-batching columns: ``serve_dispatch``
    (how requests mapped onto device programs — classic ``lanes``, or the
    mixed-shape ``loop`` / ``batched`` / ``dynamic`` batcher paths),
    ``serve_mix`` (the weighted shape mix served), ``batch_occupancy``
    (filled / dispatched batch slots), ``padding_waste`` (padded slots —
    a dynamic batcher that pads a 3-request batch to width 4 *reports*
    that quarter, never hides it), ``serve_batches`` (device programs
    dispatched), and ``bucket_latency_us`` (per-bucket request counts and
    p50/p95/p99 latency percentiles keyed by bucket label).
    """

    name: str
    level: int
    dwarf: str | None
    domain: str | None
    preset: int
    us_per_call: float
    achieved_gflops: float
    achieved_gbps: float
    compute_util10: int  # paper-style 0..10 bar (roofline fraction of compute)
    memory_util10: int
    dominant: str
    derived: str = ""
    status: str = "ok"
    error: str = ""
    devices: int = 1
    placement: str = "replicate"
    scaling_efficiency: float | None = None
    # Windowed timing columns (schema v5) — None when only sync mode ran
    # (timing_window=1 plans, no_jit workloads, pre-v5 rows).
    us_per_call_windowed: float | None = None
    timing_window: int | None = None
    timer_dispatch_us: float | None = None  # sync − windowed, clamped at 0
    # Implementation axis (schema v6). impl is the *effective* lowering;
    # pre-v6 rows loaded from disk read the default "xla", which is what
    # they were.
    impl: str = "xla"
    impl_interpret: bool | None = None  # pallas ran interpret (non-TPU host)
    impl_fallback: str | None = None  # why a pallas plan fell back to xla
    tuned_params: dict | None = None  # autotune winner (None = not tuned)
    tune_trials: int | None = None  # candidates timed (0 = cache restore)
    tune_trials_us: float | None = None  # sweep wall-clock cost
    # Serving columns (schema v3) — None unless the plan had a ServeSpec.
    serve_mode: str | None = None
    serve_lanes: int | None = None
    serve_requests: int | None = None
    latency_p50_us: float | None = None
    latency_p95_us: float | None = None
    latency_p99_us: float | None = None
    latency_max_us: float | None = None
    achieved_qps: float | None = None
    offered_qps: float | None = None
    goodput_qps: float | None = None
    serve_colocate: str | None = None
    slowdown_vs_isolated: float | None = None
    # Serving-client columns (schema v4).
    serve_client: str | None = None
    serve_truncated: bool | None = None
    serve_slo_us: float | None = None  # the SLO goodput was measured against
    dispatch_overhead_us: float | None = None
    lane_qps: list[float] | None = None  # list, not tuple: JSON round-trip
    # Continuous-batching columns (schema v7) — None unless the row was
    # served. batch_occupancy / padding_waste / serve_batches are further
    # None outside the mixed-shape dispatch paths (classic lanes serving
    # dispatches no batches).
    serve_dispatch: str | None = None
    serve_mix: str | None = None  # "label@weight,..." (None = no mix)
    batch_occupancy: float | None = None  # filled / dispatched slots
    padding_waste: float | None = None  # padded / dispatched slots
    serve_batches: int | None = None  # device programs dispatched
    # bucket label -> {"requests", "p50_us", "p95_us", "p99_us"}; a plain
    # dict (not a dataclass) so JSON round-trips it unchanged.
    bucket_latency_us: dict | None = None
    # Observability (schema v8): stage name -> wall microseconds this row
    # spent in that stage (build/place shared timings are copied into
    # every pass's row). Always collected — the perf_counter pairs cost
    # nanoseconds — so committed reports explain where time went even
    # without --trace-out. None only on pre-v8 rows and serve-only
    # partner rows.
    stage_timings_us: dict | None = None

    def apply_serve(
        self,
        stats,
        *,
        mode: str,
        lanes: int,
        client: str = "single",
        colocate: str | None = None,
        slowdown: float | None = None,
        dispatch: str | None = None,
        mix: str | None = None,
    ) -> "BenchmarkRecord":
        """Fold a ``serve.latency.LatencyStats`` into this record."""
        self.serve_mode = mode
        self.serve_lanes = lanes
        self.serve_requests = stats.requests
        self.latency_p50_us = stats.p50_us
        self.latency_p95_us = stats.p95_us
        self.latency_p99_us = stats.p99_us
        self.latency_max_us = stats.max_us
        self.achieved_qps = stats.achieved_qps
        self.offered_qps = stats.offered_qps
        self.goodput_qps = stats.goodput_qps
        self.serve_colocate = colocate
        self.slowdown_vs_isolated = slowdown
        self.serve_client = client
        self.serve_truncated = stats.truncated
        self.serve_slo_us = stats.slo_us
        self.dispatch_overhead_us = stats.dispatch_overhead_us
        self.lane_qps = (
            list(stats.lane_qps) if stats.lane_qps is not None else None
        )
        # Continuous-batching accounting (schema v7). getattr-tolerant so
        # plain stats objects without the batching fields still fold in.
        self.serve_dispatch = dispatch
        self.serve_mix = mix
        self.batch_occupancy = getattr(stats, "batch_occupancy", None)
        self.padding_waste = getattr(stats, "padding_waste", None)
        self.serve_batches = getattr(stats, "n_batches", None)
        bucket_stats = getattr(stats, "bucket_stats", None)
        self.bucket_latency_us = (
            {
                label: {
                    "requests": b.requests,
                    "p50_us": b.p50_us,
                    "p95_us": b.p95_us,
                    "p99_us": b.p99_us,
                }
                for label, b in bucket_stats
            }
            if bucket_stats
            else None
        )
        return self

    @classmethod
    def from_serve(
        cls,
        spec,
        preset: int,
        stats,
        *,
        mode: str,
        lanes: int,
        client: str = "single",
        name: str | None = None,
        colocate: str | None = None,
        slowdown: float | None = None,
        devices: int = 1,
        placement: str = "replicate",
    ) -> "BenchmarkRecord":
        """A serve-only row (the co-location partner, which was served but
        not separately measured/characterized): ``us_per_call`` is its p50
        serving latency so tables stay meaningfully sortable."""
        rec = cls(
            name=name if name is not None else spec.name,
            level=spec.level,
            dwarf=spec.dwarf,
            domain=spec.domain,
            preset=preset,
            us_per_call=stats.p50_us,
            achieved_gflops=0.0,
            achieved_gbps=0.0,
            compute_util10=0,
            memory_util10=0,
            dominant="serve",
            derived=f"colocated_with={colocate}" if colocate else "serve",
            devices=devices,
            placement=placement,
        )
        return rec.apply_serve(
            stats, mode=mode, lanes=lanes, client=client,
            colocate=colocate, slowdown=slowdown,
        )

    @classmethod
    def from_measurement(
        cls,
        spec,
        preset: int,
        timing: TimingResult,
        compiled: CompiledInfo,
        *,
        devices: int = 1,
        placement: str = "replicate",
        impl: str = "xla",
        impl_interpret: bool | None = None,
        impl_fallback: str | None = None,
        tuned_params: dict | None = None,
        tune_trials: int | None = None,
        tune_trials_us: float | None = None,
    ) -> "BenchmarkRecord":
        r = compiled.roofline
        bound = r.bound_s if r.bound_s > 0 else 1.0
        return cls(
            name=timing.name,
            level=spec.level,
            dwarf=spec.dwarf,
            domain=spec.domain,
            preset=preset,
            us_per_call=timing.us_per_call,
            achieved_gflops=timing.achieved_gflops,
            achieved_gbps=timing.achieved_gbps,
            compute_util10=utilization_scale10(r.compute_s / bound),
            memory_util10=utilization_scale10(r.memory_s / bound),
            dominant=r.dominant,
            derived=(
                f"flops={r.flops:.3e};bytes={r.hbm_bytes:.3e};"
                f"coll={r.collective_bytes:.3e}"
            ),
            devices=devices,
            placement=placement,
            us_per_call_windowed=timing.us_per_call_windowed,
            timing_window=timing.timing_window,
            timer_dispatch_us=timing.timer_dispatch_us,
            impl=impl,
            impl_interpret=impl_interpret,
            impl_fallback=impl_fallback,
            tuned_params=tuned_params,
            tune_trials=tune_trials,
            tune_trials_us=tune_trials_us,
        )

    @classmethod
    def from_error(
        cls,
        spec,
        preset: int,
        *,
        stage: str,
        error: str,
        backward: bool = False,
        devices: int = 1,
        placement: str = "replicate",
        impl: str = "xla",
    ) -> "BenchmarkRecord":
        return cls(
            name=spec.name + (".bwd" if backward else ""),
            level=spec.level,
            dwarf=spec.dwarf,
            domain=spec.domain,
            preset=preset,
            us_per_call=0.0,
            achieved_gflops=0.0,
            achieved_gbps=0.0,
            compute_util10=0,
            memory_util10=0,
            dominant="error",
            derived=f"stage={stage}",
            status="error",
            error=error,
            devices=devices,
            placement=placement,
            impl=impl,
        )

    @classmethod
    def csv_header(cls) -> str:
        return "name,us_per_call,devices,placement,derived"

    def csv(self) -> str:
        eff = (
            f";eff={self.scaling_efficiency:.3f}"
            if self.scaling_efficiency is not None
            else ""
        )
        if self.us_per_call_windowed is not None:
            # The windowed per-call time and the dispatch overhead it
            # exposes ride the derived field next to the sync number.
            eff += (
                f";win_us={self.us_per_call_windowed:.2f}"
                f";timer_dispatch_us={self.timer_dispatch_us:.2f}"
            )
        imp = ""
        if self.impl != "xla" or self.impl_fallback is not None:
            imp = f";impl={self.impl}"
            if self.impl_interpret:
                imp += ";interpret=1"
            if self.impl_fallback is not None:
                imp += f";impl_fallback={self.impl_fallback}"
        if self.tuned_params is not None:
            tuned = "/".join(
                f"{k}={v}" for k, v in sorted(self.tuned_params.items())
            )
            imp += (
                f";tuned={tuned or 'default'};tune_trials={self.tune_trials};"
                f"tune_us={self.tune_trials_us:.0f}"
            )
        serve = ""
        if self.serve_mode is not None:
            # Pre-v4 rows have no serve_client; they were served by the
            # only client that existed then.
            client = self.serve_client if self.serve_client else "single"
            serve = (
                f";serve={self.serve_mode};client={client};"
                f"lanes={self.serve_lanes};"
                f"p50_us={self.latency_p50_us:.1f};"
                f"p99_us={self.latency_p99_us:.1f};qps={self.achieved_qps:.1f}"
            )
            if self.serve_truncated:
                serve += ";truncated=1"
            if self.serve_slo_us is not None:
                # Goodput is only a distinct number under an SLO; emitting
                # it SLO-less would just repeat qps.
                serve += (
                    f";slo_us={self.serve_slo_us:.0f};"
                    f"goodput_qps={self.goodput_qps:.1f}"
                )
            if self.dispatch_overhead_us is not None:
                serve += f";dispatch_us={self.dispatch_overhead_us:.1f}"
            if self.serve_dispatch is not None and self.serve_dispatch != "lanes":
                serve += f";dispatch={self.serve_dispatch}"
            if self.batch_occupancy is not None:
                serve += (
                    f";occupancy={self.batch_occupancy:.3f};"
                    f"padding_waste={self.padding_waste:.3f}"
                )
            if self.bucket_latency_us:
                buckets = "/".join(
                    f"{label}:p50={b['p50_us']:.0f}"
                    for label, b in sorted(self.bucket_latency_us.items())
                )
                serve += f";buckets={buckets}"
            if self.slowdown_vs_isolated is not None:
                serve += (
                    f";colocate={self.serve_colocate};"
                    f"slowdown={self.slowdown_vs_isolated:.2f}"
                )
        if self.status != "ok":
            return (
                f"{self.name},0.00,{self.devices},{self.placement},"
                f"{self.status}:{self.derived}"
            )
        return (
            f"{self.name},{self.us_per_call:.2f},{self.devices},"
            f"{self.placement},{self.derived}{eff}{imp}{serve}"
        )


@dataclasses.dataclass(frozen=True)
class RunMetadata:
    """Provenance header for a run: enough to interpret the rows later."""

    backend: str
    device_count: int
    jax_version: str
    schema_version: int = SCHEMA_VERSION
    preset: int | None = None
    devices: int = 1
    placement: str = "replicate"
    device_sweep: tuple[int, ...] = (1,)
    serve: ServeSpec | None = None
    timing_window: int = 1  # 1 = sync-only (pre-v5 runs)
    impl: str = "xla"  # the plan's requested implementation axis
    tune: bool = False  # whether the autotune stage was enabled
    # Observability (schema v8), stamped at end of run — None at capture
    # time and on pre-v8 reports. cache_stats is the HloDiskCache counter
    # totals (hits/misses/stores/fallback_count/...),
    # present whenever the run had a --cache-dir, so a committed report
    # says whether the run was warm without needing verbose stdout.
    # counters is the obs layer's counter snapshot, present when tracing
    # was enabled.
    cache_stats: dict | None = None
    counters: dict | None = None

    def __post_init__(self) -> None:
        # JSON round-trips tuples as lists and nested dataclasses as dicts;
        # normalize so loaded metadata compares equal to captured metadata.
        if not isinstance(self.device_sweep, tuple):
            object.__setattr__(self, "device_sweep", tuple(self.device_sweep))
        if isinstance(self.serve, dict):
            fields = {f.name for f in dataclasses.fields(ServeSpec)}
            object.__setattr__(
                self,
                "serve",
                ServeSpec(**{k: v for k, v in self.serve.items() if k in fields}),
            )

    @classmethod
    def capture(
        cls,
        *,
        preset: int | None = None,
        devices: int = 1,
        placement: str = "replicate",
        device_sweep: tuple[int, ...] | None = None,
        serve: ServeSpec | None = None,
        timing_window: int = 1,
        impl: str = "xla",
        tune: bool = False,
    ) -> "RunMetadata":
        import jax

        return cls(
            backend=jax.default_backend(),
            device_count=jax.device_count(),
            jax_version=jax.__version__,
            preset=preset,
            devices=devices,
            placement=placement,
            device_sweep=device_sweep if device_sweep is not None else (devices,),
            serve=serve,
            timing_window=timing_window,
            impl=impl,
            tune=tune,
        )


def to_csv_lines(records: Iterable[BenchmarkRecord]) -> list[str]:
    return [BenchmarkRecord.csv_header()] + [r.csv() for r in records]


def write_report(records: Sequence[BenchmarkRecord], path: str) -> None:
    """JSON report, one object per record (the artifact EXPERIMENTS.md reads)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump([dataclasses.asdict(r) for r in records], f, indent=1, sort_keys=True)
    os.replace(tmp, path)


class JsonlReportWriter:
    """Streaming JSONL report: a ``meta`` line, then one line per record.

    Each line is flushed as written so partial runs leave usable reports.
    """

    def __init__(self, path: str, metadata: RunMetadata | None = None) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self.path = path
        self._f: IO[str] = open(path, "w")
        if metadata is not None:
            self._emit({"kind": "meta", **dataclasses.asdict(metadata)})

    def _emit(self, obj: dict) -> None:
        self._f.write(json.dumps(obj, sort_keys=True) + "\n")
        self._f.flush()

    def write(self, record: BenchmarkRecord) -> None:
        self._emit({"kind": "record", **dataclasses.asdict(record)})

    def write_meta(self, metadata: RunMetadata) -> None:
        """Emit a(nother) meta line. ``load_run`` is last-meta-wins, so
        the engine re-emits the final metadata — with end-of-run cache
        stats and counter totals — just before close, and readers of a
        *complete* report see the stamped version while a killed run
        still has the header line from open time."""
        self._emit({"kind": "meta", **dataclasses.asdict(metadata)})

    def close(self) -> None:
        if not self._f.closed:
            self._f.close()

    def __enter__(self) -> "JsonlReportWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _record_from_dict(d: dict) -> BenchmarkRecord:
    fields = {f.name for f in dataclasses.fields(BenchmarkRecord)}
    return BenchmarkRecord(**{k: v for k, v in d.items() if k in fields})


def load_run(path: str) -> tuple[RunMetadata | None, list[BenchmarkRecord]]:
    """Read either report format; metadata is None for legacy JSON arrays.

    Raises :class:`ReportError` (one clear line, no traceback for CLIs that
    catch it) when the report is missing or holds no records at all.
    """
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        raise ReportError(f"cannot read report {path}: {e.strerror or e}") from None
    if text.lstrip().startswith("["):  # legacy JSON array
        try:
            return None, [_record_from_dict(d) for d in json.loads(text)]
        except (json.JSONDecodeError, TypeError) as e:
            raise ReportError(f"report {path} is not valid JSON: {e}") from None
    meta: RunMetadata | None = None
    records: list[BenchmarkRecord] = []
    lines = [ln for ln in text.splitlines() if ln.strip()]
    if not lines:
        raise ReportError(f"report {path} is empty (no metadata, no records)")
    for i, line in enumerate(lines):
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            if i == len(lines) - 1:
                # A run killed mid-write leaves a torn final line; every
                # completed row before it must stay readable.
                break
            raise
        kind = obj.pop("kind", "record")
        if kind == "meta":
            fields = {f.name for f in dataclasses.fields(RunMetadata)}
            meta = RunMetadata(**{k: v for k, v in obj.items() if k in fields})
        else:
            records.append(_record_from_dict(obj))
    return meta, records


def load_records(path: str) -> list[BenchmarkRecord]:
    return load_run(path)[1]
