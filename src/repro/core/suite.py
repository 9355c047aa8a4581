"""The unified suite runner — a thin CLI over the staged execution engine.

``run_suite`` is what `examples/run_suite.py` and `python -m repro.core.suite`
invoke. Since the plan/engine refactor it only *assembles* an
:class:`~repro.core.plan.ExecutionPlan` (selection by level / name / tag /
domain, preset + overrides, passes, iters/warmup, device placement and
scaling sweep) and hands it to the module-level
:class:`~repro.core.engine.Engine`, which owns the stage sequence (build →
place → compile → measure → characterize → report), the compile-once cache
shared by every caller in the process, and per-benchmark fault isolation.
Output is the paper's Fig.-5-style table plus a machine-readable JSON report
and/or a streaming JSONL report with run metadata.

Placement flags: ``--placement {replicate,shard}`` picks what multi-device
runs put on each device; ``--scale-devices 1,2,4`` sweeps the selection
across device counts, producing one record per (benchmark, pass, count)
with ``scaling_efficiency`` on the multi-device rows.

Serving flags: ``--serve {open,closed}`` runs every selected workload
under generated load after measuring it (``--qps`` open-loop arrival rate,
``--concurrency`` closed-loop in-flight cap, ``--lanes`` dispatch lanes,
``--serve-duration`` seconds); ``--serve-client {single,threaded}`` picks
the host issue architecture (one thread for all lanes vs one issuing
thread per lane, with dispatch-overhead and per-lane QPS columns);
``--slo-us`` adds a latency SLO and the ``goodput_qps`` column;
``--colocate NAME`` serves each workload against a partner benchmark and
records both tenants' slowdown vs their isolated baselines.
``--cache-dir`` persists serialized executables across processes (warm
runs skip tracing AND XLA compilation — the zero-compile warm start); the
CLI always prints the cache's hit/fallback summary so a cache that never
hits is visible. JAX's own persistent compilation cache lives in
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<cache-dir>/jax-persistent``,
else ``<checkout>/.jax_cache``.

Timing flags: sync-mode timing (synchronize every call) always runs and
fills ``us_per_call``; ``--timing-window K`` (default 4; 1 disables)
additionally measures with K calls in flight per synchronization, riding
async dispatch, filling ``us_per_call_windowed`` and the derived per-call
dispatch overhead — the accurate-kernel-time story for small kernels on
an async runtime.

Implementation flags: ``--impl {xla,pallas}`` picks which lowering to
compile and time — the lax/XLA path (default) or the hand-written Pallas
kernel for workloads that declare one (others fall back to xla with the
reason recorded in the row); ``--tune`` sweeps each kernel's block/grid
tune space before compiling and times the winner (the winning config
persists in ``--cache-dir``, so a warm tuned run performs zero trials
and zero compiles).

Batching flags (mixed-shape serving): ``--serve-mix`` gives each open-loop
request a shape drawn from a weighted preset/override distribution;
``--serve-dispatch {lanes,loop,batched,dynamic}`` picks how requests map
onto device programs (``dynamic`` is the continuous batcher, coalescing
compatible requests into the largest vmapped bucket that fits under
``--batch-latency-budget`` microseconds, padding — measured as
``padding_waste`` — up to ``--max-batch``); ``--serve-trace PATH`` saves
the generated arrival+shape stream as replayable JSONL, or replays it
verbatim when the file already exists.
"""

from __future__ import annotations

import argparse
import sys
from typing import Any, Mapping, Sequence

from repro.core.engine import Engine, enable_compile_cache
from repro.obs import Tracer
from repro.core.plan import (
    IMPLS,
    PLACEMENT_MODES,
    SERVE_CLIENTS,
    SERVE_DISPATCH,
    SERVE_MODES,
    ExecutionPlan,
    Placement,
    PlanError,
    ServeSpec,
    ShapeBucket,
)
from repro.core.results import BenchmarkRecord, to_csv_lines

__all__ = ["run_suite", "main", "DEFAULT_ENGINE"]

# Shared across run_suite callers (figure drivers, examples, tests) so a
# workload compiled for one section is reused by every later section.
DEFAULT_ENGINE = Engine()

_EPILOG = """\
examples:
  # open-loop serving: pathfinder at 200 QPS through 4 lanes for 3 s
  python -m repro.core.suite --names pathfinder --serve open --qps 200 \\
      --lanes 4 --serve-duration 3
  # threaded client: one issuing thread per lane, so host-side dispatch
  # contention is measured (dispatch_us column) instead of hidden
  python -m repro.core.suite --names gemm_f32_nn --serve closed \\
      --concurrency 8 --lanes 4 --serve-client threaded
  # co-location interference: gemm and kmeans share the lanes; both rows
  # carry slowdown-vs-isolated
  python -m repro.core.suite --names gemm_f32_nn --serve closed \\
      --concurrency 8 --lanes 4 --colocate kmeans
  # mixed-shape continuous batching: 2/3 of requests at preset 0, 1/3 at
  # preset 0 with cols=256, coalesced by the dynamic batcher into vmapped
  # buckets of up to 8 under a 2 ms wait budget
  python -m repro.core.suite --names pathfinder --serve open --qps 500 \\
      --serve-mix "0@2,0/cols=256@1" --serve-dispatch dynamic \\
      --batch-latency-budget 2000 --max-batch 8
  # trace-driven replay: the first run saves the arrival+shape stream,
  # later runs (any --serve-dispatch) replay the identical trace
  python -m repro.core.suite --names pathfinder --serve open --qps 500 \\
      --serve-mix "0@2,1@1" --serve-trace /tmp/mix.jsonl --serve-dispatch loop
  # structured tracing: every engine stage, serve request, and batcher
  # flush becomes a span in a Chrome trace-event file
  python -m repro.core.suite --names gemm_f32_nn --serve closed \\
      --concurrency 8 --lanes 4 --trace-out run.trace.json

reading the trace in Perfetto:
  open https://ui.perfetto.dev (or chrome://tracing) and load the
  --trace-out file. The "engine" process holds one track of stage spans
  (build / place / tune / compile / measure / characterize / serve) with
  bench + impl attributes on each; the "serve" process has one named
  track per dispatch lane carrying request enqueue->complete events; the
  "batcher" process has one track per shape-bucket queue whose batch[N]
  spans carry width / filled / cause (full | expired | flush). Or skim it
  from the terminal: python tools/trace_report.py run.trace.json

serving semantics:
  open-loop rows report offered_qps (the target arrival rate); a schedule
  cut short at its request cap additionally carries truncated=1, so the
  row never claims a load it did not offer. --slo-us S adds goodput_qps,
  the rate of completions with latency <= S microseconds (a request at
  exactly the SLO counts as good); without an SLO, goodput == achieved.
  The threaded client splits the arrival process into per-lane Poisson
  sub-schedules from seeded child RNGs: the merged stream still offers
  the target QPS and is deterministic for a fixed --seed.

batching semantics:
  --serve-mix is a comma-separated list of PRESET[/PARAM=VALUE...][@WEIGHT]
  buckets (weights default 1 and are normalized); each request's bucket is
  drawn from its own seeded stream, so the arrival process is identical
  with and without a mix. The engine precompiles one vmapped executable
  per (bucket, batch width) through the compile cache AND --cache-dir, so
  a warm run restores every bucket with zero XLA compiles. The dynamic
  batcher dispatches a bucket's queue when it can fill --max-batch, or
  when its oldest request has waited --batch-latency-budget microseconds —
  a partial batch is padded up to the smallest compiled width that holds
  it. Padding is measured, not hidden: rows carry batch_occupancy
  (filled/dispatched slots) and padding_waste (padded/dispatched slots,
  = 1 - occupancy), plus per-bucket p50/p95/p99 in bucket_latency_us.
  Latency is stamped from the scheduled arrival, so time spent waiting in
  a coalescing queue counts toward latency and goodput.

static contracts:
  the invariants this suite depends on (Workload batch_dims/pallas_kernel
  declarations, cache-key completeness, _timed_stage coverage, the
  zero-overhead hot-loop rule, record-schema stability, serve/obs lock
  discipline) are enforced by `python -m repro.check` — stdlib-ast only,
  no JAX needed, wired into CI as the lint job and locally via
  `tools/smoke.sh --check`. See `python -m repro.check --help` for rule
  ids and the per-line suppression comment.
"""


def run_suite(
    *,
    levels: Sequence[int] = (0, 1, 2),
    names: Sequence[str] | None = None,
    tags: Sequence[str] | None = None,
    domains: Sequence[str] | None = None,
    preset: int = 0,
    overrides: Mapping[str, Mapping[str, Any]] | None = None,
    iters: int = 5,
    warmup: int = 2,
    include_backward: bool = True,
    seed: int = 0,
    timing_window: int | None = None,
    devices: int = 1,
    placement: str = "replicate",
    scale_devices: Sequence[int] | None = None,
    serve: ServeSpec | None = None,
    impl: str = "xla",
    tune: bool = False,
    report_path: str | None = None,
    jsonl_path: str | None = None,
    verbose: bool = True,
    engine: Engine | None = None,
) -> list[BenchmarkRecord]:
    plan_kwargs: dict[str, Any] = {}
    if timing_window is not None:  # None = the plan's default window
        plan_kwargs["timing_window"] = timing_window
    plan = ExecutionPlan(
        levels=tuple(levels),
        names=tuple(names) if names is not None else None,
        tags=tuple(tags) if tags is not None else None,
        domains=tuple(domains) if domains is not None else None,
        preset=preset,
        overrides=overrides or {},
        include_backward=include_backward,
        iters=iters,
        warmup=warmup,
        seed=seed,
        placement=Placement(devices=devices, mode=placement),
        device_sweep=tuple(scale_devices) if scale_devices is not None else None,
        serve=serve,
        impl=impl,
        tune=tune,
        **plan_kwargs,
    )
    result = (engine or DEFAULT_ENGINE).run(
        plan, report_path=report_path, jsonl_path=jsonl_path, verbose=verbose
    )
    return result.records


def _parse_overrides(items: Sequence[str]) -> dict[str, dict[str, Any]]:
    """``name.param=value`` CLI overrides -> {name: {param: value}}."""
    out: dict[str, dict[str, Any]] = {}
    for item in items:
        try:
            target, value = item.split("=", 1)
            name, param = target.rsplit(".", 1)
        except ValueError:
            raise SystemExit(f"bad --override {item!r}; expected name.param=value")
        try:
            parsed: Any = int(value)
        except ValueError:
            try:
                parsed = float(value)
            except ValueError:
                parsed = value
        out.setdefault(name, {})[param] = parsed
    return out


def _parse_scale_devices(text: str | None) -> tuple[int, ...] | None:
    """``"1,2,4"`` -> (1, 2, 4)."""
    if text is None:
        return None
    try:
        counts = tuple(int(part) for part in text.split(",") if part.strip())
    except ValueError:
        raise SystemExit(
            f"bad --scale-devices {text!r}; expected comma-separated ints, e.g. 1,2,4"
        )
    if not counts:
        raise SystemExit(f"bad --scale-devices {text!r}; no device counts given")
    return counts


def _parse_mix(text: str) -> tuple[ShapeBucket, ...]:
    """``"0@2,0/cols=256@1"`` -> weighted ShapeBuckets.

    Grammar per comma-separated bucket: ``PRESET[/PARAM=VALUE...][@WEIGHT]``
    (weight defaults to 1.0; values parse as int, then float, then str —
    the --override convention).
    """

    def parse_value(value: str) -> Any:
        try:
            return int(value)
        except ValueError:
            try:
                return float(value)
            except ValueError:
                return value

    buckets = []
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        weight = 1.0
        if "@" in part:
            part, w = part.rsplit("@", 1)
            try:
                weight = float(w)
            except ValueError:
                raise SystemExit(
                    f"bad --serve-mix weight {w!r} in {text!r}; expected a number"
                )
        fields = part.split("/")
        try:
            preset = int(fields[0])
        except ValueError:
            raise SystemExit(
                f"bad --serve-mix bucket {part!r} in {text!r}; expected "
                "PRESET[/PARAM=VALUE...][@WEIGHT], e.g. 0@2,1/cols=256@1"
            )
        overrides = []
        for field in fields[1:]:
            if "=" not in field:
                raise SystemExit(
                    f"bad --serve-mix override {field!r} in {text!r}; "
                    "expected PARAM=VALUE"
                )
            k, v = field.split("=", 1)
            overrides.append((k, parse_value(v)))
        buckets.append(
            ShapeBucket(preset=preset, weight=weight, overrides=tuple(overrides))
        )
    if not buckets:
        raise SystemExit(f"bad --serve-mix {text!r}; no buckets given")
    return tuple(buckets)


def _parse_serve(args) -> ServeSpec | None:
    """A ServeSpec when any serving flag was used (--colocate alone
    implies a closed-loop serve), else None. Serve-tuning flags without a
    serve mode are a configuration error, not silently dropped."""
    tuning = {
        "--qps": args.qps,
        "--concurrency": args.concurrency,
        "--lanes": args.lanes,
        "--serve-duration": args.serve_duration,
        "--serve-client": args.serve_client,
        "--slo-us": args.slo_us,
        "--serve-dispatch": args.serve_dispatch,
        "--serve-mix": args.serve_mix,
        "--serve-trace": args.serve_trace,
        "--batch-latency-budget": args.batch_latency_budget,
        "--max-batch": args.max_batch,
    }
    if args.serve is None and args.colocate is None:
        stray = [flag for flag, value in tuning.items() if value is not None]
        if stray:
            raise PlanError(
                f"{', '.join(stray)} require --serve {{open,closed}} "
                "or --colocate NAME"
            )
        return None
    spec = ServeSpec()  # defaults live on the dataclass, not the CLI
    return ServeSpec(
        mode=args.serve or "closed",
        qps=args.qps if args.qps is not None else 50.0,
        concurrency=(
            args.concurrency if args.concurrency is not None else spec.concurrency
        ),
        lanes=args.lanes if args.lanes is not None else spec.lanes,
        duration_s=(
            args.serve_duration
            if args.serve_duration is not None
            else spec.duration_s
        ),
        colocate=args.colocate,
        client=args.serve_client if args.serve_client is not None else spec.client,
        slo_us=args.slo_us,
        dispatch=(
            args.serve_dispatch
            if args.serve_dispatch is not None
            else spec.dispatch
        ),
        mix=_parse_mix(args.serve_mix) if args.serve_mix is not None else None,
        trace=args.serve_trace,
        batch_budget_us=(
            args.batch_latency_budget
            if args.batch_latency_budget is not None
            else spec.batch_budget_us
        ),
        max_batch=args.max_batch if args.max_batch is not None else spec.max_batch,
    )


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the Mirovia/Altis suite",
        epilog=_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--levels", type=int, nargs="*", default=[0, 1, 2])
    ap.add_argument("--names", type=str, nargs="*", default=None)
    ap.add_argument("--tags", type=str, nargs="*", default=None)
    ap.add_argument("--domains", type=str, nargs="*", default=None)
    ap.add_argument("--preset", type=int, default=0)
    ap.add_argument("--override", action="append", default=[],
                    metavar="NAME.PARAM=VALUE",
                    help="Rodinia-style size override, repeatable")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timing-window", type=int, default=None, metavar="K",
                    help="windowed timing: K calls in flight per "
                         "synchronization alongside the sync-mode number "
                         "(default 4; 1 = sync-only)")
    ap.add_argument("--devices", type=int, default=1,
                    help="run on the first N devices")
    ap.add_argument("--placement", choices=PLACEMENT_MODES, default="replicate",
                    help="what multi-device runs put on each device: full "
                         "copies (replicate) or batch_dims-partitioned "
                         "inputs (shard)")
    ap.add_argument("--scale-devices", type=str, default=None,
                    metavar="N1,N2,...",
                    help="device-scaling sweep, e.g. 1,2,4,8: one record "
                         "per (benchmark, pass, count)")
    ap.add_argument("--serve", choices=SERVE_MODES, default=None,
                    help="serve each selected workload under load after "
                         "measuring it: open-loop arrivals at --qps or "
                         "closed-loop at --concurrency")
    ap.add_argument("--qps", type=float, default=None,
                    help="open-loop arrival rate (requests/s, default 50)")
    ap.add_argument("--concurrency", type=int, default=None,
                    help="closed-loop in-flight requests (also the "
                         "open-loop in-flight cap; default 4)")
    ap.add_argument("--lanes", type=int, default=None,
                    help="dispatch lanes (HyperQ-style work queues, "
                         "default 2)")
    ap.add_argument("--serve-duration", type=float, default=None,
                    metavar="SECONDS",
                    help="serving duration per workload (default 2.0)")
    ap.add_argument("--serve-client", choices=SERVE_CLIENTS, default=None,
                    help="host issue architecture: 'single' dispatches "
                         "every lane from one thread (default); 'threaded' "
                         "gives each lane its own issuing thread and "
                         "records dispatch overhead + per-lane QPS")
    ap.add_argument("--slo-us", type=float, default=None, metavar="US",
                    help="latency SLO in microseconds; rows gain "
                         "goodput_qps (completions with latency <= SLO "
                         "per second; latency == SLO counts as good)")
    ap.add_argument("--serve-dispatch", choices=SERVE_DISPATCH, default=None,
                    help="how requests map onto device programs: classic "
                         "N-lane dispatch (lanes, default), or the mixed-"
                         "shape paths — sync per-request (loop), fixed-"
                         "width vmap that waits to fill (batched), or the "
                         "continuous batcher (dynamic)")
    ap.add_argument("--serve-mix", type=str, default=None,
                    metavar="P[/K=V...][@W],...",
                    help="weighted request-shape mix for open-loop serving, "
                         "e.g. '0@2,1@1' or '0@3,0/cols=256@1'; per-request "
                         "buckets are drawn from a seeded stream so the mix "
                         "is deterministic per --seed (see batching "
                         "semantics below)")
    ap.add_argument("--serve-trace", type=str, default=None, metavar="PATH",
                    help="replayable JSONL arrival+shape trace: replayed "
                         "verbatim when PATH exists, else the generated "
                         "schedule is saved there for later runs to replay")
    ap.add_argument("--batch-latency-budget", type=float, default=None,
                    metavar="US",
                    help="dynamic batcher wait budget in microseconds "
                         "(default 2000): a partial batch dispatches — "
                         "padded, and the padding measured — once its "
                         "oldest request has waited this long")
    ap.add_argument("--max-batch", type=int, default=None, metavar="N",
                    help="largest batch width (default 8); the dynamic "
                         "batcher compiles power-of-two widths up to N per "
                         "bucket, --serve-dispatch batched uses exactly N")
    ap.add_argument("--impl", choices=IMPLS, default="xla",
                    help="implementation to compile and time: the lax/XLA "
                         "lowering (xla, default) or the hand-written "
                         "Pallas kernel (pallas) for workloads that declare "
                         "one — others fall back to xla with the reason in "
                         "the row (interpret mode on non-TPU hosts, flagged "
                         "impl_interpret)")
    ap.add_argument("--tune", action="store_true",
                    help="sweep each Pallas kernel's block/grid tune space "
                         "before compiling (windowed-timer trials); the "
                         "winner joins the record (tuned_params) and "
                         "persists in --cache-dir so warm runs skip the "
                         "sweep entirely")
    ap.add_argument("--colocate", type=str, default=None, metavar="NAME",
                    help="co-locate every served workload with this "
                         "benchmark and record slowdown-vs-isolated "
                         "(implies --serve closed)")
    ap.add_argument("--cache-dir", type=str, default=None,
                    help="persist compile artifacts here (serialized "
                         "executables, keyed by compile-"
                         "cache key, versioned by jax/jaxlib/backend/"
                         "topology) so warm runs skip retracing and XLA "
                         "compilation entirely; a CI accelerator — warm-run "
                         "timings include a thin dispatch wrapper")
    ap.add_argument("--no-backward", action="store_true")
    ap.add_argument("--report", type=str, default=None, help="JSON report path")
    ap.add_argument("--jsonl", type=str, default=None,
                    help="streaming JSONL report path (with run metadata)")
    ap.add_argument("--trace-out", type=str, default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON file (load in "
                         "https://ui.perfetto.dev or chrome://tracing, or "
                         "summarize with tools/trace_report.py): engine "
                         "stage spans plus per-lane serve requests and "
                         "per-queue batcher flushes as separate tracks")
    args = ap.parse_args(argv)
    enable_compile_cache(args.cache_dir)
    tracer = Tracer() if args.trace_out else None
    engine = (
        Engine(cache_dir=args.cache_dir, tracer=tracer)
        if (args.cache_dir or tracer is not None)
        else None
    )
    try:
        records = _run_cli(args, engine)
    except (PlanError, ValueError) as e:
        # Bad selection / placement / device count: a configuration error,
        # not a crash — exit 2 (the benchmarks/run.py --sections convention)
        # telling the operator what this host actually has.
        import jax

        print(f"error: {e}", file=sys.stderr)
        print(
            f"available devices: {jax.device_count()} "
            f"(backend={jax.default_backend()})",
            file=sys.stderr,
        )
        return 2
    for line in to_csv_lines(records):
        print(line)
    if engine is not None and engine.disk_cache is not None:
        # A disk cache that never hits is otherwise invisible from the
        # CLI: always say what it did, and why warm loads fell back.
        print(f"# {engine.disk_cache.summary()}", file=sys.stderr)
    if tracer is not None:
        n = tracer.export_chrome(args.trace_out)
        print(
            f"# trace: {n} spans -> {args.trace_out} "
            "(load in https://ui.perfetto.dev or chrome://tracing; "
            "summarize with tools/trace_report.py)",
            file=sys.stderr,
        )
    errors = [r for r in records if r.status != "ok"]
    for r in errors:
        print(f"# ERROR {r.name}: {r.error}", file=sys.stderr)
    return 1 if errors else 0


def _run_cli(args, engine: Engine | None = None) -> list[BenchmarkRecord]:
    return run_suite(
        levels=args.levels,
        names=args.names,
        tags=args.tags,
        domains=args.domains,
        preset=args.preset,
        overrides=_parse_overrides(args.override),
        iters=args.iters,
        warmup=args.warmup,
        seed=args.seed,
        timing_window=args.timing_window,
        devices=args.devices,
        placement=args.placement,
        scale_devices=_parse_scale_devices(args.scale_devices),
        serve=_parse_serve(args),
        impl=args.impl,
        tune=args.tune,
        include_backward=not args.no_backward,
        report_path=args.report,
        jsonl_path=args.jsonl,
        verbose=False,
        engine=engine,
    )


if __name__ == "__main__":
    sys.exit(main())
