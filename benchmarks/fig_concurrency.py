"""Concurrency figure: dispatch-lane speedup, client architectures, and
co-location interference.

The §V-B HyperQ study, generalized suite-wide through the serving
subsystem (``repro.serve``): any registered workload is served closed-loop
at each lane count in the sweep — under *both* host issue architectures,
side by side — and the dispatch speedup is its achieved QPS over the
single-lane serial baseline (lanes=1, concurrency=1 — one request in
flight, the no-concurrency floor). The paper's curve saturates near the
32 hardware work queues; here saturation lands wherever host dispatch
stops hiding behind device execution — and comparing the ``single``
client (every lane issued from one thread) against the ``threaded``
client (one issuing thread per lane) shows exactly where the
single-threaded client itself was the bottleneck. Threaded rows carry
the measured per-request dispatch overhead.

Both clients serve the *same cached executable*: one compile per
workload feeds the entire sweep (the engine's compile cache is keyed on
the workload, not the serving client), and the script prints the cache
traffic so "no recompile" is visible, not assumed. With ``--cache-dir``
the sweep runs against the executable cache: a warm directory
restores serialized executables, so the whole figure — timer, roofline
characterization, and every serving row — costs *zero* XLA compilations
(the disk-cache summary printed at the end is the evidence).

The co-location half serves a workload pair through split lanes
(``ServeSpec.colocate``) and reports both tenants' p50 slowdown vs their
isolated baselines — the §V-B kernel co-location experiment as a table.

As a section (``benchmarks/run.py --sections fig_concurrency``) it emits
the standard CSV rows; as a script it renders the tables. Everything
routes through ``run_suite`` and the shared engine.
"""

from __future__ import annotations

import os
import sys

if __package__ in (None, ""):  # `python benchmarks/fig_concurrency.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import Row, parse_derived, record_rows
from repro.core import run_suite
from repro.core.plan import SERVE_CLIENTS, ServeSpec

DEFAULT_LANES = (1, 2, 4, 8, 16, 32)
DEFAULT_CLIENTS = SERVE_CLIENTS  # ("single", "threaded")
# One wavefront DP kernel (the paper's HyperQ subject) and one MXU kernel,
# so the dispatch curve and the interference pair cover both regimes.
DEFAULT_NAMES = ("pathfinder", "gemm_f32_nn")
FAST = dict(iters=1, warmup=0, include_backward=False, verbose=False)


def _serve_rows(tag: str, records, extra) -> list[Row]:
    return record_rows(
        tag,
        records,
        lambda r: (
            f"{extra(r)}p50_us={r.latency_p50_us:.1f};"
            f"p99_us={r.latency_p99_us:.1f};qps={r.achieved_qps:.1f}"
        ),
    )


def lane_sweep_rows(
    preset: int = 0,
    names=DEFAULT_NAMES,
    lanes_sweep=DEFAULT_LANES,
    duration_s: float = 0.3,
    clients=DEFAULT_CLIENTS,
    engine=None,
) -> list[Row]:
    """One row per (workload, client, lane count): achieved QPS plus the
    dispatch speedup over the same (workload, client)'s narrowest-lane
    baseline (lanes=1 when the sweep includes it — one request in flight,
    the serial floor). Threaded rows add ``dispatch_overhead_us``."""
    out: list[Row] = []
    base_qps: dict[tuple[str, str], float] = {}
    # Ascending order puts the baseline first, so every later row can
    # carry a speedup no matter what subset the caller swept.
    sweep = sorted(set(lanes_sweep))
    for n in sweep:
        # lanes=1 runs one request at a time (the serial-dispatch floor);
        # wider sweeps keep 2 in-flight requests per lane, the paper's
        # N-kernels-on-N-queues shape.
        concurrency = 1 if n == 1 else 2 * n
        for client in clients:
            serve = ServeSpec(
                mode="closed", concurrency=concurrency, lanes=n,
                duration_s=duration_s, client=client,
            )
            records = run_suite(
                names=list(names), preset=preset, serve=serve, engine=engine,
                **FAST,
            )
            for r in records:
                if r.status == "ok" and r.achieved_qps:
                    base_qps.setdefault((r.name, client), r.achieved_qps)

            def extra(r, n=n, concurrency=concurrency, client=client):
                base = base_qps.get((r.name, client))
                speedup = (
                    f"{r.achieved_qps / base:.2f}"
                    if base and r.achieved_qps
                    else "-"
                )
                overhead = (
                    f"{r.dispatch_overhead_us:.1f}"
                    if r.dispatch_overhead_us is not None
                    else "-"
                )
                return (
                    f"client={client};lanes={n};concurrency={concurrency};"
                    f"dispatch_speedup={speedup};"
                    f"dispatch_overhead_us={overhead};"
                )

            out.extend(
                (f"{name}.{client}.l{n}", us, derived)
                for name, us, derived in _serve_rows(
                    "fig_concurrency", records, extra
                )
            )
    return out


def colocation_rows(
    preset: int = 0,
    names=DEFAULT_NAMES,
    duration_s: float = 0.3,
    lanes: int = 2,
    concurrency: int = 4,
    engine=None,
) -> list[Row]:
    """Both tenants' slowdown-vs-isolated for each adjacent pair in
    ``names`` (the interference matrix's off-diagonal samples)."""
    out: list[Row] = []
    pairs = [(names[i], names[i + 1]) for i in range(len(names) - 1)]
    for a, b in pairs:
        serve = ServeSpec(
            mode="closed",
            concurrency=concurrency,
            lanes=lanes,
            duration_s=duration_s,
            colocate=b,
        )
        records = run_suite(
            names=[a], preset=preset, serve=serve, engine=engine, **FAST
        )
        out.extend(
            _serve_rows(
                "fig_concurrency.colocate",
                records,
                lambda r: (
                    f"pair={a}+{b};slowdown="
                    + (
                        f"{r.slowdown_vs_isolated:.2f};"
                        if r.slowdown_vs_isolated is not None
                        else "-;"
                    )
                ),
            )
        )
    return out


def rows(preset: int = 0) -> list[Row]:
    return lane_sweep_rows(preset=preset) + colocation_rows(preset=preset)


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", type=int, default=0)
    ap.add_argument("--names", nargs="*", default=list(DEFAULT_NAMES))
    ap.add_argument("--lanes", type=int, nargs="*", default=list(DEFAULT_LANES))
    ap.add_argument("--clients", nargs="*", choices=list(SERVE_CLIENTS),
                    default=list(DEFAULT_CLIENTS),
                    help="host issue architectures to sweep side by side")
    ap.add_argument("--duration", type=float, default=0.3)
    ap.add_argument("--cache-dir", type=str, default=None,
                    help="executable cache directory: a warm dir "
                         "restores serialized executables, making the "
                         "whole figure a zero-XLA-compile run")
    args = ap.parse_args()

    from repro.core.engine import Engine
    from repro.core.suite import DEFAULT_ENGINE

    engine = Engine(cache_dir=args.cache_dir) if args.cache_dir else DEFAULT_ENGINE
    misses0 = engine.cache.misses
    sweep = lane_sweep_rows(
        preset=args.preset,
        names=tuple(args.names),
        lanes_sweep=tuple(args.lanes),
        duration_s=args.duration,
        clients=tuple(args.clients),
        engine=engine,
    )
    ok = [row for row in sweep if "qps=" in row[2]]
    if not ok:
        print(
            f"fig_concurrency: no ok serve records out of {len(sweep)} rows; "
            "see stderr for per-benchmark errors",
            file=sys.stderr,
        )
        return 1

    # Pivot: (benchmark, client) x lane count -> (qps, speedup).
    table: dict[tuple[str, str], dict[int, tuple[float, str]]] = {}
    counts: list[int] = []
    for name, _us, derived in ok:
        fields = parse_derived(derived)
        n = int(fields["lanes"])
        if n not in counts:
            counts.append(n)
        client = fields.get("client", "single")
        bench = (
            name.removeprefix("fig_concurrency.")
            .rsplit(".l", 1)[0]
            .removesuffix(f".{client}")
        )
        table.setdefault((bench, client), {})[n] = (
            float(fields["qps"]), fields["dispatch_speedup"]
        )
    label_w = 34
    print(f"{'benchmark [client]':<{label_w}}" + "".join(
        f"{f'{n}-lane qps':>14}{'speedup':>10}" for n in counts
    ))
    for (bench, client), per in table.items():
        line = f"{f'{bench} [{client}]':<{label_w}}"
        for n in counts:
            qps, speedup = per.get(n, (0.0, "-"))
            line += f"{qps:>14.1f}{speedup:>10}"
        print(line)
    # One compile per served (workload, pass): both clients and every lane
    # count reuse the cached executable. Print the traffic as evidence —
    # and with a warm --cache-dir even those "misses" were executable
    # restores, not XLA compilations (the hlocache line says which).
    print(
        f"# compile cache: {engine.cache.misses - misses0} misses "
        f"across {len(args.clients)} clients x {len(counts)} lane counts "
        f"({engine.cache.hits} hits total)",
        file=sys.stderr,
    )
    if engine.disk_cache is not None:
        print(f"# {engine.disk_cache.summary()}", file=sys.stderr)

    print()
    if "threaded" in args.clients:
        # Co-location dispatch is single-threaded by construction (tenants
        # alternate submissions — ServeSpec rejects colocate+threaded), so
        # the requested threaded client does NOT apply below. Say so
        # instead of silently dropping the request.
        print(
            "# note: co-location forces the single-threaded client "
            "(tenants alternate submissions); ignoring --clients threaded "
            "for the interference table",
            file=sys.stderr,
        )
    print(f"{'pair (tenant row)':<44}{'p50_us':>10}{'qps':>10}{'slowdown':>10}")
    for name, us, derived in colocation_rows(
        preset=args.preset, names=tuple(args.names), duration_s=args.duration,
        engine=engine,
    ):
        fields = parse_derived(derived)
        label = name.removeprefix("fig_concurrency.colocate.")
        print(
            f"{fields.get('pair', '?') + ' / ' + label:<44}"
            f"{us:>10.1f}{float(fields.get('qps', 0)):>10.1f}"
            f"{fields.get('slowdown', '-'):>10}"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
