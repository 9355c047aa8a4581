"""Benchmark driver: one section per paper table/figure (DESIGN.md §7).

Prints ``name,us_per_call,derived`` CSV. Sections:
  table1   — suite listing (Table I)
  fig12    — level 0/1 utilization (Figs. 1–2 analogue)
  fig3/4   — DNN forward/backward utilization
  fig5     — application-tier utilization (Fig. 5)
  fig_scaling — device-scaling sweep (sharded data-parallel placement)
  fig_concurrency — dispatch-lane speedup + co-location interference
  fig_batching — continuous batching: loop vs lanes vs dynamic goodput
  fig_impl — XLA vs Pallas implementation axis (autotuned block sizes)
  fig_trace — per-stage engine time breakdown (obs layer, schema v8)
  table2   — per-layer kernel classification (Table II)
  feat_*   — §V-B modern-feature studies (HyperQ / UM / CG / DP analogues)
  roofline — §Roofline table from the multi-pod dry-run artifacts

Suite-backed sections (fig12/3/4/5) run through the staged engine via
``run_suite``: one shared compile cache across sections (fig4 reuses fig3's
builds) and per-benchmark fault isolation inside each section. The
try/except here is only a last-resort guard for the non-suite sections.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

if __package__ in (None, ""):  # `python benchmarks/run.py` (vs -m benchmarks.run)
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SECTION_NAMES = (
    "table1",
    "fig12",
    "fig3",
    "fig4",
    "fig5",
    "fig_scaling",
    "fig_concurrency",
    "fig_batching",
    "fig_impl",
    "fig_trace",
    "table2",
    "feat_hyperq",
    "feat_unified_memory",
    "feat_coop_groups",
    "feat_dynamic_parallelism",
    "roofline",
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sections", nargs="*", default=None,
                    help=f"subset of sections to run; valid: {', '.join(SECTION_NAMES)}")
    ap.add_argument("--preset", type=int, default=0)
    args = ap.parse_args(argv)

    selected = args.sections or list(SECTION_NAMES)
    unknown = [s for s in selected if s not in SECTION_NAMES]
    if unknown:
        print(f"unknown section(s): {', '.join(unknown)}", file=sys.stderr)
        print(f"valid sections: {', '.join(SECTION_NAMES)}", file=sys.stderr)
        return 2

    # Imported after validation so a bad --sections fails fast, before jax.
    from repro.core.engine import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        feat_coop_groups,
        feat_dynamic_parallelism,
        feat_hyperq,
        feat_unified_memory,
        fig3_dnn_forward,
        fig4_dnn_backward,
        fig5_suite_utilization,
        fig12_legacy_utilization,
        fig_batching,
        fig_concurrency,
        fig_impl,
        fig_scaling,
        fig_trace,
        roofline_table,
        table1_suite,
        table2_dnn_kernels,
    )

    sections = {
        "table1": lambda: table1_suite.rows(),
        "fig12": lambda: fig12_legacy_utilization.rows(preset=args.preset),
        "fig3": lambda: fig3_dnn_forward.rows(preset=args.preset),
        "fig4": lambda: fig4_dnn_backward.rows(preset=args.preset),
        "fig5": lambda: fig5_suite_utilization.rows(preset=args.preset),
        "fig_scaling": lambda: fig_scaling.rows(preset=args.preset),
        "fig_concurrency": lambda: fig_concurrency.rows(preset=args.preset),
        "fig_batching": lambda: fig_batching.rows(preset=args.preset),
        "fig_impl": lambda: fig_impl.rows(preset=args.preset),
        "fig_trace": lambda: fig_trace.rows(preset=args.preset),
        "table2": lambda: table2_dnn_kernels.rows(preset=max(args.preset, 1)),
        "feat_hyperq": feat_hyperq.rows,
        "feat_unified_memory": feat_unified_memory.rows,
        "feat_coop_groups": feat_coop_groups.rows,
        "feat_dynamic_parallelism": feat_dynamic_parallelism.rows,
        "roofline": lambda: roofline_table.rows("single")
        + roofline_table.rows("multi")
        + roofline_table.rows_from_latest_report(),
    }
    # SECTION_NAMES exists so --sections validates before the jax imports
    # above; keep the two in sync.
    assert set(sections) == set(SECTION_NAMES), "update SECTION_NAMES"
    from benchmarks.common import ERROR_PREFIX

    print("name,us_per_call,derived")
    failures = 0
    for name in selected:
        t0 = time.time()
        try:
            for n, us, d in sections[name]():
                if d.startswith(ERROR_PREFIX):  # engine fault-isolated row
                    failures += 1
                    print(f"# ERROR {n}: {d}", file=sys.stderr, flush=True)
                print(f"{n},{us:.2f},{d}", flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"{name}.FAILED,0.00,error", flush=True)
        print(
            f"# section {name} done in {time.time() - t0:.1f}s",
            file=sys.stderr, flush=True,
        )
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
