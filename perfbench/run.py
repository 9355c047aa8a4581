"""Run one benchmark cell once, on the chip this process is started on.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, and prints no result, where JAX finds no TPU or fewer chips
than the cell asks for, and where the checkout holds no program. Otherwise
the last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = _args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"perfbench: no program under {ROOT}/src/repro", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from perfbench import harness

    try:
        bench = harness.Bench(ROOT)
        cell = bench.cell(args.workload)
        harness.place_compile_cache(ROOT)
        devices = harness.require_chips(cell.chips)
    except harness.BenchError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    result = harness.run_cell(
        bench, cell.name, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, devices=devices,
    )
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
