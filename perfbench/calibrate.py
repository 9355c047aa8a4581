"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 2 [--control 3]

In one process, for each seed: set the cell up, run a short window at the
cell's own load, and compare its answers with the reference (the program's
reading); for the first ``--control`` seeds, also compare the reference
computed one precision lower (the control's reading). Prints one JSON line
per seed. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--control", type=int, default=3, help="seeds that also read the control")
    args = p.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from perfbench import harness

    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    harness.place_compile_cache(ROOT)
    harness.require_chips(cell.chips)
    config, traffic = bench.config(cell.config), bench.traffic(cell.traffic)
    driver, ref = bench.driver(traffic["driver"]), bench.ref(cell.config)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        session = driver.Session(config, traffic, ref, harness.seed_key(seed), seed=seed)
        session.setup()
        window = session.window(args.seconds)
        line = {"workload": cell.name, "seed": seed, "answers": window["attempted"],
                "program": session.check()}
        if i < args.control:
            line["control"] = session.check(control=True)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
