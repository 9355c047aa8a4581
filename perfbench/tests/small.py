"""A copy of the benchmark at sizes the CPU runs in a second, for tests.

``small_root(tmp)`` copies ``BENCHMARK.json`` and ``perfbench/`` under
``tmp`` and shrinks the configurations and rates there; the repository's
own files are never touched.
"""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMALL_CONFIGS = {
    "gemm-bf16-n8192": {"overrides": {"n": 256}},
    "pathfinder-mix": {
        "preset": 0,
        "buckets": [
            # 512 rows as in the cell, so path costs outgrow int8 as there
            {"label": "p0", "overrides": {"rows": 512, "cols": 512}, "rows": 512, "cols": 512,
             "weight": 2},
            {"label": "p0-cols256", "overrides": {"rows": 512, "cols": 256}, "rows": 512,
             "cols": 256, "weight": 1},
        ],
    },
}
SMALL_TRAFFIC = {"lead_in_s": 0.2, "qps": 300}


def write_json(path: str, obj) -> None:
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(obj, fh, indent=1)


def small_root(tmp: str) -> str:
    root = os.path.join(tmp, "checkout")
    shutil.copytree(
        os.path.join(REPO, "perfbench"), os.path.join(root, "perfbench"),
        ignore=shutil.ignore_patterns("__pycache__", "out", "scratch", ".jax_cache"),
    )
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for name, patch in SMALL_CONFIGS.items():
        path = os.path.join(root, "perfbench", "configs", name + ".json")
        with open(path, encoding="utf-8") as fh:
            config = json.load(fh)
        config.update(patch)
        write_json(path, config)
    tdir = os.path.join(root, "perfbench", "traffic")
    for name in os.listdir(tdir):
        if name.endswith(".json"):
            with open(os.path.join(tdir, name), encoding="utf-8") as fh:
                mix = json.load(fh)
            if mix["driver"] == "open_loop":
                mix.update(SMALL_TRAFFIC)
                write_json(os.path.join(tdir, name), mix)
    return root


def run_small(root: str, cell: str, *, seed: int = 7, seconds: float = 0.5,
              trace: bool = False, fault=None) -> dict:
    """One run of ``cell`` under ``root`` on this host's devices, past the
    harness's look for a chip."""
    import time

    import jax

    from perfbench import harness

    bench = harness.Bench(root)
    return harness.run_cell(
        bench, cell, seed=seed, seconds=seconds, trace=trace,
        t_start=time.perf_counter(), devices=jax.devices(), fault=fault,
    )
