"""Finding a cell's pieces by name; adding a cell with new files only."""

from __future__ import annotations

import hashlib
import json
import os
import shutil

import pytest

from perfbench import harness
from perfbench.tests.small import REPO, run_small, write_json

CHIPS = {
    "gemm-bf16-n8192.pallas": 1,
    "pathfinder-mix.steady": 4,
    "gemm-bf16-n8192.xla": 1,
    "pathfinder-mix.overload": 1,
}
CELLS = list(CHIPS)


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(REPO)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files(bench, cell):
    c = bench.cell(cell)
    assert c.chips == CHIPS[cell]
    config = bench.config(c.config)
    assert config["name"] == c.config
    traffic = bench.traffic(c.traffic)
    driver = bench.driver(traffic["driver"])
    assert hasattr(driver, "Session")
    ref = bench.ref(c.config)
    assert callable(ref.compare) and callable(ref.control) and ref.LIMITS
    for trace in (False, True):
        metrics = bench.metrics_for(cell, trace)
        assert metrics, (cell, trace)
        for m in metrics:
            assert callable(bench.reader(m["name"]).read)


@pytest.mark.parametrize(
    "kind, name",
    [
        ("cell", "gemm-bf16-n8192.tpu"),
        ("config", "gemm-bf16-n4096"),
        ("traffic", "closed9-xla"),
        ("reader", "tokens_per_s"),
        ("driver", "replay"),
        ("ref", "../run"),
        ("traffic", "../../BENCHMARK"),
    ],
)
def test_unknown_names_are_refused(bench, kind, name):
    with pytest.raises(harness.BenchError):
        getattr(bench, kind)(name)


def test_metrics_for_each_cell(bench):
    e2e = {c: {m["name"] for m in bench.metrics_for(c, False)} for c in CELLS}
    assert e2e == {
        "gemm-bf16-n8192.pallas": {"call_us", "setup_s"},
        "gemm-bf16-n8192.xla": {"call_us", "setup_s"},
        "pathfinder-mix.steady": {"serve_p95_us", "served_qps", "setup_s"},
        "pathfinder-mix.overload": {"served_qps", "setup_s"},
    }
    layer = {c: {m["name"] for m in bench.metrics_for(c, True)} for c in CELLS}
    assert layer["pathfinder-mix.overload"] == {
        "batch_occupancy.overload", "device_idle.overload", "compile_s",
    }
    assert "gemm_roofline" in layer["gemm-bf16-n8192.xla"]


def _digests(root: str) -> dict:
    out = {}
    for d, _, files in os.walk(os.path.join(root, "perfbench")):
        for f in files:
            if f.endswith((".py", ".json")):
                p = os.path.join(d, f)
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_a_throwaway_cell_is_new_files_and_entries_only(tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(
        os.path.join(REPO, "perfbench"), os.path.join(root, "perfbench"),
        ignore=shutil.ignore_patterns("__pycache__", "out", "scratch", ".jax_cache"),
    )
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    before = _digests(root)

    pb = os.path.join(root, "perfbench")
    with open(os.path.join(pb, "configs", "gemm-bf16-n8192.json"), encoding="utf-8") as fh:
        config = json.load(fh)
    config.update(name="gemm-tiny", overrides={"n": 384})
    write_json(os.path.join(pb, "configs", "gemm-tiny.json"), config)
    shutil.copy(os.path.join(pb, "refs", "gemm-bf16-n8192.py"), os.path.join(pb, "refs", "gemm-tiny.py"))
    write_json(os.path.join(pb, "traffic", "closed2-xla.json"),
               {"driver": "closed_loop", "in_flight": 2, "impl": "xla", "trace_seconds": 0.1})
    with open(os.path.join(pb, "metrics", "calls_done.py"), "w", encoding="utf-8") as fh:
        fh.write("def read(run):\n    return run.window['calls']\n")

    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as fh:
        spec = json.load(fh)
    spec["configs"].append({"name": "gemm-tiny", "source": "x", "file": "perfbench/configs/gemm-tiny.json",
                            "reduced": [], "why": "throwaway"})
    spec["workloads"].append({"name": "gemm-tiny.closed2", "config": "gemm-tiny",
                              "traffic": "closed2-xla", "chips": 1, "why": "throwaway"})
    spec["end_to_end"].append({"name": "calls_done", "unit": "calls", "better": "higher",
                               "bound": 0.01, "source": "host_clock", "workloads": ["gemm-tiny.closed2"]})
    write_json(os.path.join(root, "BENCHMARK.json"), spec)

    after = _digests(root)
    assert {k: after[k] for k in before} == before  # no existing file changed
    result = run_small(root, "gemm-tiny.closed2", seconds=0.3)
    assert result["correct"]
    assert result["metrics"]["calls_done"]["value"] == result["attempted"] > 0
    assert set(result["metrics"]) == {"calls_done", "setup_s"}
