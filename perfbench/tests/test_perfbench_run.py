"""``perfbench/run.py`` refuses to run, and prints no result, without a TPU
or without the program."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from perfbench import harness
from perfbench.tests.small import REPO

ARGS = ["--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"]


def _run(root: str, workload: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py"), "--workload", workload, *ARGS],
        cwd=root, env=env, capture_output=True, text=True, timeout=120,
    )


@pytest.mark.parametrize("workload", ["gemm-bf16-n8192.xla", "pathfinder-mix.steady"])
def test_no_tpu_no_result(workload):
    r = _run(REPO, workload)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert "{" not in r.stdout


def test_unknown_workload_no_result():
    r = _run(REPO, "gemm-bf16-n8192.cuda")
    assert r.returncode == 2 and "unknown workload" in r.stderr
    assert "{" not in r.stdout


def test_a_bare_directory_no_result(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "perfbench"), os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "out", "scratch", ".jax_cache"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    r = _run(root, "gemm-bf16-n8192.xla")
    assert r.returncode != 0 and "no program" in r.stderr
    assert "{" not in r.stdout


def test_require_chips_refuses_the_cpu():
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.require_chips(1)
