"""The readers of the program's own spans, on traced runs of the steady
cell at CPU sizes: every metric is there, the three shares of the queue
wait add up to the wait, and a garbage collection or a late wake-up that
is made to happen shows in its metric."""

from __future__ import annotations

import gc
import os
import time
import types

import pytest

from perfbench import spans
from perfbench.tests.small import run_small, small_root
from repro.obs import PROFILER
from repro.serve import batcher

CELL = "pathfinder-mix.steady"
SPAN_METRICS = (
    "admit_late_us.steady", "fill_wait_us.steady", "backpressure_us.steady",
    "oversleep_ms.steady", "gc_pause_ms.steady",
)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(str(tmp_path_factory.mktemp("bench")))


def _spans(root: str, name: str) -> list:
    run = types.SimpleNamespace(trace=True, cell=types.SimpleNamespace(name=CELL))
    return spans.of(run, os.path.join(root, "perfbench", "metrics", "any.py"), name)


def _value(result: dict, name: str) -> float:
    return result["metrics"][name]["value"]


def _when_profiled(nth: int, action):
    """A wrapper for the program's callables that runs ``action`` once, at
    the ``nth`` call made while the profiler records (a few calls in, so
    inside the traced window)."""
    seen = []

    def wrap(exe):
        def call(*args):
            if PROFILER.enabled:
                seen.append(None)
                if len(seen) == nth:
                    action()
            return exe(*args)

        return call

    return wrap


def test_the_waits_add_up(root):
    result = run_small(root, CELL, trace=True)
    assert result["correct"]
    for name in SPAN_METRICS:
        assert result["metrics"][name]["value"] is not None, name
    batches = [s for _, s in _spans(root, spans.DISPATCH)]
    assert batches
    filled = sum(s["filled"] for s in batches)
    waited = sum(s["wait_us"] for s in batches) / filled  # t_dispatch - due
    split = sum(_value(result, m) for m in SPAN_METRICS[:3])
    assert split == pytest.approx(waited, rel=1e-6)
    assert min(_value(result, m) for m in SPAN_METRICS[:3]) >= 0.0


def test_a_collection_shows_in_gc_pause(root):
    took = []

    def collect():
        t = time.perf_counter()
        gc.collect()
        took.append(time.perf_counter() - t)

    result = run_small(root, CELL, trace=True, fault=_when_profiled(5, collect))
    assert result["correct"] and len(took) == 1
    assert [s["generation"] for _, s in _spans(root, spans.GC) if s["generation"] == 2] == [2]
    assert _value(result, "gc_pause_ms.steady") >= 0.9 * took[0] * 1e3


def test_a_late_wake_shows_in_oversleep(root, monkeypatch):
    late_s = 0.05
    made = []

    class LateClock:
        perf_counter = staticmethod(time.perf_counter)

        @staticmethod
        def sleep(s: float) -> None:
            if PROFILER.enabled and not made:
                made.append(s)
                s += late_s
            time.sleep(s)

    monkeypatch.setattr(batcher, "time", LateClock)
    result = run_small(root, CELL, trace=True)
    assert result["correct"] and made
    assert _value(result, "oversleep_ms.steady") >= late_s * 1e3
    (late,) = [s for _, s in _spans(root, spans.LATE_WAKE) if s["slept_us"] - s["asked_us"] >= late_s * 1e6]
    assert late["asked_us"] == pytest.approx(made[0] * 1e6)


def test_a_program_without_the_spans_leaves_them_out(root, monkeypatch):
    """As on a program that predates the spans: no ``batcher.dispatch``
    span in the slice, so the readers return nothing and do not raise."""
    monkeypatch.setattr(batcher, "PROFILER", types.SimpleNamespace(enabled=False))
    result = run_small(root, CELL, trace=True)
    assert result["correct"]
    assert not set(SPAN_METRICS) & set(result["metrics"])
    assert "compile_s" in result["metrics"]
