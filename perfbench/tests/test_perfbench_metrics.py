"""The arithmetic of the metric readers, on windows made up here."""

from __future__ import annotations

import math

import pytest

from perfbench import harness
from perfbench.tests.small import REPO
from perfbench.trace import TraceSummary


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(REPO)


def _run(window, trace=None, config=None, peaks=None, stage_us=None, setup_s=12.5):
    return harness.Run(
        cell=harness.Cell("c", "cfg", "t", 1), config=config or {}, traffic={}, setup_s=setup_s,
        window=window, stage_us=stage_us or {}, peaks=peaks or {}, trace=trace,
    )


def _open(requests, completed_at=None, batches=(), t0=10.0, t1=12.0, host_until=None):
    return {
        "kind": "open", "t0": t0, "t1": t1, "requests": requests,
        "completed_at": completed_at if completed_at is not None else [r[1] for r in requests if r[1]],
        "batches": list(batches), "host_until": t1 if host_until is None else host_until,
    }


def _read(bench, name, run):
    return bench.reader(name).read(run)


def test_call_us_is_window_over_calls(bench):
    run = _run({"kind": "closed", "t0": 1.0, "t1": 3.0, "calls": 4, "dispatch_s": [1e-4, 3e-4]})
    assert _read(bench, "call_us", run) == pytest.approx(500_000.0)
    assert _read(bench, "dispatch_us.timed", run) == pytest.approx(200.0)
    assert _read(bench, "serve_p95_us", run) is None
    assert _read(bench, "setup_s", run) == 12.5


def test_serve_p95_counts_a_stall(bench):
    # 40 requests, 1 ms each, but a 200 ms stall holds back three of them.
    reqs = [(10.0 + i * 0.05, 10.0 + i * 0.05 + 0.001, 10.0 + i * 0.05) for i in range(40)]
    for i in (20, 21, 22):
        due = reqs[i][0]
        reqs[i] = (due, due + 0.2, due + 0.15)
    p95 = _read(bench, "serve_p95_us", _run(_open(reqs)))
    # nearest rank 38 of 40: the fastest of the three stalled requests
    assert p95 == pytest.approx(0.2e6 - 0.0, rel=1e-6)
    wait = _read(bench, "queue_wait_us.steady", _run(_open(reqs)))
    assert wait == pytest.approx(3 * 0.15e6 / 40)


def test_missing_requests_count_as_slowest(bench):
    reqs = [(10.0 + i * 0.05, 10.0 + i * 0.05 + 0.001, 10.0 + i * 0.05) for i in range(40)]
    reqs[5] = (reqs[5][0], None, None)
    assert _read(bench, "serve_p95_us", _run(_open(reqs))) == pytest.approx(1000.0, rel=1e-6)
    for i in (6, 7):
        reqs[i] = (reqs[i][0], None, None)
    assert _read(bench, "serve_p95_us", _run(_open(reqs))) is None
    lat_all_missing = _run(_open([(10.0, None, None)], completed_at=[]))
    assert _read(bench, "serve_p95_us", lat_all_missing) is None


def test_served_qps_counts_completions_inside_the_window(bench):
    done = [9.9, 10.0, 10.5, 11.0, 11.99, 12.0, 12.01, 13.0]  # 5 inside [10, 12]
    run = _run(_open([], completed_at=done))
    assert _read(bench, "served_qps", run) == pytest.approx(5 / 2.0)


def test_queue_wait_and_occupancy_stop_at_the_traced_slice(bench):
    reqs = [(10.0, 10.01, 10.002), (11.0, 11.01, 11.004), (11.6, 11.9, 11.8)]
    batches = [(9.9, 4, 4), (10.002, 4, 3), (11.004, 2, 1), (11.8, 4, 1), (12.5, 4, 4)]
    run = _run(_open(reqs, batches=batches, host_until=11.5))
    assert _read(bench, "queue_wait_us.steady", run) == pytest.approx((2000 + 4000) / 2)
    assert _read(bench, "batch_occupancy.overload", run) == pytest.approx(100 * 4 / 6)


def test_device_idle_needs_a_device_in_the_trace(bench):
    closed = {"kind": "closed", "t0": 0.0, "t1": 1.0, "calls": 1, "dispatch_s": []}
    for name in ("device_idle.timed", "device_idle.steady", "device_idle.overload"):
        assert _read(bench, name, _run(closed)) is None
        host_only = TraceSummary(window_s=1.0, devices=0, busy_s=0.0, ops=[], modules={}, gaps=[])
        assert _read(bench, name, _run(closed, trace=host_only)) is None
        busy = TraceSummary(window_s=2.0, devices=1, busy_s=1.5, ops=[], modules={}, gaps=[])
        assert _read(bench, name, _run(closed, trace=busy)) == pytest.approx(25.0)


def test_gemm_roofline_uses_the_busiest_program(bench):
    peaks = {"bf16_flop_per_s": 197e12, "hbm_byte_per_s": 819e9}
    n = 8192
    least = 2 * n**3 / 197e12
    trace = TraceSummary(
        window_s=1.0, devices=1, busy_s=1.0, ops=[],
        modules={"jit_fn": (10, 10 * least / 0.8), "jit_other": (50, 0.001)}, gaps=[],
    )
    closed = {"kind": "closed", "t0": 0.0, "t1": 1.0, "calls": 10, "dispatch_s": []}
    run = _run(closed, trace=trace, config={"overrides": {"n": n}}, peaks=peaks)
    assert _read(bench, "gemm_roofline", run) == pytest.approx(80.0)
    assert _read(bench, "gemm_roofline", _run(closed, config={"overrides": {"n": n}}, peaks=peaks)) is None
    assert _read(bench, "gemm_roofline", _run(closed, trace=trace, config={"overrides": {"n": n}})) is None


def test_compile_s_reads_the_engine_stage(bench):
    closed = {"kind": "closed", "t0": 0.0, "t1": 1.0, "calls": 1, "dispatch_s": []}
    assert _read(bench, "compile_s", _run(closed, stage_us={"compile": 2.5e6})) == 2.5
    assert _read(bench, "compile_s", _run(closed)) is None


def test_peaks_table_refuses_unknown_kinds():
    from perfbench import peaks

    assert peaks.for_kind("TPU v5 lite")["bf16_flop_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.for_kind("cpu")
    ops, nbytes = peaks.gemm_work(8192, 2)
    assert ops == 2 * 8192**3 and nbytes == 3 * 8192**2 * 2
    least, bound = peaks.roofline_s(ops, nbytes, peaks.for_kind("TPU v5 lite"))
    assert bound == "compute" and math.isclose(least, ops / 197e12)
