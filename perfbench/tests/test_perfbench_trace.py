"""The reduction from a profiler trace to busy time, operations and named
idle gaps: on a synthetic trace, and on one recorded here on the CPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from perfbench import trace

MS = 1_000_000  # ns


def test_summarize_a_synthetic_trace():
    window = (0.0, 10 * MS)
    ops = [
        ("%fusion.1 = f32[8]{0:T(8)} fusion(...)", 0.0, 2 * MS),
        ("%fusion.2 = f32[8]{0} fusion(...)", 1 * MS, 2 * MS),  # overlaps fusion.1
        ("%fusion.1 = f32[8]{0:T(8)} fusion(...)", 5 * MS, 1 * MS),
        ("%copy = f32[8]{0} copy(...)", 9.5 * MS, 2 * MS),  # runs past the window
    ]
    modules = [("jit_fn(1)", 0.0, 3 * MS), ("jit_fn(1)", 5 * MS, 1 * MS), ("jit_fn(1)", 12 * MS, MS)]
    host = [
        ("$batcher.py:289 _coalescing_serve", -1 * MS, 12 * MS),  # encloses everything
        ("$time sleep", 3 * MS, 1.9 * MS),
        ("PjitFunction(fn)", 4.9 * MS, 0.05 * MS),
        ("BlockUntilReady", 6.2 * MS, 3.0 * MS),
    ]
    s = trace.summarize(window, [ops], [modules], host)
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx((3 + 1 + 0.5) / 1e3)  # union, clipped to the window
    assert s.idle_share == pytest.approx(1 - 0.45)
    assert s.ops[0] == ("%fusion.1 = f32[8] fusion(...)", pytest.approx(0.003))
    assert s.modules == {"jit_fn(1)": (2, pytest.approx(0.004))}
    assert s.gaps == [
        ("BlockUntilReady", pytest.approx(0.0035)),
        ("$time sleep", pytest.approx(0.002)),
    ]
    b = s.breakdown(top=1)
    assert b["device_ops"] == [["%fusion.1 = f32[8] fusion(...)", pytest.approx(0.003)]]
    assert b["idle_gaps"] == [["BlockUntilReady", pytest.approx(0.0035)]]


def test_an_idle_device_is_not_counted():
    busy = [("a", 0.0, 5 * MS)]
    idle_in_window = [("b", 20 * MS, MS)]
    s = trace.summarize((0.0, 10 * MS), [busy, [], idle_in_window], [], [])
    assert s.devices == 1
    assert s.busy_s == pytest.approx(0.005)


def test_short_gaps_are_not_listed():
    ops = [("a", 0.0, 1000.0), ("b", 1000.0 + trace.IDLE_GAP_MIN_NS / 2, 1000.0)]
    s = trace.summarize((0.0, 2000.0 + trace.IDLE_GAP_MIN_NS / 2), [ops], [], [])
    assert s.gaps == []


def test_a_recorded_trace_has_its_window(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    span = trace.start(str(tmp_path))
    for _ in range(3):
        f(x).block_until_ready()
    trace.stop(span)
    s = trace.reduce_dir(str(tmp_path))
    assert s.window_s > 0
    assert s.devices == 0  # the CPU has no TPU plane: nothing to read
    with pytest.raises(FileNotFoundError):
        trace.reduce_dir(str(tmp_path / "nothing"))
