"""The open loop's schedule: the same seed gives the same arrivals and
buckets; every seed offers the same work in another order."""

from __future__ import annotations

import numpy as np
import pytest

from perfbench import harness
from perfbench.tests.small import REPO

WEIGHTS = {"p3": 2, "p3-cols16384": 1}


@pytest.fixture(scope="module")
def open_loop():
    return harness.Bench(REPO).driver("open_loop")


def _schedule(open_loop, seed, qps=720.0):
    return open_loop.schedule(qps=qps, lead_in_s=2.0, seconds=15.0, weights=WEIGHTS, seed=seed)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40 + 1])
def test_same_seed_same_schedule(open_loop, seed):
    t1, b1, f1 = _schedule(open_loop, seed)
    t2, b2, f2 = _schedule(open_loop, seed)
    np.testing.assert_array_equal(t1, t2)
    assert b1 == b2 and f1 == f2


def test_seeds_reorder_the_same_work(open_loop):
    (ta, ba, fa), (tb, bb, fb) = _schedule(open_loop, 1), _schedule(open_loop, 2)
    assert fa == fb == round(720 * 2.0)
    assert len(ta) == len(tb) == fa + round(720 * 15.0)
    assert not np.array_equal(ta, tb)
    np.testing.assert_allclose(
        np.sort(np.diff(ta, prepend=0.0)), np.sort(np.diff(tb, prepend=0.0)), rtol=1e-9, atol=1e-12
    )
    assert sorted(ba) == sorted(bb)
    window = ba[fa:]
    assert window.count("p3") == 2 * window.count("p3-cols16384")


def test_window_requests_fall_inside_the_window(open_loop):
    t, _, first = _schedule(open_loop, 3)
    assert np.all(np.diff(t) > 0)
    assert t[first - 1] == pytest.approx(2.0)
    assert 2.0 < t[first] and t[-1] == pytest.approx(17.0)


def test_gaps_are_exponential(open_loop):
    g = open_loop.gaps(20000, 20000 / 500.0, np.random.default_rng(0))
    assert g.mean() == pytest.approx(1 / 500.0)
    assert np.median(g) == pytest.approx(np.log(2) / 500.0, rel=0.02)
