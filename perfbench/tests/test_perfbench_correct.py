"""What decides ``correct``: whole runs at CPU sizes, past the look for a
chip. Sound runs pass; the lower-precision control fails each
configuration's comparison; a timed path broken underneath makes
``correct`` false, once for each fault the cell can have."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness
from perfbench.tests.small import run_small, small_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(str(tmp_path_factory.mktemp("bench")))


def _altered_gemm(exe):
    """An answer altered where it is produced: one element moved by the
    output's root mean square."""

    def call(a, b):
        c = exe(a, b)
        return c.at[0, 0].add(jnp.sqrt(jnp.mean(jnp.square(c.astype(jnp.float32)))).astype(c.dtype))

    return call


def _half_contraction(exe):
    """Half of the work left out, the rest scaled up to stand for it: the
    product over the first half of the shared dimension, doubled."""

    def call(a, b):
        k = a.shape[1] // 2
        return (2 * jnp.dot(a[:, :k], b[:k], preferred_element_type=jnp.float32)).astype(a.dtype)

    return call


def _altered_path(exe):
    def call(grid):
        out = exe(grid)
        return out.at[..., 0].add(1)

    return call


def _half_batch(exe):
    """Half of a batch left out: the batch's second half answered with
    the first half's answers."""

    def call(grid):
        out = exe(grid)
        if grid.ndim == 2:
            return out
        half = out[: grid.shape[0] // 2]
        return jnp.concatenate([half, half])

    return call


@pytest.mark.parametrize(
    "cell, trace",
    [
        ("gemm-bf16-n8192.xla", False),
        ("gemm-bf16-n8192.pallas", True),
        ("pathfinder-mix.steady", False),
        ("pathfinder-mix.overload", True),
    ],
)
def test_sound_runs_are_correct(root, cell, trace):
    result = run_small(root, cell, seed=2**33 + 5, trace=trace)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == jax.devices()[0].platform


@pytest.mark.parametrize(
    "cell, fault",
    [
        ("gemm-bf16-n8192.xla", _altered_gemm),
        ("gemm-bf16-n8192.xla", _half_contraction),
        ("gemm-bf16-n8192.pallas", _altered_gemm),
        ("gemm-bf16-n8192.pallas", _half_contraction),
        ("pathfinder-mix.steady", _altered_path),
        ("pathfinder-mix.steady", _half_batch),
        ("pathfinder-mix.overload", _altered_path),
        ("pathfinder-mix.overload", _half_batch),
    ],
)
def test_a_broken_timed_path_is_not_correct(root, cell, fault):
    result = run_small(root, cell, fault=fault, seconds=0.3)
    assert not result["correct"], result["checks"]
    assert result["failed"] > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_gemm_control_fails_and_the_program_passes(root, seed):
    bench = harness.Bench(root)
    ref = bench.ref("gemm-bf16-n8192")
    config = bench.config("gemm-bf16-n8192")
    a, b = ref.make_inputs(harness.seed_key(seed), config)
    program = ref.compare(jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype), (a, b))
    control = ref.compare(ref.control(a, b), (a, b))
    limit = ref.LIMITS["max_err"]
    assert program["max_err"] < limit < control["max_err"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_pathfinder_control_fails(root, seed):
    bench = harness.Bench(root)
    ref = bench.ref("pathfinder-mix")
    grids = ref.make_inputs(harness.seed_key(seed), bench.config("pathfinder-mix"))
    for stacked in grids.values():
        grid = np.asarray(stacked[0])
        assert ref.compare(ref.min_path(grid), grid)["mismatches"] == 0
        assert ref.compare(ref.control(grid), grid)["mismatches"] > 0
