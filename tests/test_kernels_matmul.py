"""Pallas matmul kernel vs pure-jnp oracle: shape/dtype sweep."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.matmul import (
    _VMEM_BUDGET,
    _pick_blocks,
    _working_set,
    matmul_pallas,
)

SHAPES = [
    (8, 8, 8),
    (128, 128, 128),
    (130, 70, 50),  # padding in all dims
    (1, 256, 33),
    (257, 1, 128),
]


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_matmul_matches_ref(rng, m, k, n, dtype):
    a = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(dtype)
    b = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32)).astype(dtype)
    out = matmul_pallas(a, b, block_m=64, block_n=64, block_k=32, interpret=True)
    want = ref.matmul_ref(a, b)
    tol = 1e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_matmul_block_shapes_invariance(rng):
    """Result is independent of BlockSpec tiling."""
    a = jnp.asarray(rng.normal(size=(96, 64)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(64, 80)).astype(np.float32))
    outs = [
        matmul_pallas(a, b, block_m=bm, block_n=bn, block_k=bk, interpret=True)
        for bm, bn, bk in [(32, 16, 16), (96, 80, 64), (48, 40, 8)]
    ]
    for o in outs[1:]:
        # fp32 accumulation order differs across tilings — tolerance only.
        np.testing.assert_allclose(
            np.asarray(outs[0]), np.asarray(o), rtol=1e-3, atol=1e-5
        )


# -- blocks picked from the shape ----------------------------------------------

PICK_SHAPES = [  # (M, N, K)
    (8192, 8192, 8192),
    (4096, 1100, 70),
    (130, 70, 50),
    (64, 12544, 576),  # im2col convolution: few output channels
]


def _padded(dim, block):
    return -(-dim // block) * block


@pytest.mark.parametrize("m,n,k", PICK_SHAPES)
@pytest.mark.parametrize("itemsize", [2, 4], ids=["bf16", "f32"])
def test_picked_blocks_pad_no_further_than_128_rounding(m, n, k, itemsize):
    blocks = _pick_blocks(m, n, k, itemsize, itemsize)
    for dim, block in zip((m, n, k), blocks):
        rounded = _padded(dim, 128)
        assert block == dim or rounded % block == 0, (dim, block)
        assert _padded(dim, block) <= rounded, (dim, block)


@pytest.mark.parametrize("m,n,k", PICK_SHAPES)
@pytest.mark.parametrize("itemsize", [2, 4], ids=["bf16", "f32"])
def test_picked_blocks_fit_the_vmem_budget(m, n, k, itemsize):
    blocks = _pick_blocks(m, n, k, itemsize, itemsize)
    assert _working_set(*blocks, itemsize, itemsize) <= _VMEM_BUDGET


# Blocks a described v5e compiled, or refused for overrunning its 16 MiB of
# scoped VMEM, at 8192²: the estimate must admit the first and refuse the
# second.
@pytest.mark.parametrize(
    "itemsize,blocks,compiles",
    [
        (2, (128, 128, 128), True),
        (2, (512, 512, 1024), True),
        (2, (1024, 1024, 512), True),
        (2, (512, 1024, 1024), True),
        (2, (1024, 1024, 1024), False),
        (4, (512, 512, 512), True),
        (4, (512, 512, 1024), False),
        (4, (1024, 1024, 512), False),
    ],
)
def test_vmem_budget_matches_what_v5e_compiles(itemsize, blocks, compiles):
    fits = _working_set(*blocks, itemsize, itemsize) <= _VMEM_BUDGET
    assert fits == compiles


@pytest.mark.parametrize(
    "itemsize,want", [(2, (1024, 1024, 512)), (4, (512, 1024, 256))],
    ids=["bf16", "f32"],
)
def test_pick_at_8192_takes_the_fewest_steps_that_fit(itemsize, want):
    # bf16: 1,024 grid steps, the fastest of the 1,024-step candidates on a
    # v5e; f32 contracts at HIGHEST precision, whose temporaries cap the
    # blocks at 4,096 steps.
    assert _pick_blocks(8192, 8192, 8192, itemsize, itemsize) == want


def test_blocks_passed_in_are_kept():
    assert _pick_blocks(8192, 8192, 8192, 2, 2, 64, 64, 32) == (64, 64, 32)
    # Clamped to the dim, as before; the missing ones are still picked.
    assert _pick_blocks(100, 8192, 8192, 2, 2, block_m=128) == (100, 1024, 1024)


@pytest.mark.parametrize("m,k,n", [(130, 50, 70), (300, 70, 1100)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_matmul_with_picked_blocks_matches_ref(rng, m, k, n, dtype):
    a = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(dtype)
    b = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32)).astype(dtype)
    out = matmul_pallas(a, b, interpret=True)
    want = ref.matmul_ref(a, b)
    tol = 1e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )
