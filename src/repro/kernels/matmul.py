"""Blocked MXU matmul kernel (the GEMM benchmark + Connected/RNN layers).

TPU adaptation of the paper's cuBLAS GEMM benchmark: HBM→VMEM tiling with an
fp32 VMEM accumulator. Grid is (M/bm, N/bn, K/bk) with K innermost — TPU
executes the grid sequentially per core, so the accumulator scratch persists
across the K steps of one (i, j) tile ("arbitrary" dimension semantics).

Blocks a caller leaves out are picked from the shapes and the dtype
(``_pick_blocks``): multiples of 128 up to 1024, so the MXU (128×128
systolic array) sees hardware-aligned operands, or the whole dim when it is
under 128. The pick takes the fewest grid steps whose VMEM working set fits
a budget under v5e's 16 MiB of scoped VMEM, and never pads a dim past its
128-rounded extent. Each grid step costs a fixed ~0.35 us on v5e (starting
and waiting on DMAs, accumulator read-modify-write), so big blocks matter: at
8192³ a 128³ grid is 262,144 steps.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["matmul_pallas", "tune_space"]

_TILES = (1024, 512, 256, 128)
# The working set a pick may take: 2 MiB under the 16 MiB of scoped VMEM
# Mosaic grants a kernel on v5e, for the scratch _working_set leaves out.
_VMEM_BUDGET = 14 << 20


def tune_space() -> tuple[dict, ...]:
    """Autotune candidates (first entry = the kernel's defaults, picked from
    the shapes by ``_pick_blocks``).

    Oversized blocks are safe: the wrapper clamps each block to the actual
    dim (``min(block, dim)``) and pads, so one space serves every preset.
    """
    return (
        {},
        {"block_m": 128, "block_n": 128, "block_k": 128},
        {"block_m": 256, "block_n": 128, "block_k": 128},
        {"block_m": 128, "block_n": 256, "block_k": 128},
        {"block_m": 128, "block_n": 128, "block_k": 256},
        {"block_m": 256, "block_n": 256, "block_k": 128},
    )


def _dim_blocks(dim: int) -> tuple[int, ...]:
    """Block sizes for one dim that pad it no further than rounding it up to
    128: the whole dim when it is under 128, else each tile that divides the
    128-rounded dim."""
    if dim < 128:
        return (dim,)
    padded = -(-dim // 128) * 128
    return tuple(t for t in _TILES if padded % t == 0)


def _working_set(
    bm: int, bn: int, bk: int, in_itemsize: int, out_itemsize: int
) -> float:
    """VMEM bytes of one grid step: double-buffered A, B and output blocks,
    the f32 accumulator, and Mosaic's temporaries, which scale with the A
    block. Mosaic asked a described v5e for up to 2.4 more bytes an element
    of A with bf16 operands, and 18 with f32 ones, which contract at
    ``HIGHEST`` precision through bf16 parts (each block in
    {256, 512, 1024}³ at 8192²); this estimate never falls short of it."""
    a_temp = 18 if in_itemsize >= 4 else 2.5
    return (
        2 * (bm * bk + bk * bn) * in_itemsize
        + 2 * bm * bn * out_itemsize
        + 4 * bm * bn
        + a_temp * bm * bk
    )


def _pick_blocks(
    M: int,
    N: int,
    K: int,
    in_itemsize: int,
    out_itemsize: int,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
) -> tuple[int, int, int]:
    """(bm, bn, bk) for an (M, K) @ (K, N) call; a block passed in is kept
    (clamped to its dim). The rest come from ``_dim_blocks``: the fewest
    grid steps within ``_VMEM_BUDGET``, then the highest arithmetic
    intensity bm·bn/(bm+bn), then the smallest working set. Where nothing
    fits (only with big blocks passed in), the least overshoot wins."""

    def options(block, dim):
        return (min(block, dim),) if block else _dim_blocks(dim)

    def cost(blocks):
        bm, bn, bk = blocks
        over = _working_set(bm, bn, bk, in_itemsize, out_itemsize) - _VMEM_BUDGET
        steps = -(-M // bm) * -(-N // bn) * -(-K // bk)
        return (max(over, 0), steps, -bm * bn / (bm + bn), over)

    return min(
        itertools.product(
            options(block_m, M), options(block_n, N), options(block_k, K)
        ),
        key=cost,
    )


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a, b = a_ref[...], b_ref[...]
    acc_ref[...] += jnp.dot(
        a, b,
        # f32 operands contract at full f32 precision, matching the
        # reference; Mosaic refuses that setting for bf16 operands.
        precision=(
            jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
        ),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def matmul_pallas(
    a: jax.Array,  # (M, K)
    b: jax.Array,  # (K, N)
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = _pick_blocks(
        M, N, K,
        max(a.dtype.itemsize, b.dtype.itemsize), a.dtype.itemsize,
        block_m, block_n, block_k,
    )
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    Mp, Kp = a.shape
    Np = b.shape[1]
    k_steps = Kp // bk
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=(Mp // bm, Np // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return out[:M, :N]
