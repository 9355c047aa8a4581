"""Blocked MXU matmul kernel (the GEMM benchmark + Connected/RNN layers).

TPU adaptation of the paper's cuBLAS GEMM benchmark: HBM→VMEM tiling with an
fp32 VMEM accumulator. Grid is (M/bm, N/bn, K/bk) with K innermost — TPU
executes the grid sequentially per core, so the accumulator scratch persists
across the K steps of one (i, j) tile ("arbitrary" dimension semantics).
Block sizes default to 128/256 multiples so the MXU (128×128 systolic array)
sees hardware-aligned operands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["matmul_pallas", "tune_space"]


def tune_space() -> tuple[dict, ...]:
    """Autotune candidates (first entry = the kernel's defaults).

    Oversized blocks are safe: the wrapper clamps each block to the actual
    dim (``min(block, dim)``) and pads, so one space serves every preset.
    """
    return (
        {"block_m": 128, "block_n": 128, "block_k": 128},
        {"block_m": 256, "block_n": 128, "block_k": 128},
        {"block_m": 128, "block_n": 256, "block_k": 128},
        {"block_m": 128, "block_n": 128, "block_k": 256},
        {"block_m": 256, "block_n": 256, "block_k": 128},
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
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
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
