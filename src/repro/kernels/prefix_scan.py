"""Blocked inclusive prefix-sum kernel (substrate of the Where benchmark).

TPU adaptation of the GPU scan: GPUs do block-local scans + a spine scan +
a fixup pass because blocks run concurrently. A TPU core walks the grid
**sequentially**, so the cross-block carry is just an SMEM scalar that
persists across grid steps — one pass, no spine, no fixup.

The block-local scan runs on the MXU (Mosaic has no ``cumsum``): the input
is laid out as rows of ``lanes`` elements, each row is scanned by a matmul
with the (lanes × lanes) upper-triangular ones matrix, and each row's
offset — the sum of the rows before it — is a matmul of the row totals
with the strictly-lower-triangular ones matrix. Both run at full f32
precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["prefix_scan_pallas", "tune_space"]

_LANES = 128


def tune_space() -> tuple[dict, ...]:
    """Autotune candidates (first entry = the kernel's defaults)."""
    return ({"block_n": 2048}, {"block_n": 1024}, {"block_n": 4096})


def _dot(a, b):
    return jnp.dot(
        a, b,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _scan_kernel(x_ref, o_ref, carry_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[0] = 0.0

    block = x_ref[...].astype(jnp.float32)  # (rows, lanes)
    rows, lanes = block.shape
    li = jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes), 1)
    row_scan = _dot(block, (li <= lj).astype(jnp.float32))
    ri = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    rj = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    totals = jnp.broadcast_to(row_scan[:, lanes - 1 :], (rows, lanes))
    offsets = _dot((rj < ri).astype(jnp.float32), totals)
    o_ref[...] = (row_scan + offsets + carry_ref[0]).astype(o_ref.dtype)
    carry_ref[0] = carry_ref[0] + jnp.sum(block)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def prefix_scan_pallas(
    x: jax.Array,  # (N,)
    *,
    block_n: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    (N,) = x.shape
    bn = min(block_n, N)
    # Rows of 128 lanes when the block holds whole rows; a small or odd
    # block is a single row.
    lanes = _LANES if bn % _LANES == 0 else bn
    pn = (-N) % bn
    x2 = jnp.pad(x, (0, pn)).reshape(-1, lanes)  # zeros keep the sum
    out = pl.pallas_call(
        _scan_kernel,
        grid=(x2.shape[0] * lanes // bn,),
        in_specs=[pl.BlockSpec((bn // lanes, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bn // lanes, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
    )(x2)
    return out.reshape(-1)[:N]
