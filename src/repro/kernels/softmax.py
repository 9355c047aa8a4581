"""Row-softmax kernel (DNN Softmax benchmark, paper eq. 2).

Two internal passes over column chunks held in VMEM: pass 1 accumulates the
running max and sum-of-exponentials (online softmax, numerically safe for
long rows); pass 2 writes the normalized values. Rows are tiled over the
grid; columns are chunked inside the kernel. A block holds whole padded
rows, so the default row count per block is derived from the row width:
the double-buffered input and output blocks stay inside a fixed VMEM
budget at any class count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["softmax_pallas", "tune_space"]


def tune_space() -> tuple[dict, ...]:
    """Autotune candidates (first entry = the kernel's defaults). Row
    counts are left to the width-derived default except one small fixed
    count, so every candidate fits VMEM at every width."""
    return (
        {"block_cols": 512},
        {"block_cols": 256},
        {"block_cols": 1024},
        {"block_rows": 32, "block_cols": 512},
    )

_NEG_INF = -1e30
# Bytes the pipelined blocks may take: input + output, each double-
# buffered. Half of the 16 MiB scoped-VMEM default, leaving room for the
# kernel's column-chunk temporaries.
_BLOCK_VMEM_BYTES = 8 * 1024 * 1024
_MAX_BLOCK_ROWS = 256


def _default_block_rows(width: int, itemsize: int) -> int:
    """Rows per block for rows of ``width`` padded elements: the most (a
    multiple of 8, at most 256) whose four pipelined blocks fit
    ``_BLOCK_VMEM_BYTES``."""
    rows = _BLOCK_VMEM_BYTES // (4 * width * itemsize)
    return max(8, min(_MAX_BLOCK_ROWS, rows // 8 * 8))


def _softmax_kernel(x_ref, o_ref, *, block_c: int, c_valid: int):
    br, cp = x_ref.shape
    n_blocks = cp // block_c

    def stat_body(j, carry):
        m, l = carry
        blk = x_ref[:, pl.dslice(j * block_c, block_c)].astype(jnp.float32)
        col = j * block_c + jax.lax.broadcasted_iota(jnp.int32, (1, block_c), 1)
        blk = jnp.where(col < c_valid, blk, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(blk, axis=-1, keepdims=True))
        l_new = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(blk - m_new), axis=-1, keepdims=True
        )
        return m_new, l_new

    init = (
        jnp.full((br, 1), _NEG_INF, jnp.float32),
        jnp.zeros((br, 1), jnp.float32),
    )
    m, l = jax.lax.fori_loop(0, n_blocks, stat_body, init)
    inv = 1.0 / jnp.maximum(l, 1e-30)

    def write_body(j, _):
        blk = x_ref[:, pl.dslice(j * block_c, block_c)].astype(jnp.float32)
        o_ref[:, pl.dslice(j * block_c, block_c)] = (
            jnp.exp(blk - m) * inv
        ).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, n_blocks, write_body, 0)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "block_cols", "interpret")
)
def softmax_pallas(
    x: jax.Array,  # (..., C) — flattened to (R, C)
    *,
    block_rows: int | None = None,  # None: _default_block_rows(width)
    block_cols: int = 512,
    interpret: bool = False,
) -> jax.Array:
    orig_shape = x.shape
    C = orig_shape[-1]
    x2 = x.reshape(-1, C)
    R = x2.shape[0]
    bc = min(block_cols, C)
    pc = (-C) % bc
    if block_rows is None:
        block_rows = _default_block_rows(C + pc, x.dtype.itemsize)
    br = min(block_rows, R)
    pr = (-R) % br
    if pr or pc:
        x2 = jnp.pad(x2, ((0, pr), (0, pc)))
    Rp, Cp = x2.shape
    out = pl.pallas_call(
        functools.partial(_softmax_kernel, block_c=bc, c_valid=C),
        grid=(Rp // br,),
        in_specs=[pl.BlockSpec((br, Cp), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, Cp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, Cp), x.dtype),
        interpret=interpret,
    )(x2)
    return out[:R, :C].reshape(orig_shape)
