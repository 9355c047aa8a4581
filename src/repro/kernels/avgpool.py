"""Average-pooling kernel (DNN Pooling benchmark, non-overlapping window).

The paper benchmarks cuDNN's average pool; its common configuration (and the
one the paper describes) is stride == kernel size. On TPU that case needs no
halo exchange, so one kernel invocation handles a (channels-block × full
spatial extent) tile. The window is reduced in two separable steps, neither
of which reshapes a vector (Mosaic cannot cast a (H, W) tile to
(H/k, k, W/k, k)): ``k`` strided sublane loads sum the window's rows, then a
matmul with a (W, W/k) pooling matrix (entries ``1/k²``, at full f32
precision) sums its columns and scales. Overlapping windows fall back to
``lax.reduce_window`` in ops.py (documented).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["avgpool_pallas", "tune_space"]


def tune_space() -> tuple[dict, ...]:
    """Autotune candidates (first entry = the kernel's defaults)."""
    return ({"block_c": 8}, {"block_c": 16}, {"block_c": 32})


def _avgpool_kernel(x_ref, pool_ref, o_ref, *, ksize: int):
    _, bc, h, w = x_ref.shape
    oh = h // ksize
    rows = x_ref[0, :, pl.ds(0, oh, stride=ksize), :].astype(jnp.float32)
    for a in range(1, ksize):
        rows = rows + x_ref[0, :, pl.ds(a, oh, stride=ksize), :].astype(
            jnp.float32
        )
    pooled = jnp.dot(
        rows.reshape(bc * oh, w),
        pool_ref[...],
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    o_ref[0] = pooled.reshape(bc, oh, w // ksize).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ksize", "block_c", "interpret"))
def avgpool_pallas(
    x: jax.Array,  # (N, C, H, W)
    *,
    ksize: int = 2,
    block_c: int = 8,
    interpret: bool = False,
) -> jax.Array:
    N, C, H, W = x.shape
    assert H % ksize == 0 and W % ksize == 0, (H, W, ksize)
    bc = min(block_c, C)
    pc = (-C) % bc
    if pc:
        x = jnp.pad(x, ((0, 0), (0, pc), (0, 0), (0, 0)))
    Cp = x.shape[1]
    OH, OW = H // ksize, W // ksize
    # pool[w, j] = 1/k² where column w falls in output column j.
    pool = jnp.where(
        jnp.arange(W)[:, None] // ksize == jnp.arange(OW)[None, :],
        1.0 / ksize**2,
        0.0,
    ).astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_avgpool_kernel, ksize=ksize),
        grid=(N, Cp // bc),
        in_specs=[
            pl.BlockSpec((1, bc, H, W), lambda n, c: (n, c, 0, 0)),
            pl.BlockSpec((W, OW), lambda n, c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, OH, OW), lambda n, c: (n, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Cp, OH, OW), x.dtype),
        interpret=interpret,
    )(x, pool)
    return out[:, :C]
