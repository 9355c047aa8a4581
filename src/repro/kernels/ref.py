"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``*_ref`` is the semantic ground truth: numerically straightforward,
un-tiled, fp32-accumulating jnp code. Kernel tests sweep shapes/dtypes and
``assert_allclose`` the Pallas output against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "matmul_ref",
    "attention_ref",
    "softmax_ref",
    "lrn_ref",
    "avgpool_ref",
    "srad_step_ref",
    "prefix_scan_ref",
    "sort_kv_ref",
]


def matmul_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """C = A @ B with fp32 accumulation, cast back to A's dtype. Full
    precision: on TPU the default would round f32 operands to bf16."""
    out = jnp.dot(
        a, b, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    return out.astype(a.dtype)


def attention_ref(
    q: jax.Array,  # (B, Hq, T, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,  # (B, Hkv, S, D)
    *,
    causal: bool = False,
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Dense (materialized-scores) GQA attention oracle.

    Queries occupy the *last* T positions of the S-long key timeline
    (``offset = S - T``), which covers prefill (T == S) and cached decode
    (T << S). ``window`` is sliding-window attention: query at absolute
    position p attends to keys in (p - window, p].
    """
    B, Hq, T, D = q.shape
    Hkv = k.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if scale is None:
        scale = D**-0.5
    kx = jnp.repeat(k, group, axis=1)  # (B, Hq, S, D)
    vx = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32), kx.astype(jnp.float32))
    s *= scale
    S = k.shape[2]
    offset = S - T
    q_pos = jnp.arange(T)[:, None] + offset  # absolute positions
    k_pos = jnp.arange(S)[None, :]
    mask = jnp.ones((T, S), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # Fully-masked rows produce NaN from softmax(-inf row); define as zeros.
    p = jnp.where(jnp.any(mask, axis=-1)[None, None, :, None], p, 0.0)
    out = jnp.einsum("bhts,bhsd->bhtd", p, vx.astype(jnp.float32))
    return out.astype(q.dtype)


def softmax_ref(x: jax.Array) -> jax.Array:
    """Row softmax over the last axis, fp32 internally."""
    return jax.nn.softmax(x.astype(jnp.float32), axis=-1).astype(x.dtype)


def lrn_ref(
    x: jax.Array,  # (N, C, H, W)
    *,
    size: int = 5,
    alpha: float = 1e-4,
    beta: float = 0.75,
    k: float = 2.0,
) -> jax.Array:
    """AlexNet local response normalization across channels (paper eq. 3)."""
    xf = x.astype(jnp.float32)
    sq = xf * xf
    half = size // 2
    C = x.shape[1]
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    win = sum(padded[:, i : i + C] for i in range(size))
    return (xf / jnp.power(k + alpha * win, beta)).astype(x.dtype)


def avgpool_ref(x: jax.Array, *, ksize: int = 2) -> jax.Array:
    """Non-overlapping (stride == ksize) average pooling on (N, C, H, W)."""
    n, c, h, w = x.shape
    assert h % ksize == 0 and w % ksize == 0, (h, w, ksize)
    xf = x.astype(jnp.float32)
    out = xf.reshape(n, c, h // ksize, ksize, w // ksize, ksize).mean(axis=(3, 5))
    return out.astype(x.dtype)


def _srad_coeff(img: jax.Array, q0sqr: jax.Array):
    """Phase 1: diffusion coefficient from 4-neighbour gradients (Rodinia)."""
    # Replicated (clamped) boundary neighbours.
    north = jnp.concatenate([img[:1], img[:-1]], axis=0)
    south = jnp.concatenate([img[1:], img[-1:]], axis=0)
    west = jnp.concatenate([img[:, :1], img[:, :-1]], axis=1)
    east = jnp.concatenate([img[:, 1:], img[:, -1:]], axis=1)
    dN, dS, dW, dE = north - img, south - img, west - img, east - img
    g2 = (dN * dN + dS * dS + dW * dW + dE * dE) / (img * img)
    l = (dN + dS + dW + dE) / img
    num = 0.5 * g2 - 0.0625 * l * l
    den = 1.0 + 0.25 * l
    qsqr = num / (den * den)
    c = 1.0 / (1.0 + (qsqr - q0sqr) / (q0sqr * (1.0 + q0sqr)))
    return jnp.clip(c, 0.0, 1.0), (dN, dS, dW, dE)


def srad_step_ref(img: jax.Array, *, lam: float = 0.5, q0sqr: float = 0.05) -> jax.Array:
    """One SRAD diffusion step (phases 1+2) on a 2-D fp32 image."""
    imgf = img.astype(jnp.float32)
    c, (dN, dS, dW, dE) = _srad_coeff(imgf, jnp.float32(q0sqr))
    cS = jnp.concatenate([c[1:], c[-1:]], axis=0)  # c at south neighbour
    cE = jnp.concatenate([c[:, 1:], c[:, -1:]], axis=1)  # c at east neighbour
    div = c * dN + cS * dS + c * dW + cE * dE
    return (imgf + 0.25 * lam * div).astype(img.dtype)


def prefix_scan_ref(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the last axis, fp32 accumulation."""
    return jnp.cumsum(x.astype(jnp.float32), axis=-1).astype(x.dtype)


def sort_kv_ref(keys: jax.Array, values: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Ascending key sort carrying values (the paper's key-value Sort)."""
    order = jnp.argsort(keys, axis=-1, stable=True)
    return jnp.take_along_axis(keys, order, axis=-1), jnp.take_along_axis(
        values, order, axis=-1
    )
