"""Core transformer layers: RMSNorm, RoPE/M-RoPE, GQA attention, SwiGLU.

Pure-functional style: every layer is an ``init_*(key, cfg) -> params-dict``
plus an ``apply`` function. Parameters are plain nested dicts of arrays so
they pytree-map cleanly onto sharding rules (runtime/sharding.py) and
checkpoints.

Numerics policy: parameters and activations in ``cfg.dtype`` (bf16 for the
production configs), normalization statistics / softmax / attention
accumulation in fp32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig

__all__ = [
    "dtype_of",
    "rms_norm",
    "init_dense",
    "init_attention",
    "apply_attention",
    "init_mlp",
    "apply_mlp",
    "rope_angles",
    "apply_rope",
]


def dtype_of(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def init_dense(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = 0.02 if scale is None else scale
    return (scale * jax.random.truncated_normal(key, -2, 2, (d_in, d_out))).astype(dtype)


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * gamma


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE + Qwen2-VL M-RoPE).
# ---------------------------------------------------------------------------


def rope_angles(
    cfg: ArchConfig, positions: jax.Array, dim: int | None = None
) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for (possibly multimodal) positions.

    ``positions``: (B, T) int for plain RoPE, or (B, T, 3) for M-RoPE where
    the trailing axis is (temporal, height, width) position ids. M-RoPE
    assigns each rotary frequency pair to one of the three sections
    (Qwen2-VL §3.1); for text, all three ids are equal, making M-RoPE
    degenerate to RoPE — checked in tests.
    ``dim`` is the width rotated (default head_dim; MLA rotates only its
    rope part). Returns cos/sin of shape (B, T, dim/2), fp32.
    """
    half = (dim or cfg.head_dim) // 2
    freqs = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if positions.ndim == 2:
        pos = positions[..., None].astype(jnp.float32)  # (B, T, 1)
        angles = pos * freqs  # (B, T, half)
    else:
        # Normalize the (t, h, w) section lengths to the actual half size
        # (static python — sections are config constants).
        s0, s1, s2 = cfg.mrope_sections
        tot = s0 + s1 + s2
        n0, n1 = (s0 * half) // tot, (s1 * half) // tot
        sec_id = jnp.concatenate(
            [
                jnp.full((n0,), 0),
                jnp.full((n1,), 1),
                jnp.full((half - n0 - n1,), 2),
            ]
        )  # (half,) -> which position component drives each frequency
        pos = jnp.take_along_axis(
            positions.astype(jnp.float32),
            jnp.broadcast_to(sec_id, positions.shape[:2] + (half,)).astype(jnp.int32),
            axis=-1,
        )  # (B, T, half)
        angles = pos * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, T, n_heads, head_dim); llama-style half rotation."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(jnp.float32)
    s = sin[:, :, None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * c - x2f * s, x2f * c + x1f * s], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention.
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ArchConfig) -> dict:
    dt = dtype_of(cfg)
    d, hd = cfg.d_model, cfg.head_dim
    kq, kk, kv, ko, kb = jax.random.split(key, 5)
    p = {
        "wq": init_dense(kq, d, cfg.n_heads * hd, dt),
        "wk": init_dense(kk, d, cfg.n_kv_heads * hd, dt),
        "wv": init_dense(kv, d, cfg.n_kv_heads * hd, dt),
        "wo": init_dense(ko, cfg.n_heads * hd, d, dt, scale=0.02 / (2 * cfg.n_layers) ** 0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dt)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dt)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dt)
    return p


def project_qkv(p: dict, cfg: ArchConfig, x: jax.Array, positions: jax.Array):
    """x (B, T, d) -> q (B, T, H, hd), k/v (B, T, KV, hd), RoPE applied."""
    B, T, _ = x.shape
    hd = cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, cfg.n_heads, hd)
    k = k.reshape(B, T, cfg.n_kv_heads, hd)
    v = v.reshape(B, T, cfg.n_kv_heads, hd)
    if cfg.rope != "none":
        cos, sin = rope_angles(cfg, positions)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def sdpa(
    q: jax.Array,  # (B, T, H, hd)
    k: jax.Array,  # (B, S, KV, hd)
    v: jax.Array,  # (B, S, KV, hv); hv may differ from hd (MLA)
    *,
    causal: bool,
    window: int | None,
    kv_len: jax.Array | None = None,
    chunk: int = 0,
    score_dtype=jnp.float32,
    unroll_inner: bool = False,
) -> jax.Array:
    """Scaled dot-product GQA attention. Queries sit at the *end* of the key
    timeline; ``kv_len`` masks a partially-filled cache.

    Perf knobs (EXPERIMENTS.md §Perf):
    - ``chunk > 0``: online-softmax over KV blocks via ``lax.scan`` — the
      flash-attention recurrence in pure XLA. Never materializes the (T, S)
      score matrix; the per-step working set is (T, chunk). This is the
      memory-term optimization that brings 32k prefill under the HBM budget.
    - ``score_dtype``: operand dtype of the QKᵀ and PV matmuls, which
      accumulate in fp32 (bf16 halves the operand traffic).
    """
    B, T, H, hd = q.shape
    S, KV, hv = k.shape[1], k.shape[2], v.shape[-1]
    f32 = jnp.float32
    group = H // KV
    scale = hd**-0.5
    qf = (q.astype(jnp.float32) * scale).astype(score_dtype).reshape(B, T, KV, group, hd)
    q_pos = jnp.arange(T)[:, None] + (S if kv_len is None else kv_len) - T

    def mask_for(k_pos):
        m = jnp.ones((T, k_pos.shape[-1]), bool)
        if causal:
            m &= k_pos <= q_pos
        if window is not None:
            m &= k_pos > q_pos - window
        if kv_len is not None:
            m &= k_pos < kv_len
        return m

    if chunk and S % chunk == 0 and S > chunk:
        n_chunks = S // chunk
        kc = k.astype(score_dtype).reshape(B, n_chunks, chunk, KV, hd)
        vc = v.astype(score_dtype).reshape(B, n_chunks, chunk, KV, hv)

        def body(carry, inp):
            m_run, l_run, acc = carry
            kj, vj, j = inp
            s = jnp.einsum("btkgh,bskh->bkgts", qf, kj, preferred_element_type=f32)
            k_pos = j * chunk + jnp.arange(chunk)[None, :]
            m = mask_for(k_pos)
            s = jnp.where(m[None, None, None], s, -1e30)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgts,bskh->bkgth", p.astype(vj.dtype), vj, preferred_element_type=f32)
            acc = acc * corr[..., None] + pv
            return (m_new, l_new, acc), None

        init = (
            jnp.full((B, KV, group, T), -1e30, f32),
            jnp.zeros((B, KV, group, T), f32),
            jnp.zeros((B, KV, group, T, hv), f32),
        )
        ks = jnp.swapaxes(kc, 0, 1)  # (n_chunks, B, chunk, KV, hd)
        vs = jnp.swapaxes(vc, 0, 1)
        (m_run, l_run, acc), _ = jax.lax.scan(
            body, init, (ks, vs, jnp.arange(n_chunks)), unroll=unroll_inner
        )
        out = acc / jnp.maximum(l_run, 1e-30)[..., None]
        out = jnp.moveaxis(out, -2, 1)  # (B, T, KV, group, hv)
        return out.reshape(B, T, H, hv).astype(q.dtype)

    kf = k.astype(score_dtype)
    s = jnp.einsum("btkgh,bskh->bkgts", qf, kf, preferred_element_type=f32)
    k_pos = jnp.arange(S)[None, :]
    s = jnp.where(mask_for(k_pos)[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    vf = v.astype(score_dtype)
    out = jnp.einsum("bkgts,bskh->btkgh", p.astype(vf.dtype), vf, preferred_element_type=f32)
    return out.reshape(B, T, H, hv).astype(q.dtype)


def apply_attention(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    k_cache: jax.Array | None = None,
    v_cache: jax.Array | None = None,
    kv_len: jax.Array | None = None,
):
    """Full-sequence path (training/prefill): returns (out, (k, v)).

    With ``k_cache/v_cache`` (decode): attends over the cache; returns out.
    """
    B, T, _ = x.shape
    q, k, v = project_qkv(p, cfg, x, positions)
    opts = dict(
        chunk=cfg.attn_chunk,
        score_dtype=jnp.dtype(cfg.score_dtype),
        unroll_inner=cfg.unroll_inner,
    )
    if k_cache is not None:
        out = sdpa(
            q, k_cache, v_cache, causal=cfg.causal, window=cfg.window,
            kv_len=kv_len, **opts,
        )
        new_kv = (k, v)
    else:
        out = sdpa(q, k, v, causal=cfg.causal, window=cfg.window, **opts)
        new_kv = (k, v)
    out = out.reshape(B, T, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"], new_kv


# ---------------------------------------------------------------------------
# SwiGLU MLP.
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ArchConfig) -> dict:
    dt = dtype_of(cfg)
    kg, ku, kd = jax.random.split(key, 3)
    return {
        "w_gate": init_dense(kg, cfg.d_model, cfg.d_ff, dt),
        "w_up": init_dense(ku, cfg.d_model, cfg.d_ff, dt),
        "w_down": init_dense(
            kd, cfg.d_ff, cfg.d_model, dt, scale=0.02 / (2 * cfg.n_layers) ** 0.5
        ),
    }


def apply_mlp(p: dict, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
