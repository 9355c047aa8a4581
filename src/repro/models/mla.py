"""Multi-head latent attention (MLA), DeepSeek-V2/V3's attention.

Per token, with ``H`` heads, latent rank ``r``, head widths ``n`` (nope),
``e`` (rope) and ``v``:

- q = x·W_q, split per head into [q_nope (n) | q_rope (e)];
- [c (r) | k_rope (e)] = x·W_kv_a, then c = RMSNorm(c); k_rope is one
  vector shared by every head;
- [k_nope (n) | v (v)] = c·W_kv_b, per head;
- score = (q_nope·k_nope + RoPE(q_rope)·RoPE(k_rope)) / sqrt(n + e), causal
  softmax, and the heads' values through W_o.

The cache is the latent: [c | RoPE(k_rope)], r + e values a token per layer,
not K and V per head. Prefill expands the latent into K and V per head and
runs the attention core on one of two routes, whichever ``ops.attention``
resolves to under the engine's ``impl``: on the Pallas route the fused
flash kernel (``kernels/flash_attention.py``, heads-major operands, its own
128-wide values against 192-wide queries and keys, the operands' dtype on
the MXU, causal key blocks above the diagonal never visited); on the XLA
route ``sdpa``'s chunked online softmax (``cfg.attn_chunk``), never the
dense oracle. Both take their operands in ``cfg.score_dtype`` and keep the
softmax and the accumulation in f32. A decode step instead folds W_kv_b into
the query and the output ("absorbed" form), so it reads only the latent.

Weights are the checkpoint's own tensors (``self_attn.q_proj.weight``, ...),
in [in, out] orientation; ``p`` is one layer's dict of them, keyed without
the ``model.layers.<i>.`` prefix. RoPE rotates halves, as ``apply_rope``
does; the checkpoint stores the rope columns interleaved, which is a fixed
permutation of W_q's and W_kv_a's rope columns.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.config import ArchConfig
from repro.models.layers import apply_rope, rms_norm, rope_angles, sdpa

__all__ = ["attend", "attend_step", "mm"]

Q = "self_attn.q_proj.weight"
KV_A = "self_attn.kv_a_proj_with_mqa.weight"
KV_NORM = "self_attn.kv_a_layernorm.weight"
KV_B = "self_attn.kv_b_proj.weight"
O = "self_attn.o_proj.weight"


def mm(x: jax.Array, w: jax.Array) -> jax.Array:
    """x (..., k) @ w (k, n) through the kernel layer's matmul."""
    return ops.matmul(x.reshape(-1, x.shape[-1]), w).reshape(*x.shape[:-1], w.shape[-1])


def _latent(p: dict, cfg: ArchConfig, x: jax.Array, positions: jax.Array):
    """x (B, T, d) -> q_nope (B, T, H, n), roped q_rope (B, T, H, e), and the
    cache entry [c | roped k_rope] (B, T, r + e)."""
    B, T, _ = x.shape
    H, n, e, r = cfg.n_heads, cfg.head_dim, cfg.qk_rope_dim, cfg.kv_lora_rank
    q = mm(x, p[Q]).reshape(B, T, H, n + e)
    kv_a = mm(x, p[KV_A])
    c = rms_norm(kv_a[..., :r], p[KV_NORM], cfg.norm_eps)
    cos, sin = rope_angles(cfg, positions, dim=e)
    q_rope = apply_rope(q[..., n:], cos, sin)
    k_rope = apply_rope(kv_a[..., None, r:], cos, sin)[..., 0, :]
    return q[..., :n], q_rope, jnp.concatenate([c, k_rope], axis=-1)


def attend(p: dict, cfg: ArchConfig, x: jax.Array, positions: jax.Array):
    """Full-sequence (prefill) MLA: x (B, T, d) -> (out (B, T, d), the
    layer's cache entry (B, T, r + e)).

    The attention core is the Pallas flash kernel where ``ops.attention``
    resolves to it, fed heads-major (B, H, T, ·) operands; elsewhere the
    chunked ``sdpa``. Its dense oracle, ``ops.attention``'s ref branch,
    would hold a (T, T) f32 score matrix per head and is never taken."""
    B, T, _ = x.shape
    H, n, r = cfg.n_heads, cfg.head_dim, cfg.kv_lora_rank
    score_dtype = jnp.dtype(cfg.score_dtype)
    q_nope, q_rope, latent = _latent(p, cfg, x, positions)
    kv = mm(latent[..., :r], p[KV_B]).reshape(B, T, H, n + cfg.v_head_dim)
    k_rope = jnp.broadcast_to(latent[..., None, r:], q_rope.shape)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([kv[..., :n], k_rope], axis=-1)
    if ops.resolves_to_pallas("attention"):
        q, k, v = (jnp.swapaxes(a, 1, 2).astype(score_dtype) for a in (q, k, kv[..., n:]))
        out = jnp.swapaxes(ops.attention(q, k, v, causal=True), 1, 2).astype(x.dtype)
    else:
        out = sdpa(
            q, k, kv[..., n:], causal=True, window=None, chunk=cfg.attn_chunk,
            score_dtype=score_dtype, unroll_inner=cfg.unroll_inner,
        )
    return mm(out.reshape(B, T, H * cfg.v_head_dim), p[O]), latent


def attend_step(p: dict, cfg: ArchConfig, x_t: jax.Array, cache: jax.Array, pos: jax.Array):
    """One decode step in the absorbed form: x_t (B, d), cache (B, S, r + e)
    holding positions < pos. Returns (out (B, d), cache with pos written)."""
    B = x_t.shape[0]
    H, n, e, r = cfg.n_heads, cfg.head_dim, cfg.qk_rope_dim, cfg.kv_lora_rank
    positions = jnp.broadcast_to(pos, (B, 1))
    q_nope, q_rope, latent = _latent(p, cfg, x_t[:, None, :], positions)
    cache = jax.lax.dynamic_update_index_in_dim(cache, latent[:, 0], pos, axis=1)
    w_kv_b = p[KV_B].reshape(r, H, n + cfg.v_head_dim).astype(jnp.float32)
    f32 = jnp.float32
    # Fold W_kv_b's key half into the query: q_nope·k_nope = (q_nope·W_uk^T)·c.
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0].astype(f32), w_kv_b[..., :n])
    q_full = jnp.concatenate([q_lat, q_rope[:, 0].astype(f32)], axis=-1)
    s = jnp.einsum("bhc,bsc->bhs", q_full, cache.astype(f32)) * (n + e) ** -0.5
    s = jnp.where(jnp.arange(cache.shape[1]) <= pos, s, -1e30)
    o_lat = jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(s, axis=-1), cache[..., :r].astype(f32))
    out = jnp.einsum("bhr,rhv->bhv", o_lat, w_kv_b[..., n:]).astype(x_t.dtype)
    return mm(out.reshape(B, H * cfg.v_head_dim), p[O]), cache
