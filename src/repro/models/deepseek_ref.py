"""Plain float32 forward of a DeepSeek-V3 stack (Moonlight-16B-A3B's
architecture), to test ``Model`` against.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``: layers one after another, attention over the whole (T, T)
score matrix, every held expert applied to every token and weighted by its
routing weight (zero where the token did not select it). No kernels, cache,
batching, sorting or capacity. It reads the same checkpoint-named tensors
as ``Model`` and holds the same expert share (``held_experts``): what the
experts held elsewhere add is left out of both.

Departures from the published model: RoPE rotates halves, where the
checkpoint stores the rope columns interleaved (a fixed permutation of
W_q's and W_kv_a's rope columns); the router's single group
(n_group = topk_group = 1) is plain top-k.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig

__all__ = ["forward", "moe_layer"]

f32 = jnp.float32
GUD = ("gate", "up", "down")


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x (B, T, ..., e): rotate halves by angle position * theta^(-i/(e/2))."""
    half = x.shape[-1] // 2
    ang = positions[..., None] * theta ** (-jnp.arange(half, dtype=f32) / half)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _swiglu(x, p, name):
    g, u, d = (p[name.format(w)].astype(f32) for w in ("gate", "up", "down"))
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def _attention(p, cfg: ArchConfig, x, positions):
    """-> (output (B, T, d), latent (B, T, r + e))."""
    B, T, _ = x.shape
    H, n, e, r, v = cfg.n_heads, cfg.head_dim, cfg.qk_rope_dim, cfg.kv_lora_rank, cfg.v_head_dim
    q = (x @ p["self_attn.q_proj.weight"].astype(f32)).reshape(B, T, H, n + e)
    kv_a = x @ p["self_attn.kv_a_proj_with_mqa.weight"].astype(f32)
    c = _norm(kv_a[..., :r], p["self_attn.kv_a_layernorm.weight"].astype(f32), cfg.norm_eps)
    k_rope = _rope(kv_a[..., r:], positions, cfg.rope_theta)
    q_rope = _rope(q[..., n:], positions, cfg.rope_theta)
    kv = (c @ p["self_attn.kv_b_proj.weight"].astype(f32)).reshape(B, T, H, n + v)
    scores = (
        jnp.einsum("bthn,bshn->bhts", q[..., :n], kv[..., :n])
        + jnp.einsum("bthe,bse->bhts", q_rope, k_rope)
    ) / jnp.sqrt(float(n + e))
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhts,bshv->bthv", probs, kv[..., n:]).reshape(B, T, H * v)
    return out @ p["self_attn.o_proj.weight"].astype(f32), jnp.concatenate([c, k_rope], -1)


def _route(x, p, cfg: ArchConfig):
    """x (N, d) -> routing weights (N, n_experts), zero where not selected:
    sigmoid scores, top_k of score + bias, score over the selected sum."""
    s = jax.nn.sigmoid(x @ p["mlp.gate.weight"].astype(f32))
    biased = s + p["mlp.gate.e_score_correction_bias"].astype(f32)
    kth = jax.lax.top_k(biased, cfg.top_k)[0][..., -1:]
    w = jnp.where(biased >= kth, s, 0.0)
    return w / jnp.sum(w, axis=-1, keepdims=True) * cfg.routed_scale


def moe_layer(p, cfg: ArchConfig, x):
    """x (N, d) -> (the held experts' and the shared experts' sum (N, d),
    tokens routed to each held expert)."""
    first, stop = cfg.held
    w = _route(x, p, cfg)[:, first:stop]
    y = _swiglu(x, p, "mlp.shared_experts.{}_proj.weight")
    for j in range(stop - first):
        g, u, dn = (p[f"mlp.experts.*.{n}_proj.weight"][j].astype(f32) for n in GUD)
        y = y + w[:, j : j + 1] * ((jax.nn.silu(x @ g) * (x @ u)) @ dn)
    return y, jnp.sum(w > 0, axis=0)


def _block(p, cfg: ArchConfig, x, positions, moe: bool):
    with jax.default_matmul_precision("highest"):
        xn = _norm(x, p["input_layernorm.weight"].astype(f32), cfg.norm_eps)
        h, latent = _attention(p, cfg, xn, positions)
        x = x + h
        xn = _norm(x, p["post_attention_layernorm.weight"].astype(f32), cfg.norm_eps)
        B, T, d = x.shape
        if moe:
            y, counts = moe_layer(p, cfg, xn.reshape(B * T, d))
        else:
            y, counts = _swiglu(xn.reshape(B * T, d), p, "mlp.{}_proj.weight"), None
        return x + y.reshape(B, T, d), latent, counts


def forward(params: dict, cfg: ArchConfig, tokens):
    """tokens (B, T) -> (logits (B, T, V), [latent (B, T, r + e) per layer],
    [held-expert token counts per MoE layer])."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=f32)[None], (B, T))
    x = params["model.embed_tokens.weight"].astype(f32)[tokens]
    latents, counts = [], []

    def layer(prefix, index=None):
        return {
            k[len(prefix):]: (w if index is None else w[index])
            for k, w in params.items()
            if k.startswith(prefix)
        }

    for i in range(cfg.first_dense):
        x, latent, _ = _block(layer(f"model.layers.{i}."), cfg, x, positions, moe=False)
        latents.append(latent)
    for i in range(cfg.n_periods):
        x, latent, c = _block(layer("model.layers.*.", i), cfg, x, positions, moe=True)
        latents.append(latent)
        counts.append(c)
    x = _norm(x, params["model.norm.weight"].astype(f32), cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        logits = x @ params["lm_head.weight"].astype(f32)
    return logits, latents, counts
