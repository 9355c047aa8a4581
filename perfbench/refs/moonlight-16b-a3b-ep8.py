"""Plain reference of ``moonlight-16b-a3b-ep8``: one prefill call of
Moonlight-16B-A3B (a DeepSeek-V3 stack) as one chip of an 8-way
expert-parallel deployment runs it, holding routed experts 0-7 of 64.

Inputs, from the seed: the weights under the checkpoint's own tensor
names, [in, out] oriented, the MoE layers' tensors stacked on a leading
axis (``model.layers.*.``) and the held experts on a second
(``mlp.experts.*.``), in the configuration's weight precision (bfloat16)
but for the float32 router and its correction bias; and ``batch`` x
``seq`` token ids uniform over the vocabulary. This module imports
nothing of the program.

The reference runs layer by layer in float32 at ``Precision.HIGHEST``, each
layer's weights upcast only while it runs (the whole model in float32,
13.5 GB, does not fit beside the program's): RMSNorm; MLA with the latent
c = RMSNorm(x W_kv_a[:r]), k_rope = RoPE(x W_kv_a[r:]) shared by the heads,
[k_nope | v] = c W_kv_b, scores (q_nope k_nope + q_rope k_rope) / sqrt(192)
under a causal mask, in blocks of queries; the router s = sigmoid(x W_r),
the top 6 of s + bias selected, weights s over the selected sum x 2.446;
each held expert applied to every token and weighted by its routing weight
(zero where not selected), plus the shared experts. RoPE rotates halves
(the checkpoint's interleaved rope columns are a fixed permutation of it).

The comparison, on the last position's logits and each layer's latent
cache, is relative RMS error: a largest error over 32 k tokens would be
set by routing flips between bfloat16 and float32. Held-expert token
counts compare as the share of routed slots that differ.

The control is the reference with every projection's and expert's weights
rounded to float8 (e4m3) on the host, as the GEMM reference rounds its
inputs.
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

# Readings on one TPU v5e at the published widths, program (bf16) at most
# over 9 seeds / float8 control at least over 2 (PERF.md, "How correct is
# decided"): logits 0.163 / 0.234, set by routing flips in the 8 last
# tokens, so the least room; cache 0.082 / 0.247; count gap 0.0043 /
# 0.0263. Each limit lies between its two readings, with room on both
# sides.
LIMITS = {"logits_rel_rms": 0.2, "cache_rel_rms": 0.14, "count_gap": 0.01}

f32 = jnp.float32
STACK = "model.layers.*."
QUERY_BLOCK = 512
# The router's correction bias: normal at this scale, against sigmoid scores
# that spread about 0.2. A trained noaux_tc bias balances the experts' load;
# at 0.02 it changes the selection of about 60% of tokens while the held
# experts' share of the routed load stays within about 7% of 8/64 a layer
# (at 0.1, 31%, and the cell's time swung with the seed).
BIAS_SCALE = 0.02
# Tensors the control rounds to float8: every projection and expert.
ROUNDED = ("_proj.weight", "_proj_with_mqa.weight", "lm_head.weight")


def _dims(config: dict) -> dict:
    c = config
    return {
        "d": c["hidden_size"], "H": c["num_attention_heads"], "r": c["kv_lora_rank"],
        "n": c["qk_nope_head_dim"], "e": c["qk_rope_head_dim"], "v": c["v_head_dim"],
        "f": c["moe_intermediate_size"], "F": c["intermediate_size"], "V": c["vocab_size"],
        "L": c["num_hidden_layers"], "dense": c["first_k_dense_replace"],
        "held": c["n_routed_experts"], "E": c["deployment"]["n_routed_experts"],
        "first": c["deployment"]["held_experts"][0], "shared": c["n_shared_experts"],
        "k": c["num_experts_per_tok"], "scale": c["routed_scaling_factor"],
        "eps": c["rms_norm_eps"], "theta": float(c["rope_theta"]),
        "dtype": c["precision"]["weights"],
    }


def _layout(k: dict) -> tuple:
    """(name, shape, dtype, init) of every tensor; init is a normal's scale,
    "ones" or "bias". Each projection's scale is 1/sqrt(its input width) and
    the embedding's 1, so that every sub-layer adds to the residual stream
    as much as it holds: a sub-layer left out then shows in the answer."""
    bf = k["dtype"]
    d, H, r, n, e, v = k["d"], k["H"], k["r"], k["n"], k["e"], k["v"]

    def w(rows, lead, cols):
        return (lead + (rows, cols), bf, rows**-0.5)

    def attn(lead):
        return [
            ("input_layernorm.weight", lead + (d,), bf, "ones"),
            ("self_attn.q_proj.weight", *w(d, lead, H * (n + e))),
            ("self_attn.kv_a_proj_with_mqa.weight", *w(d, lead, r + e)),
            ("self_attn.kv_a_layernorm.weight", lead + (r,), bf, "ones"),
            ("self_attn.kv_b_proj.weight", *w(r, lead, H * (n + v))),
            ("self_attn.o_proj.weight", *w(H * v, lead, d)),
            ("post_attention_layernorm.weight", lead + (d,), bf, "ones"),
        ]

    def mlp(name, lead, width):
        return [
            (name.format("gate"), *w(d, lead, width)),
            (name.format("up"), *w(d, lead, width)),
            (name.format("down"), *w(width, lead, d)),
        ]

    rows = [("model.embed_tokens.weight", (k["V"], d), bf, 1.0)]
    for i in range(k["dense"]):
        rows += [(f"model.layers.{i}." + t, *rest)
                 for t, *rest in attn(()) + mlp("mlp.{}_proj.weight", (), k["F"])]
    L = (k["L"] - k["dense"],)
    stacked = (
        attn(L)
        + [("mlp.gate.weight", L + (d, k["E"]), "float32", d**-0.5),
           ("mlp.gate.e_score_correction_bias", L + (k["E"],), "float32", "bias")]
        + mlp("mlp.experts.*.{}_proj.weight", L + (k["held"],), k["f"])
        + mlp("mlp.shared_experts.{}_proj.weight", L, k["shared"] * k["f"])
    )
    rows += [(STACK + t, *rest) for t, *rest in stacked]
    rows += [("model.norm.weight", (d,), bf, "ones"), ("lm_head.weight", *w(d, (), k["V"]))]
    return tuple(rows)


@functools.partial(jax.jit, static_argnames=("layout", "tokens_shape", "vocab"))
def _make(key, layout, tokens_shape, vocab):
    weights = {}
    for i, (name, shape, dtype, init) in enumerate(layout):
        kk = jax.random.fold_in(key, i)
        if init == "ones":
            w = jnp.ones(shape)
        elif init == "bias":
            w = BIAS_SCALE * jax.random.normal(kk, shape)
        else:
            w = init * jax.random.truncated_normal(kk, -2, 2, shape)
        weights[name] = w.astype(dtype)
    tokens = jax.random.randint(jax.random.fold_in(key, len(layout)), tokens_shape, 0, vocab)
    return weights, tokens.astype(jnp.int32)


def make_inputs(key, config: dict) -> tuple:
    """(weights, tokens) from the seed's key, on the device."""
    k = _dims(config)
    o = config["overrides"]
    return jax.block_until_ready(
        _make(key, layout=_layout(k), tokens_shape=(o["batch"], o["seq"]), vocab=k["V"])
    )


# ---- the reference, one layer at a time ------------------------------------


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(f32)


def _rope(x, theta):
    """x (T, ..., e) at positions 0..T-1, halves rotated."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=f32) / half)
    ang = jnp.arange(x.shape[0], dtype=f32)[:, None] * freqs
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x[..., :half] * c - x[..., half:] * s,
                            x[..., half:] * c + x[..., :half] * s], axis=-1)


def _attention_one(x, p, k):
    """One prompt: x (T, d) -> (output (T, d), latent (T, r + e))."""
    T = x.shape[0]
    H, n, e, r, v = k["H"], k["n"], k["e"], k["r"], k["v"]
    q = (x @ p["self_attn.q_proj.weight"].astype(f32)).reshape(T, H, n + e)
    kv_a = x @ p["self_attn.kv_a_proj_with_mqa.weight"].astype(f32)
    c = _norm(kv_a[:, :r], p["self_attn.kv_a_layernorm.weight"], k["eps"])
    k_rope = _rope(kv_a[:, r:], k["theta"])
    q_rope = _rope(q[..., n:], k["theta"])
    kv = (c @ p["self_attn.kv_b_proj.weight"].astype(f32)).reshape(T, H, n + v)
    qb = min(QUERY_BLOCK, T)

    def block(i):
        rows = jax.lax.dynamic_slice_in_dim
        qn, qr = rows(q[..., :n], i * qb, qb), rows(q_rope, i * qb, qb)
        s = (jnp.einsum("qhn,khn->hqk", qn, kv[..., :n])
             + jnp.einsum("qhe,ke->hqk", qr, k_rope)) / jnp.sqrt(float(n + e))
        causal = jnp.arange(T)[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        probs = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khv->qhv", probs, kv[..., n:])

    out = jax.lax.map(block, jnp.arange(T // qb)).reshape(T, H * v)
    return out @ p["self_attn.o_proj.weight"].astype(f32), jnp.concatenate([c, k_rope], -1)


def _swiglu(x, g, u, dn):
    return (jax.nn.silu(x @ g.astype(f32)) * (x @ u.astype(f32))) @ dn.astype(f32)


def _moe(x, p, k):
    """x (N, d) -> (held and shared experts' sum, tokens per held expert)."""
    s = jax.nn.sigmoid(x @ p["mlp.gate.weight"].astype(f32))
    biased = s + p["mlp.gate.e_score_correction_bias"].astype(f32)
    kth = jax.lax.top_k(biased, k["k"])[0][:, -1:]
    w = jnp.where(biased >= kth, s, 0.0)
    w = (w / jnp.sum(w, axis=-1, keepdims=True) * k["scale"])[:, k["first"]:k["first"] + k["held"]]
    shared = _swiglu(x, *(p[f"mlp.shared_experts.{t}_proj.weight"] for t in ("gate", "up", "down")))

    def expert(y, inp):
        g, u, dn, wj = inp
        return y + wj[:, None] * _swiglu(x, g, u, dn), None

    experts = tuple(p[f"mlp.experts.*.{t}_proj.weight"] for t in ("gate", "up", "down"))
    y, _ = jax.lax.scan(expert, shared, experts + (w.T,))
    return y, jnp.sum(w > 0, axis=0)


@functools.partial(jax.jit, static_argnames=("k", "moe"))
def _layer(x, p, k, moe):
    """x (B, T, d) float32 through one layer -> (x, latent, counts)."""
    k = dict(k)
    with jax.default_matmul_precision("highest"):
        xn = _norm(x, p["input_layernorm.weight"], k["eps"])
        h, latent = jax.lax.map(lambda xb: _attention_one(xb, p, k), xn)
        x = x + h
        B, T, d = x.shape
        xn = _norm(x, p["post_attention_layernorm.weight"], k["eps"]).reshape(B * T, d)
        if moe:
            y, counts = _moe(xn, p, k)
        else:
            y = _swiglu(xn, *(p[f"mlp.{t}_proj.weight"] for t in ("gate", "up", "down")))
            counts = jnp.zeros((0,), jnp.int32)
        return x + y.reshape(B, T, d), latent, counts


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(f32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x_last, norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return _norm(x_last, norm, eps) @ head.astype(f32)


def _f8(w):
    """``w`` rounded to float8 (e4m3) on the host, back in float32."""
    return jnp.asarray(np.asarray(w.astype(f32)).astype(jnp.float8_e4m3fn).astype(np.float32))


def _run(weights, tokens, k: dict, rounded: bool):
    """-> (last-position logits (B, V), [latent (B, T, r + e) on the host
    per layer], counts (MoE layers, held)), all float32."""
    def prepared(p):
        if not rounded:
            return p
        return {t: _f8(w) if t.endswith(ROUNDED) else w for t, w in p.items()}

    frozen = tuple(sorted(k.items()))
    x = _embed(weights["model.embed_tokens.weight"], tokens)
    latents, counts = [], []
    for i in range(k["L"]):
        moe = i >= k["dense"]
        prefix = STACK if moe else f"model.layers.{i}."
        p = {t[len(prefix):]: (w[i - k["dense"]] if moe else w)
             for t, w in weights.items() if t.startswith(prefix)}
        x, latent, c = _layer(x, prepared(p), frozen, moe)
        latents.append(np.asarray(latent))
        if moe:
            counts.append(np.asarray(c))
    head = prepared({"lm_head.weight": weights["lm_head.weight"]})["lm_head.weight"]
    logits = np.asarray(_head(x[:, -1], weights["model.norm.weight"], head, k["eps"]))
    return logits, latents, np.stack(counts)


def _own_dims() -> dict:
    """The widths of the configuration file beside this module's directory
    (``configs/<this module's name>.json``): ``compare`` and ``control``
    are given only the inputs."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    name = os.path.splitext(os.path.basename(__file__))[0]
    with open(os.path.join(root, "configs", name + ".json"), encoding="utf-8") as fh:
        return _dims(json.load(fh))


# The last inputs' reference answer: the window's first and last answers
# are compared with the same one, which takes tens of seconds to compute.
_memo: dict = {}


def _reference(args):
    key = (id(args[0]), id(args[1]))
    if key not in _memo:
        _memo.clear()
        _memo[key] = _run(*args, _own_dims(), rounded=False)
    return _memo[key]


def _rel_rms(got, want) -> float:
    got = np.asarray(got, np.float32)
    return float(np.sqrt(np.sum((got - want) ** 2) / np.sum(want**2)))


def compare(out, args) -> dict:
    """The numbers compared for one answer of the program: (logits (B, V),
    cache {prefix: latent}, counts (MoE layers, held))."""
    logits, cache, counts = out
    ref_logits, ref_latents, ref_counts = _reference(args)
    dense = len(ref_latents) - len(ref_counts)
    got = [cache[f"model.layers.{i}."] for i in range(dense)] + list(np.asarray(cache[STACK]))
    counts = np.asarray(counts)
    return {
        "logits_rel_rms": _rel_rms(logits, ref_logits),
        "cache_rel_rms": max(_rel_rms(g, w) for g, w in zip(got, ref_latents)),
        "count_gap": float(np.abs(counts - ref_counts).sum() / ref_counts.sum()),
    }


def control(weights, tokens):
    """The reference with every projection and expert rounded to float8,
    in the program's output form."""
    k = _own_dims()
    logits, latents, counts = _run(weights, tokens, k, rounded=True)
    cache = {f"model.layers.{i}.": latents[i] for i in range(k["dense"])}
    cache[STACK] = np.stack(latents[k["dense"]:])
    return logits, cache, counts
