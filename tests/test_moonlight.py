"""Moonlight-16B-A3B (a DeepSeek-V3 stack) against its plain float32
reference, at the smoke config's widths, all in float32: the forward on
both matmul implementations, prefill then decode through the latent cache,
the expert share, dropless routing and the router's bias."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.kernels import ops
from repro.models import Model, deepseek_ref, mla
from repro.models.model import STACK, ckpt_layout, dense_prefix
from repro.models.moe import BIAS, ROUTER, SHARED, apply_moe_dropless, route_sigmoid, swiglu


def _cfg(held_experts=(0, 4)):
    return dataclasses.replace(
        get_smoke_config("moonlight-16b-a3b"), dtype="float32", held_experts=held_experts
    )


def _weights(cfg, seed=0):
    """Random weights at 1/sqrt(input width), so that every sub-layer moves
    the residual stream; the router's bias drawn too."""
    out = {}
    for i, (name, (shape, dt, init)) in enumerate(ckpt_layout(cfg).items()):
        key = jax.random.fold_in(jax.random.key(seed), i)
        if init == "ones":
            out[name] = jnp.ones(shape, dt)
        elif init == "zeros":  # the router's correction bias
            out[name] = 0.1 * jax.random.normal(key, shape, dt)
        else:
            scale = 1.0 if name == "model.embed_tokens.weight" else shape[-2] ** -0.5
            out[name] = scale * jax.random.normal(key, shape, dt)
    return out


def _latents(cfg, cache):
    dense = [cache[dense_prefix(i)] for i in range(cfg.first_dense)]
    return dense + list(cache[STACK])


def _tokens(cfg, B=2, T=32):
    return jax.random.randint(jax.random.key(7), (B, T), 0, cfg.vocab)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_forward_and_latent_cache_match_the_reference(impl):
    cfg = _cfg()
    params, tokens = _weights(cfg), _tokens(cfg)
    model = Model(cfg, remat=False)
    with ops.force_impl("pallas" if impl == "pallas" else "ref", "matmul"):
        cache, logits = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, 32))(params, tokens)
        last, _, counts = jax.jit(lambda p, t: model.prefill_last(p, {"tokens": t}, 32))(
            params, tokens
        )
    want, latents, want_counts = deepseek_ref.forward(params, cfg, tokens)
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(last, want[:, -1], rtol=1e-4, atol=1e-4)
    for got, ref in zip(_latents(cfg, cache), latents, strict=True):
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(counts, np.stack(want_counts))


def _avals(jaxpr):
    """The abstract value of every intermediate of ``jaxpr``, sub-jaxprs
    (scan bodies, called functions) included."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _avals(inner)


def test_the_xla_route_never_holds_a_score_matrix_per_head():
    """At preset 3 (8 prompts of 4,096 tokens) MLA prefill on the XLA route
    stays on the chunked scan: no intermediate is (T, T) per head, as the
    dense oracle's 8.6-GB score tensor would be. Traced, nothing run."""
    cfg = get_config("moonlight-16b-a3b")
    B, T = 8, 4096
    params = {
        k[len(STACK):]: jax.ShapeDtypeStruct(shape[1:], dt)
        for k, (shape, dt, _) in ckpt_layout(cfg).items()
        if k.startswith(STACK)
    }
    x = jax.ShapeDtypeStruct((B, T, cfg.d_model), jnp.bfloat16)
    positions = jax.ShapeDtypeStruct((B, T), jnp.int32)
    with ops.force_impl("ref"):
        jaxpr = jax.make_jaxpr(lambda p, x, pos: mla.attend(p, cfg, x, pos))(params, x, positions)
    shapes = [a.shape for a in _avals(jaxpr.jaxpr) if hasattr(a, "shape")]
    assert any(s[-1:] == (cfg.attn_chunk,) for s in shapes)  # the scan's score blocks
    # (B, T, H * (n + v)) is 4,096 wide too: a score tensor also has H.
    assert not [s for s in shapes if s.count(T) >= 2 and cfg.n_heads in s]


def test_prefill_then_decode_matches_the_full_forward():
    cfg = _cfg()
    params, tokens = _weights(cfg), _tokens(cfg)
    model = Model(cfg, remat=False)
    T0, T = 28, 32
    want, _, _ = deepseek_ref.forward(params, cfg, tokens)
    cache, _ = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, T))(params, tokens[:, :T0])
    step = jax.jit(model.decode_step)
    for t in range(T0, T):
        logits, cache = step(params, cache, tokens[:, t], jnp.int32(t))
        np.testing.assert_allclose(logits, want[:, t], rtol=1e-4, atol=1e-4)


def _moe_layer(params, layer=0):
    return {k[len(STACK):]: w[layer] for k, w in params.items() if k.startswith(STACK)}


def _shared(p, x):
    return swiglu(x, *(p[SHARED.format(n)] for n in ("gate", "up", "down")))


def test_expert_shares_add_up_to_the_uncut_layer():
    """Experts [0:4] on one chip and [4:8] on another, with the shared
    experts every chip computes counted once, give the whole layer."""
    full = _moe_layer(_weights(_cfg(held_experts=(0, 8))))
    x = jax.random.normal(jax.random.key(3), (64, full[ROUTER].shape[0]))
    parts = []
    for first, stop in ((0, 4), (4, 8)):
        p = {**full}
        for n in ("gate", "up", "down"):
            name = f"mlp.experts.*.{n}_proj.weight"
            p[name] = full[name][first:stop]
        parts.append(apply_moe_dropless(p, _cfg(held_experts=(first, stop)), x)[0])
    want, _ = deepseek_ref.moe_layer(full, _cfg(held_experts=(0, 8)), x)
    np.testing.assert_allclose(parts[0] + parts[1] - _shared(full, x), want, rtol=1e-5, atol=1e-5)


def test_routing_every_token_to_one_expert_drops_none():
    cfg = _cfg()
    p = _moe_layer(_weights(cfg))
    # Equal scores; the bias sends every token to experts 0, 1 and 2.
    p[ROUTER] = jnp.zeros_like(p[ROUTER])
    p[BIAS] = jnp.zeros_like(p[BIAS]).at[:3].set(jnp.array([3.0, 2.0, 1.0]))
    x = jax.random.normal(jax.random.key(4), (256, p[ROUTER].shape[0]))
    y, counts = apply_moe_dropless(p, cfg, x)
    want, want_counts = deepseek_ref.moe_layer(p, cfg, x)
    np.testing.assert_array_equal(counts, [256, 256, 256, 0])
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)


def test_the_router_bias_selects_but_does_not_weight():
    cfg = _cfg()
    logits = jax.random.normal(jax.random.key(5), (16, cfg.n_experts))
    s = jax.nn.sigmoid(logits)
    bias = jnp.zeros(cfg.n_experts).at[:3].set(10.0)  # experts 0-2, whatever their scores
    idx, w = route_sigmoid(logits, bias, cfg)
    np.testing.assert_array_equal(np.sort(idx, axis=-1), np.broadcast_to([0, 1, 2], idx.shape))
    picked, biased = (jnp.take_along_axis(a, idx, axis=-1) for a in (s, s + bias))
    scale = cfg.routed_scale
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True) * scale, rtol=1e-6)
    assert not np.allclose(w, biased / biased.sum(-1, keepdims=True) * scale)
