"""DNN: language-model prefill — a registered model workload.

One prefill call of a DeepSeek-V3 stack (``repro.configs``; today
Moonlight-16B-A3B) through ``Model.prefill_last``: ``batch`` prompts of
``seq`` tokens in, the last position's logits, every layer's MLA latent
cache and each MoE layer's held-expert token counts out. The chip holds
the routed experts [0, ``held``) of each MoE layer, as one chip of an
expert-parallel deployment does; the router still scores all of them.

Inputs are ``(weights, tokens)``: the checkpoint-named tensor dict the
model takes as its parameters, and token ids drawn uniformly from the
vocabulary. Presets 0-1 are the architecture's smoke config (what the CPU
tests run); presets 2-4 are the published widths.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.registry import DNN_DOMAIN, BenchmarkSpec, Workload, register


def arch_config(arch: str, size: str, held: int):
    """The architecture's config at ``size`` ("smoke" or "published"),
    holding routed experts [0, held)."""
    from repro.configs import get_config, get_smoke_config

    cfg = (get_config if size == "published" else get_smoke_config)(arch)
    if cfg.attention != "mla":
        raise ValueError(f"lm_prefill runs DeepSeek-V3 stacks; {arch} is not one")
    return dataclasses.replace(cfg, held_experts=(0, held))


def prefill_flops(cfg, batch: int, seq: int) -> float:
    """Operations of one prefill call: multiply-adds as two, attention over
    causal (query, key) pairs, routed experts at top_k · held/n_experts
    experts a token, the head at the last position only."""
    d, H, n, r = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.kv_lora_rank
    e, v, f = cfg.qk_rope_dim, cfg.v_head_dim, cfg.d_ff
    first, stop = cfg.held
    proj = d * H * (n + e) + d * (r + e) + r * H * (n + v) + H * v * d
    routed = cfg.top_k * (stop - first) / cfg.n_experts
    moe = 3 * d * f * (cfg.n_shared_experts + routed) + d * cfg.n_experts
    per_token = cfg.n_layers * proj + cfg.first_dense * 3 * d * cfg.dense_d_ff + cfg.n_periods * moe
    pairs = batch * seq * (seq + 1) / 2
    attn = cfg.n_layers * pairs * H * (n + e + v)
    return 2.0 * (batch * seq * per_token + attn + batch * d * cfg.vocab)


def _make(arch: str, size: str, batch: int, seq: int, held: int) -> Workload:
    from repro.models import Model

    cfg = arch_config(arch, size, held)
    model = Model(cfg, remat=False)

    def make_inputs(seed: int):
        kw, kt = jax.random.split(jax.random.key(seed))
        weights = jax.jit(model.init)(kw)
        return weights, jax.random.randint(kt, (batch, seq), 0, cfg.vocab, jnp.int32)

    def fn(weights, tokens):
        return model.prefill_last(weights, {"tokens": tokens}, seq)

    weight_bytes = cfg.param_counts()["total"] * jnp.dtype(cfg.dtype).itemsize
    cache_bytes = cfg.n_layers * batch * seq * (cfg.kv_lora_rank + cfg.qk_rope_dim) * 2
    return Workload(
        name=f"lm_prefill.{cfg.name}.held{held}.b{batch}.t{seq}",
        fn=fn,
        make_inputs=make_inputs,
        flops=prefill_flops(cfg, batch, seq),
        bytes_moved=weight_bytes + cache_bytes,
        # Prompts are independent: data-parallel over the batch, weights
        # replicated (the expert counts sum over the shards).
        batch_dims=(None, 0),
        pallas_kernel="matmul",
    )


_SMOKE = {"arch": "moonlight-16b-a3b", "size": "smoke", "held": 4}
_PUBLISHED = {"arch": "moonlight-16b-a3b", "size": "published", "held": 8}

register(
    BenchmarkSpec(
        name="lm_prefill",
        level=2,
        dwarf="Dense linear algebra",
        domain=DNN_DOMAIN,
        cuda_feature=None,
        tpu_feature="MLA + dropless grouped-matmul MoE on the MXU (Pallas matmul)",
        presets={
            0: {**_SMOKE, "batch": 2, "seq": 32},
            1: {**_SMOKE, "batch": 4, "seq": 128},
            2: {**_PUBLISHED, "batch": 1, "seq": 4096},
            3: {**_PUBLISHED, "batch": 8, "seq": 4096},
            4: {**_PUBLISHED, "batch": 16, "seq": 4096},
        },
        build=_make,
        tags=("model",),
    )
)
