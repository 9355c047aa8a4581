"""Per-architecture smoke tests + family-specific correctness properties."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models import Model
from repro.models.moe import apply_moe, init_moe, moe_oracle


def _batch_for(cfg, B=2, T=16, seed=1):
    if cfg.input_mode == "embeds":
        batch = {
            "embeds": jax.random.normal(jax.random.key(seed), (B, T, cfg.d_model), jnp.float32),
            "labels": jax.random.randint(jax.random.key(seed + 1), (B, T), 0, cfg.vocab),
        }
        if cfg.rope == "mrope":
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(T)[None, :, None], (B, T, 3)
            )
        return batch
    return {
        "tokens": jax.random.randint(jax.random.key(seed), (B, T), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.key(seed + 1), (B, T), 0, cfg.vocab),
    }


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    """Assignment requirement: reduced config, one forward/train step on CPU,
    output shapes + no NaNs."""
    cfg = get_smoke_config(arch)
    model = Model(cfg, remat=False)
    params = model.init(jax.random.key(0))
    B, T = 2, 16
    batch = _batch_for(cfg, B, T)
    logits = jax.jit(model.forward)(params, batch)
    assert logits.shape == (B, T, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits)))
    loss, grads = jax.jit(
        lambda p, b: jax.value_and_grad(lambda q: model.loss_fn(q, b)[0])(p)
    )(params, batch)
    assert np.isfinite(float(loss))
    gn = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32)))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The exact published numbers from the assignment block."""
    expected = {
        "granite-3-8b": (40, 4096, 32, 8, 12800, 49155),
        "qwen1.5-0.5b": (24, 1024, 16, 16, 2816, 151936),
        "granite-8b": (36, 4096, 32, 8, 14336, 49152),
        "deepseek-7b": (30, 4096, 32, 32, 11008, 102400),
        "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 1408, 163840),
    }[arch]
    cfg = get_config(arch)
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab)
    assert got == expected
    # MoE structure
    if arch == "mixtral-8x22b":
        assert (cfg.n_experts, cfg.top_k, cfg.window) == (8, 2, 4096)
    if arch == "dbrx-132b":
        assert (cfg.n_experts, cfg.top_k) == (16, 4)
    if arch == "jamba-1.5-large-398b":
        kinds = cfg.block_kinds()
        assert sum(k.startswith("attn") for k in kinds) == 9  # 1:7 attn:mamba
        assert sum(k.endswith("_moe") for k in kinds) == 36  # every other layer
    if arch == "hubert-xlarge":
        assert cfg.encoder_only and not cfg.causal
    if arch == "qwen1.5-0.5b":
        assert cfg.qkv_bias and cfg.tie_embeddings
    if arch == "qwen2-vl-2b":
        assert cfg.rope == "mrope"
    if arch == "moonlight-16b-a3b":
        assert (cfg.attention, cfg.kv_lora_rank, cfg.head_dim, cfg.qk_rope_dim, cfg.v_head_dim) == (
            "mla", 512, 128, 64, 128,
        )
        assert (cfg.n_experts, cfg.top_k, cfg.n_shared_experts, cfg.d_ff) == (64, 6, 2, 1408)
        assert (cfg.first_dense, cfg.dense_d_ff, cfg.router, cfg.routed_scale) == (
            1, 11264, "sigmoid", 2.446,
        )
        assert cfg.block_kinds() == ("attn_mlp",) + ("attn_moe",) * 26
        assert cfg.held == (0, 64) and cfg.rope_theta == 5e4


_DECODABLE = [a for a in ARCHS if a not in ("hubert-xlarge", "qwen2-vl-2b")]


@pytest.mark.parametrize("arch", _DECODABLE)
def test_decode_matches_teacher_forcing(arch):
    """prefill+decode logits == full forward logits (every family's cache)."""
    cfg = get_smoke_config(arch)
    cfg = dataclasses.replace(cfg, dtype="float32")
    model = Model(cfg, remat=False)
    params = model.init(jax.random.key(0))
    B, T, T0 = 2, 16, 8
    tokens = jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab)
    full = jax.jit(model.forward)(params, {"tokens": tokens})
    cache, pl_logits = jax.jit(lambda p, b: model.prefill(p, b, 32))(
        params, {"tokens": tokens[:, :T0]}
    )
    np.testing.assert_allclose(
        np.asarray(pl_logits), np.asarray(full[:, :T0]), rtol=2e-3, atol=2e-3
    )
    step = jax.jit(model.decode_step)
    for t in range(T0, T):
        logits_t, cache = step(params, cache, tokens[:, t], jnp.int32(t))
    np.testing.assert_allclose(
        np.asarray(logits_t), np.asarray(full[:, T - 1]), rtol=5e-3, atol=5e-3
    )


def test_swa_ring_cache_beyond_window():
    """Mixtral-style SWA: decoding past the window with a ring cache matches
    teacher forcing (the cache holds only the last W tokens)."""
    cfg = dataclasses.replace(get_smoke_config("mixtral-8x22b"), window=8, dtype="float32")
    model = Model(cfg, remat=False)
    params = model.init(jax.random.key(0))
    B, T, T0 = 1, 24, 4  # decode well past window=8
    tokens = jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab)
    full = jax.jit(model.forward)(params, {"tokens": tokens})
    cache, _ = jax.jit(lambda p, b: model.prefill(p, b, T))(params, {"tokens": tokens[:, :T0]})
    # ring cache is window-sized regardless of max_len
    k_leaf = jax.tree.leaves(cache)[0]
    step = jax.jit(model.decode_step)
    for t in range(T0, T):
        logits_t, cache = step(params, cache, tokens[:, t], jnp.int32(t))
    np.testing.assert_allclose(
        np.asarray(logits_t), np.asarray(full[:, T - 1]), rtol=5e-3, atol=5e-3
    )


def test_moe_dispatch_matches_oracle():
    """GShard dispatch == per-token dense oracle at full capacity."""
    cfg = dataclasses.replace(
        get_smoke_config("dbrx-132b"),
        dtype="float32",
        capacity_factor=float(8 / 4),  # E/top_k → capacity can hold everything
        moe_group_size=16,
    )
    p = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model), jnp.float32)
    got = apply_moe(p, cfg, x)
    want = moe_oracle(p, cfg, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_moe_capacity_drops_are_bounded():
    """With cf < E/k some tokens drop; outputs stay finite and norm-bounded."""
    cfg = dataclasses.replace(
        get_smoke_config("mixtral-8x22b"), dtype="float32",
        capacity_factor=0.5, moe_group_size=16,
    )
    p = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model), jnp.float32)
    got = np.asarray(apply_moe(p, cfg, x))
    assert np.all(np.isfinite(got))
    want = np.asarray(moe_oracle(p, cfg, x))
    assert np.linalg.norm(got) <= np.linalg.norm(want) * 1.5 + 1e-3


def test_hubert_is_bidirectional():
    """Encoder attends to future frames: perturbing frame t+k changes
    output at t (it wouldn't under a causal mask)."""
    cfg = dataclasses.replace(get_smoke_config("hubert-xlarge"), dtype="float32")
    model = Model(cfg, remat=False)
    params = model.init(jax.random.key(0))
    B, T = 1, 12
    e = jax.random.normal(jax.random.key(1), (B, T, cfg.d_model), jnp.float32)
    out1 = model.forward(params, {"embeds": e})
    e2 = e.at[:, -1].add(1.0)
    out2 = model.forward(params, {"embeds": e2})
    assert not np.allclose(np.asarray(out1[:, 0]), np.asarray(out2[:, 0]))


def test_causal_model_ignores_future():
    cfg = dataclasses.replace(get_smoke_config("granite-3-8b"), dtype="float32")
    model = Model(cfg, remat=False)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 12), 0, cfg.vocab)
    out1 = model.forward(params, {"tokens": toks})
    toks2 = toks.at[0, -1].set((toks[0, -1] + 1) % cfg.vocab)
    out2 = model.forward(params, {"tokens": toks2})
    np.testing.assert_allclose(
        np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), rtol=1e-5, atol=1e-5
    )


def test_remat_does_not_change_values():
    cfg = dataclasses.replace(get_smoke_config("granite-8b"), dtype="float32")
    batch = _batch_for(cfg)
    m1 = Model(cfg, remat=False)
    m2 = Model(cfg, remat=True)
    params = m1.init(jax.random.key(0))
    l1 = float(m1.loss_fn(params, batch)[0])
    l2 = float(m2.loss_fn(params, batch)[0])
    assert abs(l1 - l2) < 1e-5
    g1 = jax.grad(lambda p: m1.loss_fn(p, batch)[0])(params)
    g2 = jax.grad(lambda p: m2.loss_fn(p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=1e-4, atol=1e-5
        )


def test_scan_unroll_does_not_change_values():
    cfg = dataclasses.replace(get_smoke_config("jamba-1.5-large-398b"), dtype="float32")
    batch = _batch_for(cfg)
    m1 = Model(cfg, remat=False, scan_unroll=False)
    m2 = Model(cfg, remat=False, scan_unroll=True)
    params = m1.init(jax.random.key(0))
    np.testing.assert_allclose(
        np.asarray(m1.forward(params, batch)),
        np.asarray(m2.forward(params, batch)),
        rtol=1e-5, atol=1e-5,
    )


def test_param_counts_match_actual():
    """Analytic param_counts (drives MODEL_FLOPS) ≈ actual init sizes."""
    for arch in ("granite-3-8b", "mixtral-8x22b", "jamba-1.5-large-398b"):
        cfg = get_smoke_config(arch)
        model = Model(cfg, remat=False)
        params_sds = jax.eval_shape(model.init, jax.random.key(0))
        actual = sum(np.prod(l.shape) for l in jax.tree.leaves(params_sds))
        est = cfg.param_counts()["total"]
        assert abs(est - actual) / actual < 0.15, (arch, est, actual)
