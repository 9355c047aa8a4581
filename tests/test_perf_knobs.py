"""Correctness of the §Perf optimization knobs: every speed/memory lever
must be a semantic no-op."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.configs import get_smoke_config
from repro.models import Model
from repro.models.layers import sdpa
from repro.models.moe import apply_moe, init_moe, moe_oracle, split_moe_params


@pytest.mark.parametrize("causal,window", [(False, None), (True, None), (True, 7)])
@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_sdpa_equals_dense(rng, causal, window, chunk):
    B, T, H, KV, hd, S = 2, 16, 4, 2, 8, 48
    q = jnp.asarray(rng.normal(size=(B, T, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)).astype(np.float32))
    dense = sdpa(q, k, v, causal=causal, window=window)
    chunked = sdpa(q, k, v, causal=causal, window=window, chunk=chunk)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(chunked),
                               rtol=2e-4, atol=2e-4)
    # unroll_inner is analysis-only sugar: same values
    unrolled = sdpa(q, k, v, causal=causal, window=window, chunk=chunk,
                    unroll_inner=True)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(unrolled),
                               rtol=1e-6, atol=1e-6)


def test_chunked_sdpa_respects_kv_len(rng):
    B, H, KV, hd, S = 1, 2, 2, 8, 32
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)).astype(np.float32))
    kvl = jnp.int32(19)
    dense = sdpa(q, k, v, causal=False, window=None, kv_len=kvl)
    chunked = sdpa(q, k, v, causal=False, window=None, kv_len=kvl, chunk=8)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(chunked),
                               rtol=2e-4, atol=2e-4)


def test_score_bf16_is_close(rng):
    B, T, H, hd = 1, 8, 2, 16
    q = jnp.asarray(rng.normal(size=(B, T, H, hd)).astype(np.float32))
    f32 = sdpa(q, q, q, causal=True, window=None)
    bf16 = sdpa(q, q, q, causal=True, window=None, score_dtype=jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(f32), np.asarray(bf16), rtol=5e-2, atol=5e-2)


def test_expert_slicing_equals_unsplit(rng):
    cfg = dataclasses.replace(
        get_smoke_config("mixtral-8x22b"), dtype="float32",
        capacity_factor=2.0, moe_group_size=16,
    )
    p = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model), jnp.float32)
    want = moe_oracle(p, cfg, x)
    for split in (2, 4):
        cfg_s = dataclasses.replace(cfg, moe_split=split)
        got = apply_moe(split_moe_params(p, split), cfg_s, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)


def test_split_init_shards_over_16():
    """The point of slicing: 8 experts × split 2 = 16 virtual experts divide
    the 16-way model axis → EP rule engages."""
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.runtime.sharding import ShardingRules, param_pspecs

    class FakeMesh:
        def __init__(self, shape):
            self.shape = shape

    cfg = dataclasses.replace(get_config("mixtral-8x22b"), moe_split=2)
    model = Model(cfg, remat=False)
    params = jax.eval_shape(model.init, jax.random.key(0))
    rules = ShardingRules(mesh=FakeMesh({"data": 16, "model": 16}))
    specs = param_pspecs(params, rules)
    assert tuple(specs["blocks"][0]["ffn"]["w_gate"]) == (None, "model", None, None)


def test_chunked_attention_model_forward_matches(rng):
    cfg = dataclasses.replace(get_smoke_config("granite-3-8b"), dtype="float32")
    cfg_c = dataclasses.replace(cfg, attn_chunk=8)
    m1, m2 = Model(cfg, remat=False), Model(cfg_c, remat=False)
    params = m1.init(jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)}
    np.testing.assert_allclose(
        np.asarray(m1.forward(params, batch)),
        np.asarray(m2.forward(params, batch)),
        rtol=2e-3, atol=2e-3,
    )


def test_chunkwise_mlstm_equals_sequential(rng):
    """The chunkwise-parallel stabilized mLSTM (EXPERIMENTS.md §Perf
    derivation) is bit-for-bit the same recurrence, state included."""
    from repro.models.ssm import apply_mlstm, init_mlstm

    cfg = dataclasses.replace(get_smoke_config("xlstm-350m"), dtype="float32")
    p = init_mlstm(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model), jnp.float32)
    out_seq, st_seq = apply_mlstm(p, cfg, x)
    for L in (4, 16):
        cfg_c = dataclasses.replace(cfg, xlstm_chunk=L)
        out_ch, st_ch = apply_mlstm(p, cfg_c, x)
        np.testing.assert_allclose(
            np.asarray(out_seq), np.asarray(out_ch), rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(st_seq["C"]), np.asarray(st_ch["C"]), rtol=2e-3, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(st_seq["m"]), np.asarray(st_ch["m"]), rtol=1e-4, atol=1e-5
        )


def test_dp_only_sharder_never_reuses_axes():
    """Regression: with the model axis folded into data, logits/seq specs
    must not reference it again (DuplicateSpecError in iteration 2)."""
    from repro.runtime.sharding import ShardingRules, make_activation_sharder

    rules = ShardingRules(
        mesh=jax.make_mesh(
            (1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2
        ),
        data_axes=("data", "model"),
        seq_shard=True,
    )
    shard = make_activation_sharder(rules)
    # No mesh context here: with_sharding_constraint would fail on a bad
    # spec at trace time inside jit; build the specs via a traced fn.
    x = jnp.zeros((4, 8, 16))

    def f(x):
        return shard(x, "logits") + shard(x, "residual")

    jax.eval_shape(f, x)  # must not raise DuplicateSpecError
