"""Flash-attention kernel vs dense oracle: GQA / causal / SWA / decode sweep."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import (
    _VMEM_BUDGET,
    _pick_blocks,
    _working_set,
    flash_attention_pallas,
)


def _qkv(rng, B, Hq, Hkv, T, S, D, dtype=np.float32, hv=None):
    q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(dtype))
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(dtype))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, hv or D)).astype(dtype))
    return q, k, v


# Tolerance against the f32 oracle, by the kernel's input dtype.
_TOL = {"float32": 2e-4, "bfloat16": 2e-2}

CASES = [
    # B, Hq, Hkv, T, S, D, causal, window, hv (None: D), dtype
    (1, 2, 2, 32, 32, 16, False, None, None, "float32"),
    (2, 4, 2, 32, 32, 16, True, None, None, "float32"),  # GQA causal
    (1, 8, 1, 17, 17, 8, True, None, None, "float32"),  # MQA, ragged T
    (2, 4, 4, 33, 33, 16, True, 9, None, "float32"),  # SWA
    (1, 4, 2, 1, 64, 16, True, None, None, "float32"),  # decode: 1 query vs cache
    (1, 4, 2, 1, 64, 16, True, 17, None, "float32"),  # SWA decode
    (2, 2, 2, 16, 48, 8, True, None, None, "float32"),  # chunked prefill (kv_len > q_len)
    (1, 2, 2, 64, 64, 24, True, None, 16, "float32"),  # values narrower than q/k (MLA)
    (1, 2, 2, 64, 64, 24, True, None, 16, "bfloat16"),  # the same, bf16 on the MXU
]


def _case_id(case):
    """The f32 same-width cases keep their ids from before hv and dtype."""
    *shape, hv, dtype = case
    extra = ([f"hv{hv}"] if hv else []) + ([dtype] if dtype != "float32" else [])
    return "-".join(str(x) for x in shape + extra)


@pytest.mark.parametrize(
    "B,Hq,Hkv,T,S,D,causal,window,hv,dtype", CASES, ids=[_case_id(c) for c in CASES]
)
def test_flash_matches_ref(rng, B, Hq, Hkv, T, S, D, causal, window, hv, dtype):
    q, k, v = _qkv(rng, B, Hq, Hkv, T, S, D, hv=hv)
    qd, kd, vd = (x.astype(dtype) for x in (q, k, v))
    out = flash_attention_pallas(
        qd, kd, vd, causal=causal, window=window, block_q=16, block_k=16, interpret=True
    )
    assert out.dtype == jnp.dtype(dtype) and out.shape == (B, Hq, T, hv or D)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want), rtol=_TOL[dtype], atol=_TOL[dtype]
    )


@pytest.mark.parametrize(
    "T,S,D,hv,itemsize,want",
    [
        # Moonlight's MLA prefill in bf16: 36 of the 64 (query, key) block
        # pairs are at or below the diagonal.
        (4096, 4096, 192, 128, 2, (512, 512)),
        (4096, 4096, 192, 128, 4, None),
        (2048, 2048, 128, 128, 2, None),
        (1000, 3000, 64, 64, 4, None),  # ragged: padded to 1024 and 3072
        (17, 17, 8, 8, 4, (17, 17)),  # under 128: the whole axis
        (1, 640, 16, 16, 2, None),  # decode
    ],
)
def test_block_pick_divides_fits_and_keeps_blocks(T, S, D, hv, itemsize, want):
    bq, bk = _pick_blocks(T, S, D, hv, itemsize)
    assert want is None or (bq, bk) == want
    for n, block in ((T, bq), (S, bk)):
        assert (n if n < 128 else -(-n // 128) * 128) % block == 0, (n, block)
    assert _working_set(bq, bk, S, D, hv, itemsize) <= _VMEM_BUDGET
    assert _pick_blocks(T, S, D, hv, itemsize, block_q=16, block_k=256) == (
        min(16, T), min(256, S)
    )
    assert _pick_blocks(T, S, D, hv, itemsize, block_k=256)[1] == min(256, S)


def test_flash_bf16(rng):
    q, k, v = _qkv(rng, 1, 2, 2, 32, 32, 16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention_pallas(qb, kb, vb, causal=True, block_q=16, block_k=16, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want), rtol=2e-2, atol=2e-2
    )


def test_flash_swa_equals_model_sdpa(rng):
    """The kernel and the model-layer sdpa agree (two independent impls)."""
    from repro.models.layers import sdpa

    q, k, v = _qkv(rng, 2, 4, 2, 24, 24, 16)
    out = flash_attention_pallas(q, k, v, causal=True, window=7, block_q=8,
                                 block_k=8, interpret=True)
    got2 = sdpa(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=True, window=7,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(jnp.swapaxes(got2, 1, 2)), rtol=2e-4, atol=2e-4
    )


def test_flash_grad_via_ref_path():
    """Training path (ops.attention mode=ref) is differentiable and finite."""
    from repro.kernels import ops

    key = jax.random.key(0)
    q = jax.random.normal(key, (1, 2, 8, 4))

    def f(q):
        return jnp.sum(ops.attention(q, q, q, causal=True, mode="ref"))

    g = jax.grad(f)(q)
    assert np.all(np.isfinite(np.asarray(g)))
