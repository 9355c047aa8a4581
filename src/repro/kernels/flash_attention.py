"""Flash (online-softmax) attention kernel with GQA + causal + sliding window.

TPU adaptation notes (DESIGN.md §2):

- GQA is expressed through the **BlockSpec index map** — the kv block for
  query head ``h`` is head ``h // group``; kv heads are never materialized
  per-query-head in HBM (the wrapper-level ``jnp.repeat`` of the oracle is
  exactly what this avoids).
- The online-softmax running (m, l, acc) state lives in VMEM registers inside
  a ``fori_loop`` over key blocks; the loop *trip count is dynamic* per query
  block: causal masking bounds the top, sliding-window masking bounds the
  bottom, so SWA decode does O(window) work per token — this is what makes
  ``long_500k`` sub-quadratic for mixtral-style archs. Key blocks above the
  causal diagonal are never visited, and the mask is computed only on the
  blocks that straddle a bound (the diagonal, the window's edge, padding).
- Queries occupy the last ``t_valid`` positions of the ``s_valid``-long key
  timeline (offset = s_valid - t_valid), covering prefill and cached decode.
- The value width is ``v.shape[-1]`` and may differ from the q/k width
  (MLA's 192-wide q/k against 128-wide values); the output takes v's width.
- The MXU operands keep the inputs' dtype and accumulate in f32: q is scaled
  in f32 and cast back, and p is cast to v's dtype before PV, so bf16 inputs
  run single-pass bf16 matmuls. The max, the exp, the running (m, l) and the
  accumulator stay f32. For f32 inputs every step is f32.
- Blocks a caller leaves out are picked from the shapes and the dtype
  (``_pick_blocks``): the fewest (query, key) block pairs whose VMEM working
  set fits a budget under v5e's 16 MiB of scoped VMEM. The whole K and V of
  one kv head stay in VMEM, fetched once per (batch, head).
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention_pallas", "tune_space"]

_TILES = (512, 256, 128)
# The working set a pick may take: 2 MiB under the 16 MiB of scoped VMEM
# Mosaic grants a kernel on v5e, for the scratch _working_set leaves out.
_VMEM_BUDGET = 14 << 20


def tune_space() -> tuple[dict, ...]:
    """Autotune candidates (first entry = the kernel's defaults, picked from
    the shapes by ``_pick_blocks``)."""
    return (
        {},
        {"block_q": 128, "block_k": 128},
        {"block_q": 256, "block_k": 128},
        {"block_q": 128, "block_k": 256},
    )


_NEG_INF = -1e30


def _seq_blocks(n: int) -> tuple[int, ...]:
    """Block sizes for a sequence axis that pad it no further than rounding
    it up to 128: the whole axis when it is under 128, else each tile that
    divides the 128-rounded length."""
    if n < 128:
        return (n,)
    padded = -(-n // 128) * 128
    return tuple(t for t in _TILES if padded % t == 0)


def _working_set(bq: int, bk: int, S: int, D: int, hv: int, itemsize: int) -> int:
    """VMEM bytes of one grid step: double-buffered q and output blocks and
    the whole padded K and V of one head, the f32 accumulator and running
    (m, l) (lane-padded to 128), and four f32 (bq, bk) score temporaries
    (s, p, the mask and exp's operand)."""
    Sp = -(-S // bk) * bk
    return (
        2 * (bq * D + bq * hv + Sp * (D + hv)) * itemsize
        + 4 * bq * (hv + 2 * 128)
        + 4 * 4 * bq * bk
    )


def _pick_blocks(
    T: int,
    S: int,
    D: int,
    hv: int,
    itemsize: int,
    block_q: int | None = None,
    block_k: int | None = None,
) -> tuple[int, int]:
    """(bq, bk) for T queries against S keys; a block passed in is kept
    (clamped to its axis). The rest come from ``_seq_blocks``: the fewest
    (query block, key block) pairs within ``_VMEM_BUDGET``, then the
    smallest working set. Where nothing fits, the least overshoot wins."""

    def options(block, n):
        return (min(block, n),) if block else _seq_blocks(n)

    def cost(blocks):
        bq, bk = blocks
        over = _working_set(bq, bk, S, D, hv, itemsize) - _VMEM_BUDGET
        return (max(over, 0), -(-T // bq) * -(-S // bk), over)

    return min(itertools.product(options(block_q, T), options(block_k, S)), key=cost)


def _flash_kernel(
    q_ref,  # (1, 1, bq, D)
    k_ref,  # (1, 1, Sp, D)
    v_ref,  # (1, 1, Sp, hv)
    o_ref,  # (1, 1, bq, hv)
    *,
    block_k: int,
    s_valid: int,
    t_valid: int,
    causal: bool,
    window: int | None,
    scale: float,
    num_k_blocks: int,
):
    bq = q_ref.shape[2]
    hv = v_ref.shape[3]
    qi = pl.program_id(2)
    offset = s_valid - t_valid  # absolute position of query row 0
    first_q = offset + qi * bq
    last_q = first_q + bq - 1
    q_pos = first_q + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    q = (q_ref[0, 0].astype(jnp.float32) * scale).astype(q_ref.dtype)  # (bq, D)

    # Visited key blocks [lo, hi): causal masking bounds the top, the
    # sliding window the bottom. Inside them, [full_lo, full_hi) hold only
    # keys every query of the block sees, and skip the mask.
    hi, full_hi = num_k_blocks, s_valid // block_k
    lo = full_lo = 0
    if causal:
        hi = jnp.minimum(last_q // block_k + 1, hi)
        full_hi = jnp.minimum((first_q + 1) // block_k, full_hi)
    if window is not None:
        lo = jnp.maximum((first_q - window + 1) // block_k, 0)
        full_lo = (last_q - window + block_k) // block_k

    def body(j, carry, masked):
        m, l, acc = carry
        start = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, 0, pl.dslice(start, block_k), :]
        v = v_ref[0, 0, pl.dslice(start, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        if masked:
            k_pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            mask = k_pos < s_valid
            if causal:
                mask &= k_pos <= q_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:  # a row with every key masked so far has m_new = _NEG_INF
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    a = jnp.clip(full_lo, lo, hi)
    b = jnp.clip(full_hi, a, hi)
    carry = (
        jnp.full((bq, 1), _NEG_INF, jnp.float32),
        jnp.zeros((bq, 1), jnp.float32),
        jnp.zeros((bq, hv), jnp.float32),
    )
    masked = functools.partial(body, masked=True)
    carry = jax.lax.fori_loop(lo, a, masked, carry)
    carry = jax.lax.fori_loop(a, b, functools.partial(body, masked=False), carry)
    m, l, acc = jax.lax.fori_loop(b, hi, masked, carry)
    out = acc / jnp.maximum(l, 1e-30)
    o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # (B, Hq, T, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,  # (B, Hkv, S, hv)
    *,
    causal: bool = False,
    window: int | None = None,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """-> (B, Hq, T, hv) in q's dtype."""
    B, Hq, T, D = q.shape
    _, Hkv, S, _ = k.shape
    hv = v.shape[-1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if scale is None:
        scale = float(D) ** -0.5

    bq, bk = _pick_blocks(
        T, S, D, hv, max(x.dtype.itemsize for x in (q, k, v)), block_q, block_k
    )
    pt, ps = (-T) % bq, (-S) % bk
    if pt:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pt), (0, 0)))
    if ps:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, ps), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, ps), (0, 0)))
    Tp, Sp = T + pt, S + ps

    kernel = functools.partial(
        _flash_kernel,
        block_k=bk,
        s_valid=S,
        t_valid=T,
        causal=causal,
        window=window,
        scale=scale,
        num_k_blocks=Sp // bk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, Tp // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Sp, D), lambda b, h, i, g=group: (b, h // g, 0, 0)),
            pl.BlockSpec((1, 1, Sp, hv), lambda b, h, i, g=group: (b, h // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hv), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Tp, hv), q.dtype),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :T]
