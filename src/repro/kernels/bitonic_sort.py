"""Tiled bitonic key-value sort (the Sort benchmark).

TPU adaptation of the paper's radix sort (Satish et al.): radix sort's
per-digit histogram + scatter is gather/scatter-heavy, which the TPU's
vector unit punishes. A bitonic network is branch-free: every stage is a
compare-exchange of element ``i`` with its partner ``i ^ j``, pure vector
min/max/select with zero gathers. O(n log² n) work trades for full lane
utilization.

The array is laid out as rows of 128 lanes and cut into tiles of ``tile``
elements that fit VMEM. Two kernels cover the network:

- the *tile* kernel runs every stage whose partner lies in the same tile
  (``j < tile``). Partners come from ``pltpu.roll`` along the lanes
  (``j < 128``) or the rows (``j >= 128``); both roll directions are taken
  and each element keeps the one whose rolled index is its partner's, so
  the kernel does not depend on the roll's sign convention.
- the *cross* kernel runs one stage with ``j >= tile``: the array is viewed
  as (n/2j, 2, j/tile, rows, 128), so one block holds a tile and its
  partner tile, and the exchange is elementwise between the two.

For n <= tile one tile-kernel call sorts everything; beyond that each merge
level ``k`` runs its cross stages then one tile-kernel call for the rest.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bitonic_sort_pallas", "tune_space"]

_LANES = 128
_TILE = 1 << 14  # elements per tile: 64 KiB per int32 array in VMEM


def tune_space() -> tuple[dict, ...]:
    """No block parameters: the network shape is fixed by N (single entry)."""
    return ({},)


def _exchange(keys, vals, kp, vp, take_min):
    """Keep min or max of (key, partner key); strict comparisons on both
    sides, so on equal keys each element keeps its own (key, value) pair
    (otherwise one pair is duplicated and its partner dropped). Masks are
    combined with logical ops: Mosaic cannot select between two boolean
    vectors."""
    swap = (take_min & (keys > kp)) | (~take_min & (keys < kp))
    return jnp.where(swap, kp, keys), jnp.where(swap, vp, vals)


def _tile_kernel(k_ref, v_ref, ko_ref, vo_ref, *, stages, tile):
    keys, vals = k_ref[...], v_ref[...]  # (rows, lanes)
    rows, lanes = keys.shape
    idx = lanes * jax.lax.broadcasted_iota(
        jnp.int32, (rows, lanes), 0
    ) + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    gidx = pl.program_id(0) * tile + idx  # global element index
    for k, j in stages:
        up = (gidx & k) == 0  # ascending iff bit k of the global index is 0
        axis, dist = (1, j) if j < lanes else (0, j // lanes)
        size = keys.shape[axis]
        partner = idx ^ j
        fwd = pltpu.roll(idx, dist, axis) == partner
        kp = jnp.where(
            fwd, pltpu.roll(keys, dist, axis), pltpu.roll(keys, size - dist, axis)
        )
        vp = jnp.where(
            fwd, pltpu.roll(vals, dist, axis), pltpu.roll(vals, size - dist, axis)
        )
        # Ascending pairs: the lower index keeps the min. Descending: max.
        take_min = ((idx & j) == 0) == up
        keys, vals = _exchange(keys, vals, kp, vp, take_min)
    ko_ref[...] = keys
    vo_ref[...] = vals


def _cross_kernel(k_ref, v_ref, ko_ref, vo_ref, *, k, j, tile):
    pairs = j // tile  # partner distance, in tiles
    p = pl.program_id(0)
    base = (p // pairs) * 2 * j + (p % pairs) * tile  # first low element
    lo_k, hi_k = k_ref[0, 0, 0], k_ref[0, 1, 0]
    lo_v, hi_v = v_ref[0, 0, 0], v_ref[0, 1, 0]
    # The whole tile pair shares one direction: bit k of its base index.
    up = (jnp.full(lo_k.shape, base, jnp.int32) & k) == 0
    swap = (up & (lo_k > hi_k)) | (~up & (lo_k < hi_k))
    ko_ref[0, 0, 0] = jnp.where(swap, hi_k, lo_k)
    ko_ref[0, 1, 0] = jnp.where(swap, lo_k, hi_k)
    vo_ref[0, 0, 0] = jnp.where(swap, hi_v, lo_v)
    vo_ref[0, 1, 0] = jnp.where(swap, lo_v, hi_v)


def _tile_call(keys, vals, stages, tile, interpret):
    rows, lanes = keys.shape
    t_rows = tile // lanes
    spec = pl.BlockSpec((t_rows, lanes), lambda t: (t, 0))
    return pl.pallas_call(
        functools.partial(_tile_kernel, stages=tuple(stages), tile=tile),
        grid=(rows // t_rows,),
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        out_shape=(
            jax.ShapeDtypeStruct(keys.shape, keys.dtype),
            jax.ShapeDtypeStruct(vals.shape, vals.dtype),
        ),
        interpret=interpret,
    )(keys, vals)


def _cross_call(keys, vals, k, j, tile, interpret):
    rows, lanes = keys.shape
    n = rows * lanes
    pairs, t_rows = j // tile, tile // lanes
    view = (n // (2 * j), 2, pairs, t_rows, lanes)
    spec = pl.BlockSpec(
        (1, 2, 1, t_rows, lanes),
        lambda p: (p // pairs, 0, p % pairs, 0, 0),
    )
    ko, vo = pl.pallas_call(
        functools.partial(_cross_kernel, k=k, j=j, tile=tile),
        grid=(n // (2 * tile),),
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        out_shape=(
            jax.ShapeDtypeStruct(view, keys.dtype),
            jax.ShapeDtypeStruct(view, vals.dtype),
        ),
        interpret=interpret,
    )(keys.reshape(view), vals.reshape(view))
    return ko.reshape(rows, lanes), vo.reshape(rows, lanes)


def _halvings(m: int) -> list[int]:
    """m/2, m/4, ..., 1."""
    return [m >> e for e in range(1, m.bit_length())]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_pallas(
    keys: jax.Array,  # (N,) — N padded to a power of two by the wrapper
    values: jax.Array,  # (N,)
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    (N,) = keys.shape
    assert N & (N - 1) == 0, f"bitonic sort needs a power-of-two length, got {N}"
    assert values.shape == (N,)
    lanes = min(_LANES, N)
    tile = min(_TILE, N)
    k2, v2 = keys.reshape(-1, lanes), values.reshape(-1, lanes)
    # Sort each tile (directions alternate by the global index bits).
    sort_stages = [(2 << e, j) for e in range(tile.bit_length() - 1)
                   for j in _halvings(2 << e)]
    k2, v2 = _tile_call(k2, v2, sort_stages, tile, interpret)
    k = 2 * tile
    while k <= N:
        for j in _halvings(k):
            if j >= tile:
                k2, v2 = _cross_call(k2, v2, k, j, tile, interpret)
        merge_stages = [(k, j) for j in _halvings(tile)]
        k2, v2 = _tile_call(k2, v2, merge_stages, tile, interpret)
        k *= 2
    return k2.reshape(N), v2.reshape(N)
