"""Plain reference of ``pathfinder-mix``: Rodinia's pathfinder, the least
cost of a path down a rows x cols grid of int32 weights, entering anywhere
in row 0 and moving to one of the three nearest cells of the next row.

``dist[0] = grid[0]``; ``dist[i][j] = grid[i][j] + min(dist[i-1][j-1],
dist[i-1][j], dist[i-1][j+1])``, an edge cell taking its own column in
place of the missing neighbour. The answer is ``dist[rows-1]``. The
reference is a NumPy loop over rows on the host and imports nothing of the
program. Integer sums are exact, so the comparison counts the cells that
differ, and its limit is 0.

The control is the reference in int8, the next integer width that cannot
hold every cost. (int16 holds them: a cost is at most 9 x 512 = 4,608, so
int16 would be a sound narrowing and no control.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LIMITS = {"mismatches": 0}


@functools.partial(jax.jit, static_argnames=("shapes",))
def _make(key, shapes):
    return tuple(
        jax.random.randint(jax.random.fold_in(key, i), shape, 0, 10, jnp.int32)
        for i, shape in enumerate(shapes)
    )


def make_inputs(key, config: dict) -> dict:
    """``max_batch`` grids for each bucket, stacked on a leading axis, from
    the seed's key, on the device, in one jitted call."""
    shapes = tuple(
        (config["max_batch"], b["rows"], b["cols"]) for b in config["buckets"]
    )
    grids = jax.block_until_ready(_make(key, shapes))
    return {b["label"]: g for b, g in zip(config["buckets"], grids)}


def min_path(grid: np.ndarray, dtype=np.int32) -> np.ndarray:
    g = np.asarray(grid).astype(dtype)
    dist = g[0].copy()
    for row in g[1:]:
        left = np.concatenate([dist[:1], dist[:-1]])
        right = np.concatenate([dist[1:], dist[-1:]])
        dist = row + np.minimum(dist, np.minimum(left, right))
    return dist


def compare(out, grid) -> dict:
    """The numbers compared for one answer: ``out`` for one ``grid``."""
    want = min_path(grid)
    got = np.asarray(out)
    if got.shape != want.shape:
        return {"mismatches": int(want.size)}
    return {"mismatches": int(np.count_nonzero(got != want))}


def control(grid) -> np.ndarray:
    return min_path(grid, np.int8)
