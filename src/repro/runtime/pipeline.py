"""GPipe-style pipeline parallelism over the ``pod`` axis.

The pod axis defaults to extra data parallelism; this module is the
alternative binding (DESIGN.md §5): the layer stack is split into P
contiguous stages (params sharded over ``pod`` on their stacked-layer dim by
``shard_map``), microbatches flow stage-to-stage via ``lax.ppermute`` in a
``lax.scan`` over M + P - 1 ticks (the GPipe schedule: P-1 bubble ticks).

This is the *cross-pod traffic shape-changer*: DP-over-pod moves the full
gradient every step over the slow link; PP moves only microbatch activations
(B_mb × T × d per tick). Which wins is quantified in EXPERIMENTS.md §Perf
for jamba (the most collective-bound cell).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["gpipe_forward"]


def gpipe_forward(
    stage_fn: Callable,  # stage_fn(stage_params, x) -> x
    mesh: Mesh,
    *,
    axis: str = "pod",
):
    """Returns f(stacked_params, x_microbatches) running the pipeline.

    ``stacked_params``: pytree with leading dim = n_stages·layers_per_stage
    (sharded over ``axis``); ``x_microbatches``: (M, mb, ...) replicated in.
    Output: (M, mb, ...) of last-stage results (replicated out).
    """
    n_stages = mesh.shape[axis]

    def pipelined(stage_params, x_mb):
        stage = jax.lax.axis_index(axis)
        M = x_mb.shape[0]
        ticks = M + n_stages - 1

        def tick(carry, t):
            act = carry  # activation entering this stage this tick
            # Stage 0 ingests microbatch t (clamped; bubbles are masked out).
            mb = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
            )
            inp = jnp.where(stage == 0, mb, act)
            out = stage_fn(stage_params, inp)
            # Results of the final stage for microbatch t-(P-1).
            is_result = (t - (n_stages - 1) >= 0) & (stage == n_stages - 1)
            emitted = jnp.where(is_result, out, jnp.zeros_like(out))
            # Everyone reduces so the result is replicated (cheap at test
            # scale; a real launch would keep results on the last stage).
            emitted = jax.lax.psum(emitted, axis)
            # Hand activations to the next stage.
            act_next = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return act_next, emitted

        x0 = jax.lax.pcast(jnp.zeros_like(x_mb[0]), (axis,), to="varying")
        _, results = jax.lax.scan(tick, x0, jnp.arange(ticks))
        return results[n_stages - 1 :]  # (M, mb, ...)

    in_specs = (P(axis), P())  # params stage-sharded; microbatches replicated
    out_specs = P()
    return jax.shard_map(pipelined, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
