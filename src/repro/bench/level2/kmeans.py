"""Level 2: KMeans — Lloyd iterations (data mining).

Assignment is a dense distance matmul (‖x−c‖² = ‖x‖² − 2x·cᵀ + ‖c‖², the
MXU-friendly expansion) + argmin; update is a one-hot matmul (segment mean
without scatters — TPU adaptation of the GPU's atomic accumulation).
Validation: inertia is non-increasing across iterations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.presets import geometric_presets
from repro.core.registry import BenchmarkSpec, Workload, register


def kmeans_step(points: jax.Array, centers: jax.Array):
    """One Lloyd iteration. points (N, D), centers (K, D) -> (centers', inertia)."""
    # Full f32 precision: the TPU's default rounds matmul operands to
    # bf16, which moves assignments and centres by far more than f32
    # rounding and makes placements disagree.
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    x2 = jnp.sum(points * points, axis=1, keepdims=True)  # (N, 1)
    c2 = jnp.sum(centers * centers, axis=1)[None]  # (1, K)
    d2 = x2 - 2.0 * dot(points, centers.T) + c2  # (N, K)
    assign = jnp.argmin(d2, axis=1)
    inertia = jnp.sum(jnp.min(d2, axis=1))
    onehot = jax.nn.one_hot(assign, centers.shape[0], dtype=points.dtype)  # (N, K)
    sums = dot(onehot.T, points)  # (K, D)
    counts = jnp.sum(onehot, axis=0)[:, None]
    new_centers = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), centers)
    return new_centers, inertia


def _make(n: int, d: int, k: int, iters: int) -> Workload:
    def make_inputs(seed: int):
        key = jax.random.key(seed)
        kp, kc = jax.random.split(key)
        pts = jax.random.normal(kp, (n, d), jnp.float32)
        ctr = pts[jax.random.choice(kc, n, (k,), replace=False)]
        return (pts, ctr)

    def fn(points, centers):
        def body(carry, _):
            centers, _ = carry
            new_centers, inertia = kmeans_step(points, centers)
            return (new_centers, inertia), inertia

        (centers, _), history = jax.lax.scan(
            body, (centers, jnp.float32(0)), None, length=iters
        )
        return centers, history

    def validate(out, args):
        import numpy as np

        _, history = out
        h = np.asarray(history)
        assert np.all(np.diff(h) <= 1e-2 * np.abs(h[:-1]) + 1e-3), (
            f"k-means inertia increased: {h}"
        )

    return Workload(
        name=f"kmeans.n{n}.d{d}.k{k}.i{iters}",
        fn=fn,
        make_inputs=make_inputs,
        flops=float(iters * (2.0 * n * d * k + 2.0 * n * k * d)),
        bytes_moved=float(iters * n * d * 4 * 2),
        validate=validate,
        # Classic data-parallel Lloyd: points shard over rows, centers
        # replicate; the one-hot segment sums reduce with a psum per iter.
        batch_dims=(0, None),
    )


register(
    BenchmarkSpec(
        name="kmeans",
        level=2,
        dwarf="Dense linear algebra",
        domain="Data mining",
        cuda_feature=None,
        tpu_feature="one-hot matmul segment reduce",
        presets=geometric_presets(
            {"n": 4096, "d": 16, "k": 16, "iters": 5},
            scale_keys={"n": 4.0, "d": 2.0},
            round_to=8,
        ),
        build=lambda n, d, k, iters: _make(n, d, k, iters),
    )
)
