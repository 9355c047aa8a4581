"""DNN: Pooling — average pooling fwd/bwd (paper: cuDNN avg pool).

Forward uses the Pallas reshape-reduce kernel on TPU (`kernels.avgpool`);
backward is the uniform-spread gradient (each input gets grad/k²).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.bench.dnn.common import dnn_workload
from repro.core.presets import geometric_presets
from repro.core.registry import DNN_DOMAIN, BenchmarkSpec, register
from repro.kernels import ops


def _make(n: int, c: int, hw: int, ksize: int):
    shape = (n, c, hw, hw)

    def make_inputs(seed: int):
        return (jax.random.normal(jax.random.key(seed), shape, jnp.float32),)

    def fn(x):
        return ops.avgpool(x, ksize=ksize)

    def validate(out, args):
        import numpy as np

        # Exact window means in float64. Any f32 summation order lands
        # within k²·eps·mean|window| of them, so the check holds the
        # kernel to f32 rounding without demanding one order of adds.
        (x,) = args
        win = np.asarray(x, np.float64).reshape(
            n, c, hw // ksize, ksize, hw // ksize, ksize
        )
        want = win.mean(axis=(3, 5))
        slack = ksize**2 * np.finfo(np.float32).eps * np.abs(win).mean(axis=(3, 5))
        err = np.abs(np.asarray(out, np.float64) - want)
        excess = err - (1e-5 * np.abs(want) + slack)
        assert np.all(excess <= 0), f"avgpool off by {excess.max():.3g} past f32 rounding"

    numel = float(n * c * hw * hw)
    return dnn_workload(
        f"pooling.avg{ksize}.{n}x{c}x{hw}x{hw}",
        fn,
        make_inputs,
        flops=numel,
        bytes_moved=numel * 4 * (1 + 1 / ksize**2),
        validate=validate,
        pallas_kernel="avgpool",
    )


register(
    BenchmarkSpec(
        name="pooling",
        level=2,
        dwarf="Dense linear algebra",
        domain=DNN_DOMAIN,
        cuda_feature=None,
        tpu_feature="reshape-reduce kernel (Pallas)",
        presets=geometric_presets(
            {"n": 8, "c": 16, "hw": 32, "ksize": 2},
            scale_keys={"n": 2.0, "c": 2.0},
            round_to=4,
        ),
        build=lambda n, c, hw, ksize: _make(n, c, hw, ksize),
    )
)
