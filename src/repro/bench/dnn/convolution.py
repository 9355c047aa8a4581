"""DNN: Convolution — 2-D conv fwd/bwd.

Two paths, both benchmarked:

- ``xla``: `lax.conv_general_dilated` (the cuDNN analogue — XLA's native
  convolution, which on TPU lowers to MXU convolutions),
- ``im2col``: explicit im2col + Pallas blocked matmul — the TPU-native
  expression of "convolution as GEMM" the paper's
  `maxwell_scudnn_128x128_relu_*` kernels embody on GPU; validated against
  the XLA path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.bench.dnn.common import dnn_workload
from repro.core.presets import geometric_presets
from repro.core.registry import DNN_DOMAIN, BenchmarkSpec, register
from repro.kernels import ops


def conv2d_xla(x, w):
    """x (N, C, H, W), w (O, C, KH, KW), VALID padding, stride 1."""
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST,
    )


def conv2d_im2col(x, w):
    n, c, h, wd = x.shape
    o, _, kh, kw = w.shape
    oh, ow = h - kh + 1, wd - kw + 1
    # Patches: (N, OH, OW, C*KH*KW) via static strided slices.
    cols = jnp.stack(
        [
            x[:, :, i : i + oh, j : j + ow]
            for i in range(kh)
            for j in range(kw)
        ],
        axis=2,
    )  # (N, C, KH*KW, OH, OW)
    cols = cols.reshape(n, c * kh * kw, oh * ow)
    wmat = w.reshape(o, c * kh * kw)
    out = jax.vmap(lambda col: ops.matmul(wmat, col))(cols)  # (N, O, OH*OW)
    return out.reshape(n, o, oh, ow)


def _make(n: int, c: int, hw: int, o: int, k: int, impl: str):
    def make_inputs(seed: int):
        key = jax.random.key(seed)
        kx, kw = jax.random.split(key)
        s = (c * k * k) ** -0.5
        return (
            jax.random.normal(kx, (n, c, hw, hw), jnp.float32),
            s * jax.random.normal(kw, (o, c, k, k), jnp.float32),
        )

    fn = conv2d_im2col if impl == "im2col" else conv2d_xla

    def validate(out, args):
        import numpy as np

        want = conv2d_xla(*args)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    oh = hw - k + 1
    flops = 2.0 * n * o * c * k * k * oh * oh
    return dnn_workload(
        f"convolution.{impl}.{n}x{c}x{hw}.o{o}k{k}",
        fn,
        make_inputs,
        flops=flops,
        bytes_moved=4.0 * (n * c * hw * hw + o * c * k * k + n * o * oh * oh),
        validate=validate,
        # Only the im2col variant routes through the kernel layer; the xla
        # variant is lax.conv by definition (this spec's own `impl` preset
        # key is the conv algorithm, orthogonal to the plan's impl axis).
        pallas_kernel="matmul" if impl == "im2col" else None,
    )


for _impl in ("xla", "im2col"):
    register(
        BenchmarkSpec(
            name=f"convolution_{_impl}",
            level=2,
            dwarf="Dense linear algebra",
            domain=DNN_DOMAIN,
            cuda_feature=None,
            tpu_feature="conv-as-GEMM on MXU" if _impl == "im2col" else "XLA native conv",
            presets=geometric_presets(
                {"n": 4, "c": 16, "hw": 32, "o": 16, "k": 3, "impl": _impl},
                scale_keys={"n": 2.0, "c": 2.0, "o": 2.0},
                round_to=4,
            ),
            build=lambda n, c, hw, o, k, impl: _make(n, c, hw, o, k, impl),
        )
    )
