"""The Pallas kernels compile for a TPU v5e at the suite's one-chip size.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: VMEM overruns, vector shape casts Mosaic does not
support, primitives with no TPU lowering. Here each kernel a workload
declares is compiled — not run — for one chip of a described ``v5e:2x2``
topology, at the preset-3 shapes of the workloads that declare it, with
``interpret=False`` and the kernel's default blocks. This is the only test
file that describes a TPU; the topology is built inside a fixture, so
collection never loads the TPU library.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from repro.core.registry import all_benchmarks, get_benchmark
from repro.kernels import ops

PRESET = 3
# Workloads that declare a Pallas kernel (gemm x4, maxflops x2, sort,
# where, srad, softmax, convolution_im2col, lrn, connected, pooling,
# lm_prefill).
PALLAS_WORKLOADS = (
    "gemm_bf16_nn", "gemm_bf16_tn", "gemm_f32_nn", "gemm_f32_tn",
    "maxflops_bf16", "maxflops_f32", "sort", "where", "srad", "softmax",
    "convolution_im2col", "lrn", "connected", "pooling", "lm_prefill",
)
V5E_HBM = 16e9


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent compilation
    cache off: entries compiled for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes,
    )
    return jax.jit(fn).lower(*args).compile()


def test_pallas_workload_list_is_complete():
    declared = {
        s.name for s in all_benchmarks() if s.build_preset(PRESET).pallas_kernel
    }
    assert declared == set(PALLAS_WORKLOADS)


@pytest.mark.parametrize("name", PALLAS_WORKLOADS)
def test_declared_kernel_compiles_for_v5e(name, one_chip, monkeypatch):
    # Steer the kernel ops as a TPU host would: compiled, not interpreted.
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    workload = get_benchmark(name).build_preset(PRESET)
    shapes = jax.eval_shape(lambda: workload.make_inputs(0))
    with ops.force_impl("pallas", workload.pallas_kernel):
        compiled = _compile(workload.fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    # One call's inputs, outputs and temporaries fit the chip.
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes < V5E_HBM


# The GEMM benchmark's size: blocks picked for 8192² must stay inside the
# chip's scoped VMEM, which the CPU host sees only through this compile.
@pytest.mark.parametrize("name", ["gemm_bf16_nn", "gemm_f32_nn"])
def test_gemm_compiles_for_v5e_at_n8192(name, one_chip, monkeypatch):
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    workload = get_benchmark(name).build_preset(PRESET, n=8192)
    shapes = jax.eval_shape(lambda: workload.make_inputs(0))
    with ops.force_impl("pallas", workload.pallas_kernel):
        compiled = _compile(workload.fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


# Same-width heads, and Moonlight's MLA prefill (8 prompts of 4,096 tokens,
# 192-wide q/k, 128-wide v): the picked blocks fit the scoped VMEM.
@pytest.mark.parametrize(
    "qk,v", [((1, 8, 2048, 128), (1, 8, 2048, 128)), ((8, 16, 4096, 192), (8, 16, 4096, 128))]
)
def test_flash_attention_compiles_for_v5e(one_chip, qk, v):
    from repro.kernels.flash_attention import flash_attention_pallas

    q = jax.ShapeDtypeStruct(qk, jnp.bfloat16)
    v = jax.ShapeDtypeStruct(v, jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
        (q, q, v),
        one_chip,
    )
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes < V5E_HBM
