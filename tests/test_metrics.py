"""Roofline metrics: flop conventions, HLO collective parsing, classification."""

import jax
import jax.numpy as jnp

from repro.core.metrics import (
    TPUv5e,
    collective_bytes_from_hlo,
    collective_ops_from_hlo,
    cost_analysis_dict,
    model_flops,
    roofline_terms,
    utilization_scale10,
)


def test_cost_analysis_flops_convention():
    """XLA counts 2·m·n·k for a matmul — the convention §Roofline assumes."""
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    c = jax.jit(lambda a, b: a @ b).lower(a, a).compile()
    assert abs(cost_analysis_dict(c)["flops"] - 2 * 256**3) < 1


def test_scan_body_counted_once():
    """The measurement hazard the dry-run's 1/2-period extrapolation fixes."""
    def make(n):
        w = jax.ShapeDtypeStruct((n, 64, 64), jnp.float32)
        x = jax.ShapeDtypeStruct((4, 64), jnp.float32)

        def f(w, x):
            return jax.lax.scan(lambda x, wi: (jnp.tanh(x @ wi), None), x, w)[0]

        return cost_analysis_dict(jax.jit(f).lower(w, x).compile())["flops"]

    assert make(4) == make(8)  # trip count invisible to cost_analysis


def test_collective_parsing_on_crafted_hlo():
    hlo = """
  %ag = bf16[16,512,128]{2,1,0} all-gather(bf16[1,512,128] %x), dim=0
  %ar.1 = f32[1024]{0} all-reduce(f32[1024] %y), to_apply=%sum
  %rs = f32[64]{0} reduce-scatter(f32[1024] %z), dimensions={0}
  %cp = u32[8,128]{1,0} collective-permute(u32[8,128] %w)
  %a2a = s8[4,4]{1,0} all-to-all(s8[4,4] %v)
  %done = f32[1024]{0} all-reduce-done(f32[1024] %h)
"""
    ops = collective_ops_from_hlo(hlo)
    kinds = sorted(k for k, _ in ops)
    assert kinds == sorted(
        ["all-gather", "all-reduce", "reduce-scatter", "collective-permute", "all-to-all"]
    )
    d = dict(ops)
    assert d["all-gather"] == 16 * 512 * 128 * 2
    assert d["all-reduce"] == 1024 * 4 * 2  # 2× for ring reduce+broadcast
    assert d["reduce-scatter"] == 64 * 4
    assert d["collective-permute"] == 8 * 128 * 4
    assert d["all-to-all"] == 16 * 1
    assert collective_bytes_from_hlo(hlo) == sum(b for _, b in ops)


def test_real_psum_hlo_is_parsed():
    mesh = jax.make_mesh((1,), ("d",))
    from jax.sharding import PartitionSpec as P

    def f(x):
        return jax.lax.psum(x, "d")

    fm = jax.shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P())
    c = jax.jit(fm).lower(jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile()
    # single-device: collective may be optimized away; parsing must not crash
    assert collective_bytes_from_hlo(c.as_text()) >= 0.0


def test_roofline_classification():
    rt = roofline_terms({"flops": 197e12, "bytes accessed": 819e9 / 2},
                        collective_bytes=0.0)
    assert abs(rt.compute_s - 1.0) < 1e-9
    assert rt.dominant == "compute"
    assert abs(rt.roofline_fraction - 1.0) < 1e-9
    rt2 = roofline_terms({"flops": 1e12, "bytes accessed": 819e9 * 2})
    assert rt2.dominant == "memory"
    rt3 = roofline_terms({"flops": 1e12, "bytes accessed": 1e9},
                         collective_bytes=50e9 * 3)
    assert rt3.dominant == "collective"


def test_utilization_scale10():
    assert utilization_scale10(0.0) == 0
    assert utilization_scale10(1.0) == 10
    assert utilization_scale10(0.449) == 4
    assert utilization_scale10(2.0) == 10  # clamped


def test_model_flops_moe_active():
    dense = model_flops(1e9, 1e6)
    moe = model_flops(8e9, 1e6, active_params=2e9)
    assert dense == 6e15
    assert moe == 12e15


def test_hw_constants_are_assignment_values():
    assert TPUv5e.peak_bf16_flops == 197e12
    assert TPUv5e.hbm_bw == 819e9
    assert TPUv5e.ici_bw == 50e9
