"""The Moonlight prefill cell at CPU size: its files load, sound runs are
correct, an altered answer and a half-computed expert layer are not, the
float8 control fails the comparison, and the scope reader and
``prefill_mfu`` read made-up traces."""

from __future__ import annotations

import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness, scopes
from perfbench.tests.small import REPO, run_small, small_root, write_json
from perfbench.trace import TraceSummary

CELL = "moonlight-16b-a3b-ep8.prefill8x4k"
CONFIG = "moonlight-16b-a3b-ep8"
# The configuration at lm_prefill's preset-0 widths (the smoke config),
# holding experts 0-3 of 8, in float32 as the model's CPU tests run it: in
# bfloat16 a model 64 wide flips routing too often for limits set at the
# published widths.
SMALL = {
    "precision": {"weights": "float32", "activations": "float32"},
    "preset": 0, "overrides": {"batch": 2, "seq": 32, "held": 4},
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "n_routed_experts": 4, "num_experts_per_tok": 3, "vocab_size": 256,
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = small_root(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "perfbench", "configs", CONFIG + ".json")
    with open(path, encoding="utf-8") as fh:
        config = json.load(fh)
    config.update(SMALL)
    config["deployment"].update(n_routed_experts=8, held_experts=[0, 4])
    write_json(path, config)
    return root


def test_the_cell_finds_its_files():
    bench = harness.Bench(REPO)
    cell = bench.cell(CELL)
    assert (cell.config, cell.chips) == (CONFIG, 1)
    config = bench.config(CONFIG)
    assert (config["registry"], config["n_routed_experts"]) == ("lm_prefill", 8)
    assert bench.traffic(cell.traffic) == {
        "driver": "closed_loop", "in_flight": 2, "impl": "pallas", "trace_seconds": 4.0,
    }
    ref = bench.ref(CONFIG)
    assert callable(ref.compare) and callable(ref.control) and ref.LIMITS
    assert {m["name"] for m in bench.metrics_for(CELL, False)} == {"call_us", "setup_s"}
    layer = {m["name"] for m in bench.metrics_for(CELL, True)}
    assert layer == {
        "prefill_mfu", "mla_us.prefill", "moe_us.prefill", "device_idle.timed", "compile_s",
    }
    for name in layer:
        assert callable(bench.reader(name).read)


def test_a_sound_run_is_correct(root):
    result = run_small(root, CELL, seed=2**33 + 5, trace=True)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    # No device plane on the CPU: the scope readers find nothing to read.
    assert "mla_us.prefill" not in result["metrics"]


def _altered(exe):
    """An answer altered where it is produced: the prompts' logits rolled
    by one prompt."""

    def call(weights, tokens):
        logits, cache, counts = exe(weights, tokens)
        return jnp.roll(logits, 1, axis=0), cache, counts

    return call


def _half_experts(exe):
    """Half of the expert layer left out: the second half of the held
    experts answer zero."""

    def call(weights, tokens):
        name = "model.layers.*.mlp.experts.*.down_proj.weight"
        w = weights[name]
        return exe({**weights, name: w.at[:, w.shape[1] // 2:].set(0)}, tokens)

    return call


@pytest.mark.parametrize("fault", [_altered, _half_experts])
def test_a_broken_timed_path_is_not_correct(root, fault):
    result = run_small(root, CELL, fault=fault, seconds=0.3)
    assert not result["correct"], result["checks"]
    assert result["failed"] > 0


def test_the_control_fails(root):
    bench = harness.Bench(root)
    ref = bench.ref(CONFIG)
    args = ref.make_inputs(harness.seed_key(3), bench.config(CONFIG))
    control = ref.compare(ref.control(*args), args)
    assert any(control[k] > limit for k, limit in ref.LIMITS.items()), control


def _run(modules, peaks=None, config=None):
    trace = TraceSummary(window_s=1.0, devices=1, busy_s=0.9, ops=[], modules=modules, gaps=[])
    return types.SimpleNamespace(
        trace=trace, peaks=peaks or {}, config=config or {},
        cell=types.SimpleNamespace(name="made-up"),
    )


def _xspace(scoped: bool) -> bytes:
    """A traced window of 60 ns on one device, in which one execution of
    ``jit_fn`` starts (0-100 ns) and another starts after it (100-150 ns).
    In the first, a loop under ``mla`` (0-100) with a body op inside it
    (10-40), and an op under ``moe/route`` (50-70); in the second, an op
    under ``mla``. ``scoped`` false leaves the ops' metadata without
    scopes."""
    stat = 'stats { metadata_id: 7 str_value: "%s" }' if scoped else "%.0s"
    ops = [("%while.1 = loop", "jit(f)/mla/while:"),
           ("%fusion.2 = body", "jit(f)/mla/while/body/dot_general:"),
           ("%fusion.3 = route", "jit(f)/moe/route/top_k:")]
    metadata = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" {stat % path} }} }}'
        for i, (name, path) in enumerate(ops, 1)
    )
    text = f"""
    planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
        events {{ metadata_id: 9 offset_ps: 0 duration_ps: 100000 }}
        events {{ metadata_id: 9 offset_ps: 100000 duration_ps: 50000 }} }}
      lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
        events {{ metadata_id: 1 offset_ps: 0 duration_ps: 100000 }}
        events {{ metadata_id: 2 offset_ps: 10000 duration_ps: 30000 }}
        events {{ metadata_id: 3 offset_ps: 50000 duration_ps: 20000 }}
        events {{ metadata_id: 2 offset_ps: 110000 duration_ps: 30000 }} }}
      {metadata}
      event_metadata {{ key: 9 value {{ id: 9 name: "jit_fn(1)" }} }}
      stat_metadata {{ key: 7 value {{ id: 7 name: "tf_op" }} }} }}
    planes {{ id: 2 name: "/host:CPU"
      lines {{ id: 1 name: "python" timestamp_ns: 1000
        events {{ metadata_id: 1 offset_ps: 0 duration_ps: 60000 }} }}
      event_metadata {{ key: 1 value {{ id: 1 name: "perfbench.window" }} }} }}
    """
    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(text)


@pytest.mark.parametrize("scoped", [True, False])
def test_the_scope_reader_reads_a_made_up_trace(tmp_path, scoped):
    reader_file = str(tmp_path / "metrics" / "mla_us.prefill.py")
    trace_dir = tmp_path / "out" / "trace" / "made-up" / "plugins" / "profile" / "1"
    trace_dir.mkdir(parents=True)
    (trace_dir / "host.xplane.pb").write_bytes(_xspace(scoped))
    run = _run({"jit_fn(1)": (1, 1e-7)})
    got = {s: scopes.per_call_us(run, reader_file, s) for s in ("mla", "moe", "route", "experts")}
    if scoped:
        # The execution that starts in the window, past the window's end too;
        # the loop and its body overlap, so the union counts 100 ns once.
        assert got == {"mla": pytest.approx(0.1), "moe": pytest.approx(0.02),
                       "route": pytest.approx(0.02), "experts": None}
    else:
        assert set(got.values()) == {None}


def test_the_scope_reader_returns_none_without_a_trace(tmp_path):
    reader_file = str(tmp_path / "metrics" / "mla_us.prefill.py")
    assert scopes.per_call_us(_run({"jit_fn": (2, 1.0)}), reader_file, "mla") is None
    assert scopes.per_call_us(types.SimpleNamespace(trace=None), reader_file, "mla") is None


def test_prefill_mfu_reads_a_made_up_trace():
    bench = harness.Bench(REPO)
    config = bench.config(CONFIG)
    reader = bench.reader("prefill_mfu")
    from perfbench.lm_work import prefill_ops

    # 4 calls in 2 device-seconds: 0.5 s a call.
    run = _run({"jit_fn": (4, 2.0), "jit_small": (9, 0.1)}, {"bf16_flop_per_s": 197e12}, config)
    assert reader.read(run) == pytest.approx(100 * prefill_ops(config) / 0.5 / 197e12)
    assert prefill_ops(config) == pytest.approx(88.2113e12, rel=1e-5)
    assert reader.read(_run({})) is None
