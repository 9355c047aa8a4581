"""Persistent executable cache: warm runs restore serialized executables
(zero retrace, zero XLA compile), unusable entries fall back to retracing
with counted, explained fallbacks, and entries are versioned by toolchain
+ topology."""

import json
import os

import jax

from repro.core.engine import Engine
from repro.core.plan import ExecutionPlan

FAST = dict(preset=0, iters=1, warmup=0, include_backward=False)


def _version_dir(root: str) -> str:
    # Exactly one toolchain dir for this process; "jax-persistent" is
    # jax's own compilation cache, colocated but not ours.
    (sub,) = [d for d in os.listdir(root) if d != "jax-persistent"]
    return os.path.join(root, sub)


def test_cold_run_populates_cache_dir_with_versioned_entries(tmp_path):
    root = str(tmp_path / "hlo")
    eng = Engine(cache_dir=root)
    res = eng.run(ExecutionPlan(names=("pathfinder", "softmax"), **FAST))
    assert [r.status for r in res.records] == ["ok", "ok"]
    assert eng.disk_cache.stores == 2
    assert eng.disk_cache.hits == 0
    version_dir = _version_dir(root)
    # Versioned by toolchain (jax + jaxlib + backend), topology (device
    # kind x count x process count — serialized executables are compiled
    # *for* a device topology), AND a content hash of the repro package,
    # so an edited kernel misses instead of replaying its old artifacts.
    base = os.path.basename(version_dir)
    assert base.startswith(f"jax-{jax.__version__}-jaxlib-")
    assert f"-{jax.default_backend()}-" in base
    assert f"x{jax.device_count()}p{jax.process_count()}-" in base
    entries = sorted(os.listdir(version_dir))
    # One .json payload + one .exe serialized-executable sidecar per entry.
    assert len(entries) == 4
    assert [e for e in entries if e.endswith(".json")] != []
    assert len([e for e in entries if e.endswith(".exe")]) == 2
    payload_path = next(e for e in entries if e.endswith(".json"))
    payload = json.load(open(os.path.join(version_dir, payload_path)))
    assert payload["device_ids"] == [jax.devices()[0].id]
    assert "cost" in payload and "memory" in payload


def test_warm_run_hits_exe_tier_and_matches_cold_records(tmp_path):
    root = str(tmp_path / "hlo")
    plan = ExecutionPlan(names=("pathfinder",), **FAST)
    cold = Engine(cache_dir=root).run(plan)

    warm_engine = Engine(cache_dir=root)
    warm = warm_engine.run(plan)
    assert warm_engine.disk_cache.hits == 1  # restored: no compilation
    assert warm_engine.disk_cache.misses == 0
    assert warm_engine.disk_cache.fallback_count == 0
    (c,), (w,) = cold.records, warm.records
    assert w.status == "ok"
    assert w.name == c.name
    # The stored characterization reproduces the roofline analysis.
    assert w.dominant == c.dominant
    assert w.derived == c.derived
    assert w.us_per_call > 0


def test_warm_suite_run_performs_zero_xla_compiles(tmp_path):
    """The zero-compile warm start, asserted on counters: every warm
    lookup restores a serialized executable — no retrace (misses=0), no
    silent degradation (fallbacks=0) — across a multi-benchmark slice
    including forward and backward passes. A program is traced and
    compiled only after a miss, so misses=0 means zero XLA compiles."""
    root = str(tmp_path / "hlo")
    plan = ExecutionPlan(
        names=("pathfinder", "softmax", "gemm_f32_nn"),
        preset=0, iters=1, warmup=0, include_backward=True,
    )
    cold_engine = Engine(cache_dir=root)
    cold = cold_engine.run(plan)
    n_entries = cold_engine.disk_cache.stores
    assert n_entries == len(cold.ok_records) >= 4  # fwd rows + some bwd

    warm_engine = Engine(cache_dir=root)
    warm = warm_engine.run(plan)
    dc = warm_engine.disk_cache
    assert [r.status for r in warm.records] == ["ok"] * len(cold.records)
    assert dc.hits == n_entries, dc.summary()
    assert dc.misses == 0, dc.summary()
    assert dc.fallback_count == 0, dc.summary()
    # Warm rows still carry both timing modes (schema v5).
    assert all(r.us_per_call_windowed is not None for r in warm.ok_records)


def test_corrupt_cache_entry_falls_back_to_retrace(tmp_path):
    root = str(tmp_path / "hlo")
    plan = ExecutionPlan(names=("pathfinder",), **FAST)
    Engine(cache_dir=root).run(plan)
    version_dir = _version_dir(root)
    for entry in os.listdir(version_dir):
        with open(os.path.join(version_dir, entry), "w") as f:
            f.write("{not json")

    eng = Engine(cache_dir=root)
    res = eng.run(plan)
    assert [r.status for r in res.records] == ["ok"]
    assert eng.disk_cache.hits == 0
    assert eng.disk_cache.misses == 1
    assert eng.disk_cache.stores == 1  # the retrace re-stored a good entry


def test_corrupt_exe_sidecar_degrades_to_hlo_tier_not_retrace(tmp_path):
    """A blown executable blob next to an intact payload is a counted,
    named fallback and a retrace (there is no HLO-text tier: no public API
    compiles stored HLO text), and the retrace re-stores a good entry."""
    root = str(tmp_path / "hlo")
    plan = ExecutionPlan(names=("pathfinder",), **FAST)
    Engine(cache_dir=root).run(plan)
    version_dir = _version_dir(root)
    for entry in os.listdir(version_dir):
        if entry.endswith(".exe"):
            with open(os.path.join(version_dir, entry), "wb") as f:
                f.write(b"not an executable")

    eng = Engine(cache_dir=root)
    res = eng.run(plan)
    dc = eng.disk_cache
    assert [r.status for r in res.records] == ["ok"]
    assert dc.hits == 0 and dc.misses == 1
    assert dc.fallback_count == 1
    assert dc.last_fallback is not None and "pathfinder" in dc.last_fallback
    assert dc.stores == 1  # the retrace re-stored the entry

    again = Engine(cache_dir=root)
    again.run(plan)
    assert again.disk_cache.hits == 1 and again.disk_cache.fallback_count == 0


def test_fallbacks_are_counted_and_explained_not_silent(tmp_path, capsys):
    """A present-but-unusable entry is a diagnosable *fallback* (counter +
    reason, printed by verbose engine runs); a simply-absent entry is an
    ordinary cold miss and records no reason."""
    root = str(tmp_path / "hlo")
    plan = ExecutionPlan(names=("pathfinder",), **FAST)

    cold = Engine(cache_dir=root)
    cold.run(plan)
    assert cold.disk_cache.fallback_count == 0  # cold miss, no fallback
    assert cold.disk_cache.last_fallback is None

    version_dir = _version_dir(root)
    for entry in os.listdir(version_dir):
        with open(os.path.join(version_dir, entry), "w") as f:
            f.write("{not json")

    eng = Engine(cache_dir=root)
    eng.run(plan, verbose=True)
    dc = eng.disk_cache
    assert dc.fallback_count == 1
    assert dc.last_fallback is not None
    assert "pathfinder" in dc.last_fallback  # which key fell back...
    assert "JSONDecodeError" in dc.last_fallback  # ...and why
    assert dc.fallback_reasons == [dc.last_fallback]
    out = capsys.readouterr().out
    assert "hlocache:" in out and "fallbacks=1" in out
    assert "JSONDecodeError" in out


def test_suite_cli_prints_cache_summary_with_cache_dir(tmp_path, capsys):
    from repro.core.suite import main

    rc = main([
        "--names", "pathfinder", "--cache-dir", str(tmp_path / "hlo"),
        "--iters", "1", "--warmup", "0", "--no-backward",
    ])
    assert rc == 0
    err = capsys.readouterr().err
    assert "hlocache:" in err and "stores=1" in err


def test_disk_cache_persists_and_restores_sharded_executables(tmp_path):
    """Multi-device executables persist like single-device ones
    (serialized via jax.experimental.serialize_executable, restored onto
    the device ids they were compiled for). Cold run stores; a warm run in
    a fresh process restores them without a retrace."""
    import subprocess
    import sys
    import textwrap

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = src
    script = textwrap.dedent(f"""
        from repro.core.engine import Engine
        from repro.core.plan import ExecutionPlan, Placement

        eng = Engine(cache_dir={str(tmp_path / 'hlo')!r})
        res = eng.run(ExecutionPlan(
            names=("gemm_f32_nn",), preset=0, iters=1, warmup=0,
            include_backward=False,
            placement=Placement(devices=4, mode="shard"),
        ))
        assert res.records[0].status == "ok", res.records[0].error
        dc = eng.disk_cache
        assert dc.stores == 1, dc.stores
        print("COLD-OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=420,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"

    warm = textwrap.dedent(f"""
        from repro.core.engine import Engine
        from repro.core.plan import ExecutionPlan, Placement

        eng = Engine(cache_dir={str(tmp_path / 'hlo')!r})
        res = eng.run(ExecutionPlan(
            names=("gemm_f32_nn",), preset=0, iters=1, warmup=0,
            include_backward=False,
            placement=Placement(devices=4, mode="shard"),
        ))
        assert res.records[0].status == "ok", res.records[0].error
        dc = eng.disk_cache
        assert dc.hits == 1, dc.summary()
        assert dc.misses == 0, dc.summary()
        assert dc.fallback_count == 0, dc.summary()
        # The restored program is still sharded over the 4-device mesh.
        assert res.records[0].devices == 4
        print("WARM-OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", warm], env=env, capture_output=True,
        text=True, timeout=420,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
