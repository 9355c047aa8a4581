"""Staged execution engine: compile-once cache, fault isolation, JSONL."""

import json

import pytest

from repro.core.engine import Engine
from repro.core.plan import ExecutionPlan
from repro.core.registry import BenchmarkSpec, Workload, get_benchmark
from repro.core.results import SCHEMA_VERSION, load_records, load_run

FAST = dict(preset=0, iters=1, warmup=0)


def _plan(**kw):
    return ExecutionPlan(**{**FAST, **kw})


def test_compile_cache_compiles_each_pass_exactly_once():
    eng = Engine()
    plan = _plan(
        levels=(0,),
        names=("maxflops_bf16", "devicemem_stream"),
        include_backward=False,
    )
    res = eng.run(plan)
    assert [r.status for r in res.records] == ["ok", "ok"]
    # One compilation per (workload, pass): timing and characterization
    # shared the executable, so no second lowering happened.
    assert eng.cache.misses == 2
    assert eng.cache.hits == 0
    # Re-running the same plan against a warm engine recompiles nothing.
    res2 = eng.run(plan)
    assert [r.status for r in res2.records] == ["ok", "ok"]
    assert eng.cache.misses == 2
    assert eng.cache.hits == 2


def test_compile_cache_counts_forward_and_backward_separately():
    eng = Engine()
    res = eng.run(_plan(names=("softmax",), include_backward=True))
    assert [r.name for r in res.records] == [
        res.records[0].name,
        res.records[0].name + ".bwd",
    ]
    assert eng.cache.misses == 2  # fwd + bwd each compiled once
    assert eng.cache.hits == 0


def test_overrides_get_distinct_cache_entries():
    eng = Engine()
    eng.run(_plan(names=("kmeans",), include_backward=False))
    eng.run(
        _plan(
            names=("kmeans",),
            include_backward=False,
            overrides={"kmeans": {"n": 512, "k": 4}},
        )
    )
    assert eng.cache.misses == 2  # different shapes must not share executables
    assert eng.cache.hits == 0


def _broken_build(**_kw):
    raise RuntimeError("deliberately broken benchmark")


_BROKEN_BUILD = BenchmarkSpec(
    name="zz_broken_build", level=0, dwarf=None, domain=None,
    cuda_feature=None, tpu_feature=None, presets={0: {}}, build=_broken_build,
)


def _build_trace_bomb(**_kw):
    def fn(x):
        raise ValueError("explodes at trace time")

    return Workload(
        name="zz_broken_trace",
        fn=fn,
        make_inputs=lambda seed: (1.0,),
    )


_BROKEN_TRACE = BenchmarkSpec(
    name="zz_broken_trace", level=0, dwarf=None, domain=None,
    cuda_feature=None, tpu_feature=None, presets={0: {}}, build=_build_trace_bomb,
)


def test_fault_isolation_suite_completes_past_broken_benchmarks():
    good = get_benchmark("maxflops_bf16")
    plan = _plan(
        specs=(_BROKEN_BUILD, good, _BROKEN_TRACE), include_backward=False
    )
    res = Engine().run(plan)
    assert len(res.records) == 3  # one row per benchmark, none dropped
    by_status = {r.name: r for r in res.records}
    build_err = by_status["zz_broken_build"]
    assert build_err.status == "error"
    assert "deliberately broken" in build_err.error
    assert build_err.derived == "stage=build"
    trace_err = by_status["zz_broken_trace"]
    assert trace_err.status == "error"
    assert trace_err.derived == "stage=compile"
    assert len(res.ok_records) == 1
    assert res.ok_records[0].us_per_call > 0


def test_characterize_reuses_run_cache():
    eng = Engine()
    plan = _plan(names=("softmax",), include_backward=False)
    eng.run(plan)
    assert (eng.cache.misses, eng.cache.hits) == (1, 0)
    info = eng.characterize(get_benchmark("softmax"), plan)
    assert (eng.cache.misses, eng.cache.hits) == (1, 1)  # shared executable
    assert info.roofline.dominant in ("compute", "memory", "collective")


def test_jsonl_report_roundtrip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    plan = _plan(
        levels=(0,),
        names=("maxflops_bf16", "devicemem_stream"),
        include_backward=False,
    )
    res = Engine().run(plan, jsonl_path=path)
    meta, recs = load_run(path)
    assert meta is not None
    assert meta.backend and meta.device_count >= 1
    assert meta.jax_version
    assert meta.schema_version == SCHEMA_VERSION
    assert [r.name for r in recs] == [r.name for r in res.records]
    assert recs == res.records
    assert load_records(path) == res.records  # generic loader handles JSONL
    # First line is the meta object, then one line per record, then the
    # re-emitted final meta (v8: carries cache_stats/counters; loaders
    # take the last meta line they see).
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert lines[0]["kind"] == "meta"
    assert all(l["kind"] == "record" for l in lines[1:-1])
    assert lines[-1]["kind"] == "meta"


def test_jsonl_torn_final_line_keeps_completed_rows(tmp_path):
    path = str(tmp_path / "torn.jsonl")
    res = Engine().run(
        _plan(names=("maxflops_bf16",), levels=(0,), include_backward=False),
        jsonl_path=path,
    )
    with open(path, "a") as f:
        f.write('{"kind": "record", "name": "half-writ')  # killed mid-write
    meta, recs = load_run(path)
    assert meta is not None
    assert recs == res.records


def test_error_text_is_single_line():
    from repro.core.engine import _err_text

    assert _err_text(ValueError("multi\nline\n  xla   dump")) == (
        "ValueError: multi line xla dump"
    )


def test_jsonl_report_streams_error_records(tmp_path):
    path = str(tmp_path / "err.jsonl")
    plan = _plan(specs=(_BROKEN_BUILD,), include_backward=False)
    Engine().run(plan, jsonl_path=path)
    recs = load_records(path)
    assert len(recs) == 1 and recs[0].status == "error"


def test_characterize_warm_cache_skips_build():
    eng = Engine()
    plan = _plan(names=("kmeans",), include_backward=False)
    eng.run(plan)
    spec = get_benchmark("kmeans")
    broken_spec = BenchmarkSpec(
        name=spec.name, level=spec.level, dwarf=spec.dwarf, domain=spec.domain,
        cuda_feature=None, tpu_feature=None, presets=spec.presets,
        build=_broken_build,
    )
    # Same cache key, but build would raise: a warm cache with memoized
    # analysis must return without ever building the workload.
    info = eng.characterize(broken_spec, plan)
    assert info.roofline is not None


def test_unhashable_override_fails_fast():
    with pytest.raises(ValueError, match="not hashable"):
        ExecutionPlan(overrides={"kmeans": {"n": {"a": 1}}})
    # Lists are coerced to tuples rather than rejected.
    plan = ExecutionPlan(overrides={"kmeans": {"n": [512, 4]}})
    assert plan.overrides_for("kmeans") == {"n": (512, 4)}


def test_record_rows_surfaces_error_records():
    from benchmarks.common import ERROR_PREFIX, record_rows

    res = Engine().run(_plan(specs=(_BROKEN_BUILD, get_benchmark("maxflops_bf16")),
                             include_backward=False))
    rows = record_rows("figX", res.records, lambda r: f"gflops={r.achieved_gflops:.2f}")
    assert len(rows) == 2
    by_name = {n: d for n, _, d in rows}
    assert by_name["figX.zz_broken_build"].startswith(ERROR_PREFIX)
    assert "deliberately broken" in by_name["figX.zz_broken_build"]
    assert not by_name[f"figX.{res.ok_records[0].name}"].startswith(ERROR_PREFIX)


def test_plan_validation():
    with pytest.raises(ValueError, match="unknown benchmark"):
        ExecutionPlan(names=("not_a_benchmark",)).select()
    with pytest.raises(ValueError, match="iters"):
        ExecutionPlan(iters=0)
    with pytest.raises(ValueError, match="devices"):
        ExecutionPlan(devices=0)
    with pytest.raises(ValueError, match="devices"):
        Engine().run(_plan(names=("maxflops_bf16",), devices=4096))


def test_run_sections_rejects_unknown_section(capsys):
    import benchmarks.run as run

    rc = run.main(["--sections", "bogus"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "bogus" in err
    assert "table1" in err and "fig5" in err  # lists the valid sections


def test_outputs_reuse_the_run_executable_and_match_a_plain_jit():
    import jax
    import numpy as np

    eng = Engine()
    plan = _plan(names=("gemm_f32_nn",), include_backward=False)
    eng.run(plan)
    misses = eng.cache.misses
    spec = get_benchmark("gemm_f32_nn")
    got = eng.outputs(spec, plan, 1)
    assert eng.cache.misses == misses  # served from the run's cache entry
    w = spec.build_preset(0)
    want = jax.jit(w.fn)(*w.make_inputs(plan.seed))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
