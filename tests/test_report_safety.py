"""Report crash-safety and loader error contracts.

A run that dies mid-suite must still leave a parseable JSONL report
(metadata header + every completed record); the loaders must tolerate a
torn final line and turn missing/empty reports into one-line
:class:`ReportError` messages rather than tracebacks.
"""

import pytest

from repro.core.engine import Engine
from repro.core.plan import ExecutionPlan, ServeSpec
from repro.core.registry import BenchmarkSpec, get_benchmark
from repro.core.results import (
    BenchmarkRecord,
    JsonlReportWriter,
    ReportError,
    RunMetadata,
    load_records,
    load_run,
)
from repro.serve.lanes import Completion
from repro.serve.latency import stats_from_completions

FAST = dict(preset=0, iters=1, warmup=0, include_backward=False)


def _exit_bomb(**_kw):
    # BaseException-adjacent: escapes the engine's per-benchmark Exception
    # isolation, like a Ctrl-C or a watchdog kill would.
    raise SystemExit("suite killed mid-run")


_EXIT_BOMB = BenchmarkSpec(
    name="zz_exit_bomb", level=0, dwarf=None, domain=None,
    cuda_feature=None, tpu_feature=None, presets={0: {}}, build=_exit_bomb,
)


def test_crash_mid_suite_leaves_parseable_jsonl(tmp_path):
    """SystemExit after one completed benchmark: the JSONL file still
    carries the metadata header and the completed record."""
    path = str(tmp_path / "crash.jsonl")
    plan = ExecutionPlan(
        specs=(get_benchmark("maxflops_bf16"), _EXIT_BOMB), **FAST
    )
    with pytest.raises(SystemExit, match="mid-run"):
        Engine().run(plan, jsonl_path=path)
    meta, recs = load_run(path)
    assert meta is not None and meta.backend
    assert len(recs) == 1
    assert recs[0].status == "ok" and recs[0].name.startswith("maxflops")


def test_abandoned_writer_plus_torn_line_still_loads(tmp_path):
    """Records are flushed as written: a writer that is never closed (hard
    crash) plus a torn final line still yields every complete record."""
    path = str(tmp_path / "torn.jsonl")
    meta = RunMetadata.capture(preset=0)
    writer = JsonlReportWriter(path, meta)
    recs = Engine().run(ExecutionPlan(names=("pathfinder",), **FAST)).records
    for r in recs:
        writer.write(r)
    # No writer.close(): simulate the process dying, then a torn write.
    with open(path, "a") as f:
        f.write('{"kind": "record", "name": "half-writ')
    loaded_meta, loaded = load_run(path)
    assert loaded_meta == meta
    assert loaded == recs


def test_torn_line_mid_file_still_raises(tmp_path):
    """Only the *final* line may be torn (crash residue); corruption
    elsewhere in the file is a real error and must surface."""
    import dataclasses
    import json

    path = tmp_path / "midtorn.jsonl"
    rec = json.dumps(
        {"kind": "record", **dataclasses.asdict(BenchmarkRecord(
            name="x", level=0, dwarf=None, domain=None, preset=0,
            us_per_call=1.0, achieved_gflops=0.0, achieved_gbps=0.0,
            compute_util10=0, memory_util10=0, dominant="memory",
        ))}
    )
    # A lone torn line is also the final line -> tolerated, zero records.
    path.write_text('{"kind": "meta", "torn')
    meta, recs = load_run(str(path))
    assert meta is None and recs == []
    # A torn first line with records after it is corruption, not residue.
    path.write_text('{"kind": "meta", "torn\n' + rec + "\n")
    with pytest.raises(json.JSONDecodeError):
        load_run(str(path))


def test_load_run_missing_file_is_one_line_report_error(tmp_path):
    missing = str(tmp_path / "nope.jsonl")
    with pytest.raises(ReportError) as exc:
        load_run(missing)
    msg = str(exc.value)
    assert "nope.jsonl" in msg and "\n" not in msg
    with pytest.raises(ReportError):
        load_records(missing)


def test_load_run_empty_file_is_report_error(tmp_path):
    path = tmp_path / "empty.jsonl"
    path.write_text("")
    with pytest.raises(ReportError, match="empty"):
        load_run(str(path))
    path.write_text("   \n\n")
    with pytest.raises(ReportError, match="empty"):
        load_run(str(path))


def test_load_run_bad_legacy_json_is_report_error(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text("[{broken")
    with pytest.raises(ReportError, match="not valid JSON"):
        load_run(str(path))


def test_report_error_is_a_value_error():
    # CLI catch sites use `except (PlanError, ValueError)`; ReportError
    # must flow through them.
    assert issubclass(ReportError, ValueError)


def _v9_report(tmp_path, serve_extra: dict, record_extra: dict) -> str:
    """A schema-v9 JSONL report: today's fields plus the v9 columns that
    left in v10, as a v9 writer produced them."""
    import dataclasses
    import json

    serve = ServeSpec(mode="open", qps=100.0, duration_s=1.0)
    meta = dataclasses.asdict(RunMetadata.capture(preset=0, serve=serve))
    meta.update(schema_version=9)
    meta["serve"].update(serve_extra)
    rec = BenchmarkRecord(
        name="pathfinder", level=1, dwarf=None, domain=None, preset=0,
        us_per_call=1.0, achieved_gflops=0.0, achieved_gbps=0.0,
        compute_util10=0, memory_util10=0, dominant="serve",
    )
    comps = [Completion(index=i, lane=0, t_submit=0.01 * i,
                        t_done=0.01 * i + 0.001, warmup=False)
             for i in range(8)]
    rec.apply_serve(stats_from_completions(comps, offered_qps=100.0),
                    mode="open", lanes=1)
    row = {**dataclasses.asdict(rec), **record_extra}
    path = tmp_path / "v9.jsonl"
    path.write_text(
        json.dumps({"kind": "meta", **meta}) + "\n"
        + json.dumps({"kind": "record", **row}) + "\n"
    )
    return str(path)


@pytest.mark.parametrize("case", ["records", "metadata"])
def test_v9_report_loads_under_v10(tmp_path, case):
    """v10 dropped the multi-process load generator's two record columns
    and its ServeSpec field; a v9 report that carries them still loads."""
    if case == "records":
        path = _v9_report(tmp_path, {}, {"client_procs": 2,
                                         "proc_qps": [48.0, 52.0]})
    else:
        path = _v9_report(tmp_path, {"client_procs": 2}, {})
    meta, (rec,) = load_run(path)
    assert meta is not None and meta.schema_version == 9
    if case == "records":
        csv = rec.csv()
        assert ";serve=open;" in csv
        assert "procs" not in csv and "proc_qps" not in csv
    else:
        assert meta.serve == ServeSpec(mode="open", qps=100.0, duration_s=1.0)
