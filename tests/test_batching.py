"""Continuous batching: shape-bucket mixes, replayable traces, the
dynamic batcher's coalescing/padding policy, and the engine's bucketed
serve path through the compile caches.

Pure-policy tests drive the batcher with counting Python closures (no
device work), so dispatch decisions — full-width, budget expiry,
end-of-stream flush, padding — assert exactly. Engine tests serve a real
workload for a fraction of a second; throughput *comparisons* (dynamic
beats loop) live in tools/smoke.sh --bench, not here, per the
flaky-timing policy.
"""

import dataclasses
import json
import os
import time

import pytest

from repro.core.plan import ExecutionPlan, PlanError, ServeSpec, ShapeBucket
from repro.serve.batcher import (
    BatchExecution,
    BatchReport,
    bucket_widths,
    serve_dynamic,
    serve_fixed_batched,
    serve_mixed_lanes,
    serve_mixed_loop,
)
from repro.serve.loadgen import (
    Request,
    Schedule,
    load_trace,
    merge_schedules,
    open_loop_schedule,
    sample_mix,
    save_trace,
)

FAST = dict(preset=0, iters=1, warmup=0, include_backward=False)
# Narrow-cols pathfinder variants: cheap to compile, cheap to serve.
TINY_MIX = (
    ShapeBucket(preset=0, weight=2.0, overrides=(("cols", 64),)),
    ShapeBucket(preset=0, weight=1.0, overrides=(("cols", 128),)),
)


def _mixed_serve(**kw) -> ServeSpec:
    base = dict(
        mode="open", qps=300.0, duration_s=0.25, concurrency=8,
        dispatch="dynamic", mix=TINY_MIX, batch_budget_us=500.0, max_batch=2,
    )
    base.update(kw)
    return ServeSpec(**base)


# -- merge_schedules edge cases (lane sub-schedules) -----------------------


def test_merge_schedules_tolerates_empty_sublanes():
    """A starved lane contributes an empty sub-schedule; the merge must
    keep its offered share and stay a well-formed stream, not choke or
    drop the lane's rate from the target."""
    busy = Schedule(
        requests=tuple(Request(index=i, arrival_s=0.01 * (i + 1)) for i in range(4)),
        offered_qps=100.0,
    )
    empty = Schedule(requests=(), offered_qps=100.0)
    merged = merge_schedules([busy, empty, empty])
    assert len(merged) == 4
    assert merged.offered_qps == pytest.approx(300.0)  # empty lanes still offer
    assert [r.arrival_s for r in merged] == sorted(r.arrival_s for r in merged)
    assert not merged.truncated
    # All-empty is still a valid (empty) stream at the summed rate.
    all_empty = merge_schedules([empty, empty])
    assert len(all_empty) == 0 and all_empty.offered_qps == pytest.approx(200.0)


def test_merge_schedules_truncation_sticky_through_empty_sublanes():
    """One truncated sub-schedule poisons the merge — even when other
    lanes are empty (an empty truncated lane means its stream was cut
    before its first arrival, which is still under-offering)."""
    busy = Schedule(
        requests=(Request(index=0, arrival_s=0.01),), offered_qps=50.0
    )
    cut = Schedule(requests=(), offered_qps=50.0, truncated=True)
    assert merge_schedules([busy, cut]).truncated
    assert merge_schedules([cut]).truncated
    assert not merge_schedules([busy]).truncated
    with pytest.raises(ValueError, match="at least one"):
        merge_schedules([])


# -- shape-mix sampling ----------------------------------------------------


def test_sample_mix_deterministic_per_seed_and_arrival_preserving():
    sched = open_loop_schedule(qps=800.0, duration_s=0.5, seed=11, warmup=3)
    mix = {"a": 2.0, "b": 1.0}
    one = sample_mix(sched, mix, seed=5)
    two = sample_mix(sched, mix, seed=5)
    assert one == two  # bit-identical bucket assignment
    other = sample_mix(sched, mix, seed=6)
    assert [r.bucket for r in one] != [r.bucket for r in other]
    # The arrival process is untouched: only the bucket field changes.
    for before, after in zip(sched, one):
        assert dataclasses.replace(after, bucket=None) == before
    # Every request got a label from the mix, both labels actually drawn.
    assert {r.bucket for r in one} == {"a", "b"}
    # Mapping and (label, weight) sequence agree when the sequence is in
    # sorted-label order (the mapping is normalized to exactly that).
    assert sample_mix(sched, [("a", 2.0), ("b", 1.0)], seed=5) == one


def test_sample_mix_validation():
    sched = open_loop_schedule(qps=100.0, duration_s=0.1, seed=0)
    with pytest.raises(ValueError, match="at least one"):
        sample_mix(sched, {}, seed=0)
    with pytest.raises(ValueError, match="weights"):
        sample_mix(sched, {"a": 0.0}, seed=0)


# -- trace save / load -----------------------------------------------------


def test_trace_roundtrip_exact(tmp_path):
    sched = sample_mix(
        open_loop_schedule(qps=500.0, duration_s=0.3, seed=2, warmup=2),
        {"p0": 3.0, "p0/cols=64": 1.0},
        seed=2,
    )
    path = str(tmp_path / "trace.jsonl")
    save_trace(sched, path)
    assert load_trace(path) == sched  # buckets, warmup flags, qps, all of it


def test_load_trace_rejects_foreign_and_truncated_files(tmp_path):
    notatrace = tmp_path / "report.jsonl"
    notatrace.write_text(json.dumps({"kind": "run-report"}) + "\n")
    with pytest.raises(ValueError, match="kind='run-report'"):
        load_trace(str(notatrace))
    sched = open_loop_schedule(qps=200.0, duration_s=0.2, seed=0)
    path = tmp_path / "cut.jsonl"
    save_trace(sched, str(path))
    lines = path.read_text().splitlines()
    path.write_text("\n".join(lines[:-1]) + "\n")  # drop the last request
    with pytest.raises(ValueError, match="truncated on disk"):
        load_trace(str(path))


# -- batcher policy (pure Python calls, exact assertions) ------------------


def test_bucket_widths_per_dispatch_policy():
    assert bucket_widths("dynamic", 8) == (1, 2, 4, 8)
    assert bucket_widths("dynamic", 6) == (1, 2, 4, 6)  # non-pow2 reachable
    assert bucket_widths("dynamic", 1) == (1,)
    assert bucket_widths("batched", 4) == (4,)
    assert bucket_widths("loop", 8) == (1,)
    assert bucket_widths("lanes", 8) == (1,)


def test_batch_report_occupancy_math():
    mk = lambda w, f: BatchExecution(  # noqa: E731
        bucket="b", width=w, filled=f, t_dispatch=0.0, t_done=1.0
    )
    report = BatchReport(completions=(), batches=(mk(4, 4), mk(4, 2), mk(2, 2)))
    assert report.total_slots == 10
    assert report.filled_slots == 8
    assert report.occupancy == pytest.approx(0.8)
    assert report.padding_waste == pytest.approx(0.2)
    assert report.mean_width == pytest.approx(10 / 3)
    empty = BatchReport(completions=(), batches=())
    assert empty.occupancy == 0.0 and empty.mean_width == 0.0
    with pytest.raises(ValueError, match="fill"):
        mk(4, 5)
    with pytest.raises(ValueError, match="fill"):
        mk(4, 0)


def _counting_calls(buckets, widths):
    """calls[bucket][width] -> closure counting dispatches per (b, w)."""
    dispatched = []

    def make(b, w):
        return lambda: dispatched.append((b, w))

    return {b: {w: make(b, w) for w in widths} for b in buckets}, dispatched


def _instant(reqs) -> Schedule:
    return Schedule(requests=tuple(reqs), offered_qps=1000.0)


def test_serve_mixed_loop_is_width1_and_fully_occupied():
    calls, dispatched = _counting_calls(["a", "b"], [1])
    sched = _instant(
        Request(index=i, arrival_s=0.0, bucket="ab"[i % 2]) for i in range(6)
    )
    report = serve_mixed_loop(calls, sched)
    assert len(report.completions) == 6
    assert [c.bucket for c in report.completions] == ["a", "b"] * 3
    assert dispatched == [("a", 1), ("b", 1)] * 3
    assert report.occupancy == 1.0 and report.padding_waste == 0.0
    assert all(b.width == 1 for b in report.batches)


def test_serve_mixed_lanes_routes_by_bucket():
    calls, dispatched = _counting_calls(["a", "b"], [1])
    sched = _instant(
        Request(index=i, arrival_s=0.0, bucket="ab"[i % 2]) for i in range(8)
    )
    report = serve_mixed_lanes(calls, sched, n_lanes=2, concurrency=4)
    assert len(report.completions) == 8
    assert sorted(c.index for c in report.completions) == list(range(8))
    assert {c.bucket for c in report.completions} == {"a", "b"}
    assert dispatched.count(("a", 1)) == 4 and dispatched.count(("b", 1)) == 4
    assert report.occupancy == 1.0


def test_dynamic_coalesces_full_width_then_pads_the_flush():
    """7 simultaneous requests, widths (1, 2, 4): a full 4-batch goes out
    first; the end-of-stream flush takes the remaining 3 padded into a
    4-slot program. Occupancy accounts for the one padded slot."""
    calls, dispatched = _counting_calls(["a"], [1, 2, 4])
    sched = _instant(
        Request(index=i, arrival_s=0.0, bucket="a") for i in range(7)
    )
    report = serve_dynamic(calls, sched, budget_s=10.0, concurrency=32)
    assert len(report.completions) == 7
    assert [(b.width, b.filled) for b in report.batches] == [(4, 4), (4, 3)]
    assert dispatched == [("a", 4), ("a", 4)]
    assert report.occupancy == pytest.approx(7 / 8)
    assert report.padding_waste == pytest.approx(1 / 8)


def test_dynamic_budget_expiry_dispatches_partial_batch():
    """Two early requests can't fill max width; with a later arrival still
    pending, only the latency budget can release them — as a width-2
    batch, long before the straggler arrives."""
    calls, _ = _counting_calls(["a"], [1, 2, 4])
    sched = _instant([
        Request(index=0, arrival_s=0.0, bucket="a"),
        Request(index=1, arrival_s=0.0, bucket="a"),
        Request(index=2, arrival_s=0.25, bucket="a"),
    ])
    report = serve_dynamic(calls, sched, budget_s=0.02, concurrency=32)
    first = report.batches[0]
    assert (first.width, first.filled) == (2, 2)
    # Released by the budget (~20ms), not the straggler's arrival (250ms).
    assert first.t_dispatch - report.completions[0].t_submit < 0.15
    assert len(report.completions) == 3


def test_fixed_batched_waits_for_full_width_and_pads_only_the_flush():
    calls, dispatched = _counting_calls(["a"], [4])
    sched = _instant(
        Request(index=i, arrival_s=0.0, bucket="a") for i in range(6)
    )
    report = serve_fixed_batched(calls, sched, batch=4, concurrency=32)
    assert [(b.width, b.filled) for b in report.batches] == [(4, 4), (4, 2)]
    assert dispatched == [("a", 4), ("a", 4)]
    assert report.occupancy == pytest.approx(6 / 8)
    with pytest.raises(ValueError, match="batch"):
        serve_fixed_batched(calls, sched, batch=0)


def test_unknown_bucket_and_missing_width_are_loud():
    calls, _ = _counting_calls(["a"], [1])
    stray = _instant([Request(index=0, arrival_s=0.0, bucket="zz")])
    with pytest.raises(KeyError, match="no compiled executables"):
        serve_dynamic(calls, stray, budget_s=0.01)
    with pytest.raises(KeyError, match="width=1"):
        serve_mixed_loop({"a": {}}, _instant([Request(index=0, bucket="a")]))
    with pytest.raises(ValueError, match="budget_s"):
        serve_dynamic(calls, stray, budget_s=-1.0)


def test_dynamic_inflight_cap_still_serves_every_request():
    """concurrency=2 caps in-flight *requests* at 2: width-2 batches must
    retire one at a time, but every request still completes exactly once
    and the batch accounting stays exact."""
    calls, dispatched = _counting_calls(["a"], [1, 2])
    sched = _instant(
        Request(index=i, arrival_s=0.0, bucket="a") for i in range(8)
    )
    report = serve_dynamic(calls, sched, budget_s=10.0, concurrency=2)
    assert sorted(c.index for c in report.completions) == list(range(8))
    assert dispatched == [("a", 2)] * 4
    assert report.occupancy == 1.0


def test_dynamic_batch_wider_than_inflight_cap_dispatches_alone():
    """max width > concurrency must not deadlock the cap-wait loop: the
    oversized batch goes out alone once the window drains."""
    calls, dispatched = _counting_calls(["a"], [1, 2, 4])
    sched = _instant(
        Request(index=i, arrival_s=0.0, bucket="a") for i in range(8)
    )
    report = serve_dynamic(calls, sched, budget_s=10.0, concurrency=1)
    assert sorted(c.index for c in report.completions) == list(range(8))
    assert dispatched == [("a", 4)] * 2
    assert report.occupancy == 1.0


def test_dynamic_zero_budget_dispatches_spaced_arrivals_at_width_one():
    """budget_s=0 is eager dispatch: each request, 100 ms apart, goes out
    alone even though wider programs exist. The same stream under a long
    budget waits for the end of the stream and goes out as one batch."""
    calls, dispatched = _counting_calls(["a"], [1, 2, 4])
    sched = _instant(
        Request(index=i, arrival_s=0.1 * i, bucket="a") for i in range(4)
    )
    eager = serve_dynamic(calls, sched, budget_s=0.0, concurrency=32)
    assert sorted(c.index for c in eager.completions) == [0, 1, 2, 3]
    assert [(b.width, b.filled) for b in eager.batches] == [(1, 1)] * 4
    assert dispatched == [("a", 1)] * 4
    dispatched.clear()
    held = serve_dynamic(calls, sched, budget_s=10.0, concurrency=32)
    assert [(b.width, b.filled) for b in held.batches] == [(4, 4)]


def test_dynamic_interleaved_buckets_never_share_a_batch():
    """Two buckets arriving interleaved, each with its own compiled
    widths: a batch only ever holds one bucket's requests, and a partial
    batch pads to the smallest width *its own* bucket compiled."""
    calls = {}
    dispatched = []
    for bucket, widths in (("a", (1, 4)), ("b", (1, 2))):
        calls[bucket] = {
            w: (lambda b=bucket, w=w: dispatched.append((b, w)))
            for w in widths
        }
    buckets = "abababaa"  # a: 5 requests, b: 3
    sched = _instant(
        Request(index=i, arrival_s=0.0, bucket=b) for i, b in enumerate(buckets)
    )
    report = serve_dynamic(calls, sched, budget_s=10.0, concurrency=32)
    assert sorted(c.index for c in report.completions) == list(range(8))
    for c in report.completions:
        assert c.bucket == buckets[c.index]
    # a: a full 4, then its flushed single; b: a full 2, then its single.
    assert dispatched == [("a", 4), ("b", 2), ("a", 1), ("b", 1)]
    for batch in report.batches:
        assert batch.width in calls[batch.bucket]
    assert report.occupancy == 1.0


def test_dynamic_latency_counts_from_the_due_time_of_a_late_admission():
    """The first call holds the loop for 50 ms, so requests due at 5 and
    10 ms are admitted late. Each completion's t_submit is still its due
    time (t0 + arrival_s), so the wait for admission is latency."""
    slept = []

    def slow_first():
        if not slept:
            slept.append(True)
            time.sleep(0.05)

    arrivals = (0.0, 0.005, 0.010)
    sched = _instant(
        Request(index=i, arrival_s=t, bucket="a") for i, t in enumerate(arrivals)
    )
    report = serve_dynamic({"a": {1: slow_first}}, sched, budget_s=0.0)
    by_index = sorted(report.completions, key=lambda c: c.index)
    assert [c.index for c in by_index] == [0, 1, 2]
    t0 = by_index[0].t_submit - arrivals[0]
    for c, due in zip(by_index, arrivals):
        assert c.t_submit == pytest.approx(t0 + due, abs=1e-9)
    # The held-up requests finish after the 50 ms call, and their latency
    # includes the wait from their due time.
    for c, due in zip(by_index[1:], arrivals[1:]):
        assert c.t_done - c.t_submit >= 0.05 - due


# -- ServeSpec mixed validation --------------------------------------------


def test_shapebucket_labels_and_validation():
    assert ShapeBucket(preset=1).label == "p1"
    b = ShapeBucket(preset=0, overrides=(("cols", 64), ("rows", 32)))
    assert b.label == "p0/cols=64/rows=32"  # sorted params, stable label
    # JSON round-trip shape: list-of-lists overrides normalize to tuples.
    assert ShapeBucket(preset=0, overrides=[["cols", 64]]) == ShapeBucket(
        preset=0, overrides=(("cols", 64),)
    )
    with pytest.raises(PlanError, match="weight"):
        ShapeBucket(weight=0.0)
    with pytest.raises(PlanError, match="preset"):
        ShapeBucket(preset=-1)


def test_servespec_mixed_validation():
    spec = _mixed_serve()
    assert spec.is_mixed
    assert not ServeSpec(mode="closed").is_mixed
    with pytest.raises(PlanError, match="dispatch"):
        _mixed_serve(dispatch="bogus")
    with pytest.raises(PlanError, match="mode='open'"):
        ServeSpec(mode="closed", dispatch="dynamic")
    with pytest.raises(PlanError, match="client='single'"):
        _mixed_serve(client="threaded")
    with pytest.raises(PlanError, match="colocate"):
        ServeSpec(
            mode="open", qps=10.0, dispatch="dynamic", colocate="kmeans"
        )
    with pytest.raises(PlanError, match="duplicate"):
        _mixed_serve(mix=(ShapeBucket(preset=0), ShapeBucket(preset=0)))
    with pytest.raises(PlanError, match="at least one"):
        _mixed_serve(mix=())
    with pytest.raises(PlanError, match="batch_budget_us"):
        _mixed_serve(batch_budget_us=0.0)
    with pytest.raises(PlanError, match="max_batch"):
        _mixed_serve(max_batch=0)
    with pytest.raises(PlanError, match="ShapeBucket"):
        _mixed_serve(mix=("p0",))
    # Dict entries (the RunMetadata JSON round-trip) normalize in place.
    from_json = _mixed_serve(
        mix=[{"preset": 0, "weight": 2.0, "overrides": [["cols", 64]]},
             {"preset": 0, "weight": 1.0, "overrides": [["cols", 128]]}]
    )
    assert from_json.mix == TINY_MIX
    # A trace alone selects the mixed path with a single default bucket.
    traced = ServeSpec(mode="open", qps=10.0, trace="/tmp/t.jsonl")
    assert traced.is_mixed
    assert [b.label for b in traced.buckets(2)] == ["p2"]
    assert spec.buckets(2) == TINY_MIX  # an explicit mix wins


# -- engine: bucketed serve through the caches -----------------------------


def test_engine_mixed_dynamic_end_to_end_records_batching_columns():
    from repro.core.engine import Engine

    plan = ExecutionPlan(names=("pathfinder",), serve=_mixed_serve(), **FAST)
    res = Engine().run(plan)
    (rec,) = res.records
    assert rec.status == "ok", rec.error
    assert rec.serve_dispatch == "dynamic"
    assert rec.serve_mix == "p0/cols=64@2,p0/cols=128@1"
    assert rec.serve_batches is not None and rec.serve_batches >= 1
    assert rec.batch_occupancy is not None and 0 < rec.batch_occupancy <= 1.0
    assert rec.padding_waste == pytest.approx(1.0 - rec.batch_occupancy)
    assert rec.latency_p50_us > 0 and rec.achieved_qps > 0
    # Coalescing means strictly fewer device programs than requests is
    # *possible* but not guaranteed on a sparse schedule; what IS
    # guaranteed is that every request landed in some batch slot.
    assert rec.serve_requests >= 1
    labels = {b.label for b in TINY_MIX}
    assert rec.bucket_latency_us is not None
    assert set(rec.bucket_latency_us) <= labels
    for stats in rec.bucket_latency_us.values():
        assert stats["requests"] >= 1
        assert stats["p50_us"] <= stats["p95_us"] <= stats["p99_us"]
    csv = rec.csv()
    assert "dispatch=dynamic" in csv and "occupancy=" in csv


def test_engine_mixed_serve_precompiles_every_bucket_width():
    """dynamic with max_batch=2 over a 2-bucket mix needs 4 executables
    (2 buckets x widths {1, 2}); the measure stage's own executable is a
    5th distinct compile (plan preset != either bucket's overrides), and
    re-running the same plan compiles nothing new."""
    from repro.core.engine import Engine

    eng = Engine()
    plan = ExecutionPlan(names=("pathfinder",), serve=_mixed_serve(), **FAST)
    res = eng.run(plan)
    assert res.records[0].status == "ok", res.records[0].error
    assert eng.cache.misses == 5
    eng.run(plan)
    assert eng.cache.misses == 5  # warm in-process rerun: all hits


def test_engine_mixed_bucket_at_plan_preset_reuses_the_measure_executable():
    """A bucket at the plan's own preset and overrides shares the measure
    stage's cache key at width 1: the 2 buckets x widths {1, 2} table
    costs 4 compiles in all, the measured program included, and the
    shared width-1 program is a cache hit."""
    from repro.core.engine import Engine

    mix = (
        ShapeBucket(preset=0),
        ShapeBucket(preset=0, overrides=(("cols", 64),)),
    )
    eng = Engine()
    plan = ExecutionPlan(names=("pathfinder",), serve=_mixed_serve(mix=mix), **FAST)
    res = eng.run(plan)
    assert res.records[0].status == "ok", res.records[0].error
    assert eng.cache.misses == 4
    assert eng.cache.hits >= 1


def test_engine_mixed_trace_replay_pins_the_load(tmp_path):
    """Run 1 (loop) generates and saves the trace; run 2 (dynamic) replays
    it — identical request stream, identical offered load, whatever the
    dispatch policy."""
    from repro.core.engine import Engine

    trace = str(tmp_path / "mix.jsonl")
    base = _mixed_serve(trace=trace, dispatch="loop")
    plan = ExecutionPlan(names=("pathfinder",), serve=base, **FAST)
    res1 = Engine().run(plan)
    assert res1.records[0].status == "ok", res1.records[0].error
    assert os.path.exists(trace)
    saved = load_trace(trace)

    replay = dataclasses.replace(plan, serve=dataclasses.replace(base, dispatch="dynamic"))
    res2 = Engine().run(replay)
    (rec2,) = res2.records
    assert rec2.status == "ok", rec2.error
    assert load_trace(trace) == saved  # replay never rewrites the trace
    assert rec2.serve_requests == res1.records[0].serve_requests
    assert rec2.offered_qps == res1.records[0].offered_qps
    assert rec2.serve_dispatch == "dynamic"


def test_engine_mixed_rejects_no_jit_and_unknown_trace_bucket(tmp_path):
    from repro.core.engine import Engine

    # Host-transfer (no_jit) workloads have no device program to batch.
    plan = ExecutionPlan(
        names=("busspeeddownload",),
        serve=_mixed_serve(mix=(ShapeBucket(preset=0),)),
        **FAST,
    )
    (rec,) = Engine().run(plan).records
    assert rec.status == "error"
    assert "no_jit" in rec.error

    # A trace naming a bucket the mix never compiled is a loud error.
    sched = sample_mix(
        open_loop_schedule(qps=200.0, duration_s=0.2, seed=0),
        {"p9/zz=1": 1.0},
        seed=0,
    )
    trace = str(tmp_path / "alien.jsonl")
    save_trace(sched, trace)
    bad = ExecutionPlan(
        names=("pathfinder",), serve=_mixed_serve(trace=trace), **FAST
    )
    (rec,) = Engine().run(bad).records
    assert rec.status == "error"
    assert "p9/zz=1" in rec.error


def test_jsonl_roundtrips_mixed_serve_metadata(tmp_path):
    from repro.core.engine import Engine
    from repro.core.results import SCHEMA_VERSION, load_run

    path = str(tmp_path / "mixed.jsonl")
    spec = _mixed_serve()
    plan = ExecutionPlan(names=("pathfinder",), serve=spec, **FAST)
    res = Engine().run(plan, jsonl_path=path)
    meta, recs = load_run(path)
    assert meta.schema_version == SCHEMA_VERSION >= 7
    assert meta.serve == spec  # dict mix entries -> ShapeBucket round-trip
    assert recs == res.records
    assert recs[0].bucket_latency_us == res.records[0].bucket_latency_us


# -- suite CLI surface -----------------------------------------------------


def test_parse_mix_grammar():
    from repro.core.suite import _parse_mix

    mix = _parse_mix("0@2,0/cols=64@1,1/rows=32/cols=2.5")
    assert mix == (
        ShapeBucket(preset=0, weight=2.0),
        ShapeBucket(preset=0, weight=1.0, overrides=(("cols", 64),)),
        ShapeBucket(
            preset=1, weight=1.0, overrides=(("rows", 32), ("cols", 2.5))
        ),
    )
    for bad in ("", "x@1", "0@zero", "0/cols@1", "0@"):
        with pytest.raises(SystemExit):
            _parse_mix(bad)


def test_suite_cli_dynamic_mix_end_to_end(capsys):
    from repro.core.suite import main

    rc = main([
        "--names", "pathfinder", "--serve", "open", "--qps", "300",
        "--serve-duration", "0.25", "--serve-mix", "0/cols=64@2,0/cols=128@1",
        "--serve-dispatch", "dynamic", "--max-batch", "2",
        "--batch-latency-budget", "500", "--iters", "1", "--warmup", "0",
        "--no-backward",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "dispatch=dynamic" in out
    assert "occupancy=" in out and "padding_waste=" in out
    assert "buckets=" in out and "p0/cols=64" in out


def test_suite_cli_stray_batching_flags_are_config_errors(capsys):
    from repro.core.suite import main

    rc = main(["--names", "pathfinder", "--serve-mix", "0@1"])
    assert rc == 2
    assert "--serve-mix" in capsys.readouterr().err
    rc = main(["--names", "pathfinder", "--serve-dispatch", "dynamic"])
    assert rc == 2
    assert "--serve-dispatch" in capsys.readouterr().err


def test_suite_help_epilog_shows_batching_examples(capsys):
    from repro.core.suite import main

    with pytest.raises(SystemExit) as exc:
        main(["--help"])
    assert exc.value.code == 0
    out = capsys.readouterr().out
    assert "--serve-mix" in out and "--serve-trace" in out
    assert "--batch-latency-budget" in out
    assert "padding" in out and "occupancy" in out
