"""The observability layer (schema v8): spans, counters, Chrome export,
stage timings, and the zero-cost-when-disabled contract.

Timing-sensitive assertions follow the repo's flaky-timing policy:
generous tolerances and best-of-N sampling (the minimum of several
medians is the least-contended sample), so a noisy CI neighbour cannot
fail the build.
"""

import gc
import glob
import inspect
import json
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import harness
from repro.core.engine import Engine
from repro.core.plan import ExecutionPlan
from repro.core.registry import BenchmarkSpec, Workload
from repro.core.results import load_records, load_run
from repro.obs import (
    NULL_TRACER,
    PROFILER,
    Counters,
    NullTracer,
    Tracer,
    collection_spans,
    current_tracer,
    use_tracer,
)
from repro.serve.batcher import serve_dynamic
from repro.serve.client import run_closed_loop_threaded
from repro.serve.loadgen import Request, Schedule

FAST = dict(preset=0, iters=2, warmup=1)


def _plan(**kw):
    return ExecutionPlan(**{**FAST, **kw})


def _spec(name="zz_obs", fn=None, meta=None):
    """A tiny self-contained benchmark for engine-level obs tests."""

    def build(**size):
        f = fn if fn is not None else (lambda x: x * 2.0 + 1.0)
        return Workload(
            name=name,
            fn=f,
            make_inputs=lambda key: (jnp.ones((8, 8), jnp.float32),),
            flops=1.0,
            bytes_moved=1.0,
            meta=meta or {},
        )

    return BenchmarkSpec(
        name=name, level=0, dwarf=None, domain=None,
        cuda_feature=None, tpu_feature=None, presets={0: {}}, build=build,
    )


# -- tracer core -------------------------------------------------------------


def test_span_nesting_and_ordering():
    tr = Tracer()
    with tr.span("outer", bench="b"):
        with tr.span("inner"):
            pass
    events = tr.events()
    assert [e.name for e in events] == ["inner", "outer"]  # exit order
    inner, outer = events
    # The inner span is contained in the outer one on the shared clock.
    assert outer.t_start_us <= inner.t_start_us
    assert (
        inner.t_start_us + inner.dur_us
        <= outer.t_start_us + outer.dur_us + 1.0
    )
    assert outer.args == {"bench": "b"}


def test_span_records_on_exception():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("failing"):
            raise RuntimeError("boom")
    assert [e.name for e in tr.events()] == ["failing"]


def test_retrospective_event_durations_are_exact():
    tr = Tracer()
    t0 = time.perf_counter()
    tr.event("req", t_start=t0, t_end=t0 + 0.25, track="serve", tid="lane 0")
    (ev,) = tr.events()
    assert ev.dur_us == pytest.approx(0.25 * 1e6)
    assert ev.tid == "lane 0"


def test_counters_threadsafe_and_sorted():
    c = Counters()
    threads = [
        threading.Thread(target=lambda: [c.inc("n") for _ in range(1000)])
        for _ in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    c.inc("a_us", 2.5)
    c.set("a_us", 7.5)  # set overwrites, it does not accumulate
    snap = c.snapshot()
    assert snap == {"a_us": 7.5, "n": 4000}
    assert list(snap) == sorted(snap)


def test_ambient_tracer_scoping():
    assert current_tracer() is NULL_TRACER
    tr = Tracer()
    with use_tracer(tr):
        assert current_tracer() is tr
        with use_tracer(None):  # None reinstalls the null tracer
            assert current_tracer() is NULL_TRACER
        assert current_tracer() is tr
    assert current_tracer() is NULL_TRACER


def test_null_tracer_is_falsy_and_inert():
    assert not NULL_TRACER and not NULL_TRACER.enabled
    # One shared context manager object: the disabled span() allocates
    # nothing per call.
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b")
    with NULL_TRACER.span("a"):
        pass
    NULL_TRACER.event("x", t_start=0.0, t_end=1.0)
    NULL_TRACER.counters.inc("n")
    NULL_TRACER.counters.set("n", 5)
    assert NULL_TRACER.events() == []
    assert NULL_TRACER.counters.snapshot() == {}


def test_null_tracer_span_is_shared_noop_when_nothing_records():
    assert not PROFILER.enabled
    span = NULL_TRACER.span("batcher.dispatch", track="serve loop", width=4)
    assert span is NULL_TRACER.span("other")
    with span as stats:
        stats["blocked_us"] = 1.0  # a span nothing records drops its stats
        stats.update(wait_us=2.0)
    assert dict(stats) == {}


def test_tracer_span_stats_join_the_event():
    tr = Tracer()
    with tr.span("batcher.dispatch", track="serve loop", width=4) as stats:
        stats["blocked_us"] = 12.5
    (ev,) = tr.events()
    assert ev.args == {"width": 4, "blocked_us": 12.5}
    assert ev.track == "serve loop"


def _profiled_host_events(trace_dir) -> dict:
    import jax

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(path)
    found: dict = {}
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    found.setdefault(e.name, []).append(dict(e.stats))
    return found


@pytest.mark.parametrize("installed", [False, True], ids=["null", "tracer"])
def test_spans_reach_the_profiler_with_their_stats(tmp_path, installed):
    """While jax.profiler records, a span is a profiler event carrying its
    attributes and its late stats, whether or not a Tracer is installed."""
    import jax

    tracer = Tracer() if installed else None
    with use_tracer(tracer):
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert PROFILER.enabled
            with current_tracer().span(
                "obs.test", track="serve loop", bucket="p3", width=4,
            ) as stats:
                stats["blocked_us"] = 7.25
            with current_tracer().span("obs.marker", asked_us=100.0):
                pass
        finally:
            jax.profiler.stop_trace()
    assert not PROFILER.enabled
    found = _profiled_host_events(tmp_path)
    assert found["obs.test"] == [{"bucket": "p3", "width": 4, "blocked_us": 7.25}]
    assert found["obs.marker"] == [{"asked_us": 100.0}]
    if installed:
        assert [e.name for e in tracer.events()] == ["obs.test", "obs.marker"]
        assert tracer.events()[0].args["blocked_us"] == 7.25


def test_collection_spans_record_each_collection():
    tr = Tracer()
    with use_tracer(tr):
        with collection_spans():
            gc.collect()
        gc.collect()  # outside the scope: not recorded
    spans = [e for e in tr.events() if e.name == "gc.collect"]
    assert [e.args["generation"] for e in spans] == [2]
    assert spans[0].track == "host runtime" and spans[0].dur_us > 0


class _Slow:
    """A stand-in device result that is ready ``seconds`` after its call."""

    def __init__(self, seconds):
        self.t_ready = time.perf_counter() + seconds

    def is_ready(self):
        return time.perf_counter() >= self.t_ready

    def block_until_ready(self):
        time.sleep(max(0.0, self.t_ready - time.perf_counter()))
        return self


def _dispatch_spans(calls, requests, **kw):
    tr = Tracer()
    sched = Schedule(requests=tuple(requests), offered_qps=100.0)
    with use_tracer(tr):
        report = serve_dynamic(calls, sched, **kw)
    return [e.args for e in tr.events() if e.name == "batcher.dispatch"], report


def test_a_backlog_waits_as_blocked_not_as_fill():
    """Nine requests at once and a straggler: the first pass finds two
    full batches, so members 5-8 are dispatchable at once and their wait
    for room under the cap is blocked time, not fill; member 9 waits to
    fill until the straggler's arrival flushes the stream."""
    calls = {"a": {w: (lambda: _Slow(0.02)) for w in (1, 2, 4)}}
    reqs = [Request(index=i, arrival_s=0.0, bucket="a") for i in range(9)]
    reqs.append(Request(index=9, arrival_s=0.3, bucket="a"))
    spans, _ = _dispatch_spans(calls, reqs, budget_s=10.0, concurrency=4)
    assert [(a["filled"], a["cause"]) for a in spans] == [(4, "full"), (4, "full"), (2, "flush")]
    first, second, last = spans
    assert first["fill_us"] == second["fill_us"] == 0.0
    assert second["blocked_us"] >= 4 * 0.015e6  # the first batch's 20 ms
    assert last["fill_us"] >= 0.25e6  # member 9, for the straggler


def test_batcher_dispatch_spans_split_each_wait():
    """For every batch, late + fill + blocked over its members equals the
    members' summed wait from due time to t_dispatch, as the report's own
    timestamps give it; a slow call makes arrivals late and a cap of 2
    in flight makes batches wait for room."""

    def call():
        time.sleep(0.001)
        return _Slow(0.004)

    calls = {b: {w: call for w in (1, 2, 4)} for b in "ab"}
    rng = np.random.default_rng(3)
    arrivals = np.cumsum(rng.exponential(0.0015, size=60))
    spans, report = _dispatch_spans(
        calls,
        [
            Request(index=i, arrival_s=float(t), bucket="b" if i % 3 == 0 else "a")
            for i, t in enumerate(arrivals)
        ],
        budget_s=0.002, concurrency=2,
    )
    assert len(spans) == len(report.batches)
    blocked = 0.0
    for a, batch in zip(spans, report.batches):
        assert (a["bucket"], a["width"], a["filled"], a["cause"]) == (
            batch.bucket, batch.width, batch.filled, batch.cause,
        )
        members = [
            c for c in report.completions
            if (c.bucket, c.t_done) == (batch.bucket, batch.t_done)
        ]
        assert len(members) == batch.filled
        waited = sum(batch.t_dispatch - c.t_submit for c in members) * 1e6
        split = a["late_us"] + a["fill_us"] + a["blocked_us"]
        assert split == pytest.approx(waited, rel=1e-6, abs=1e-3)
        assert a["wait_us"] == pytest.approx(waited, rel=1e-6, abs=1e-3)
        assert min(a["late_us"], a["fill_us"], a["blocked_us"]) >= 0.0
        blocked += a["blocked_us"]
    assert blocked > 0  # the cap of 2 in flight held some batch back


# -- Chrome export -----------------------------------------------------------


def _chrome_by_phase(events):
    meta = [e for e in events if e["ph"] == "M"]
    spans = [e for e in events if e["ph"] == "X"]
    return meta, spans


def test_chrome_export_tracks_and_threads(tmp_path):
    tr = Tracer()
    with tr.span("compile", bench="b"):
        pass
    t0 = time.perf_counter()
    tr.event("request", t_start=t0, t_end=t0 + 0.01, track="serve", tid="lane 0")
    tr.event("request", t_start=t0, t_end=t0 + 0.01, track="serve", tid="lane 1")
    tr.event(
        "batch[4]", t_start=t0, t_end=t0 + 0.01, track="batcher",
        tid="queue p0", width=4, filled=3, cause="expired",
    )
    path = tmp_path / "out" / "run.trace.json"  # export creates the dir
    n = tr.export_chrome(str(path))
    assert n == 4
    doc = json.loads(path.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    meta, spans = _chrome_by_phase(doc["traceEvents"])
    procs = {
        e["pid"]: e["args"]["name"]
        for e in meta if e["name"] == "process_name"
    }
    assert sorted(procs.values()) == ["batcher", "engine", "serve"]
    threads = {
        (e["pid"], e["tid"]): e["args"]["name"]
        for e in meta if e["name"] == "thread_name"
    }
    # Explicit string tids keep their label; the engine thread is "main";
    # the two lanes land on distinct tids within the serve pid.
    assert "main" in threads.values()
    lane_tids = {
        tid for (pid, tid), name in threads.items()
        if name in ("lane 0", "lane 1")
    }
    assert len(lane_tids) == 2
    assert "queue p0" in threads.values()
    by_name = {e["name"]: e for e in spans}
    assert by_name["batch[4]"]["args"]["cause"] == "expired"
    assert by_name["compile"]["cat"] == "engine"


def test_threaded_serve_client_tids_merge_into_one_trace():
    """Spans from N lane threads merge into one valid Chrome trace with
    one named serve track per lane (the ISSUE's determinism test)."""
    n_lanes = 3
    tr = Tracer()
    with use_tracer(tr):
        result = run_closed_loop_threaded(
            lambda: np.zeros(4),
            concurrency=n_lanes * 2,
            n_lanes=n_lanes,
            duration_s=0.05,
        )
    assert result.completions
    events = tr.events()
    lane_spans = [e for e in events if e.name == "serve.lane"]
    assert len(lane_spans) == n_lanes
    assert sorted(e.tid for e in lane_spans) == [f"lane {k}" for k in range(n_lanes)]
    chrome = Tracer.chrome_events(tr)
    meta, spans = _chrome_by_phase(chrome)
    serve_pids = {
        e["pid"] for e in meta
        if e["name"] == "process_name" and e["args"]["name"] == "serve"
    }
    assert len(serve_pids) == 1  # one process, N thread tracks
    lane_names = {
        e["args"]["name"] for e in meta if e["name"] == "thread_name"
    }
    assert {f"lane {k}" for k in range(n_lanes)} <= lane_names
    # Deterministic export: same events -> byte-identical ordering.
    assert chrome == tr.chrome_events()


# -- engine integration ------------------------------------------------------


def test_engine_records_stage_timings_and_spans():
    tr = Tracer()
    res = Engine(tracer=tr).run(
        _plan(specs=(_spec(),), include_backward=False), verbose=False
    )
    (rec,) = res.records
    assert rec.status == "ok"
    timings = rec.stage_timings_us
    assert set(timings) >= {"build", "place", "compile", "measure", "characterize"}
    assert all(v >= 0 for v in timings.values())
    names = {e.name for e in tr.events()}
    assert {"build", "place", "compile", "measure", "characterize"} <= names
    # Metadata carries the counter snapshot when tracing is on (a dict —
    # possibly empty for a serve-less, cache-less run).
    assert isinstance(res.metadata.counters, dict)


def test_stage_timings_sum_tracks_wall_time():
    """Per-record stage sum stays within 10% of the run's wall time
    (stages run back to back, so the sum can only *undershoot* by the
    inter-stage bookkeeping)."""
    spec = _spec(
        name="zz_sleepy",
        fn=lambda x: (time.sleep(0.02), x)[1],
        meta={"no_jit": True},  # host fn: measure dominates, timing is real
    )
    engine = Engine()
    w0 = time.perf_counter()
    res = engine.run(_plan(specs=(spec,), include_backward=False), verbose=False)
    wall_us = (time.perf_counter() - w0) * 1e6
    (rec,) = res.records
    assert rec.status == "ok"
    total = sum(rec.stage_timings_us.values())
    assert total <= wall_us * 1.10
    assert total >= wall_us * 0.5  # the stages are where the time went


def test_stage_timings_roundtrip_jsonl(tmp_path):
    path = tmp_path / "run.jsonl"
    Engine().run(
        _plan(specs=(_spec(),), include_backward=False),
        jsonl_path=str(path), verbose=False,
    )
    (rec,) = load_records(str(path))
    assert rec.stage_timings_us is not None
    assert set(rec.stage_timings_us) >= {"build", "compile", "measure"}
    assert all(
        isinstance(v, float) and v >= 0
        for v in rec.stage_timings_us.values()
    )


def test_error_record_carries_partial_stage_timings():
    def broken(**size):
        raise RuntimeError("no such workload")

    spec = BenchmarkSpec(
        name="zz_broken", level=0, dwarf=None, domain=None,
        cuda_feature=None, tpu_feature=None, presets={0: {}}, build=broken,
    )
    res = Engine().run(_plan(specs=(spec, _spec())), verbose=False)
    err = [r for r in res.records if r.status != "ok"]
    assert err and all(
        r.stage_timings_us is not None and "build" in r.stage_timings_us
        for r in err
    )


def test_metadata_cache_stats_stamped(tmp_path):
    """Satellite 1: disk-cache counter totals land in RunMetadata on
    every run, and survive the JSONL roundtrip (last meta wins)."""
    path = tmp_path / "run.jsonl"
    engine = Engine(cache_dir=str(tmp_path / "cache"))
    res = engine.run(
        _plan(specs=(_spec(),), include_backward=False),
        jsonl_path=str(path), verbose=False,
    )
    stats = res.metadata.cache_stats
    assert stats is not None
    assert set(stats) >= {"hits", "misses", "stores", "fallback_count"}
    assert all(isinstance(v, int) for v in stats.values())
    meta, _ = load_run(str(path))
    assert meta is not None and meta.cache_stats == stats
    # Warm run: the same engine reports cumulative totals, and a traced
    # run folds them into the counter snapshot under the cache. prefix.
    tr = Tracer()
    engine.tracer = tr
    res2 = engine.run(_plan(specs=(_spec(),), include_backward=False), verbose=False)
    assert res2.metadata.counters is not None
    for k, v in res2.metadata.cache_stats.items():
        assert res2.metadata.counters[f"cache.{k}"] == v


def test_tune_trials_us_is_sum_of_trial_spans(monkeypatch):
    """Satellite 2: the record's tune_trials_us equals the sum of the
    per-candidate tune.trial span durations, exactly."""
    monkeypatch.setattr(
        Engine, "_time_tune_trial", lambda self, e, a, p: 1.0
    )
    tr = Tracer()
    res = Engine(tracer=tr).run(
        _plan(
            names=("softmax",), include_backward=False,
            impl="pallas", tune=True,
        ),
        verbose=False,
    )
    (rec,) = res.records
    assert rec.status == "ok" and rec.tune_trials
    trial_events = [e for e in tr.events() if e.name == "tune.trial"]
    assert len(trial_events) == rec.tune_trials
    assert rec.tune_trials_us == pytest.approx(
        sum(e.dur_us for e in trial_events), abs=1e-6
    )
    assert tr.counters.get("tune.trials") == rec.tune_trials


def test_serve_events_have_lane_tracks():
    tr = Tracer()
    from repro.core.plan import ServeSpec

    res = Engine(tracer=tr).run(
        _plan(
            specs=(_spec(),), include_backward=False,
            serve=ServeSpec(mode="closed", concurrency=4, lanes=2,
                            duration_s=0.1),
        ),
        verbose=False,
    )
    (rec,) = res.records
    assert rec.status == "ok"
    reqs = [e for e in tr.events() if e.name == "request"]
    assert reqs and all(e.track == "serve" for e in reqs)
    assert {e.tid for e in reqs} <= {"lane 0", "lane 1"}
    assert tr.counters.get("serve.requests") == len(reqs)
    assert "serve" in rec.stage_timings_us


# -- zero-overhead contract --------------------------------------------------


def test_timing_hot_loop_is_structurally_uninstrumented():
    """The inner measurement loop must never consult the tracer — the
    disabled-path overhead there is zero by construction, not by guard."""
    src = inspect.getsource(harness)
    assert "tracer" not in src and "obs" not in src.replace("obs_", "")


def test_disabled_tracing_overhead_under_two_percent():
    """us_per_call medians with the NULL tracer stay within 2% (plus a
    small absolute epsilon for timer granularity) of an engine built
    before any tracer existed — which is the same code path, so this
    guards against someone instrumenting the measure stage's hot loop.
    Best-of-5: the minimum of several runs is the least-contended
    sample."""

    def best_us(tracer):
        best = float("inf")
        for _ in range(5):
            res = Engine(tracer=tracer).run(
                _plan(specs=(_spec(),), include_backward=False, iters=30),
                verbose=False,
            )
            (rec,) = res.records
            assert rec.status == "ok"
            best = min(best, rec.us_per_call)
        return best

    off = best_us(None)  # default engine: NULL_TRACER
    on = best_us(NullTracer())  # explicit disabled tracer, same contract
    assert on <= off * 1.02 + 2.0
    assert off <= on * 1.02 + 2.0


# -- tools -------------------------------------------------------------------


def test_trace_report_cli(tmp_path):
    tr = Tracer()
    with tr.span("compile", bench="b"):
        time.sleep(0.001)
    t0 = time.perf_counter()
    tr.event("request", t_start=t0, t_end=t0 + 0.01, track="serve", tid="lane 0")
    path = tmp_path / "run.trace.json"
    tr.export_chrome(str(path))
    proc = subprocess.run(
        [sys.executable, "tools/trace_report.py", str(path)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert "engine stages" in proc.stdout
    assert "serve lanes" in proc.stdout
    bad = tmp_path / "not_a_trace.json"
    bad.write_text("{}\nnot json\n")
    proc = subprocess.run(
        [sys.executable, "tools/trace_report.py", str(bad)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 2
