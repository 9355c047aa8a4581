"""Distributed load generation: wire-protocol round-trips, seeded
sub-schedule determinism (byte-identical merged traces), merged-stream
accounting identity, plan validation, and the launcher end-to-end (real
client subprocesses against a shared executable cache)."""

import dataclasses
import socket

import pytest

from repro.core.plan import PlanError, ServeSpec
from repro.dist import proto
from repro.serve.latency import stats_from_completions
from repro.serve.lanes import Completion
from repro.serve.loadgen import (
    merge_schedules,
    open_loop_lane_schedules,
    open_loop_schedule,
    save_trace,
)

_SAMPLES = {
    "hello": proto.Hello(proc_id=3, pid=4242),
    "assign": proto.Assign(
        benchmark="pathfinder", preset=0, overrides={"rows": 64},
        serve={"mode": "open", "qps": 100.0}, seed=7, proc_id=1, n_procs=4,
        warmup=8, devices=1, placement="replicate", impl="xla",
        cache_dir="/tmp/c",
    ),
    "ready": proto.Ready(proc_id=1, requests=97),
    "start": proto.Start(epoch=1723.25),
    "stamp": proto.Stamp(
        proc_id=1, completions=[[0, 0, 0.001, 0.002, True], [1, 0, 0.01, 0.02, False]]
    ),
    "done": proto.Done(
        proc_id=1, requests=97, truncated=False,
        cache_counters={"misses": 0, "hits": 1},
    ),
    "error": proto.Error(proc_id=2, message="boom"),
}


def test_every_registered_message_type_roundtrips():
    assert set(_SAMPLES) == set(proto.MESSAGE_TYPES)
    for tag, msg in _SAMPLES.items():
        frame = proto.encode(msg)
        assert proto.decode(frame[proto._HEADER.size:]) == msg


def test_socket_framing_preserves_message_order():
    a, b = socket.socketpair()
    try:
        for msg in _SAMPLES.values():
            proto.send_msg(a, msg)
        for msg in _SAMPLES.values():
            assert proto.recv_msg(b) == msg
        a.close()
        with pytest.raises(proto.ConnectionClosed):
            proto.recv_msg(b)
    finally:
        a.close()
        b.close()


def test_decode_rejects_garbage_and_unknown_types():
    with pytest.raises(proto.ProtocolError):
        proto.decode(b"not json")
    with pytest.raises(proto.ProtocolError):
        proto.decode(b'{"type":"warp-drive"}')
    with pytest.raises(proto.ProtocolError):
        proto.decode(b'{"type":"ready"}')  # missing required fields
    with pytest.raises(proto.ProtocolError):
        proto.encode(object())  # unregistered type


def test_subschedules_deterministic_and_merged_trace_byte_identical(tmp_path):
    kw = dict(qps=400.0, duration_s=2.0, n_lanes=4, seed=123, warmup=6)
    subs_a = open_loop_lane_schedules(**kw)
    subs_b = open_loop_lane_schedules(**kw)
    assert [s.requests for s in subs_a] == [s.requests for s in subs_b]
    # Each sub-stream carries its share of the target; the merged stream
    # is the full offered load in arrival order with dense global indices.
    merged_a = merge_schedules(subs_a)
    merged_b = merge_schedules(subs_b)
    assert merged_a.offered_qps == pytest.approx(400.0)
    assert [r.index for r in merged_a.requests] == list(range(len(merged_a.requests)))
    pa, pb = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    save_trace(merged_a, str(pa))
    save_trace(merged_b, str(pb))
    assert pa.read_bytes() == pb.read_bytes()
    # A different seed is a different stream — the traces must not collide.
    other = merge_schedules(open_loop_lane_schedules(**{**kw, "seed": 124}))
    save_trace(other, str(pb))
    assert pa.read_bytes() != pb.read_bytes()


def _synthetic_completions(n_procs: int, lanes: int, per_lane: int):
    """Per-process completion lists with distinct latencies everywhere."""
    streams = []
    k = 0
    for _ in range(n_procs):
        rows = []
        for lane in range(lanes):
            for i in range(per_lane):
                t = 0.01 * k
                rows.append(Completion(
                    index=k, lane=lane, t_submit=t, t_done=t + 0.001 * (k % 17 + 1),
                    warmup=k < 3,
                ))
                k += 1
        streams.append(rows)
    return streams


def test_merged_stream_percentiles_equal_concatenated_stream():
    lanes = 2
    streams = _synthetic_completions(n_procs=3, lanes=lanes, per_lane=40)
    # The launcher's merge: relabel to global lanes, order by t_done.
    merged = sorted(
        (
            dataclasses.replace(c, lane=proc_id * lanes + c.lane)
            for proc_id, rows in enumerate(streams)
            for c in rows
        ),
        key=lambda c: c.t_done,
    )
    concat = [c for rows in streams for c in rows]
    a = stats_from_completions(merged, offered_qps=300.0, n_lanes=3 * lanes)
    b = stats_from_completions(concat, offered_qps=300.0)
    assert (a.p50_us, a.p95_us, a.p99_us) == (b.p50_us, b.p95_us, b.p99_us)
    assert a.requests == b.requests
    assert a.achieved_qps == pytest.approx(b.achieved_qps)
    assert a.lane_qps is not None and len(a.lane_qps) == 3 * lanes


def test_too_short_duration_yields_explicit_empty_schedule():
    sched = open_loop_schedule(qps=0.5, duration_s=1e-9, seed=0)
    assert len(sched) == 0
    assert sched.truncated is False
    assert sched.offered_qps == 0.5
    with pytest.raises(ValueError, match="schedule was empty"):
        stats_from_completions(list(sched), offered_qps=0.5)


def test_servespec_client_procs_validation():
    ok = ServeSpec(mode="open", qps=10.0, duration_s=1.0, client_procs=2)
    assert ok.client_procs == 2
    with pytest.raises(PlanError):
        ServeSpec(mode="open", qps=10.0, duration_s=1.0, client_procs=-1)
    with pytest.raises(PlanError):
        ServeSpec(mode="closed", client_procs=2)
    with pytest.raises(PlanError):
        ServeSpec(mode="open", qps=10.0, duration_s=1.0, client_procs=2,
                  dispatch="batched")
    with pytest.raises(PlanError):
        ServeSpec(mode="open", qps=10.0, duration_s=1.0, client_procs=2,
                  client="threaded")


def test_launcher_two_procs_merged_accounting_and_warm_zero_compiles(tmp_path):
    from repro.dist.launcher import DistLatencyStats, run_distributed

    serve = ServeSpec(mode="open", qps=120.0, duration_s=0.75,
                      concurrency=8, lanes=1, client_procs=2)
    kw = dict(benchmark="pathfinder", preset=0, overrides={}, serve=serve,
              seed=11, devices=1, placement_mode="replicate", impl="xla",
              cache_dir=str(tmp_path / "hlo"))
    cold = run_distributed(**kw)
    assert isinstance(cold, DistLatencyStats)
    assert cold.client_procs == 2
    assert cold.proc_qps is not None and len(cold.proc_qps) == 2
    assert cold.requests > 0
    assert "client_procs=2" in cold.derived()
    warm = run_distributed(**kw)
    # Determinism: same seed, same sub-schedules, same request count.
    assert warm.requests == cold.requests
    # Shared-cache contract: a warm distributed run restores executables
    # in every client — zero misses (so zero retraces and zero XLA
    # compiles) and zero fallbacks across processes.
    assert warm.client_cache_counters is not None
    assert warm.client_cache_counters["misses"] == 0
    assert warm.client_cache_counters["fallback_count"] == 0
    assert warm.client_cache_counters["hits"] == 2


def test_engine_routes_client_procs_and_record_carries_dist_fields(tmp_path):
    from repro.core.engine import Engine
    from repro.core.plan import ExecutionPlan

    serve = ServeSpec(mode="open", qps=120.0, duration_s=0.75,
                      concurrency=8, lanes=1, client_procs=2)
    eng = Engine(cache_dir=str(tmp_path / "hlo"))
    res = eng.run(ExecutionPlan(
        names=("pathfinder",), preset=0, iters=1, warmup=0,
        include_backward=False, serve=serve, seed=5,
    ))
    rec = res.records[0]
    assert rec.status == "ok", rec.error
    assert rec.client_procs == 2
    assert rec.proc_qps is not None and len(rec.proc_qps) == 2
    assert "client_procs=2" in rec.csv()
    assert rec.achieved_qps is not None and rec.achieved_qps > 0
