#!/usr/bin/env bash
# CI smoke: run a preset-0 suite slice through the staged engine with a
# streaming JSONL report, verify the report loads back, then tier-1 pytest.
#
# With --multi-device, instead run the placement smoke: force 8 host
# devices and drive a sharded device-scaling sweep, asserting zero
# status=error records and populated scaling_efficiency columns.
#
# With --serve [CLIENT], instead run the serving smoke on forced host
# devices with that serving client (single|threaded, default single): a
# tiny closed-loop serve (2 lanes, ~2 s) asserting schema-v4 latency/QPS
# columns (threaded runs additionally assert the dispatch-overhead and
# per-lane QPS accounting), plus — for the single client — one
# co-location pair asserting slowdown-vs-isolated on both tenants' rows.
#
# With --warm-cache, instead run the zero-compile smoke: the same suite
# slice twice against one --cache-dir, asserting the warm run restored
# every entry from the serialized-executable tier — zero retraces, zero
# XLA compilations, zero fallbacks (the printed hlocache counters are
# parsed and checked) — and produced only ok records.
#
# With --impl [IMPL], instead run the implementation-axis smoke (default
# pallas; interpret mode off-TPU): a kernel-backed slice under
# --impl/--tune/--cache-dir twice, asserting cold rows carry
# impl/tuned_params/tune_trials>0 and the warm run restored every tuned
# winner AND every executable — zero XLA compiles, zero tune trials.
#
# With --batching, instead run the continuous-batching smoke: a dynamic
# mixed-shape serve under --cache-dir twice (cold stores one executable
# per (shape bucket, batch width); warm restores every one of them with
# zero retraces and zero XLA compiles), then a loop-dispatch run
# replaying the *same* saved trace, asserting the dynamic batcher's
# goodput strictly beats the sync loop's at identical offered load.
#
# With --trace, instead run the observability smoke: a small suite slice
# served through 2 lanes with --trace-out, asserting the trace parses as
# Chrome trace-event JSON with >=1 span per engine stage and named
# serve-lane tracks, that every record carries stage_timings_us summing
# within 10% of the run's wall time, that the final metadata line holds
# the counter snapshot, and that tools/trace_report.py reads the file.
#
# With --check, instead run the static lint leg: the repro.check contract
# checker (AST-only, needs no JAX) must exit clean, and ruff (F/E9/B
# scope, see ruff.toml) runs when installed. This is the only leg that
# works on a bare Python install.
#
# With --bench [PATH], instead write the perf-trajectory artifact
# (default artifacts/BENCH_7.json): loop vs lanes vs dynamic-batcher
# latency/goodput over one fixed seeded mixed-shape trace (the
# fig_batching comparison), asserting dynamic goodput strictly beats
# loop goodput, so future PRs have a baseline.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

out="$(mktemp -d)"
trap 'rm -rf "$out"' EXIT

if [[ "${1:-}" == "--check" ]]; then
  python -m repro.check
  if command -v ruff >/dev/null 2>&1; then
    ruff check .
  else
    echo "# ruff not installed; skipping lint (repro.check still enforced)" >&2
  fi
  exit 0
fi

if [[ "${1:-}" == "--multi-device" ]]; then
  export XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}"

  python -m repro.core.suite \
    --levels 1 --preset 0 --iters 1 --warmup 0 --no-backward \
    --placement shard --scale-devices 1,2,4 \
    --jsonl "$out/scaling.jsonl"

  python - "$out/scaling.jsonl" <<'PY'
import sys

from repro.core.results import load_run

meta, records = load_run(sys.argv[1])
assert meta is not None and meta.placement == "shard", meta
assert meta.device_sweep == (1, 2, 4), meta
bad = [r for r in records if r.status == "error"]
for r in bad:
    print(f"ERROR {r.name}: {r.error}", file=sys.stderr)
assert not bad, f"{len(bad)} error records in the scaling sweep"
counts = sorted({r.devices for r in records})
assert counts == [1, 2, 4], counts
multi = [r for r in records if r.devices > 1]
assert multi and all(r.scaling_efficiency is not None for r in multi), (
    "multi-device rows missing scaling_efficiency")
sharded = [r for r in multi if r.placement == "shard"]
assert sharded, "no workload actually sharded in the sweep"
print(f"multi-device smoke: {len(records)} records over counts {counts}, "
      f"{len(sharded)} sharded rows, 0 errors")
PY
  exit 0
fi

if [[ "${1:-}" == "--serve" ]]; then
  client="${2:-single}"
  export XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}"

  python -m repro.core.suite \
    --names pathfinder --preset 0 --iters 1 --warmup 0 --no-backward \
    --serve closed --concurrency 4 --lanes 2 --serve-duration 2 \
    --serve-client "$client" --jsonl "$out/serve.jsonl"

  python - "$out/serve.jsonl" "$client" <<'PY'
import sys

from repro.core.results import load_run

meta, records = load_run(sys.argv[1])
client = sys.argv[2]
assert meta is not None and meta.schema_version >= 4, meta
assert meta.serve is not None and meta.serve.mode == "closed", meta.serve
assert meta.serve.client == client, meta.serve
bad = [r for r in records if r.status != "ok"]
for r in bad:
    print(f"ERROR {r.name}: {r.error}", file=sys.stderr)
assert not bad, f"{len(bad)} error records in the serve smoke"
(rec,) = records
assert rec.serve_mode == "closed" and rec.serve_lanes == 2, rec
assert rec.serve_client == client, rec.serve_client
assert rec.latency_p50_us and rec.latency_p95_us and rec.latency_p99_us
assert rec.latency_p50_us <= rec.latency_p99_us <= rec.latency_max_us
assert rec.achieved_qps and rec.achieved_qps > 0, rec
assert rec.serve_truncated is False, rec.serve_truncated
assert rec.lane_qps and len(rec.lane_qps) == 2, rec.lane_qps
if client == "threaded":
    assert rec.dispatch_overhead_us and rec.dispatch_overhead_us > 0, rec
print(f"serve smoke [{client}]: {rec.name} p50={rec.latency_p50_us:.0f}us "
      f"p99={rec.latency_p99_us:.0f}us qps={rec.achieved_qps:.0f} "
      f"lane_qps={[round(q) for q in rec.lane_qps]}")
PY

  # Co-location rides the single-threaded dispatch path by design.
  if [[ "$client" == "single" ]]; then
    python -m repro.core.suite \
      --names pathfinder --preset 0 --iters 1 --warmup 0 --no-backward \
      --serve closed --concurrency 4 --lanes 2 --serve-duration 1 \
      --colocate gemm_f32_nn --jsonl "$out/colocate.jsonl"

    python - "$out/colocate.jsonl" <<'PY'
import sys

from repro.core.results import load_run

meta, records = load_run(sys.argv[1])
assert meta.serve is not None and meta.serve.colocate == "gemm_f32_nn"
bad = [r for r in records if r.status != "ok"]
for r in bad:
    print(f"ERROR {r.name}: {r.error}", file=sys.stderr)
assert not bad, f"{len(bad)} error records in the co-location smoke"
assert len(records) == 2, [r.name for r in records]
primary, partner = records
assert primary.serve_colocate == "gemm_f32_nn", primary
assert partner.name == "gemm_f32_nn@pathfinder", partner.name
for r in records:
    assert r.slowdown_vs_isolated is not None and r.slowdown_vs_isolated > 0, r
print("co-location smoke: slowdowns "
      + ", ".join(f"{r.name}={r.slowdown_vs_isolated:.2f}" for r in records))
PY
  fi
  exit 0
fi

if [[ "${1:-}" == "--warm-cache" ]]; then
  cache="$out/cache"

  python -m repro.core.suite \
    --levels 0 1 --preset 0 --iters 1 --warmup 0 --no-backward \
    --cache-dir "$cache" --jsonl "$out/cold.jsonl" 2> "$out/cold.err" \
    || { cat "$out/cold.err" >&2; exit 1; }
  grep '^# hlocache:' "$out/cold.err"
  python -m repro.core.suite \
    --levels 0 1 --preset 0 --iters 1 --warmup 0 --no-backward \
    --cache-dir "$cache" --jsonl "$out/warm.jsonl" 2> "$out/warm.err" \
    || { cat "$out/warm.err" >&2; exit 1; }
  grep '^# hlocache:' "$out/warm.err"

  python - "$out/cold.err" "$out/warm.err" "$out/warm.jsonl" <<'PY'
import re
import sys

from repro.core.results import load_run


def counters(path):
    with open(path) as f:
        (line,) = [l for l in f if l.startswith("# hlocache:")]
    return {k: int(v) for k, v in re.findall(r"(\w+)=(\d+)", line)}, line

cold, cold_line = counters(sys.argv[1])
warm, warm_line = counters(sys.argv[2])
assert cold["stores"] > 0, f"cold run stored nothing: {cold_line}"
# The zero-compile warm start: every lookup restored a serialized
# executable — no retrace and so no XLA compile (misses=0), no silent
# degradation (fallbacks=0).
assert warm["hits"] == cold["stores"], (cold_line, warm_line)
assert warm["misses"] == 0, warm_line
assert warm["fallbacks"] == 0, warm_line
meta, records = load_run(sys.argv[3])
bad = [r for r in records if r.status != "ok"]
for r in bad:
    print(f"ERROR {r.name}: {r.error}", file=sys.stderr)
assert not bad, f"{len(bad)} error records in the warm run"
# Warm rows still carry both timing modes (schema v5).
assert meta is not None and meta.schema_version >= 5, meta
windowed = [r for r in records if r.us_per_call_windowed is not None]
assert windowed, "warm run produced no windowed timings"
print(f"warm-cache smoke: {warm['hits']} executables restored, "
      f"0 XLA compiles, {len(records)} ok records "
      f"({len(windowed)} with windowed timings)")
PY
  exit 0
fi

if [[ "${1:-}" == "--impl" ]]; then
  impl="${2:-pallas}"
  cache="$out/cache"

  python -m repro.core.suite \
    --names gemm_f32_nn softmax where --preset 0 --iters 1 --warmup 0 \
    --no-backward --impl "$impl" --tune --cache-dir "$cache" \
    --jsonl "$out/impl_cold.jsonl" 2> "$out/impl_cold.err" \
    || { cat "$out/impl_cold.err" >&2; exit 1; }
  grep '^# hlocache:' "$out/impl_cold.err"
  python -m repro.core.suite \
    --names gemm_f32_nn softmax where --preset 0 --iters 1 --warmup 0 \
    --no-backward --impl "$impl" --tune --cache-dir "$cache" \
    --jsonl "$out/impl_warm.jsonl" 2> "$out/impl_warm.err" \
    || { cat "$out/impl_warm.err" >&2; exit 1; }
  grep '^# hlocache:' "$out/impl_warm.err"

  python - "$out/impl_cold.jsonl" "$out/impl_warm.jsonl" "$out/impl_warm.err" "$impl" <<'PY'
import re
import sys

from repro.core.results import load_run

cold_meta, cold = load_run(sys.argv[1])
warm_meta, warm = load_run(sys.argv[2])
impl = sys.argv[4]
with open(sys.argv[3]) as f:
    (line,) = [l for l in f if l.startswith("# hlocache:")]
counters = {k: int(v) for k, v in re.findall(r"(\w+)=(\d+)", line)}

for meta in (cold_meta, warm_meta):
    assert meta is not None and meta.schema_version >= 6, meta
    assert meta.impl == impl and meta.tune is True, (meta.impl, meta.tune)
for tag, records in (("cold", cold), ("warm", warm)):
    bad = [r for r in records if r.status != "ok"]
    for r in bad:
        print(f"ERROR {r.name}: {r.error}", file=sys.stderr)
    assert not bad, f"{len(bad)} error records in the {tag} impl run"
    for r in records:
        assert r.impl == impl and r.impl_fallback is None, (r.name, r.impl)
        if impl == "pallas":
            assert r.impl_interpret is not None, r.name
            assert r.tuned_params, (r.name, "no tuned_params")
# Cold run actually swept the tune space; warm run restored every winner
# from the .tune.json sidecar (zero trials) and every executable from the
# serialized tier (zero XLA compiles).
assert sum(r.tune_trials or 0 for r in cold) > 0, "cold run swept nothing"
assert all((r.tune_trials or 0) == 0 for r in warm), "warm run re-tuned"
assert counters["misses"] == 0 and counters["fallbacks"] == 0, line
assert counters["tune_hits"] == len(warm), line
won = {r.name: r.tuned_params for r in warm}
assert won == {r.name: r.tuned_params for r in cold}, "winners drifted"
trials = sum(r.tune_trials or 0 for r in cold)
print(f"impl smoke [{impl}]: {len(warm)} records, cold swept {trials} "
      f"trials, warm restored {counters['tune_hits']} winners with "
      "0 XLA compiles and 0 tune trials")
PY
  exit 0
fi

if [[ "${1:-}" == "--batching" ]]; then
  cache="$out/cache"
  trace="$out/mix_trace.jsonl"
  mix="0/cols=64@2,0/cols=128@1"
  common=(--names pathfinder --preset 0 --iters 1 --warmup 0 --no-backward
    --serve open --qps 45000 --serve-duration 0.5 --concurrency 16
    --serve-mix "$mix" --serve-trace "$trace" --slo-us 20000
    --max-batch 8 --batch-latency-budget 1000)

  # Cold: the dynamic batcher compiles one executable per (bucket, width)
  # through the executable cache — and saves the generated trace.
  python -m repro.core.suite "${common[@]}" --serve-dispatch dynamic \
    --cache-dir "$cache" --jsonl "$out/dyn_cold.jsonl" 2> "$out/dyn_cold.err" \
    || { cat "$out/dyn_cold.err" >&2; exit 1; }
  grep '^# hlocache:' "$out/dyn_cold.err"
  # Warm: the same run (now replaying the trace) restores every bucket.
  python -m repro.core.suite "${common[@]}" --serve-dispatch dynamic \
    --cache-dir "$cache" --jsonl "$out/dyn_warm.jsonl" 2> "$out/dyn_warm.err" \
    || { cat "$out/dyn_warm.err" >&2; exit 1; }
  grep '^# hlocache:' "$out/dyn_warm.err"
  # The sync-loop floor, replaying the identical trace (same offered load).
  python -m repro.core.suite "${common[@]}" --serve-dispatch loop \
    --jsonl "$out/loop.jsonl"

  python - "$out/dyn_cold.err" "$out/dyn_warm.err" \
    "$out/dyn_warm.jsonl" "$out/loop.jsonl" <<'PY'
import re
import sys

from repro.core.results import load_run


def counters(path):
    with open(path) as f:
        (line,) = [l for l in f if l.startswith("# hlocache:")]
    return {k: int(v) for k, v in re.findall(r"(\w+)=(\d+)", line)}, line

cold, cold_line = counters(sys.argv[1])
warm, warm_line = counters(sys.argv[2])
# Cold compiles: the measure-stage executable plus 2 buckets x 4 dynamic
# widths (1, 2, 4, 8) = 9 distinct programs, every one stored.
assert cold["stores"] == 9, cold_line
# Warm restores the whole bucket table from serialized executables:
# zero retraces (so zero XLA compiles), zero fallbacks.
assert warm["hits"] == cold["stores"], (cold_line, warm_line)
assert warm["misses"] == 0, warm_line
assert warm["fallbacks"] == 0, warm_line

_, dyn_records = load_run(sys.argv[3])
_, loop_records = load_run(sys.argv[4])
(dyn,) = dyn_records
(loop,) = loop_records
for tag, rec in (("dynamic", dyn), ("loop", loop)):
    assert rec.status == "ok", (tag, rec.error)
    assert rec.serve_dispatch == tag, rec.serve_dispatch
    assert rec.serve_mix == "p0/cols=64@2,p0/cols=128@1", rec.serve_mix
    assert rec.batch_occupancy and 0 < rec.batch_occupancy <= 1.0, rec
    assert rec.serve_batches and rec.goodput_qps, rec
    assert rec.bucket_latency_us and set(rec.bucket_latency_us) == {
        "p0/cols=64", "p0/cols=128"}, rec.bucket_latency_us
# Identical replayed trace -> identical offered load and request count.
assert dyn.serve_requests == loop.serve_requests, (dyn, loop)
assert dyn.offered_qps == loop.offered_qps, (dyn, loop)
# Coalescing is the point: far fewer device programs than requests, and
# strictly more goodput than the sync loop under the same SLO.
assert dyn.serve_batches < loop.serve_batches, (dyn.serve_batches,
                                                loop.serve_batches)
assert dyn.goodput_qps > loop.goodput_qps, (dyn.goodput_qps,
                                            loop.goodput_qps)
print(f"batching smoke: {warm['hits']} bucket executables restored "
      f"warm with 0 XLA compiles; dynamic goodput {dyn.goodput_qps:.0f} "
      f"qps > loop {loop.goodput_qps:.0f} qps over {dyn.serve_requests} "
      f"replayed requests ({dyn.serve_batches} vs {loop.serve_batches} "
      "device programs)")
PY
  exit 0
fi

if [[ "${1:-}" == "--trace" ]]; then
  export XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}"

  start_ns=$(date +%s%N)
  python -m repro.core.suite \
    --levels 0 --preset 0 --iters 1 --warmup 0 --no-backward \
    --serve closed --concurrency 4 --lanes 2 --serve-duration 0.5 \
    --serve-client threaded \
    --trace-out "$out/run.trace.json" --jsonl "$out/trace.jsonl" \
    2> "$out/trace.err" || { cat "$out/trace.err" >&2; exit 1; }
  wall_us=$(( ($(date +%s%N) - start_ns) / 1000 ))
  grep '^# trace:' "$out/trace.err"

  python - "$out/run.trace.json" "$out/trace.jsonl" "$wall_us" <<'PY'
import json
import sys

from repro.core.results import load_run

with open(sys.argv[1]) as f:
    doc = json.load(f)
events = doc["traceEvents"]
spans = [e for e in events if e["ph"] == "X"]
meta_events = [e for e in events if e["ph"] == "M"]
assert spans and meta_events, "trace missing span or metadata events"
for ev in spans:
    assert {"name", "cat", "pid", "tid", "ts", "dur"} <= set(ev), ev

# One track per engine stage: every stage appears at least once.
stages = {"build", "place", "tune", "compile", "measure",
          "characterize", "serve"}
engine_spans = {e["name"] for e in spans if e["cat"] == "engine"}
missing = stages - engine_spans
assert not missing, f"engine stages missing from trace: {sorted(missing)}"

# Serve lanes render as named thread tracks carrying request events.
lane_names = {
    e["args"]["name"] for e in meta_events if e["name"] == "thread_name"
}
assert {"lane 0", "lane 1"} <= lane_names, sorted(lane_names)
requests = [e for e in spans if e["name"] == "request"]
assert requests, "no per-request serve events in the trace"

meta, records = load_run(sys.argv[2])
bad = [r for r in records if r.status != "ok"]
for r in bad:
    print(f"ERROR {r.name}: {r.error}", file=sys.stderr)
assert not bad, f"{len(bad)} error records in the trace smoke"
assert meta is not None and meta.schema_version >= 8, meta
assert meta.counters and meta.counters.get("serve.requests", 0) > 0, (
    meta.counters)

# Every record carries the per-stage breakdown; the stages run back to
# back inside the run, so their total can only undershoot the run's
# wall clock — within 10% accounts for selection + report bookkeeping.
wall_us = int(sys.argv[3])
total = 0.0
for r in records:
    assert r.stage_timings_us, f"{r.name} missing stage_timings_us"
    assert set(r.stage_timings_us) >= {"build", "compile", "measure"}, r
    total += sum(r.stage_timings_us.values())
assert total <= wall_us * 1.10, (total, wall_us)
print(f"trace smoke: {len(spans)} spans over stages "
      f"{sorted(engine_spans)}, {len(requests)} request events on "
      f"{len(lane_names & {'lane 0', 'lane 1'})} lane tracks; stage "
      f"timings {total/1e6:.2f}s within run wall {wall_us/1e6:.2f}s")
PY

  python tools/trace_report.py "$out/run.trace.json"
  exit 0
fi

if [[ "${1:-}" == "--bench" ]]; then
  bench_path="${2:-artifacts/BENCH_7.json}"
  cache="$out/cache"

  # The fig_batching comparison: one fixed seeded mixed-shape trace
  # (generated by the first policy, replayed by the rest), loop vs lanes
  # vs dynamic at the same offered load. Two attempts: the acceptance
  # inequality (dynamic goodput > loop goodput) has a 3-5x margin at
  # these knobs, so one retry covers a pathological scheduling hiccup.
  for attempt in 1 2; do
    if python benchmarks/fig_batching.py \
        --trace "$out/bench_trace_$attempt.jsonl" \
        --json "$bench_path"; then
      if python - "$bench_path" <<'PY'
import json
import sys

with open(sys.argv[1]) as f:
    bench = json.load(f)
modes = bench["modes"]
assert set(modes) >= {"loop", "lanes", "dynamic"}, sorted(modes)
dyn, loop = modes["dynamic"], modes["loop"]
for mode, m in modes.items():
    assert m["goodput_qps"] >= 0 and m["batches"] > 0, (mode, m)
# The acceptance inequality: the continuous batcher strictly beats the
# sync loop at identical offered mixed-shape load, under the same SLO.
assert dyn["goodput_qps"] > loop["goodput_qps"], (dyn, loop)
assert dyn["batches"] < loop["batches"], (dyn, loop)
print(f"BENCH_7: dynamic goodput {dyn['goodput_qps']:.0f} qps > loop "
      f"{loop['goodput_qps']:.0f} qps "
      f"({bench['dynamic_over_loop_goodput']}x) at "
      f"{bench['offered_qps']:.0f} offered qps, mix {bench['mix']} "
      f"-> {sys.argv[1]}")
PY
      then
        exit 0
      fi
    fi
    echo "BENCH_7 attempt $attempt failed; retrying" >&2
  done
  echo "BENCH_7: dynamic goodput did not beat loop in 2 attempts" >&2
  exit 1
fi

python -m repro.core.suite \
  --levels 0 1 --preset 0 --iters 1 --warmup 0 --no-backward \
  --jsonl "$out/smoke.jsonl"

python - "$out/smoke.jsonl" <<'PY'
import sys

from repro.core.results import load_run

meta, records = load_run(sys.argv[1])
assert meta is not None and meta.backend and meta.jax_version, meta
ok = [r for r in records if r.status == "ok"]
bad = [r for r in records if r.status != "ok"]
assert ok, "smoke suite produced no ok records"
for r in bad:
    print(f"ERROR {r.name}: {r.error}", file=sys.stderr)
print(f"smoke: {len(ok)} ok / {len(bad)} error records "
      f"(backend={meta.backend}, jax={meta.jax_version})")
sys.exit(1 if bad else 0)
PY

python -m pytest -x -q
