#!/usr/bin/env python3
"""Smoke run of the suite on a TPU, through its normal entry points.

    python chip_smoke.py               # one chip: the phases below
    python chip_smoke.py --four-chips  # four chips: the shard-placement sweep

One chip, one process. Phases (any failure makes the script exit non-zero):

1. device: the first JAX device must be a TPU — there is no CPU fallback;
2. suite, xla: every registered workload at preset 3 (the one-chip size),
   forward and backward where declared; every record must be ok, which
   includes the workload's own ``validate`` in the measure stage;
3. suite, pallas: every workload that declares a Pallas kernel, untuned;
   forward rows must run the compiled kernel (not interpreted, no
   fallback), backward rows fall back to xla as documented;
4. serve: ``pathfinder`` at preset 3 under open-loop dynamic batching over
   a two-bucket shape mix; it must complete requests without errors.

``--four-chips`` runs only the shard-placement sweep: every batchable
workload at preset 3 under ``Placement(mode="shard")`` at 1, 2 and 4
devices (the ``--placement shard --scale-devices 1,2,4`` path), and checks
that each sharded result agrees with its one-device result.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
PRESET = 3
FAST = dict(iters=2, warmup=1, timing_window=1)
# Sharded-vs-one-chip agreement, relative to 1 + |one-chip value|. bf16
# chains re-tile per shard shape. k-means reduces its segment sums in a
# different order per placement, so a point at a near-tie can change
# cluster; one such move shifts a centre by |x - c| / count, about 1e-4
# at preset 3 (seen once in 262,144 points on four v5e chips).
_AGREE_TOL = {"maxflops_bf16": 2e-2, "gemm_bf16_nn": 2e-2, "kmeans": 1e-3}


def _versions() -> str:
    import jax
    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    return f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu {libtpu}"


def _row(r) -> str:
    passname = "bwd" if r.name.endswith(".bwd") else "fwd"
    compile_s = (r.stage_timings_us or {}).get("compile", 0.0) / 1e6
    if r.status != "ok":
        return f"  {r.name} {passname} ERROR ({r.derived}): {r.error}"
    return (
        f"  {r.name} {passname} impl={r.impl} us_per_call={r.us_per_call:.1f} "
        f"compile_s={compile_s:.2f}"
    )


def _suite_phase(engine, impl: str, names=None) -> list[str]:
    from repro.core.plan import ExecutionPlan

    plan = ExecutionPlan(
        levels=(0, 1, 2), names=names, preset=PRESET, impl=impl, **FAST
    )
    records = engine.run(plan).records
    print(f"# suite impl={impl}: {len(records)} records", flush=True)
    failures = []
    for r in records:
        print(_row(r), flush=True)
        if r.status != "ok":
            failures.append(f"{impl} {r.name}: {r.error}")
        elif impl == "pallas":
            backward = r.name.endswith(".bwd")
            if backward and r.impl_fallback != "backward_pass":
                failures.append(f"pallas {r.name}: fallback {r.impl_fallback}")
            if not backward and (
                r.impl != "pallas" or r.impl_interpret or r.impl_fallback
            ):
                failures.append(
                    f"pallas {r.name}: impl={r.impl} interpret="
                    f"{r.impl_interpret} fallback={r.impl_fallback}"
                )
    return failures


def _serve_phase(engine) -> list[str]:
    from repro.core.plan import ExecutionPlan, ServeSpec, ShapeBucket

    # About half the rate one v5e sustained for this mix (924 qps at
    # 2000 offered), so the latencies describe a server that keeps up.
    serve = ServeSpec(
        mode="open", qps=500.0, duration_s=2.0, concurrency=16,
        dispatch="dynamic", batch_budget_us=1000.0, max_batch=4,
        mix=(
            ShapeBucket(preset=PRESET, weight=2.0),
            ShapeBucket(preset=PRESET, weight=1.0, overrides=(("cols", 16384),)),
        ),
    )
    plan = ExecutionPlan(
        names=("pathfinder",), preset=PRESET, include_backward=False,
        serve=serve, **FAST,
    )
    (r,) = engine.run(plan).records
    if r.status != "ok":
        return [f"serve {r.name}: {r.error}"]
    print(
        f"# serve {r.name}: requests={r.serve_requests} "
        f"qps={r.achieved_qps:.1f} p50_us={r.latency_p50_us:.1f} "
        f"p99_us={r.latency_p99_us:.1f} occupancy={r.batch_occupancy:.3f} "
        f"batches={r.serve_batches}",
        flush=True,
    )
    return [] if r.serve_requests else [f"serve {r.name}: no requests completed"]


def _four_chip_phase(engine) -> list[str]:
    import jax
    import numpy as np

    from repro.core.plan import ExecutionPlan, Placement
    from repro.core.registry import all_benchmarks

    names = tuple(
        s.name for s in all_benchmarks() if s.build_preset(PRESET).batchable
    )
    plan = ExecutionPlan(
        names=names, preset=PRESET, include_backward=False,
        placement=Placement(devices=1, mode="shard"), device_sweep=(1, 2, 4),
        **FAST,
    )
    records = engine.run(plan).records
    print(f"# shard sweep: {len(names)} workloads, {len(records)} records", flush=True)
    failures = []
    for r in records:
        eff = "" if r.scaling_efficiency is None else f" eff={r.scaling_efficiency:.3f}"
        print(f"{_row(r)} devices={r.devices} placement={r.placement}{eff}", flush=True)
        if r.status != "ok":
            failures.append(f"shard {r.name}@{r.devices}: {r.error}")
    for spec in (s for s in all_benchmarks() if s.name in names):
        want = engine.outputs(spec, plan, 1)
        for devices in (2, 4):
            got = engine.outputs(spec, plan, devices)
            worst = -np.inf
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
                tol = _AGREE_TOL.get(spec.name, 1e-4)
                excess = np.abs(a - b) - tol * (1.0 + np.abs(b))
                worst = max(worst, float(excess.max(initial=-np.inf)))
            ok = worst <= 0.0
            print(
                f"  agree {spec.name} 1 vs {devices}: "
                f"{'ok' if ok else 'MISMATCH'} (worst excess {worst:.3g})",
                flush=True,
            )
            if not ok:
                failures.append(f"agree {spec.name} 1 vs {devices}")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard-placement sweep at 1, 2, 4 chips")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("error: run chip_smoke.py from a checkout of the suite", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"# device: {dev.platform} {dev.device_kind} x{len(devices)}", flush=True)
    print(f"# versions: {_versions()}", flush=True)
    if dev.platform != "tpu":
        print(f"error: no TPU (first device is {dev.platform})", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"error: need {want} chips, found {len(devices)}", file=sys.stderr)
        return 1

    from repro.core.engine import Engine, enable_compile_cache

    print(f"# jax compilation cache: {enable_compile_cache()}", flush=True)
    engine = Engine()
    if args.four_chips:
        failures = _four_chip_phase(engine)
    else:
        from repro.core.registry import all_benchmarks

        pallas = tuple(
            s.name for s in all_benchmarks() if s.build_preset(PRESET).pallas_kernel
        )
        failures = _suite_phase(engine, "xla")
        failures += _suite_phase(engine, "pallas", names=pallas)
        failures += _serve_phase(engine)
    for f in failures:
        print(f"# FAIL {f}", flush=True)
    if failures:
        print(f"error: {len(failures)} failure(s)", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
