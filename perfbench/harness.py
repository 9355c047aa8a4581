"""The benchmark's core: find a cell's pieces by name, run the cell once,
and turn what the run saw into its result line.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``perfbench/configs/<config>.json``: the system as it is run (registry
  name, preset, overrides, precision, buckets), with its source;
- ``perfbench/refs/<config>.py``: that configuration's inputs from the seed,
  its plain reference, its lower-precision control and the comparison
  that decides ``correct``;
- ``perfbench/traffic/<traffic>.json``: the mix's parameters, read by the
  general driver it names, ``perfbench/traffic/<driver>.py``;
- ``perfbench/metrics/<metric>.py``: one reader per metric, ``read(run)``
  returning a number or ``None`` when the run holds nothing to read.

A new configuration, mix or metric is new files plus new entries in
``BENCHMARK.json``; no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Callable

import numpy as np

NAME_CHARS = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_.-")


class BenchError(Exception):
    """A cell, configuration, mix or metric that the benchmark cannot run."""


def _check_name(kind: str, name: str) -> str:
    if (
        not isinstance(name, str)
        or not 1 <= len(name) <= 64
        or not set(name) <= NAME_CHARS
        or name[0] in ".-"
    ):
        raise BenchError(f"bad {kind} name {name!r}")
    return name


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int


class Bench:
    """``BENCHMARK.json`` and the files it names, under one checkout root."""

    def __init__(self, root: str) -> None:
        self.root = root
        self.dir = os.path.join(root, "perfbench")
        with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as fh:
            self.spec = json.load(fh)

    def cell(self, name: str) -> Cell:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return Cell(w["name"], w["config"], w["traffic"], int(w["chips"]))
        known = [w["name"] for w in self.spec["workloads"]]
        raise BenchError(f"unknown workload {name!r}; known: {known}")

    def _path(self, kind: str, name: str, ext: str) -> str:
        path = os.path.join(self.dir, kind, _check_name(kind, name) + ext)
        if not os.path.isfile(path):
            raise BenchError(f"no {kind} file for {name!r}: {path}")
        return path

    def config(self, name: str) -> dict:
        if not any(c["name"] == name for c in self.spec["configs"]):
            raise BenchError(f"configuration {name!r} is not in BENCHMARK.json")
        with open(self._path("configs", name, ".json"), encoding="utf-8") as fh:
            return json.load(fh)

    def traffic(self, name: str) -> dict:
        with open(self._path("traffic", name, ".json"), encoding="utf-8") as fh:
            return json.load(fh)

    def driver(self, kind: str):
        return self._module("traffic", kind)

    def ref(self, config: str):
        return self._module("refs", config)

    def reader(self, metric: str):
        return self._module("metrics", metric)

    def _module(self, kind: str, name: str):
        path = self._path(kind, name, ".py")
        mod_name = f"perfbench_{kind}_{name}".replace(".", "_").replace("-", "_")
        module = sys.modules.get(mod_name)
        if module is None or getattr(module, "__file__", None) != path:
            spec = importlib.util.spec_from_file_location(mod_name, path)
            module = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = module
            spec.loader.exec_module(module)
        return module

    def metrics_for(self, cell: str, trace: bool) -> list[dict]:
        """The cell's end-to-end metrics (``trace`` false) or its per-layer
        metrics (``trace`` true): those whose ``workloads`` list names the
        cell, and those with no list."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]


def seed_key(seed: int):
    """A JAX PRNG key from any non-negative whole number, wider than 32 bits
    included (``SeedSequence`` hashes the whole integer)."""
    import jax
    import jax.numpy as jnp

    if seed < 0:
        raise BenchError(f"--seed must be >= 0, got {seed}")
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words))


def require_chips(chips: int) -> list:
    """The first ``chips`` accelerator devices; an error on anything else.
    There is no CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(
            f"no TPU: JAX's first device is {devices[0].platform} "
            f"({devices[0].device_kind}); this benchmark runs on the chip only"
        )
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def place_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else the fixed ``perfbench/.jax_cache``
    of this checkout, so a cell's later runs load every program."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, "perfbench", ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Run:
    """What one run of a cell saw, as the metric readers get it.

    ``window`` is the driver's record of the measured window (its ``kind``
    says which keys it has); ``trace`` the reduced profiler trace of a
    traced run, else ``None``; ``stage_us`` the program's own set-up stage
    timings (``stage_timings_us`` of its engine), summed over programs."""

    cell: Cell
    config: dict
    traffic: dict
    setup_s: float
    window: dict
    stage_us: dict
    peaks: dict
    trace: Any = None


def run_cell(
    bench: Bench,
    cell_name: str,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    devices: list,
    fault: Callable | None = None,
) -> dict:
    """Run one cell once and return its result line as a dict.

    ``t_start`` is the host clock at process start: set-up runs from there
    to the window. ``fault`` (tests only) wraps the program's callable, to
    show that the comparison catches a broken timed path."""
    from perfbench import peaks as peaks_mod

    cell = bench.cell(cell_name)
    config = bench.config(cell.config)
    traffic = bench.traffic(cell.traffic)
    driver = bench.driver(traffic["driver"])
    ref = bench.ref(cell.config)
    device = devices[0]
    peaks = peaks_mod.for_device(device) if device.platform == "tpu" else {}
    readers = [(m, bench.reader(m["name"])) for m in bench.metrics_for(cell.name, trace)]

    session = driver.Session(config, traffic, ref, seed_key(seed), seed=seed, fault=fault)
    session.setup()
    tdir = None
    if trace:
        tdir = os.path.join(bench.dir, "out", "trace", cell.name)
        shutil.rmtree(tdir, ignore_errors=True)
    setup_s = time.perf_counter() - t_start
    window = session.window(seconds, trace_dir=tdir)
    memory_peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    checks = session.check()
    reduced = None
    if tdir is not None:
        from perfbench import trace as trace_mod

        reduced = trace_mod.reduce_dir(tdir)

    run = Run(
        cell=cell, config=config, traffic=traffic, setup_s=setup_s, window=window, stage_us=session.stage_us,
        peaks=peaks, trace=reduced,
    )
    metrics = {}
    for entry, reader in readers:
        value = reader.read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    device_info = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": window["attempted"],
        "failed": window["failed"] + session.failed,
        "metrics": metrics,
        "device": device_info,
    }
    if reduced is not None:
        device_info["busy_s"] = reduced.busy_s
        device_info["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = checks
    return result


def worst(limits: dict, readings: list[dict]) -> tuple[dict, int]:
    """Each compared number at its worst over the answers, beside its
    limit, and how many answers broke a limit."""
    failed = sum(any(r[k] > limits[k] for k in limits) for r in readings)
    checks = {k: {"value": max(r[k] for r in readings), "limit": limits[k]} for k in limits}
    return checks, failed


def emit(result: dict, out=None, err=None) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard output."""
    out = out or sys.stdout
    err = err or sys.stderr
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {verdict}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
