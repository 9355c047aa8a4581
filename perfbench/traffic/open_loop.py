"""Open loop through the program's dynamic batcher: requests arrive on a
schedule made from the seed, whatever the server does, and each one's
latency counts from the time it was due.

The server is ``repro.serve.batcher.serve_dynamic`` over a table of
compiled programs, one per (shape bucket, batch width), as the program's
engine builds for mixed-shape serving. Width w of a bucket computes the
bucket's first w input grids, stacked; a request is one slot of a batch.

Mix parameters (``traffic/<name>.json``): ``qps`` (offered rate),
``lead_in_s`` (arrivals before the window, so its queue has settled when
the window opens), ``trace_seconds`` (the traced slice at the end of the
window in a ``--trace 1`` run). The configuration gives the buckets and
their weights and the batcher's ``max_batch``, ``batch_budget_us`` and
``concurrency``.

The schedule is a Poisson process with its set of gaps fixed: n = qps x
seconds gaps at the quantiles of the exponential distribution, scaled to
sum to the window, in an order drawn from the seed; the lead-in likewise.
The buckets are drawn the same way, in their exact proportions. So every
seed offers the same work in another order.
"""

from __future__ import annotations

import functools
import threading
import time

import jax
import numpy as np

_ARRIVALS, _BUCKETS = 0, 1  # independent streams of one seed


def gaps(n: int, span_s: float, rng: np.random.Generator) -> np.ndarray:
    """n exponential gaps at their quantiles, scaled to sum to ``span_s``,
    in the order ``rng`` draws."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    q *= span_s / q.sum()
    rng.shuffle(q)
    return q


def labels(n: int, weights: dict, rng: np.random.Generator) -> list:
    """n bucket labels in the exact proportions of ``weights`` (largest
    remainders take the spare slots), in the order ``rng`` draws."""
    names = sorted(weights)
    w = np.array([weights[k] for k in names], dtype=np.float64)
    share = n * w / w.sum()
    counts = np.floor(share).astype(int)
    for i in np.argsort(counts - share)[: n - counts.sum()]:
        counts[i] += 1
    out = np.repeat(np.arange(len(names)), counts)
    rng.shuffle(out)
    return [names[i] for i in out]


def schedule(*, qps: float, lead_in_s: float, seconds: float, weights: dict, seed: int):
    """-> (arrival offsets in seconds, bucket labels, index of the first
    request of the window). Deterministic per seed."""
    rng_t = np.random.default_rng([seed, _ARRIVALS])
    rng_b = np.random.default_rng([seed, _BUCKETS])
    n_lead = int(round(qps * lead_in_s))
    n_win = int(round(qps * seconds))
    t = np.concatenate([
        np.cumsum(gaps(n_lead, lead_in_s, rng_t)),
        lead_in_s + np.cumsum(gaps(n_win, seconds, rng_t)),
    ])
    b = labels(n_lead, weights, rng_b) + labels(n_win, weights, rng_b)
    return t, b, n_lead


class Session:
    def __init__(self, config, traffic, ref, key, *, seed, fault=None):
        self.config, self.traffic, self.ref, self.key = config, traffic, ref, key
        self.seed = seed
        self.fault = fault
        self.failed = 0
        self.stage_us: dict[str, float] = {}

    def setup(self) -> None:
        from perfbench.system import Programs
        from repro.serve.batcher import bucket_widths

        cfg = self.config
        programs = Programs()
        self.grids = self.ref.make_inputs(self.key, cfg)
        widths = bucket_widths("dynamic", cfg["max_batch"])
        self.args: dict = {}
        self.outputs: dict = {}
        table: dict = {}
        for bucket in cfg["buckets"]:
            label = bucket["label"]
            views = _views(self.grids[label], widths)
            table[label] = {}
            for w, view in zip(widths, views):
                args = (view,)
                exe = programs.compile(
                    cfg["registry"], cfg["preset"], bucket["overrides"], "xla", w, args
                )
                call = self.fault(exe) if self.fault else exe
                jax.block_until_ready(call(*args))  # first execution
                self.args[label, w] = args
                table[label][w] = _keeping(call, args, self.outputs, (label, w))
        self.table = table
        self.stage_us = programs.stage_us

    def window(self, seconds: float, trace_dir: str | None = None) -> dict:
        from repro.serve.batcher import serve_dynamic
        from repro.serve.loadgen import Request, Schedule

        cfg, tr = self.config, self.traffic
        lead = tr["lead_in_s"]
        t, b, first = schedule(
            qps=tr["qps"], lead_in_s=lead, seconds=seconds,
            weights={x["label"]: x["weight"] for x in cfg["buckets"]}, seed=self.seed,
        )
        requests = tuple(
            Request(index=i, arrival_s=float(t[i]), bucket=b[i]) for i in range(len(t))
        )
        self.outputs.clear()
        tracer = None
        if trace_dir is not None:
            start = time.perf_counter() + lead + max(0.0, seconds - tr["trace_seconds"])
            tracer = _SliceTracer(trace_dir, start, tr["trace_seconds"])
            tracer.start()
        try:
            report = serve_dynamic(
                self.table, Schedule(requests, offered_qps=tr["qps"]),
                budget_s=cfg["batch_budget_us"] / 1e6,
                concurrency=cfg["concurrency"],
            )
        finally:
            if tracer is not None:
                tracer.join()
        self.dispatched = sorted(self.outputs)
        c = report.completions[0]
        t_base = c.t_submit - t[c.index]
        w0, w1 = t_base + lead, t_base + lead + seconds
        dispatched = {(x.bucket, x.t_done): x.t_dispatch for x in report.batches}
        done = {c.index: c for c in report.completions}
        rows = []
        for i in range(first, len(t)):
            c = done.get(i)
            due = t_base + t[i]
            if c is None:
                rows.append((due, None, None))
            else:
                rows.append((due, c.t_done, dispatched[c.bucket, c.t_done]))
        return {
            "kind": "open",
            "t0": w0,
            "t1": w1,
            "requests": rows,
            "completed_at": [c.t_done for c in report.completions],
            "batches": [(x.t_dispatch, x.width, x.filled) for x in report.batches],
            "host_until": tracer.t_start if tracer is not None else w1,
            "attempted": len(rows),
            "failed": sum(r[1] is None for r in rows),
        }

    def check(self, control: bool = False) -> dict:
        """Compare every member of the last answer of each (bucket, width)
        program that the window dispatched with the reference for its grid;
        ``control`` compares the lower-precision control's answers."""
        from perfbench.harness import worst

        readings = []
        hosts: dict = {}
        for label, w in self.dispatched:
            out = None if control else np.asarray(self.outputs.pop((label, w)))
            for j in range(w):
                grid = hosts.get((label, j))
                if grid is None:
                    grid = hosts[label, j] = np.asarray(self.grids[label][j])
                got = self.ref.control(grid) if control else (out if w == 1 else out[j])
                readings.append(self.ref.compare(got, grid))
        checks, failed = worst(self.ref.LIMITS, readings)
        self.failed += failed
        return checks


def _views(grids, widths):
    """Each width's argument: grid 0 alone for width 1, else the first w
    grids stacked (one jitted call)."""
    return jax.block_until_ready(_views_jit(grids, widths=tuple(widths)))


@functools.partial(jax.jit, static_argnames=("widths",))
def _views_jit(grids, widths):
    return tuple(grids[0] if w == 1 else grids[:w] for w in widths)


def _keeping(call, args, store: dict, key):
    """The batcher's zero-argument call, keeping the newest answer."""

    def run():
        out = call(*args)
        store[key] = out
        return out

    return run


class _SliceTracer(threading.Thread):
    """Profiles ``seconds`` of the serving loop from host time ``at``."""

    def __init__(self, trace_dir: str, at: float, seconds: float) -> None:
        super().__init__(name="perfbench-trace", daemon=True)
        self.trace_dir, self.at, self.seconds = trace_dir, at, seconds
        self.t_start = at

    def run(self) -> None:
        from perfbench.trace import start, stop

        time.sleep(max(0.0, self.at - time.perf_counter()))
        self.t_start = time.perf_counter()
        span = start(self.trace_dir)
        time.sleep(self.seconds)
        stop(span)
