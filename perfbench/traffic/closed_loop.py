"""Closed loop over one compiled program: ``in_flight`` calls stay enqueued;
the loop blocks on the oldest and enqueues the next, so the device never
drains between calls. Every call gets the same inputs, made from the seed.

Mix parameters (``traffic/<name>.json``): ``in_flight``, ``impl`` (the
implementation the program is compiled with), ``trace_seconds`` (the traced
slice at the end of the window in a ``--trace 1`` run).
"""

from __future__ import annotations

import time
from collections import deque

import jax


class Session:
    def __init__(self, config, traffic, ref, key, *, seed, fault=None):
        self.config, self.traffic, self.ref, self.key = config, traffic, ref, key
        self.fault = fault
        self.failed = 0
        self.stage_us: dict[str, float] = {}

    def setup(self) -> None:
        from perfbench.system import Programs

        cfg = self.config
        self.args = self.ref.make_inputs(self.key, cfg)
        programs = Programs()
        exe = programs.compile(
            cfg["registry"], cfg["preset"], cfg["overrides"],
            self.traffic["impl"], 1, self.args,
        )
        self.stage_us = programs.stage_us
        self.call = self.fault(exe) if self.fault else exe
        # Warm: first execution and a full pipeline of the window's depth.
        jax.block_until_ready([self.call(*self.args) for _ in range(self.traffic["in_flight"])])

    def window(self, seconds: float, trace_dir: str | None = None) -> dict:
        from perfbench import trace

        depth = self.traffic["in_flight"]
        call, args = self.call, self.args
        trace_at = None
        if trace_dir is not None:
            trace_at = max(0.0, seconds - self.traffic["trace_seconds"])
        tracing = None
        pending: deque = deque()
        dispatch_s: list[float] = []
        outputs = []
        done = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        now = t0
        while True:
            while len(pending) < depth and now < deadline:
                a = time.perf_counter()
                pending.append(call(*args))
                b = time.perf_counter()
                if tracing is None:
                    dispatch_s.append(b - a)
                now = b
            if not pending:
                break
            out = pending.popleft()
            jax.block_until_ready(out)
            done += 1
            if done == 1:
                outputs.append(out)
            last = out
            now = time.perf_counter()
            t1 = now
            if trace_at is not None and tracing is None and now - t0 >= trace_at:
                tracing = trace.start(trace_dir)
        if tracing is not None:
            trace.stop(tracing)
        if done > 1:
            outputs.append(last)
        self.outputs = outputs
        return {
            "kind": "closed",
            "t0": t0,
            "t1": t1,
            "calls": done,
            "dispatch_s": dispatch_s,
            "attempted": done,
            "failed": 0,
        }

    def check(self, control: bool = False) -> dict:
        """Compare the window's first and last answers with the reference;
        ``control`` compares the lower-precision control's answer instead."""
        from perfbench.harness import worst

        outs = [self.ref.control(*self.args)] if control else self.outputs
        checks, failed = worst(self.ref.LIMITS, [self.ref.compare(o, self.args) for o in outs])
        self.failed += failed
        return checks
