"""Reduce a JAX profiler trace to the numbers the benchmark reports.

The traced window is the host span named ``perfbench.window`` (a
``jax.profiler.TraceAnnotation`` the drivers open). Inside it:

- busy time is the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` lines of the ``/device:TPU:<n>`` planes), averaged
  over the devices used, those with an operation in the window (a cell on
  a four-chip host for steadiness runs on one of them);
- each device operation's time, summed by name;
- each program's executions and device time (the ``XLA Modules`` lines);
- the idle gaps between device operations, longest first, each named by
  what the host was doing in it: the host event that overlaps the gap most
  (the shortest on a tie), leaving out events longer than half the window,
  which enclose everything (the serving loop, the tracer's own thread).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

# (name, start_ns, duration_ns); the profiler puts host and device events
# on one clock.
Event = tuple[str, float, float]

WINDOW_SPAN = "perfbench.window"
IDLE_GAP_MIN_NS = 10_000  # shorter gaps are dispatch jitter, not a cause
_LAYOUT = re.compile(r"\{[^{}]*\}")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    devices: int  # devices with an operation in the window
    busy_s: float
    ops: list  # [(name, seconds)], most time first
    modules: dict  # name -> (executions that started in the window, device seconds)
    gaps: list  # [(host activity, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        return {
            "device_ops": [[n, s] for n, s in self.ops[:top]],
            "idle_gaps": [[n, s] for n, s in self.gaps[:top]],
        }


def op_name(name: str) -> str:
    """An HLO op's trace name without layouts, cut to 160 characters."""
    return _LAYOUT.sub("", name)[:160]


def _clip(events, w0: float, w1: float):
    for name, start, dur in events:
        a, b = max(start, w0), min(start + dur, w1)
        if b > a:
            yield name, a, b


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _host_activity(gap_a: float, gap_b: float, host: list[Event], longest: float) -> str:
    best = None
    for name, start, dur in host:
        if dur > longest or start >= gap_b or start + dur <= gap_a:
            continue
        overlap = min(start + dur, gap_b) - max(start, gap_a)
        score = (overlap, -dur)
        if best is None or score > best[0]:
            best = (score, name)
    return best[1] if best else "no traced host activity"


def summarize(
    window: tuple[float, float],
    device_ops: list[list[Event]],
    device_modules: list[list[Event]],
    host: list[Event],
) -> TraceSummary:
    """The reduction proper, on plain events: one list of op events and one
    of module events per device, and the host's events."""
    w0, w1 = window
    window_ns = w1 - w0
    busy_ns = 0.0
    op_time: dict[str, float] = defaultdict(float)
    gaps: list[tuple[float, float]] = []
    used = 0
    for ops in device_ops:
        clipped = list(_clip(ops, w0, w1))
        if not clipped:
            continue
        used += 1
        for name, a, b in clipped:
            op_time[op_name(name)] += b - a
        merged = _union((a, b) for _, a, b in clipped)
        busy_ns += sum(b - a for a, b in merged)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [
            (edges[i], edges[i + 1])
            for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= IDLE_GAP_MIN_NS
        ]
    modules: dict[str, list[float]] = defaultdict(lambda: [0, 0.0])
    for mods in device_modules:
        for name, start, dur in mods:
            if w0 <= start < w1:
                modules[name][0] += 1
                modules[name][1] += dur / 1e9
    n_dev = max(1, used)
    gaps.sort(key=lambda ab: ab[0] - ab[1])
    named = [
        (_host_activity(a, b, host, window_ns / 2), (b - a) / 1e9) for a, b in gaps
    ]
    return TraceSummary(
        window_s=window_ns / 1e9,
        devices=used,
        busy_s=busy_ns / n_dev / 1e9,
        ops=sorted(((n, t / 1e9) for n, t in op_time.items()), key=lambda x: -x[1]),
        modules={k: (int(v[0]), v[1]) for k, v in modules.items()},
        gaps=named,
    )


def load(path: str, window_span: str = WINDOW_SPAN) -> TraceSummary:
    """Read one ``.xplane.pb`` file and reduce it."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device_ops: list[list[Event]] = []
    device_modules: list[list[Event]] = []
    host: list[Event] = []
    window = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                if line.name == "XLA Ops":
                    device_ops.append(events)
                elif line.name == "XLA Modules":
                    device_modules.append(events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == window_span:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    else:
                        host.append((e.name, e.start_ns, e.duration_ns))
    if window is None:
        raise ValueError(f"trace {path} has no {window_span!r} span")
    return summarize(window, device_ops, device_modules, host)


def reduce_dir(trace_dir: str) -> TraceSummary:
    """Reduce the newest trace that ``jax.profiler`` wrote under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load(max(files, key=os.path.getmtime))


def start(trace_dir: str):
    """Start the profiler and open the window span; ``stop`` ends both."""
    import jax

    jax.profiler.start_trace(trace_dir)
    span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
    span.__enter__()
    return span


def stop(span) -> None:
    import jax

    span.__exit__(None, None, None)
    jax.profiler.stop_trace()
