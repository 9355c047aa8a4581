"""The program's own spans in a traced run, on the profiler's clock.

While ``jax.profiler`` records, the program's ``repro.obs`` spans are
profiler events on the host plane, with their attributes as stats. The
readers here take those that start inside the ``perfbench.window`` span of
the newest ``.xplane.pb`` under ``perfbench/out/trace/<cell>``, the trace
the harness reduced for the same run.

A program without such spans gives an empty list: a reader then returns
``None`` rather than a number, and does not raise.
"""

from __future__ import annotations

import functools
import glob
import os

from perfbench.trace import WINDOW_SPAN

DISPATCH = "batcher.dispatch"
LATE_WAKE = "batcher.late_wake"
GC = "gc.collect"
NAMES = (DISPATCH, LATE_WAKE, GC)


def of(run, reader_file: str, name: str) -> list[tuple[float, dict]]:
    """``(duration_ns, stats)`` of each span called ``name`` that starts in
    the traced window of ``run``; ``reader_file`` is the calling reader's
    ``__file__``, which places the checkout's ``perfbench/out``. Empty for
    a run without a trace."""
    if run.trace is None:
        return []
    pb = os.path.dirname(os.path.dirname(os.path.abspath(reader_file)))
    files = glob.glob(os.path.join(
        pb, "out", "trace", run.cell.name, "plugins", "profile", "*", "*.xplane.pb"
    ))
    if not files:
        return []
    path = max(files, key=os.path.getmtime)
    return _load(path, os.path.getmtime(path)).get(name, [])


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime: float) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    window = None
    found: list[tuple[str, float, float, dict]] = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name in NAMES:
                    found.append((e.name, e.start_ns, e.duration_ns, dict(e.stats)))
    out: dict[str, list] = {}
    if window is None:
        return out
    for name, start, dur, stats in found:
        if window[0] <= start < window[1]:
            out.setdefault(name, []).append((dur, stats))
    return out


def per_member(run, reader_file: str, stat: str) -> float | None:
    """Mean over the requests that the window's batches dispatched of one
    share of their wait (``late_us``, ``fill_us`` or ``blocked_us`` of the
    ``batcher.dispatch`` spans, each a sum over a batch's members), in
    microseconds."""
    batches = [s for _, s in of(run, reader_file, DISPATCH)]
    filled = sum(s["filled"] for s in batches)
    return sum(s[stat] for s in batches) / filled if filled else None
