"""Device time by the program's named scopes, in a traced run.

The program wraps its sub-layers in ``jax.named_scope`` (``mla``; ``moe``
with ``route``, ``experts``, ``shared``, ``combine`` inside it). XLA keeps
each operation's scope path in its metadata (``jit(f)/.../moe/route/...``),
and the TPU profiler writes it as the ``tf_op`` stat of the operation's
event metadata on the ``/device:TPU:<n>`` planes. ``jax.profiler``'s
``ProfileData`` does not show metadata stats, so ``op_paths`` reads them
from the ``.xplane.pb`` file's protobuf wire format itself.

The readers here take the executions of the busiest program that start
in the traced window (the ``perfbench.window`` span of the newest
``.xplane.pb`` under ``perfbench/out/trace/<cell>``), as ``prefill_mfu``
does, and for each scope name the union of the intervals in which an
operation whose path holds it ran inside those executions (a loop and
the operations of its body overlap), over the number of executions.

A program without such scopes gives no time: a reader then returns
``None`` rather than a number, and does not raise.
"""

from __future__ import annotations

import functools
import glob
import os
from collections import defaultdict

from perfbench.trace import WINDOW_SPAN, _union

SCOPE_STAT = "tf_op"


def scope_ns(calls, ops) -> dict[str, float]:
    """Nanoseconds inside the ``calls`` (``(device, start_ns, end_ns)``)
    during which an operation under each scope ran. ``ops`` are ``(device,
    start_ns, duration_ns, scope path)``; intervals are joined per device,
    so nested operations count once."""
    spans: dict[tuple, list] = defaultdict(list)
    for device, start, dur, path in ops:
        for call_device, c0, c1 in calls:
            a, b = max(start, c0), min(start + dur, c1)
            if call_device == device and b > a and path:
                for name in set(path.split("/")):
                    spans[name, device].append((a, b))
    out: dict[str, float] = defaultdict(float)
    for (name, _), intervals in spans.items():
        out[name] += sum(b - a for a, b in _union(intervals))
    return dict(out)


def per_call_us(run, reader_file: str, scope: str) -> float | None:
    """Device microseconds per call under ``scope`` in the traced window of
    ``run``; ``reader_file`` is the calling reader's ``__file__``, which
    places the checkout's ``perfbench/out``."""
    if run.trace is None:
        return None
    pb = os.path.dirname(os.path.dirname(os.path.abspath(reader_file)))
    files = glob.glob(os.path.join(
        pb, "out", "trace", run.cell.name, "plugins", "profile", "*", "*.xplane.pb"
    ))
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    count, ns = _load(path, os.path.getmtime(path))
    return ns[scope] / 1e3 / count if ns.get(scope) else None


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime: float) -> tuple[int, dict[str, float]]:
    """(executions of the busiest program that start in the window, the
    nanoseconds under each scope inside them)."""
    import jax

    with open(path, "rb") as fh:
        paths = op_paths(fh.read())
    data = jax.profiler.ProfileData.from_file(path)
    window = None
    ops = []
    modules: dict[str, list] = defaultdict(list)
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
        elif plane.name in paths:
            names, device = paths[plane.name], plane.name
            for line in plane.lines:
                for e in line.events:
                    if line.name == "XLA Ops":
                        ops.append((device, e.start_ns, e.duration_ns, names.get(e.name, "")))
                    elif line.name == "XLA Modules":
                        modules[e.name].append((device, e.start_ns, e.start_ns + e.duration_ns))
    if window is None:
        return 0, {}
    started = [
        [c for c in calls if window[0] <= c[1] < window[1]] for calls in modules.values()
    ]
    busiest = max(started, key=lambda calls: sum(c1 - c0 for _, c0, c1 in calls), default=[])
    return len(busiest), scope_ns(busiest, ops) if busiest else {}


# ---- the XSpace protobuf, read field by field -------------------------------
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map<int64,
# XEventMetadata>), stat_metadata = 5 (map<int64, XStatMetadata>);
# XEventMetadata: name = 2, stats = 5; XStatMetadata: name = 2; XStat:
# metadata_id = 1, str_value = 5, bytes_value = 6, ref_value = 7.


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes):
    """(field number, value) of each field of one message; a varint's value
    is an int, a length-delimited one's its bytes."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind} in an XSpace")
        yield key >> 3, value


def _map_value(entry: bytes):
    key = value = None
    for num, v in _fields(entry):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def op_paths(xspace: bytes) -> dict[str, dict[str, str]]:
    """{device plane name: {operation name: its ``tf_op`` scope path}}."""
    out = {}
    for num, plane in _fields(xspace):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((v.decode() for n, v in fields if n == 2), "")
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for n, v in fields:
            if n == 5:
                key, meta = _map_value(v)
                stat_names[key] = next((s.decode() for k, s in _fields(meta) if k == 2), "")
        scope_id = next((k for k, s in stat_names.items() if s == SCOPE_STAT), None)
        names = {}
        for n, v in fields:
            if n != 4 or scope_id is None:
                continue
            _, meta = _map_value(v)
            op, path = "", ""
            for k, s in _fields(meta):
                if k == 2:
                    op = s.decode()
                elif k == 5:
                    stat = dict(_fields(s))
                    if stat.get(1) == scope_id:
                        value = stat.get(5) or stat.get(6)
                        path = value.decode() if value else stat_names.get(stat.get(7), "")
            if path:
                names[op] = path.rstrip(":")
        out[name] = names
    return out
