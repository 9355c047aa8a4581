"""Spawn, synchronize, and supervise N load-generation client processes;
merge their completion streams into one set of serving statistics.

The launcher runs inside the engine process (the serve stage routes here
when ``ServeSpec.client_procs > 0``). It listens on a loopback TCP port,
spawns ``python -m repro.dist.client_proc`` once per process (inheriting
the environment, ``XLA_FLAGS`` included, so a forced-host-device CI
topology applies to every client), assigns each its workload + seed +
process index, waits for every client to finish compiling (``Ready``),
broadcasts one shared wall-clock start epoch, then collects the
epoch-relative completion stamps each client streams back.

Merged accounting: stamps from process p, local lane l are relabeled to
global lane ``p * lanes + l``, so the merged stream's percentiles are
computed exactly as a single client's would be (``stats_from_completions``
over the concatenation — the identity ``tests/test_dist.py`` pins), while
``proc_qps`` groups the same stamps by process to show whether every
client pulled its weight. Per-client ``HloDiskCache`` counters arrive in
each ``Done`` and are summed into ``client_cache_counters`` — the number
the ``--dist`` smoke leg asserts is zero-compile on a warm run — and
printed per process on stderr next to the engine's own ``# hlocache:``
line.
"""

from __future__ import annotations

import dataclasses
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

from repro.dist.proto import (
    Assign,
    ConnectionClosed,
    Done,
    Error,
    Hello,
    Ready,
    Stamp,
    Start,
    recv_msg,
    send_msg,
)
from repro.serve.lanes import Completion
from repro.serve.latency import (
    LatencyStats,
    lane_qps_from_completions,
    stats_from_completions,
)

__all__ = ["DistLatencyStats", "refuse_children_on_device", "run_distributed"]

# How long one client may spend building + compiling before the run is
# declared wedged. Generous: a cold multi-device compile on a loaded CI
# host is tens of seconds, not hundreds.
_READY_TIMEOUT_S = 600.0
# Seconds between the Start broadcast and the shared epoch: long enough
# for every client to receive the frame and wake its sleep loop.
_START_LEAD_S = 0.3


def refuse_children_on_device() -> None:
    """Raise ``PlanError`` unless this process runs on the CPU backend.

    Every client process builds its own ``Engine`` and compiles on the
    device. An accelerator belongs to one process at a time, and this
    process already holds it, so on anything but the CPU the children
    would fail or hang."""
    import jax

    from repro.core.plan import PlanError

    backend = jax.default_backend()
    if backend != "cpu":
        raise PlanError(
            f"client processes are CPU-only: each child would need the "
            f"{backend} device this process holds"
        )


@dataclasses.dataclass(frozen=True)
class DistLatencyStats(LatencyStats):
    """Merged serving statistics of a distributed run: a plain
    :class:`LatencyStats` over the concatenated completion stream, plus
    the per-process accounting the distributed columns report."""

    client_procs: int = 0
    proc_qps: tuple[float, ...] | None = None  # achieved QPS per process
    # Summed HloDiskCache counters across the client processes (None when
    # the run had no cache dir): misses == 0 here is the
    # "warm distributed run compiled nothing anywhere" assertion.
    client_cache_counters: dict | None = None

    def derived(self) -> str:
        parts = [super().derived(), f"client_procs={self.client_procs}"]
        if self.proc_qps is not None:
            qps = ",".join(f"{q:.1f}" for q in self.proc_qps)
            parts.append(f"proc_qps={qps}")
        return ";".join(parts)


class _StreamCollector:
    """Lock-guarded accumulator the per-client reader threads feed.

    One reader thread per client socket appends stamp rows and records
    the terminal Done/Error; the launcher thread reads everything back
    after joining the readers. All shared-container mutation happens
    under ``self._lock`` (the ``concurrency-locks`` contract).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._rows: dict[int, list] = {}
        self._done: dict[int, Done] = {}
        self._errors: list[str] = []

    def add_rows(self, proc_id: int, rows: list) -> None:
        with self._lock:
            self._rows.setdefault(proc_id, []).extend(rows)

    def mark_done(self, done: Done) -> None:
        with self._lock:
            self._done[done.proc_id] = done

    def add_error(self, message: str) -> None:
        with self._lock:
            self._errors.append(message)

    def snapshot(self) -> tuple[dict[int, list], dict[int, Done], list[str]]:
        with self._lock:
            return (
                {p: list(rows) for p, rows in self._rows.items()},
                dict(self._done),
                list(self._errors),
            )


def _read_client(sock: socket.socket, proc_id: int, out: _StreamCollector) -> None:
    """Reader-thread body: drain one client until Done/Error/EOF."""
    try:
        while True:
            msg = recv_msg(sock)
            if isinstance(msg, Stamp):
                out.add_rows(msg.proc_id, msg.completions)
            elif isinstance(msg, Done):
                out.mark_done(msg)
                return
            elif isinstance(msg, Error):
                out.add_error(f"proc {msg.proc_id}: {msg.message}")
                return
            else:
                out.add_error(
                    f"proc {proc_id}: unexpected {type(msg).__name__} frame"
                )
                return
    except (ConnectionClosed, OSError, ValueError) as e:
        out.add_error(f"proc {proc_id}: stream died: {e}")


def _src_pythonpath() -> str:
    """PYTHONPATH entry that makes ``import repro`` work in a child."""
    import repro

    pkg_dir = os.path.dirname(os.path.abspath(repro.__file__))
    src_dir = os.path.dirname(pkg_dir)
    existing = os.environ.get("PYTHONPATH")
    return src_dir if not existing else f"{src_dir}{os.pathsep}{existing}"


def _stderr_tail(path: str, limit: int = 2000) -> str:
    try:
        with open(path, encoding="utf-8", errors="replace") as f:
            text = f.read()
        return text[-limit:]
    except OSError:
        return "<stderr unavailable>"


def _sum_counters(dones: dict[int, Done]) -> dict | None:
    total: dict[str, int] = {}
    seen = False
    for done in dones.values():
        if done.cache_counters is None:
            continue
        seen = True
        for k, v in done.cache_counters.items():
            total[k] = total.get(k, 0) + int(v)
    return total if seen else None


def run_distributed(
    *,
    benchmark: str,
    preset: int,
    overrides: dict,
    serve,
    seed: int,
    devices: int,
    placement_mode: str,
    impl: str = "xla",
    cache_dir: str | None = None,
) -> DistLatencyStats:
    """One distributed open-loop serving run of ``benchmark``.

    Blocks until every client process finishes (or fails); raises
    ``RuntimeError`` naming the first failure — the engine's per-benchmark
    fault isolation turns that into an error record like any other stage
    failure.
    """
    n = int(serve.client_procs)
    if n < 1:
        raise ValueError(f"run_distributed needs client_procs >= 1, got {n}")
    refuse_children_on_device()
    serve_fields = {
        f.name: getattr(serve, f.name) for f in dataclasses.fields(type(serve))
    }
    serve_fields["client_procs"] = 0
    # Merged warmup prefix: every process fills its own pipeline, so the
    # single-process fill count scales by the process count.
    warmup = max(serve.concurrency, serve.lanes, 2) * n

    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    procs: list[subprocess.Popen] = []
    conns: dict[int, socket.socket] = {}
    stderr_paths: list[str] = []
    collector = _StreamCollector()
    try:
        listener.bind(("127.0.0.1", 0))
        listener.listen(n)
        listener.settimeout(_READY_TIMEOUT_S)
        port = listener.getsockname()[1]

        env = dict(os.environ)
        env["PYTHONPATH"] = _src_pythonpath()
        for proc_id in range(n):
            errfile = tempfile.NamedTemporaryFile(
                mode="w", suffix=f".dist{proc_id}.err", delete=False
            )
            stderr_paths.append(errfile.name)
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable,
                        "-m",
                        "repro.dist.client_proc",
                        "--port",
                        str(port),
                        "--proc-id",
                        str(proc_id),
                    ],
                    env=env,
                    stdout=errfile,
                    stderr=errfile,
                )
            )
            errfile.close()

        for _ in range(n):
            conn, _addr = listener.accept()
            conn.settimeout(_READY_TIMEOUT_S)
            hello = recv_msg(conn)
            if not isinstance(hello, Hello):
                raise RuntimeError(
                    f"expected Hello, got {type(hello).__name__}"
                )
            if hello.proc_id in conns:
                raise RuntimeError(f"duplicate proc_id {hello.proc_id}")
            conns[hello.proc_id] = conn
        for proc_id, conn in conns.items():
            send_msg(
                conn,
                Assign(
                    benchmark=benchmark,
                    preset=preset,
                    overrides=dict(overrides),
                    serve=serve_fields,
                    seed=seed,
                    proc_id=proc_id,
                    n_procs=n,
                    warmup=warmup,
                    devices=devices,
                    placement=placement_mode,
                    impl=impl,
                    cache_dir=cache_dir,
                ),
            )

        # Barrier: every client has compiled before any load starts. A
        # client that dies compiling sends Error (or just closes); either
        # way the recv raises or returns the wrong type and we abort with
        # its stderr tail.
        for proc_id, conn in conns.items():
            msg = recv_msg(conn)
            if isinstance(msg, Error):
                raise RuntimeError(
                    f"client {proc_id} failed before Ready: {msg.message}\n"
                    f"--- client stderr ---\n{_stderr_tail(stderr_paths[proc_id])}"
                )
            if not isinstance(msg, Ready):
                raise RuntimeError(
                    f"client {proc_id}: expected Ready, got {type(msg).__name__}"
                )

        epoch = time.time() + _START_LEAD_S
        for conn in conns.values():
            send_msg(conn, Start(epoch=epoch))

        readers = [
            threading.Thread(
                target=_read_client,
                args=(conn, proc_id, collector),
                name=f"dist-reader-{proc_id}",
                daemon=True,
            )
            for proc_id, conn in conns.items()
        ]
        for t in readers:
            t.start()
        deadline = serve.duration_s + _READY_TIMEOUT_S
        for t in readers:
            t.join(timeout=deadline)
            if t.is_alive():
                raise RuntimeError(
                    f"distributed run wedged: {t.name} still reading after "
                    f"{deadline:.0f}s"
                )
        for proc_id, p in enumerate(procs):
            try:
                code = p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                code = p.wait()
            if code != 0:
                collector.add_error(
                    f"proc {proc_id}: exit code {code}\n"
                    f"--- client stderr ---\n{_stderr_tail(stderr_paths[proc_id])}"
                )
    finally:
        for conn in conns.values():
            try:
                conn.close()
            except OSError:
                pass
        listener.close()
        for p in procs:
            if p.poll() is None:
                p.kill()
        for path in stderr_paths:
            try:
                os.remove(path)
            except OSError:
                pass

    rows_by_proc, dones, errors = collector.snapshot()
    if errors:
        raise RuntimeError("; ".join(errors))
    missing = sorted(set(range(n)) - set(dones))
    if missing:
        raise RuntimeError(f"clients never reported Done: {missing}")

    # Relabel (proc, local lane) -> global lane so the merged stream is
    # statistically identical to one client running n*lanes lanes.
    merged = [
        Completion(
            index=int(index),
            lane=proc_id * serve.lanes + int(lane),
            t_submit=float(t_submit),
            t_done=float(t_done),
            warmup=bool(warm),
        )
        for proc_id, rows in sorted(rows_by_proc.items())
        for index, lane, t_submit, t_done, warm in rows
    ]
    merged.sort(key=lambda c: c.t_done)
    base = stats_from_completions(
        merged,
        offered_qps=serve.qps,
        slo_us=serve.slo_us,
        truncated=any(d.truncated for d in dones.values()),
        n_lanes=n * serve.lanes,
    )
    by_proc = [
        dataclasses.replace(c, lane=c.lane // serve.lanes) for c in merged
    ]
    proc_qps = lane_qps_from_completions(by_proc, n_lanes=n)
    client_counters = _sum_counters(dones)
    if client_counters is not None:
        # Like the engine's "# hlocache:" line: always say what the
        # clients' caches did, so "the warm distributed run compiled
        # nothing anywhere" is assertable from stderr alone.
        line = " ".join(f"{k}={v}" for k, v in sorted(client_counters.items()))
        print(f"# dist-cache[{n} procs]: {line}", file=sys.stderr)
    return DistLatencyStats(
        **{f.name: getattr(base, f.name) for f in dataclasses.fields(LatencyStats)},
        client_procs=n,
        proc_qps=proc_qps,
        client_cache_counters=client_counters,
    )
