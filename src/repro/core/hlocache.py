"""Cross-process persistence of compiled executables.

The in-process :class:`~repro.core.engine.CompileCache` dies with the
process, so every CI suite run re-pays tracing *and* XLA compilation for
every workload. This cache persists, per compile-cache key, the
AOT-serialized ``jax.stages.Compiled`` (``<key>.exe``, through the public
``jax.experimental.serialize_executable`` API) plus a JSON payload
(``<key>.json``) holding the static characterization (cost / memory /
collective bytes) that rebuilds :class:`~repro.core.harness.CompiledInfo`
without touching an executable, and the ids of the devices the program
was compiled for. A warm load deserializes the executable straight into
a runnable — *zero* retracing and *zero* XLA compilation — onto those
same devices, so single-device and sharded (multi-device) programs share
one path: shardings, argument pruning and the pytree call convention all
round-trip.

A third sidecar (``<key>.tune.json``, :meth:`store_tuned` /
:meth:`load_tuned`) persists the engine's autotune winner — the Pallas
block config ``_stage_tune`` selected — next to the executable it was
selected for. It is keyed on the *base* compile-cache key (the one without
tuned params folded in), so a warm ``--tune`` run restores the winner
first, then loads the winner's executable: zero tune trials, zero
compiles. The same versioned directory scopes it: an edited kernel or a
new toolchain invalidates winners along with executables.

Entries are versioned by ``jax.__version__``, ``jaxlib.__version__``, the
backend, an explicit topology token (device kind × device count ×
process count — a serialized executable is compiled *for* a topology),
and a content hash of the ``repro`` package source (a new toolchain *or
an edited kernel* gets a fresh directory rather than stale artifacts),
keyed by a hash of the engine's compile-cache key.

Every warm load is validated by one trial execution; *any* failure —
corrupt file, toolchain drift, a device the entry names that this host
lacks — falls back to the normal trace-and-compile path. The cache can
only ever make a run faster, never wronger. Fallbacks are *counted and
explained* rather than swallowed: ``fallback_count`` /
``fallback_reasons`` / ``last_fallback`` record present-but-unusable
entries, so "the warm run restored everything" is an assertable counter:
``hits == lookups`` with ``misses == fallback_count == 0``. ``summary()``
is the one-line diagnosis the engine prints in verbose runs.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import re
from typing import Any, Callable

import jax

from repro.core.harness import CompiledInfo, _memory_analysis_dict
from repro.core.metrics import (
    collective_bytes_from_hlo,
    cost_analysis_dict,
    roofline_terms,
)

__all__ = ["HloDiskCache"]

# v4: one tier — jax.experimental.serialize_executable for every entry,
#     payload records the executable's device ids
_FORMAT_VERSION = 4
_MAX_REASONS = 20  # keep the fallback reason list bounded


def _source_digest() -> str:
    """Content hash of every .py file in the repro package: the compile-
    cache key says *which* workload, this says *which code* — an edited
    kernel must miss, not silently replay its old lowering."""
    import repro

    pkg_root = os.path.dirname(os.path.abspath(repro.__file__))
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in sorted(os.walk(pkg_root)):
        dirnames.sort()
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            h.update(os.path.relpath(path, pkg_root).encode())
            with open(path, "rb") as f:
                h.update(f.read())
    return h.hexdigest()[:12]


def _topology_token() -> str:
    """Device kind × device count × process count: a serialized
    executable is compiled for a topology, so a different accelerator, a
    different forced host-device count, or a different ``jax.distributed``
    process count must get its own cache directory, not a
    deserialization failure."""
    devices = jax.devices()
    kind = re.sub(r"[^A-Za-z0-9_.-]+", "_", devices[0].device_kind) or "unknown"
    return f"{kind}x{len(devices)}p{jax.process_count()}"


def _jaxlib_version() -> str:
    try:
        import jaxlib

        return getattr(jaxlib, "__version__", "unknown")
    except Exception:  # noqa: BLE001 — version tag is best-effort
        return "unknown"


class HloDiskCache:
    """Persistent executable cache keyed per compile-cache key."""

    def __init__(self, root: str) -> None:
        backend = jax.default_backend()
        self.root = os.path.join(
            root,
            f"jax-{jax.__version__}-jaxlib-{_jaxlib_version()}-{backend}-"
            f"{_topology_token()}-{_source_digest()}",
        )
        os.makedirs(self.root, exist_ok=True)
        self.hits = 0  # warm loads that produced a working executable
        self.misses = 0  # lookups that fell back to tracing
        self.stores = 0  # executables (+ characterization) written
        # Fallback diagnostics: a *fallback* is a present-but-unusable
        # entry (corrupt payload, stale format, failed trial call) — a
        # missing file is just a cold miss and is not recorded here.
        self.fallback_count = 0
        self.fallback_reasons: list[str] = []  # capped at _MAX_REASONS
        self.last_fallback: str | None = None
        # Autotune-winner sidecar traffic (store_tuned / load_tuned).
        self.tune_hits = 0  # winners restored (warm run: zero trials)
        self.tune_stores = 0  # winners persisted

    def _path(self, key: tuple) -> str:
        digest = hashlib.sha256(repr(key).encode()).hexdigest()[:24]
        return os.path.join(self.root, f"{digest}.json")

    def _exe_path(self, key: tuple) -> str:
        return self._path(key)[: -len(".json")] + ".exe"

    def _tune_path(self, key: tuple) -> str:
        return self._path(key)[: -len(".json")] + ".tune.json"

    def _note_fallback(self, key: tuple, exc: BaseException) -> None:
        name = key[0] if key else "?"
        reason = " ".join(f"{name}: {type(exc).__name__}: {exc}".split())
        reason = reason if len(reason) <= 200 else reason[:197] + "..."
        self.fallback_count += 1
        self.last_fallback = reason
        if len(self.fallback_reasons) < _MAX_REASONS:
            self.fallback_reasons.append(reason)

    def counter_dict(self) -> dict[str, int]:
        """The numeric counter totals as a plain dict — what the engine
        stamps into ``RunMetadata.cache_stats`` (schema v8) so a committed
        JSONL report says whether the run was warm without verbose stdout.
        Numbers only; the reason strings stay on the object / summary()."""
        return {
            "hits": self.hits,
            "misses": self.misses,
            "stores": self.stores,
            "fallback_count": self.fallback_count,
            "tune_hits": self.tune_hits,
            "tune_stores": self.tune_stores,
        }

    def summary(self) -> str:
        """One-line cache diagnosis for verbose engine output."""
        line = (
            f"hlocache: hits={self.hits} misses={self.misses} "
            f"stores={self.stores} fallbacks={self.fallback_count} "
            f"tune_hits={self.tune_hits} tune_stores={self.tune_stores}"
        )
        if self.last_fallback is not None:
            line += f" last_fallback=[{self.last_fallback}]"
        return line

    # -- autotune winners ----------------------------------------------------

    def store_tuned(
        self, key: tuple, params: dict, trials: int, trials_us: float
    ) -> None:
        """Persist the autotune stage's winning block config for ``key``
        (the *base* compile-cache key, without the params folded in), plus
        what the sweep cost — provenance for warm-run records."""
        try:
            payload = {
                "format": _FORMAT_VERSION,
                "params": dict(params),
                "trials": int(trials),
                "trials_us": float(trials_us),
            }
            path = self._tune_path(key)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)
            self.tune_stores += 1
        except Exception:  # noqa: BLE001 — persistence is advisory
            return

    def load_tuned(self, key: tuple) -> dict | None:
        """Restore a persisted autotune winner, or None (cold / unusable).
        A hit means the warm run skips the sweep entirely: zero trials."""
        path = self._tune_path(key)
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                payload = json.load(f)
            if payload.get("format") != _FORMAT_VERSION:
                raise ValueError("stale tune cache format")
            params = {str(k): v for k, v in dict(payload["params"]).items()}
        except Exception as e:  # noqa: BLE001 — unusable winner = re-sweep
            self._note_fallback(key, e)
            return None
        self.tune_hits += 1
        return params

    # -- store -------------------------------------------------------------

    def store(self, key: tuple, compiled: Any, name: str) -> None:
        """Persist one compile: the serialized executable, then the payload
        (characterization + device ids). A payload without its blob is
        useless, so a failed blob write stores nothing and a failed payload
        write removes the orphan. Best-effort: a program that does not
        serialize simply misses next run, never errors this run."""
        exe_path = self._exe_path(key)
        try:
            payload = {
                "format": _FORMAT_VERSION,
                "name": name,
                "device_ids": [
                    d.id for d in compiled.runtime_executable().local_devices()
                ],
                "cost": cost_analysis_dict(compiled),
                "memory": _memory_analysis_dict(compiled),
                "collective_bytes": collective_bytes_from_hlo(compiled.as_text()),
            }
            blob = _serialize(compiled)
            tmp = exe_path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, exe_path)
            path = self._path(key)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)
            self.stores += 1
        except Exception:  # noqa: BLE001 — persistence is advisory
            for stale in (exe_path + ".tmp", exe_path):
                if os.path.exists(stale):
                    try:
                        os.remove(stale)
                    except OSError:
                        pass

    # -- load --------------------------------------------------------------

    def load(
        self, key: tuple, args: tuple
    ) -> tuple[Callable[..., Any], CompiledInfo] | None:
        """Restore one compile from disk onto the devices it was compiled
        for, trial-call it, and rebuild the memoized characterization.
        Returns None when the caller must retrace; a present-but-unusable
        entry is counted and named in the fallback diagnostics."""
        path = self._path(key)
        if not os.path.exists(path):
            self.misses += 1  # cold miss: nothing to fall back from
            return None
        try:
            with open(path) as f:
                payload = json.load(f)
            if payload.get("format") != _FORMAT_VERSION:
                raise ValueError("stale cache format")
            by_id = {d.id: d for d in jax.devices()}
            missing = [i for i in payload["device_ids"] if i not in by_id]
            if missing:
                raise ValueError(f"entry names devices {missing} absent here")
            with open(self._exe_path(key), "rb") as f:
                blob = f.read()
            executable = _deserialize(
                blob, [by_id[i] for i in payload["device_ids"]]
            )
            jax.block_until_ready(executable(*args))  # trial call
            info = CompiledInfo(
                name=payload["name"],
                cost=dict(payload["cost"]),
                memory=dict(payload["memory"]),
                roofline=roofline_terms(
                    dict(payload["cost"]),
                    collective_bytes=float(payload["collective_bytes"]),
                ),
                hlo_collectives_bytes=float(payload["collective_bytes"]),
            )
        except Exception as e:  # noqa: BLE001 — any problem means "retrace"
            self.misses += 1
            self._note_fallback(key, e)
            return None
        self.hits += 1
        return executable, info


def _serialize(compiled: Any) -> bytes:
    """AOT-serialize a ``jax.stages.Compiled`` whole: executable payload
    plus input/output pytree defs."""
    from jax.experimental import serialize_executable as jse

    payload, in_tree, out_tree = jse.serialize(compiled)
    return pickle.dumps((payload, in_tree, out_tree))


def _deserialize(blob: bytes, devices: list) -> Callable[..., Any]:
    """Bytes → a loaded ``jax.stages.Compiled`` on ``devices`` (callable
    with the original arguments), with zero XLA compilation."""
    from jax.experimental import serialize_executable as jse

    payload, in_tree, out_tree = pickle.loads(blob)
    return jse.deserialize_and_load(
        payload, in_tree, out_tree, execution_devices=devices
    )
