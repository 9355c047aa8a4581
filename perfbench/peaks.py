"""The chip's published peaks, and the work a kernel's call needs.

Peaks come from ``peaks.json`` beside this file, keyed by JAX's
``device_kind``. A kind that is not in the table is an error, never a
default: a roofline share against another chip's peaks would be wrong.
"""

from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def for_kind(kind: str) -> dict:
    with open(_TABLE, encoding="utf-8") as fh:
        table = json.load(fh)
    try:
        return table[kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {kind!r}; known: {sorted(table)}"
        ) from None


def for_device(device) -> dict:
    return for_kind(device.device_kind)


def gemm_work(n: int, itemsize: int) -> tuple[float, float]:
    """(operations, bytes) of one n x n x n matrix product: 2n^3 multiply-
    adds counted as two operations, and A, B and C each moved once."""
    return 2.0 * n**3, 3.0 * n * n * itemsize


def roofline_s(ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for this work, and its bound."""
    compute = ops / peaks["bf16_flop_per_s"]
    memory = nbytes / peaks["hbm_byte_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
