"""Roofline characterization — the TPU analogue of the paper's nvprof study.

Mirovia/Altis characterizes every benchmark with per-functional-unit
utilization (0–10) sampled by nvprof (Figs. 1, 2, 5) and uses it to classify
kernels compute- vs memory-bound (§V-A). TPUs expose no nvprof; instead the
compiled artifact gives us *exact* static FLOP and byte counts
(``compiled.cost_analysis()``) and the full collective schedule (the optimized
HLO text). From these we derive a three-term roofline per program:

    compute_s    = HLO_FLOPs_per_device   / peak_flops
    memory_s     = HLO_bytes_per_device   / hbm_bw
    collective_s = collective_bytes_per_device / ici_bw

The dominant term is the bottleneck; ``compute_s / max(terms)`` is the
roofline fraction the perf loop hillclimbs. ``utilization_scale10`` maps
fractions onto the paper's 0–10 bar scale so the Fig. 1/2/5 analogues read
identically to the original plots.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Mapping

__all__ = [
    "TPUv5e",
    "PEAKS_BY_KIND",
    "device_peaks",
    "RooflineTerms",
    "roofline_terms",
    "cost_analysis_dict",
    "collective_bytes_from_hlo",
    "collective_ops_from_hlo",
    "utilization_scale10",
    "model_flops",
]


def cost_analysis_dict(compiled: Any) -> dict[str, float]:
    """``compiled.cost_analysis()`` with non-numeric entries dropped."""
    raw = compiled.cost_analysis()
    return {k: float(v) for k, v in dict(raw or {}).items() if isinstance(v, (int, float))}


@dataclasses.dataclass(frozen=True)
class _HW:
    """Roofline target hardware constants."""

    name: str
    peak_bf16_flops: float  # FLOP/s per chip
    hbm_bw: float  # bytes/s per chip
    hbm_bytes: float  # capacity per chip
    ici_bw: float  # bytes/s per link
    vmem_bytes: float  # on-chip vector memory


# TPU v5e (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 16 GiB HBM at 819 GB/s; ~50 GB/s per ICI link).
TPUv5e = _HW(
    name="tpu_v5e",
    peak_bf16_flops=197e12,
    hbm_bw=819e9,
    hbm_bytes=16 * 1024**3,
    ici_bw=50e9,
    vmem_bytes=128 * 1024**2,
)

# Per-chip peaks keyed by ``jax.Device.device_kind``.
PEAKS_BY_KIND: dict[str, _HW] = {"TPU v5 lite": TPUv5e}


def device_peaks(device: Any = None) -> _HW:
    """The roofline peaks of ``device`` (default: the first device).

    A TPU whose kind is not in :data:`PEAKS_BY_KIND` is an error, never a
    default. Non-TPU backends (the CPU test host) get the static
    projection onto the v5e entry; their run metadata says which backend
    ran, so the projection is never mistaken for a device measurement.
    """
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform != "tpu":
        return TPUv5e
    try:
        return PEAKS_BY_KIND[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no roofline peaks for device kind {device.device_kind!r}; "
            f"known: {sorted(PEAKS_BY_KIND)}"
        ) from None


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    """Three-term roofline for one compiled program on one chip."""

    flops: float  # per-device HLO FLOPs
    hbm_bytes: float  # per-device HLO bytes accessed
    collective_bytes: float  # per-device bytes over ICI
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.__getitem__)

    @property
    def total_s(self) -> float:
        # No-overlap upper bound; with perfect overlap the step time is
        # max(...) instead. Both are reported; the fraction uses max().
        return self.compute_s + self.memory_s + self.collective_s

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the step spent doing peak-rate compute, assuming
        perfect overlap: 1.0 means MXU-bound at peak."""
        return 0.0 if self.bound_s == 0 else self.compute_s / self.bound_s

    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.hbm_bytes, 1.0)


def roofline_terms(
    cost: Mapping[str, float],
    *,
    collective_bytes: float = 0.0,
    hw: _HW | None = None,
) -> RooflineTerms:
    """Build roofline terms from ``compiled.cost_analysis()`` output.

    ``hw`` defaults to :func:`device_peaks` of the device this process runs
    on.

    ``cost_analysis`` runs *after* SPMD partitioning, so flops/bytes are
    per-device numbers (verified in tests/test_metrics.py against a matmul of
    known size). ``bytes accessed`` includes operand + output traffic, i.e.
    an HBM-roundtrip upper bound that double counts what stays resident in
    VMEM — acceptable for a static bound, and consistent across benchmarks.
    """
    if hw is None:
        hw = device_peaks()
    flops = float(cost.get("flops", 0.0))
    # Sum every "bytes accessed..." key once; XLA splits operand/output
    # traffic into e.g. 'bytes accessed', 'bytes accessed0{}', 'utilization..'.
    if "bytes accessed" in cost:
        hbm = float(cost["bytes accessed"])
    else:
        hbm = float(
            sum(v for k, v in cost.items() if k.startswith("bytes accessed"))
        )
    return RooflineTerms(
        flops=flops,
        hbm_bytes=hbm,
        collective_bytes=collective_bytes,
        compute_s=flops / hw.peak_bf16_flops,
        memory_s=hbm / hw.hbm_bw,
        collective_s=collective_bytes / hw.ici_bw,
    )


# ---------------------------------------------------------------------------
# Collective traffic from optimized HLO text.
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1,
    "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# Matches e.g. `  %x = bf16[16,512,128]{2,1,0:T(8,128)} all-gather(...)` and
# tuple-shaped starts `(f32[8,128]{...}, f32[8,128]{...}) all-reduce(...)`.
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|\w+\[[\d,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<op>" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\("
)


def _shape_bytes(shape_str: str) -> float:
    """Total bytes of a (possibly tuple) HLO shape string."""
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_ops_from_hlo(hlo_text: str) -> list[tuple[str, float]]:
    """Return (op_kind, ici_bytes_per_device) for every collective in the HLO.

    Bytes use ring-algorithm estimates with the (n-1)/n factor dropped
    (documented upper bound, exact as n→∞):

    - all-gather:        result bytes (each device receives the full result)
    - reduce-scatter:    operand ≈ result × n; we charge result × 1 per hop
      summed over n-1 hops ≈ full-operand bytes ≈ result bytes × n. Since n
      is not recoverable from the shape alone, we charge the *operand* side:
      the `-start` op result already reflects the scattered shape, so we
      approximate with gathered bytes when derivable, else result bytes.
    - all-reduce:        2 × result bytes (reduce-scatter + all-gather ring)
    - all-to-all:        result bytes
    - collective-permute: result bytes

    Only `-start` (or plain) forms are counted; `-done` carries no traffic.
    """
    out: list[tuple[str, float]] = []
    for line in hlo_text.splitlines():
        if "-done(" in line or "-done.(" in line:
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        nbytes = _shape_bytes(m.group("shape"))
        if op == "all-reduce":
            nbytes *= 2.0
        out.append((op, nbytes))
    return out


def collective_bytes_from_hlo(hlo_text: str) -> float:
    return float(sum(b for _, b in collective_ops_from_hlo(hlo_text)))


def utilization_scale10(fraction: float) -> int:
    """Map a roofline fraction onto the paper's 0–10 utilization bar scale."""
    return max(0, min(10, round(10.0 * fraction)))


def model_flops(n_params: float, n_tokens: float, *, active_params: float | None = None) -> float:
    """The paper-of-record useful-FLOPs estimate: 6·N·D (dense) or
    6·N_active·D (MoE) — used for the 'useful compute' ratio in §Roofline."""
    n = active_params if active_params is not None else n_params
    return 6.0 * n * n_tokens
