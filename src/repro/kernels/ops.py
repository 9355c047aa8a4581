"""Public jit'd entry points for the kernel layer.

Each op dispatches between the Pallas TPU kernel and the pure-jnp oracle:

- on TPU backends the Pallas kernel runs compiled,
- on CPU (this container) the kernel runs in ``interpret=True`` mode when
  invoked directly (tests/benchmarks), while *model/dry-run* code paths use
  the jnp reference implementation so XLA:CPU can lower the 512-device SPMD
  programs (Pallas interpret inside a 512-way pjit is neither representative
  nor compilable in reasonable time — DESIGN.md §8).

``mode`` overrides: "pallas" forces the kernel (interpret on non-TPU),
"ref" forces the oracle, "auto" picks pallas-on-TPU / ref-otherwise.

The engine's ``impl`` axis routes through this same dispatch rather than a
parallel code path: ``force_impl(mode, op, **params)`` sets a context-local
override consulted whenever an op is called with ``mode="auto"`` (an explicit
call-site ``mode=`` always wins). The engine enters this context around
``jit(fn).lower(...)`` so the choice is baked into the traced program — the
bench functions themselves never change. ``params`` are merged under the
call-site blocks, and only for the named op, which is how ``_stage_tune``'s
winning block config reaches the kernel.

``tune_space(op)`` exposes each kernel module's exported autotune candidates
(``PALLAS_OPS`` maps op name -> kernel module) for the engine's tune stage.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import (
    avgpool as _avgpool_mod,
    bitonic_sort as _bitonic_mod,
    flash_attention as _flash_mod,
    lrn as _lrn_mod,
    matmul as _matmul_mod,
    prefix_scan as _scan_mod,
    softmax as _softmax_mod,
    srad_stencil as _srad_mod,
)
from repro.kernels.avgpool import avgpool_pallas
from repro.kernels.bitonic_sort import bitonic_sort_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lrn import lrn_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.prefix_scan import prefix_scan_pallas
from repro.kernels.softmax import softmax_pallas
from repro.kernels.srad_stencil import srad_step_fused, srad_step_split

__all__ = [
    "matmul",
    "attention",
    "softmax",
    "lrn",
    "avgpool",
    "srad_step",
    "prefix_scan",
    "sort_kv",
    "on_tpu",
    "force_impl",
    "resolves_to_pallas",
    "tune_space",
    "PALLAS_OPS",
]

Mode = Literal["auto", "pallas", "ref"]

# op name -> kernel module exporting tune_space(). These names are what a
# Workload's ``pallas_kernel`` field refers to (registry.py impl contract).
PALLAS_OPS = {
    "matmul": _matmul_mod,
    "attention": _flash_mod,
    "softmax": _softmax_mod,
    "lrn": _lrn_mod,
    "avgpool": _avgpool_mod,
    "srad_step": _srad_mod,
    "prefix_scan": _scan_mod,
    "sort_kv": _bitonic_mod,
}

# (mode, op-or-None, params) set by force_impl; consulted only for mode="auto"
# call sites so an explicit mode= argument keeps absolute priority.
_FORCED: contextvars.ContextVar = contextvars.ContextVar(
    "repro_forced_impl", default=None
)


@contextlib.contextmanager
def force_impl(mode: Mode, op: str | None = None, **params):
    """Context-locally override ``mode="auto"`` dispatch for the kernel ops.

    ``op=None`` applies to every op; otherwise ``params`` (tuned block sizes)
    are merged only into calls of the named op. Must wrap *tracing* (jit
    lower / first call), not execution — dispatch happens at trace time.
    """
    if mode not in ("auto", "pallas", "ref"):
        raise ValueError(f"force_impl mode must be auto|pallas|ref, got {mode!r}")
    token = _FORCED.set((mode, op, dict(params)))
    try:
        yield
    finally:
        _FORCED.reset(token)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas(mode: Mode) -> tuple[bool, bool]:
    """-> (use_pallas, interpret)."""
    if mode == "ref":
        return False, False
    if mode == "pallas":
        return True, not on_tpu()
    return on_tpu(), False


def _resolve(op: str, mode: Mode, blocks: dict) -> tuple[bool, bool, dict]:
    """Apply any force_impl override -> (use_pallas, interpret, blocks)."""
    forced = _FORCED.get()
    if mode == "auto" and forced is not None:
        mode, f_op, f_params = forced
        if f_params and (f_op is None or f_op == op):
            blocks = {**f_params, **blocks}
    use, interp = _use_pallas(mode)
    return use, interp, blocks


def resolves_to_pallas(op: str) -> bool:
    """Whether a ``mode="auto"`` call of ``op`` made here, at trace time and
    under the current ``force_impl``, runs the Pallas kernel. For callers
    that shape their operands differently for the kernel than for their own
    XLA path."""
    return _resolve(op, "auto", {})[0]


def tune_space(op: str) -> tuple[dict, ...]:
    """The autotune candidates for ``op`` (first entry = kernel defaults)."""
    try:
        module = PALLAS_OPS[op]
    except KeyError:
        raise KeyError(
            f"unknown pallas op {op!r}; known: {sorted(PALLAS_OPS)}"
        ) from None
    return module.tune_space()


def matmul(a, b, *, mode: Mode = "auto", **blocks):
    use, interp, blocks = _resolve("matmul", mode, blocks)
    if use:
        return matmul_pallas(a, b, interpret=interp, **blocks)
    return _ref.matmul_ref(a, b)


def attention(
    q,
    k,
    v,
    *,
    causal: bool = False,
    window: int | None = None,
    scale: float | None = None,
    mode: Mode = "auto",
    **blocks,
):
    use, interp, blocks = _resolve("attention", mode, blocks)
    if use:
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, scale=scale,
            interpret=interp, **blocks,
        )
    return _ref.attention_ref(q, k, v, causal=causal, window=window, scale=scale)


def softmax(x, *, mode: Mode = "auto", **blocks):
    use, interp, blocks = _resolve("softmax", mode, blocks)
    if use:
        return softmax_pallas(x, interpret=interp, **blocks)
    return _ref.softmax_ref(x)


def lrn(x, *, size=5, alpha=1e-4, beta=0.75, k=2.0, mode: Mode = "auto", **blocks):
    use, interp, blocks = _resolve("lrn", mode, blocks)
    if use:
        return lrn_pallas(
            x, size=size, alpha=alpha, beta=beta, k=k, interpret=interp, **blocks
        )
    return _ref.lrn_ref(x, size=size, alpha=alpha, beta=beta, k=k)


def avgpool(x, *, ksize=2, mode: Mode = "auto", **blocks):
    use, interp, blocks = _resolve("avgpool", mode, blocks)
    if use:
        return avgpool_pallas(x, ksize=ksize, interpret=interp, **blocks)
    return _ref.avgpool_ref(x, ksize=ksize)


def srad_step(
    img, *, lam=0.5, q0sqr=0.05, fused: bool = True, mode: Mode = "auto"
):
    use, interp, _ = _resolve("srad_step", mode, {})
    if use:
        fn = srad_step_fused if fused else srad_step_split
        return fn(img, lam=lam, q0sqr=q0sqr, interpret=interp)
    return _ref.srad_step_ref(img, lam=lam, q0sqr=q0sqr)


def prefix_scan(x, *, mode: Mode = "auto", **blocks):
    use, interp, blocks = _resolve("prefix_scan", mode, blocks)
    if use:
        return prefix_scan_pallas(x, interpret=interp, **blocks)
    return _ref.prefix_scan_ref(x)


def sort_kv(keys, values, *, mode: Mode = "auto"):
    use, interp, _ = _resolve("sort_kv", mode, {})
    if use:
        (n,) = keys.shape
        n_pow2 = 1 << (n - 1).bit_length()
        if n_pow2 != n:
            pad = n_pow2 - n
            maxval = (
                jnp.iinfo(keys.dtype).max
                if jnp.issubdtype(keys.dtype, jnp.integer)
                else jnp.inf
            )
            keys = jnp.pad(keys, (0, pad), constant_values=maxval)
            values = jnp.pad(values, (0, pad))
        ko, vo = bitonic_sort_pallas(keys, values, interpret=interp)
        return ko[:n], vo[:n]
    return _ref.sort_kv_ref(keys, values)
