"""Plain reference of ``gemm-bf16-n8192``: C = A @ B for n x n bfloat16 A and
B with float32 products and sums, C rounded to bfloat16.

The reference upcasts A and B to float32 (exact) and multiplies at
``Precision.HIGHEST``, so its products are exact and its sums float32; it
imports nothing of the program. The comparison is the largest gap between
the program's C and the reference's, over every element, as a share of the
reference's root mean square. Rounding C to bfloat16 alone leaves up to
2**-9 of the largest element, about 0.017 of the RMS at n = 8192.

The control is the reference computed one precision lower, in float8
(e4m3) inputs: the step a later change might take for speed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Readings on one TPU v5e at n = 8192 (PERF.md, "How correct is decided"):
# the program read at most 0.0210 over 19 seeds, the float8 control at
# least 0.213 over 3.
LIMITS = {"max_err": 0.08}


@functools.partial(jax.jit, static_argnames=("n",))
def _make(key, n):
    ka, kb = jax.random.split(key)
    return (
        jax.random.normal(ka, (n, n), jnp.float32).astype(jnp.bfloat16),
        jax.random.normal(kb, (n, n), jnp.float32).astype(jnp.bfloat16),
    )


def make_inputs(key, config: dict) -> tuple:
    """A and B from the seed's key, on the device, in one jitted call."""
    return jax.block_until_ready(_make(key, n=config["overrides"]["n"]))


def _product(a, b):
    return jnp.dot(
        a.astype(jnp.float32), b.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


@jax.jit
def _max_err(c, a, b):
    ref = _product(a, b)
    return jnp.max(jnp.abs(c.astype(jnp.float32) - ref)) / jnp.sqrt(jnp.mean(ref * ref))


def compare(out, args) -> dict:
    """The numbers compared for one answer of the program."""
    a, b = args
    return {"max_err": float(_max_err(out, a, b))}


def control(a, b):
    """The reference in float8 inputs, rounded to bfloat16 like C. The
    inputs are rounded to float8 on the host: on a TPU v5e a float8 cast
    inside the jitted reference left them unchanged (the control then read
    exactly what the program did)."""

    def f8(x):
        return jnp.asarray(np.asarray(x).astype(jnp.float8_e4m3fn).astype(np.float32))

    return _rounded_product(f8(a), f8(b))


@jax.jit
def _rounded_product(a, b):
    return _product(a, b).astype(jnp.bfloat16)
