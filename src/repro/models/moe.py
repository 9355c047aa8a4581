"""Mixture-of-Experts FFN with GShard-style grouped one-hot dispatch.

TPU/GSPMD adaptation (DESIGN.md §5): MegaBlocks-style sparse grouped GEMM is
a GPU-kernel mechanism; the GSPMD-native expression is the GShard einsum
dispatch — tokens are split into groups of ``moe_group_size``, each group
routes its tokens into per-expert capacity buffers with a one-hot dispatch
tensor, expert FFNs run as batched einsums over the expert axis (shardable
as EP), and a combine einsum scatters results back. Dispatch overhead is
O(group_size) per token (≈5% of active FLOPs at group 1024 for
mixtral-scale FFNs — quantified in EXPERIMENTS.md §Roofline).

Top-k routing with softmax-renormalized weights over the selected experts
(Mixtral's scheme); tokens over capacity are dropped (standard GShard
behaviour — tests use full capacity so the oracle comparison is exact).

``apply_moe_dropless`` is DeepSeek-V3's layer (``router="sigmoid"``), as
one chip of an expert-parallel deployment runs it: the router scores all
``n_experts``, the layer computes only the experts it holds
(``held_experts``), with every token routed to them and none dropped, plus
the shared experts every token passes through. Assignments are sorted by
held expert and the experts run as one grouped matmul
(``jax.lax.ragged_dot``). What the experts held elsewhere add is left out.
Its weights are the checkpoint's own tensors (``mlp.gate.weight``, ...).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.config import ArchConfig
from repro.models.layers import dtype_of, init_dense

__all__ = ["init_moe", "apply_moe", "moe_oracle", "route_sigmoid", "apply_moe_dropless", "swiglu"]

ROUTER = "mlp.gate.weight"
BIAS = "mlp.gate.e_score_correction_bias"
EXPERTS = "mlp.experts.*.{}_proj.weight"
SHARED = "mlp.shared_experts.{}_proj.weight"


def init_moe(key, cfg: ArchConfig) -> dict:
    """Expert weights; with ``moe_split`` > 1 they are stored pre-sliced as
    (E·split, d, ff/split) virtual experts (see split_moe_params)."""
    dt = dtype_of(cfg)
    kr, kg, ku, kd = jax.random.split(key, 4)
    E, d, ff = cfg.n_experts, cfg.d_model, cfg.d_ff
    sp = cfg.moe_split
    assert ff % sp == 0, (ff, sp)
    Ev, ffv = E * sp, ff // sp

    def stack(k, din, dout, scale=None):
        return jnp.stack(
            [init_dense(kk, din, dout, dt, scale) for kk in jax.random.split(k, Ev)]
        )

    return {
        "router": init_dense(kr, d, E, jnp.float32),
        "w_gate": stack(kg, d, ffv),
        "w_up": stack(ku, d, ffv),
        "w_down": stack(kd, ffv, d, scale=0.02 / (2 * cfg.n_layers) ** 0.5),
    }


def split_moe_params(p: dict, split: int) -> dict:
    """Re-slice unsplit expert params (E, d, ff) → (E·split, d, ff/split).

    Virtual experts [e·split .. e·split+split) are the ff-slices of real
    expert e; SwiGLU is elementwise over ff and w_down sums over ff, so the
    slice outputs add exactly to the unsplit output (tested)."""
    E, d, ff = p["w_gate"].shape
    ffv = ff // split

    def col(w):  # (E, d, ff) -> (E*split, d, ffv)
        return (
            w.reshape(E, d, split, ffv).transpose(0, 2, 1, 3).reshape(E * split, d, ffv)
        )

    def row(w):  # (E, ff, d) -> (E*split, ffv, d)
        return w.reshape(E, split, ffv, d).reshape(E * split, ffv, d)

    return {
        "router": p["router"],
        "w_gate": col(p["w_gate"]),
        "w_up": col(p["w_up"]),
        "w_down": row(p["w_down"]),
    }


def _route(logits: jax.Array, top_k: int):
    """logits (N, E) -> combine weights (N, E) with top-k renormalized."""
    weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_vals, _ = jax.lax.top_k(weights, top_k)
    thresh = top_vals[..., -1:]
    selected = weights >= thresh
    w = jnp.where(selected, weights, 0.0)
    return w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)


def apply_moe(p: dict, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """x (B, T, d) -> (B, T, d)."""
    B, T, d = x.shape
    N = B * T
    E, k = cfg.n_experts, cfg.top_k
    g = min(cfg.moe_group_size, N)
    assert N % g == 0, (N, g)
    G = N // g
    cap = max(1, int(round(k * g * cfg.capacity_factor / E)))

    xg = x.reshape(G, g, d)
    logits = xg.astype(jnp.float32) @ p["router"]  # (G, g, E)
    combine_w = _route(logits.reshape(N, E), k).reshape(G, g, E)
    if cfg.moe_split > 1:
        # Virtual ff-slice experts: every selected token goes to all slices
        # of its expert with the same combine weight (slice outputs add).
        combine_w = jnp.repeat(combine_w, cfg.moe_split, axis=-1)

    # Position of each token inside its expert's capacity buffer.
    sel = combine_w > 0
    pos = jnp.cumsum(sel.astype(jnp.int32), axis=1) - 1  # (G, g, E[v])
    keep = sel & (pos < cap)
    # dispatch (G, g, E, cap): one-hot over the capacity slot.
    disp = keep[..., None] & (
        pos[..., None] == jnp.arange(cap)[None, None, None, :]
    )
    disp_f = disp.astype(x.dtype)
    comb_f = (combine_w[..., None] * disp).astype(x.dtype)

    xin = jnp.einsum("gsec,gsd->gecd", disp_f, xg)  # (G, E, cap, d)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xin, p["w_gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", xin, p["w_up"])
    out = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    y = jnp.einsum("gsec,gecd->gsd", comb_f, out)
    return y.reshape(B, T, d)


def moe_oracle(p: dict, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """Per-token dense oracle (no capacity drops) for tests."""
    B, T, d = x.shape
    xf = x.reshape(-1, d)
    w = _route(xf.astype(jnp.float32) @ p["router"], cfg.top_k)  # (N, E)
    outs = []
    for e in range(cfg.n_experts):
        h = jax.nn.silu(xf @ p["w_gate"][e]) * (xf @ p["w_up"][e])
        outs.append(h @ p["w_down"][e])
    y = sum(w[:, e : e + 1].astype(x.dtype) * outs[e] for e in range(cfg.n_experts))
    return y.reshape(B, T, d)


def route_sigmoid(logits: jax.Array, bias: jax.Array, cfg: ArchConfig):
    """DeepSeek-V3's router on f32 logits (N, E): s = sigmoid(logits); the
    top_k of s + bias are selected; each weighs s over the selected s's sum,
    times ``routed_scale``. The bias selects but does not weight. Returns
    (expert ids (N, k), weights (N, k))."""
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias, cfg.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True) * cfg.routed_scale


def swiglu(x, w_gate, w_up, w_down):
    """x (N, d) through a SwiGLU, each product through the kernel layer."""
    return ops.matmul(jax.nn.silu(ops.matmul(x, w_gate)) * ops.matmul(x, w_up), w_down)


def apply_moe_dropless(p: dict, cfg: ArchConfig, x: jax.Array):
    """x (N, d) -> (y (N, d), tokens routed to each held expert (held,)).

    ``p`` holds one layer's tensors: the router (d, E) and its correction
    bias (E,) in f32, the held experts' SwiGLUs stacked (held, d, f) /
    (held, f, d), and the shared experts as one SwiGLU of width
    n_shared_experts·f."""
    N, d = x.shape
    k = cfg.top_k
    first, stop = cfg.held
    held = stop - first
    with jax.named_scope("route"):
        logits = jnp.dot(
            x.astype(jnp.float32), p[ROUTER], precision=jax.lax.Precision.HIGHEST
        )
        idx, w = route_sigmoid(logits, p[BIAS], cfg)
        local = idx - first
        mine = (local >= 0) & (local < held)
        # Each assignment's held expert, or ``held`` for those held elsewhere,
        # which sort last and fall outside every group.
        group = jnp.where(mine, local, held).reshape(-1)
        order = jnp.argsort(group, stable=True)
        counts = jnp.sum(group[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)
    with jax.named_scope("experts"):
        xs = x[order // k]
        gate = jax.lax.ragged_dot(xs, p[EXPERTS.format("gate")], counts)
        up = jax.lax.ragged_dot(xs, p[EXPERTS.format("up")], counts)
        ys = jax.lax.ragged_dot(jax.nn.silu(gate) * up, p[EXPERTS.format("down")], counts)
    with jax.named_scope("combine"):
        back = jnp.zeros_like(order).at[order].set(jnp.arange(N * k, dtype=order.dtype))
        # Rows past the held groups are no expert's output: select, not
        # multiply, so whatever they hold cannot leak in.
        per_slot = jnp.where(mine[..., None], ys[back].reshape(N, k, d), 0)
        y = jnp.sum(per_slot.astype(jnp.float32) * w[..., None], axis=1)
    with jax.named_scope("shared"):
        shared = swiglu(x, *(p[SHARED.format(n)] for n in ("gate", "up", "down")))
    return (y + shared.astype(jnp.float32)).astype(x.dtype), counts
