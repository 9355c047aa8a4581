"""Named-axis sharding rules for the model zoo (DESIGN.md §5).

Parameters are matched by leaf name (the trees in models/ use globally
unambiguous names) against an ordered list of *candidate* dimensions to
shard over the ``model`` axis; the first candidate whose size divides the
axis is used, otherwise the leaf replicates (e.g. mixtral's 8 experts don't
divide a 16-way model axis ⇒ its expert FFNs shard the ``d_ff`` dim
instead — rule order encodes that preference). Leaves under ``blocks`` carry
a leading stacked-period dim, handled transparently.

Activations: batch shards over the data axes (("pod","data") multi-pod);
with ``seq_shard=True`` (Megatron-SP analogue) the residual stream also
shards its sequence dim over ``model``, which divides scan-saved activations
by the TP degree — the decisive term for 100B-scale training memory.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ShardingRules",
    "param_pspecs",
    "batch_pspec",
    "make_activation_sharder",
    "data_mesh",
    "replicate",
    "workload_pspecs",
    "shard_applies",
    "place_args",
]

# name -> ordered candidate shard dims (on the UNstacked leaf shape).
# dim index -> which dimension to try placing "model" on.
_PARAM_RULES: dict[str, tuple[int, ...]] = {
    "embed": (0,),  # (V, d): vocab-shard
    "unembed": (1,),  # (d, V)
    # attention
    "wq": (1,), "wk": (1,), "wv": (1,), "wo": (0,),
    "bq": (0,), "bk": (0,), "bv": (0,),
    # dense mlp
    "w_gate": (1,), "w_up": (1,), "w_down": (0,),
    # moe (expert-stacked weights): prefer EP on the expert dim, else d_ff
    "moe.w_gate": (0, 2), "moe.w_up": (0, 2), "moe.w_down": (0, 1),
    "router": (),
    # mamba
    "in_proj": (1,), "x_proj": (0,), "dt_w": (1,), "dt_b": (0,),
    "A_log": (0,), "D": (0,), "out_proj": (0,),
    "conv_w": (1,), "conv_b": (0,),
    # mlstm
    "w_gates": (0,), "b_gates": (), "gn": (0,),
    # slstm: block-diagonal recurrent mats shard their output dim (the
    # hidden state all-gathers per step inside the scan — O(d) traffic).
    "w_x": (1,), "r_z": (2,), "r_i": (2,), "r_f": (2,), "r_o": (2,), "b": (),
    "w_ff1": (1,), "w_ff2": (0,),
    # norms
    "ln": (), "ln1": (), "ln2": (), "ln_f": (),
}

# DeepSeek-V3 stacks keep the checkpoint's tensor names; a rule is keyed by
# the name without its ``model.layers.<i|*>.`` prefix, and dims count on the
# leaf without its stacked layer axis (held experts keep theirs: EP first).
_CKPT_RULES: dict[str, tuple[int, ...]] = {
    "model.embed_tokens.weight": (0,), "lm_head.weight": (1,),
    "self_attn.q_proj.weight": (1,), "self_attn.kv_b_proj.weight": (1,),
    "self_attn.o_proj.weight": (0,),
    "mlp.gate_proj.weight": (1,), "mlp.up_proj.weight": (1,), "mlp.down_proj.weight": (0,),
    "mlp.shared_experts.gate_proj.weight": (1,), "mlp.shared_experts.up_proj.weight": (1,),
    "mlp.shared_experts.down_proj.weight": (0,),
    "mlp.experts.*.gate_proj.weight": (0, 2), "mlp.experts.*.up_proj.weight": (0, 2),
    "mlp.experts.*.down_proj.weight": (0, 1),
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    mesh: Mesh
    model_axis: str = "model"
    data_axes: tuple[str, ...] = ("data",)
    seq_shard: bool = False  # SP: shard residual sequence dim over model
    # Replicate leaves below this element count: tiny per-step weights (e.g.
    # sLSTM recurrent mats) cost more in per-scan-step all-gathers than they
    # save in HBM (§Perf xlstm iteration). 0 disables.
    replicate_below: int = 0
    # Shard decode KV caches over their sequence dim instead of head_dim
    # (§Perf decode iteration): with head_dim sharded, GSPMD all-gathers the
    # whole cache per step (125 GB/step for granite decode_32k); with the
    # sequence sharded, each shard scores its own keys and the softmax
    # combines with scalar-sized reductions — flash-decoding split-K
    # semantics, expressed purely as a sharding choice.
    cache_seq_shard: bool = False
    # Gather the MoE FFN input to data-only sharding before dispatch: the
    # GShard dispatch/combine einsums contract over tokens, and seq-sharded
    # tokens force (G,E,cap,d)-sized partial-sum all-reduces over the model
    # axis (§Perf mixtral iteration — the 3.3 TB/step finding). With the
    # input gathered, the only MoE collective is the dense-MLP-like
    # row-parallel reduce of the expert down-projection.
    moe_gather_tokens: bool = False

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def data_size(self) -> int:
        out = 1
        for a in self.data_axes:
            out *= self.mesh.shape[a]
        return out


def _leaf_rule_key(path) -> str:
    names = [getattr(p, "key", getattr(p, "name", None)) for p in path]
    names = [n for n in names if isinstance(n, str)]
    leaf = names[-1] if names else ""
    if "ffn" in names and leaf in ("w_gate", "w_up", "w_down") and "router_sibling" not in names:
        # MoE expert weights are distinguished by rank at the call site.
        return leaf
    return leaf


def _pspec_for_leaf(path, leaf, rules: ShardingRules) -> P:
    names = [getattr(p, "key", getattr(p, "name", None)) for p in path]
    names = [n for n in names if isinstance(n, str)]
    name = names[-1] if names else ""
    stacked = "blocks" in names  # leading period dim
    base_rank = leaf.ndim - (1 if stacked else 0)
    key = name
    # Expert-stacked FFN weights have one extra rank vs dense MLP weights.
    if name in ("w_gate", "w_up", "w_down") and base_rank == 3:
        key = "moe." + name
    candidates = _PARAM_RULES.get(key, ())
    if name.startswith("model.layers."):  # a checkpoint-named tensor
        layer, _, key = name[len("model.layers."):].partition(".")
        stacked = layer == "*"
        candidates = _CKPT_RULES.get(key, ())
    elif "." in name:
        candidates = _CKPT_RULES.get(name, ())
    spec = [None] * leaf.ndim
    if rules.replicate_below:
        import math

        if math.prod(leaf.shape) < rules.replicate_below:
            return P(*spec)
    offset = 1 if stacked else 0
    for dim in candidates:
        d = dim + offset
        if leaf.shape[d] % rules.model_size == 0 and leaf.shape[d] >= rules.model_size:
            spec[d] = rules.model_axis
            break
    return P(*spec)


def param_pspecs(params: Any, rules: ShardingRules) -> Any:
    """Pytree of PartitionSpecs matching ``params`` (works on
    ShapeDtypeStructs too — the dry-run path)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [_pspec_for_leaf(path, leaf, rules) for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def batch_pspec(batch: Any, rules: ShardingRules) -> Any:
    """Shard the batch dim over the data axes when divisible (decode at
    batch 1 replicates — latency-bound serving has no batch to shard)."""

    def spec(leaf) -> P:
        b = leaf.shape[0] if leaf.ndim else 1
        if leaf.ndim == 0 or b % max(rules.data_size, 1) != 0:
            return P()
        return P(rules.data_axes, *([None] * (leaf.ndim - 1)))

    return jax.tree.map(spec, batch)


def cache_pspecs(cache: Any, rules: ShardingRules) -> Any:
    """Decode-cache sharding: leading dim is the stacked period axis
    (never sharded), dim 1 is batch (over data axes when divisible), and the
    last dim (head_dim / d_inner / d_model / state width) goes over
    ``model`` when divisible — head_dim sharding keeps GQA caches TP-sharded
    even when kv_heads < TP degree (DESIGN.md §5)."""

    def spec(leaf) -> P:
        if leaf.ndim < 3:
            return P()
        dims: list = [None] * leaf.ndim
        if leaf.shape[1] % max(rules.data_size, 1) == 0 and leaf.shape[1] >= rules.data_size:
            dims[1] = rules.data_axes
        # KV caches are rank 5: (periods, B, S, KV, hd). Prefer the S dim
        # under cache_seq_shard (flash-decoding split-K — see field doc).
        if (
            rules.cache_seq_shard
            and leaf.ndim == 5
            and leaf.shape[2] % rules.model_size == 0
            and leaf.shape[2] >= rules.model_size
        ):
            dims[2] = rules.model_axis
        elif leaf.shape[-1] % rules.model_size == 0 and leaf.shape[-1] >= rules.model_size:
            dims[-1] = rules.model_axis
        return P(*dims)

    return jax.tree.map(spec, cache)


def zero_pspecs(param_specs: Any, params: Any, rules: ShardingRules) -> Any:
    """ZeRO-1: extend each parameter spec with the data axes on the first
    unsharded dim that divides — optimizer moments shard over data *and*
    model, cutting optimizer HBM by the DP degree."""

    def extend(spec: P, leaf) -> P:
        dims = list(spec) + [None] * (leaf.ndim - len(spec))
        for i, d in enumerate(dims):
            if d is None and leaf.shape[i] % max(rules.data_size, 1) == 0 and leaf.shape[i] >= rules.data_size:
                dims[i] = rules.data_axes
                break
        return P(*dims)

    return jax.tree.map(
        extend, param_specs, params, is_leaf=lambda x: isinstance(x, P)
    )


def make_activation_sharder(rules: ShardingRules):
    """The ``shard_activation`` hook Model takes (DESIGN.md §5)."""
    dp = rules.data_axes
    # dp-only binding folds the model axis into data; it is then unavailable
    # for vocab/seq sharding (a spec may use each mesh axis once).
    mdl = rules.model_axis if rules.model_axis not in dp else None

    def shard(x: jax.Array, name: str) -> jax.Array:
        if x.ndim == 3:  # (B, T, d) or (B, T, V)
            b, t, _ = x.shape
            bspec = dp if b % rules.data_size == 0 else None
            if name == "logits":
                s = P(bspec, None, mdl)
            elif name == "moe_in":
                if not rules.moe_gather_tokens:
                    return x
                s = P(bspec, None, None)
            elif rules.seq_shard and t % rules.model_size == 0:
                s = P(bspec, mdl, None)
            else:
                s = P(bspec, None, None)
        elif x.ndim == 2:  # decode: (B, d) or (B, V)
            b = x.shape[0]
            bspec = dp if b % rules.data_size == 0 else None
            s = P(bspec, mdl if name == "logits" else None)
        else:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, s))

    return shard


def data_mesh(n_devices: int | None = None, axis: str = "data") -> Mesh:
    """A 1-axis mesh over the first ``n_devices`` devices (all by default).

    The benchmark engine's placement stage builds its data mesh here (both
    replicate and shard modes); model code uses the richer meshes in launch/.
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if not 1 <= n <= len(devs):
        raise ValueError(f"requested {n} devices but only {len(devs)} available")
    import numpy as np

    return Mesh(np.asarray(devs[:n]), (axis,))


def replicate(tree: Any, mesh: Mesh) -> Any:
    """device_put every array leaf fully replicated across ``mesh``."""
    s = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(x, s), tree)


def workload_pspecs(workload, mesh: Mesh, axis: str = "data") -> tuple:
    """Per-input :class:`NamedSharding` tuple from a workload's
    ``batch_dims`` declaration (the engine's shard-mode placement).

    Each declared dim becomes ``axis`` at that position; ``None`` entries
    (and every input of a non-batchable workload) replicate. Divisibility
    of the actual shapes is checked at placement time (``place_args``),
    not here — this is the pure declaration→sharding mapping.
    """
    dims = workload.batch_dims
    if dims is None:
        raise ValueError(
            f"workload {workload.name!r} declares no batch_dims; "
            "sharded placement must fall back to replicate"
        )

    def sharding(dim: int | None) -> NamedSharding:
        if dim is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(*([None] * dim), axis))

    return tuple(sharding(d) for d in dims)


def shard_applies(args: tuple, workload, n_devices: int) -> bool:
    """Shape-only check: would a ``shard`` placement actually partition
    anything? No device transfers — callers (e.g. cache-key resolution)
    can answer this without placing a byte.
    """
    if not getattr(workload, "batchable", False):
        return False
    if len(workload.batch_dims) != len(args):
        raise ValueError(
            f"workload {workload.name!r} declares {len(workload.batch_dims)} "
            f"batch_dims but make_inputs produced {len(args)} inputs"
        )
    for arg, dim in zip(args, workload.batch_dims):
        shape = getattr(arg, "shape", ())
        if dim is not None and len(shape) > dim and shape[dim] % n_devices == 0:
            return True
    return False


def place_args(args: tuple, workload, mesh: Mesh, mode: str) -> tuple[tuple, str]:
    """Place workload inputs on ``mesh`` per the requested placement mode.

    Returns ``(placed_args, effective_mode)``: a ``shard`` request on a
    workload without ``batch_dims`` — or whose declared dims don't divide
    the mesh — degrades to ``replicate``, and the caller records the mode
    that actually happened.
    """
    if mode == "shard" and shard_applies(args, workload, mesh.size):
        shardings = workload_pspecs(workload, mesh)
        n = mesh.size
        placed = []
        for arg, dim, s in zip(args, workload.batch_dims, shardings):
            shape = getattr(arg, "shape", ())
            if dim is not None and len(shape) > dim and shape[dim] % n == 0:
                placed.append(jax.device_put(arg, s))
            else:
                placed.append(jax.device_put(arg, NamedSharding(mesh, P())))
        return tuple(placed), "shard"
    # Non-batchable, or every declared dim failed the divisibility check:
    # this is a plain replicated run and must share its compile-cache entry.
    return replicate(args, mesh), "replicate"


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
