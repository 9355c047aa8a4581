"""The unified model: any ArchConfig → init / forward / loss / prefill / decode.

Structure (DESIGN.md §5):

- The layer stack is a ``lax.scan`` over *periods* (the smallest repeating
  unit of block kinds — 1 for dense, 2 for xLSTM, 8 for jamba), so compiled
  HLO size is O(period), not O(depth), and the remat policy wraps the scan
  body.
- Parameters are a tuple over period positions of per-kind dicts, with every
  leaf stacked over periods (leading dim ``n_periods``).
- Decode caches mirror the parameter structure: attention positions carry
  (k, v) ring/linear buffers, mamba positions carry (h, conv), xLSTM
  positions carry their cell states. ``lax.scan`` threads (params, cache)
  together and emits the updated cache as scan outputs.
- ``shard_activation`` is an injection point: the launch layer passes a
  function applying ``with_sharding_constraint`` to the residual stream
  (batch over data axes; sequence over model for SP) without the model
  depending on any mesh.
- DeepSeek-V3 stacks (``attention="mla"``) take the checkpoint's own
  tensors as their parameters: a flat dict under the checkpoint's names,
  [in, out] oriented, in which the leading dense layers keep their own
  names (``model.layers.0.``) and the MoE layers' tensors are stacked on a
  leading axis under ``model.layers.*.`` (the held experts on a second one,
  ``mlp.experts.*.``). The dense layers run before the scan over that axis;
  the cache is each layer's MLA latent under the same prefixes.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import mla, ssm
from repro.models.config import ArchConfig
from repro.models.layers import (
    apply_attention,
    apply_mlp,
    dtype_of,
    init_attention,
    init_dense,
    init_mlp,
    rms_norm,
)
from repro.models.moe import (
    BIAS,
    EXPERTS,
    ROUTER,
    SHARED,
    apply_moe,
    apply_moe_dropless,
    init_moe,
    swiglu,
)

__all__ = ["Model"]

ShardFn = Callable[[jax.Array, str], jax.Array]


def _identity_shard(x: jax.Array, name: str) -> jax.Array:
    return x


# ---------------------------------------------------------------------------
# Per-kind block init / full-sequence apply / single-step apply.
# ---------------------------------------------------------------------------


def _init_block(kind: str, key, cfg: ArchConfig) -> dict:
    dt = dtype_of(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    if kind in ("attn_mlp", "attn_moe"):
        ffn = init_moe(k2, cfg) if kind == "attn_moe" else init_mlp(k2, cfg)
        return {
            "ln1": jnp.ones((cfg.d_model,), dt),
            "mixer": init_attention(k1, cfg),
            "ln2": jnp.ones((cfg.d_model,), dt),
            "ffn": ffn,
        }
    if kind in ("mamba_mlp", "mamba_moe", "mamba"):
        out = {
            "ln1": jnp.ones((cfg.d_model,), dt),
            "mixer": ssm.init_mamba(k1, cfg),
        }
        if kind != "mamba":
            out["ln2"] = jnp.ones((cfg.d_model,), dt)
            out["ffn"] = init_moe(k2, cfg) if kind == "mamba_moe" else init_mlp(k2, cfg)
        return out
    if kind == "mlstm":
        return ssm.init_mlstm(k1, cfg)
    if kind == "slstm":
        return ssm.init_slstm(k1, cfg)
    raise ValueError(kind)


def _cache_len(cfg: ArchConfig, max_len: int) -> int:
    return min(cfg.window, max_len) if cfg.window else max_len


def _init_block_cache(kind: str, cfg: ArchConfig, batch: int, max_len: int):
    dt = dtype_of(cfg)
    if kind.startswith("attn"):
        s = _cache_len(cfg, max_len)
        return {
            "k": jnp.zeros((batch, s, cfg.n_kv_heads, cfg.head_dim), dt),
            "v": jnp.zeros((batch, s, cfg.n_kv_heads, cfg.head_dim), dt),
        }
    if kind.startswith("mamba"):
        return ssm.init_state_mamba(cfg, batch)
    if kind == "mlstm":
        return ssm.init_state_mlstm(cfg, batch)
    if kind == "slstm":
        return ssm.init_state_slstm(cfg, batch)
    raise ValueError(kind)


def _apply_block_full(
    kind: str,
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
    shard: ShardFn,
    max_len: int,
):
    """Full-sequence block application. Returns (x, cache_entry)."""
    if kind.startswith("attn"):
        h, (k, v) = apply_attention(
            p["mixer"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps), positions
        )
        x = shard(x + h, "residual")
        ffn_in = rms_norm(x, p["ln2"], cfg.norm_eps)
        if kind == "attn_moe":
            ffn = apply_moe(p["ffn"], cfg, shard(ffn_in, "moe_in"))
        else:
            ffn = apply_mlp(p["ffn"], ffn_in)
        x = shard(x + ffn, "residual")
        cache = _kv_to_cache(cfg, k, v, max_len)
        return x, cache
    if kind.startswith("mamba"):
        h, state = ssm.apply_mamba(p["mixer"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps))
        x = shard(x + h, "residual")
        if kind != "mamba":
            ffn_in = rms_norm(x, p["ln2"], cfg.norm_eps)
            if kind == "mamba_moe":
                ffn = apply_moe(p["ffn"], cfg, shard(ffn_in, "moe_in"))
            else:
                ffn = apply_mlp(p["ffn"], ffn_in)
            x = shard(x + ffn, "residual")
        return x, state
    if kind == "mlstm":
        x, state = ssm.apply_mlstm(p, cfg, x)
        return shard(x, "residual"), state
    if kind == "slstm":
        x, state = ssm.apply_slstm(p, cfg, x)
        return shard(x, "residual"), state
    raise ValueError(kind)


def _kv_to_cache(cfg: ArchConfig, k: jax.Array, v: jax.Array, max_len: int):
    """Pack prefill K/V (B, T, KV, hd) into the decode cache layout.

    Token at absolute position p lives at slot p (linear cache) or p % W
    (sliding-window ring buffer) — decode continues the same convention.
    """
    B, T, KV, hd = k.shape
    s = _cache_len(cfg, max_len)
    if cfg.window and T >= s:
        last_k, last_v = k[:, -s:], v[:, -s:]
        pos = jnp.arange(T - s, T) % s
        ck = jnp.zeros((B, s, KV, hd), k.dtype).at[:, pos].set(last_k)
        cv = jnp.zeros((B, s, KV, hd), v.dtype).at[:, pos].set(last_v)
        return {"k": ck, "v": cv}
    pad = s - min(T, s)
    t = min(T, s)
    ck = jnp.pad(k[:, :t], ((0, 0), (0, pad), (0, 0), (0, 0)))
    cv = jnp.pad(v[:, :t], ((0, 0), (0, pad), (0, 0), (0, 0)))
    return {"k": ck, "v": cv}


def _apply_block_step(
    kind: str,
    p: dict,
    cfg: ArchConfig,
    x_t: jax.Array,
    cache: dict,
    pos: jax.Array,
    positions_t: jax.Array,
):
    """Single-token block application. x_t (B, d). Returns (x_t, cache)."""
    if kind.startswith("attn"):
        from repro.models.layers import project_qkv, sdpa

        B, d = x_t.shape
        xn = rms_norm(x_t, p["ln1"], cfg.norm_eps)[:, None, :]  # (B, 1, d)
        q, k, v = project_qkv(p["mixer"], cfg, xn, positions_t)
        s = cache["k"].shape[1]
        slot = pos % s
        ck = jax.lax.dynamic_update_index_in_dim(cache["k"], k[:, 0], slot, axis=1)
        cv = jax.lax.dynamic_update_index_in_dim(cache["v"], v[:, 0], slot, axis=1)
        kv_len = jnp.minimum(pos + 1, s)
        # Ring/linear cache: every stored key is a valid past token; mask
        # only unfilled slots (order-independence of attention lets the ring
        # rotation stand — RoPE was applied at absolute positions).
        out = sdpa(q, ck, cv, causal=False, window=None, kv_len=kv_len)
        h = out.reshape(B, cfg.n_heads * cfg.head_dim) @ p["mixer"]["wo"]
        x_t = x_t + h
        ffn_in = rms_norm(x_t, p["ln2"], cfg.norm_eps)
        if kind == "attn_moe":
            ffn = apply_moe(p["ffn"], cfg, ffn_in[:, None, :])[:, 0]
        else:
            ffn = apply_mlp(p["ffn"], ffn_in)
        return x_t + ffn, {"k": ck, "v": cv}
    if kind.startswith("mamba"):
        h, state = ssm.step_mamba(
            p["mixer"], cfg, rms_norm(x_t, p["ln1"], cfg.norm_eps), cache
        )
        x_t = x_t + h
        if kind != "mamba":
            ffn_in = rms_norm(x_t, p["ln2"], cfg.norm_eps)
            if kind == "mamba_moe":
                ffn = apply_moe(p["ffn"], cfg, ffn_in[:, None, :])[:, 0]
            else:
                ffn = apply_mlp(p["ffn"], ffn_in)
            x_t = x_t + ffn
        return x_t, state
    if kind == "mlstm":
        return ssm.step_mlstm(p, cfg, x_t, cache)
    if kind == "slstm":
        return ssm.step_slstm(p, cfg, x_t, cache)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# DeepSeek-V3 stacks: MLA + leading dense layers + dropless MoE, on the
# checkpoint's own tensors.
# ---------------------------------------------------------------------------

EMBED = "model.embed_tokens.weight"
NORM = "model.norm.weight"
HEAD = "lm_head.weight"
LN1 = "input_layernorm.weight"
LN2 = "post_attention_layernorm.weight"
DENSE = "mlp.{}_proj.weight"
STACK = "model.layers.*."


def dense_prefix(i: int) -> str:
    return f"model.layers.{i}."


def ckpt_layout(cfg: ArchConfig) -> dict[str, tuple[tuple[int, ...], Any, float | str]]:
    """Every tensor of a DeepSeek-V3 stack: name -> (shape, dtype, init),
    init being a normal's scale, ``"ones"`` (norms) or ``"zeros"``."""
    dt, f32 = dtype_of(cfg), jnp.float32
    d, H, n, r = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.kv_lora_rank
    e, v, f, E = cfg.qk_rope_dim, cfg.v_head_dim, cfg.d_ff, cfg.n_experts
    out = 0.02 / (2 * cfg.n_layers) ** 0.5
    first, stop = cfg.held

    def attn(lead):
        return {
            LN1: (lead + (d,), dt, "ones"),
            mla.Q: (lead + (d, H * (n + e)), dt, 0.02),
            mla.KV_A: (lead + (d, r + e), dt, 0.02),
            mla.KV_NORM: (lead + (r,), dt, "ones"),
            mla.KV_B: (lead + (r, H * (n + v)), dt, 0.02),
            mla.O: (lead + (H * v, d), dt, out),
            LN2: (lead + (d,), dt, "ones"),
        }

    def mlp(name, lead, width):
        return {
            name.format("gate"): (lead + (d, width), dt, 0.02),
            name.format("up"): (lead + (d, width), dt, 0.02),
            name.format("down"): (lead + (width, d), dt, out),
        }

    layout = {EMBED: ((cfg.vocab, d), dt, 0.02)}
    for i in range(cfg.first_dense):
        layer = attn(()) | mlp(DENSE, (), cfg.dense_d_ff)
        layout |= {dense_prefix(i) + k: s for k, s in layer.items()}
    L = (cfg.n_periods,)
    layer = (
        attn(L)
        | {ROUTER: (L + (d, E), f32, 0.02), BIAS: (L + (E,), f32, "zeros")}
        | mlp(EXPERTS, L + (stop - first,), f)
        | mlp(SHARED, L, cfg.n_shared_experts * f)
    )
    layout |= {STACK + k: s for k, s in layer.items()}
    layout |= {NORM: ((d,), dt, "ones"), HEAD: ((d, cfg.vocab), dt, 0.02)}
    return layout


def _init_ckpt(key, cfg: ArchConfig) -> dict:
    params = {}
    for i, (name, (shape, dt, init)) in enumerate(ckpt_layout(cfg).items()):
        if init == "ones":
            params[name] = jnp.ones(shape, dt)
        elif init == "zeros":
            params[name] = jnp.zeros(shape, dt)
        else:
            w = jax.random.truncated_normal(jax.random.fold_in(key, i), -2, 2, shape)
            params[name] = (init * w).astype(dt)
    return params


def _layer(params: dict, prefix: str) -> dict:
    """One layer's tensors (or the stack's), keyed without ``prefix``."""
    return {k[len(prefix):]: w for k, w in params.items() if k.startswith(prefix)}


def _ckpt_ffn(p, cfg: ArchConfig, x: jax.Array, moe: bool):
    """The layer's FFN on the normed residual (N, d) -> (out, held-expert
    token counts or None)."""
    xn = rms_norm(x, p[LN2], cfg.norm_eps)
    if moe:
        with jax.named_scope("moe"):
            return apply_moe_dropless(p, cfg, xn)
    return swiglu(xn, *(p[DENSE.format(w)] for w in ("gate", "up", "down"))), None


def _ckpt_block(p, cfg: ArchConfig, x, positions, shard: ShardFn, moe: bool):
    """One full-sequence layer: x (B, T, d) -> (x, latent, held-expert
    token counts or None)."""
    with jax.named_scope("mla"):
        h, latent = mla.attend(p, cfg, rms_norm(x, p[LN1], cfg.norm_eps), positions)
    x = shard(x + h, "residual")
    B, T, d = x.shape
    ffn, counts = _ckpt_ffn(p, cfg, x.reshape(B * T, d), moe)
    return shard(x + ffn.reshape(B, T, d), "residual"), latent, counts


def _ckpt_block_step(p, cfg: ArchConfig, x_t, cache, pos, moe: bool):
    """One decode step of one layer: x_t (B, d) -> (x_t, cache)."""
    with jax.named_scope("mla"):
        h, cache = mla.attend_step(p, cfg, rms_norm(x_t, p[LN1], cfg.norm_eps), cache, pos)
    x_t = x_t + h
    return x_t + _ckpt_ffn(p, cfg, x_t, moe)[0], cache


# ---------------------------------------------------------------------------
# The model.
# ---------------------------------------------------------------------------


class Model:
    def __init__(
        self,
        cfg: ArchConfig,
        *,
        shard_activation: ShardFn | None = None,
        remat: bool = True,
        scan_unroll: bool = False,
    ):
        cfg.validate()
        self.cfg = cfg
        self.period = cfg.block_period()
        self.shard = shard_activation or _identity_shard
        self.remat = remat
        # scan_unroll=True unrolls the layer scan — used by the dry-run's
        # cost-analysis pair (XLA counts while bodies once; an unrolled pair
        # at depth 1/2 periods yields the exact per-period cost delta).
        self.scan_unroll = scan_unroll

    # ---- parameters -------------------------------------------------------
    def init(self, key) -> dict:
        cfg = self.cfg
        if cfg.attention == "mla":
            return _init_ckpt(key, cfg)
        dt = dtype_of(cfg)
        n_posns = len(self.period)
        keys = jax.random.split(key, cfg.n_layers + 3)
        periods = []
        for n in range(cfg.n_periods):
            periods.append(
                tuple(
                    _init_block(kind, keys[n * n_posns + i], cfg)
                    for i, kind in enumerate(self.period)
                )
            )
        blocks = jax.tree.map(lambda *xs: jnp.stack(xs), *periods)
        params: dict[str, Any] = {
            "blocks": blocks,
            "ln_f": jnp.ones((cfg.d_model,), dt),
        }
        params["embed"] = init_dense(keys[-1], cfg.vocab, cfg.d_model, dt)
        if not cfg.tie_embeddings:
            params["unembed"] = init_dense(keys[-2], cfg.d_model, cfg.vocab, dt)
        return params

    # ---- shared pieces ----------------------------------------------------
    def _embed_in(self, params, batch) -> tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        if cfg.input_mode == "embeds":
            x = batch["embeds"].astype(dtype_of(cfg))
        else:
            x = jnp.take(params["embed"], batch["tokens"], axis=0)
        B, T = x.shape[:2]
        if "positions" in batch:
            positions = batch["positions"]
        else:
            positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
            if cfg.rope == "mrope":
                positions = jnp.broadcast_to(positions[..., None], (B, T, 3))
        return self.shard(x, "embed"), positions

    def _unembed(self, params, x: jax.Array) -> jax.Array:
        w = params["embed"].T if self.cfg.tie_embeddings else params["unembed"]
        logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
        return self.shard(logits, "logits")

    # ---- DeepSeek-V3 stacks -------------------------------------------------
    def _ckpt_prefill(self, params, batch, max_len: int, remat: bool = False):
        """-> (final-normed x (B, T, d), cache, held-expert token counts
        (MoE layers, held))."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, T = tokens.shape
        assert T <= max_len, (T, max_len)
        x = self.shard(jnp.take(params[EMBED], tokens, axis=0), "embed")
        positions = batch.get("positions", jnp.broadcast_to(jnp.arange(T)[None], (B, T)))

        def pad(latent):
            return jnp.pad(latent, ((0, 0), (0, max_len - T), (0, 0)))

        cache = {}
        for i in range(cfg.first_dense):
            x, latent, _ = _ckpt_block(
                _layer(params, dense_prefix(i)), cfg, x, positions, self.shard, moe=False
            )
            cache[dense_prefix(i)] = pad(latent)

        def body(x, p):
            x, latent, counts = _ckpt_block(p, cfg, x, positions, self.shard, moe=True)
            return x, (pad(latent), counts)

        body = jax.checkpoint(body) if remat else body
        x, (cache[STACK], counts) = jax.lax.scan(
            body, x, _layer(params, STACK), unroll=self.scan_unroll
        )
        return rms_norm(x, params[NORM], cfg.norm_eps), cache, counts

    def _ckpt_logits(self, params, x: jax.Array) -> jax.Array:
        return self.shard(mla.mm(x, params[HEAD]).astype(jnp.float32), "logits")

    def prefill_last(self, params, batch, max_len: int):
        """The serving prefill of a DeepSeek-V3 stack: returns (logits of the
        last position (B, V), cache, tokens routed to each held expert of
        each MoE layer (n_periods, held))."""
        x, cache, counts = self._ckpt_prefill(params, batch, max_len)
        return self._ckpt_logits(params, x[:, -1]), cache, counts

    # ---- training / encoder forward ----------------------------------------
    def forward(self, params, batch) -> jax.Array:
        cfg = self.cfg
        if cfg.attention == "mla":
            x, _, _ = self._ckpt_prefill(params, batch, batch["tokens"].shape[1], self.remat)
            return self._ckpt_logits(params, x)
        x, positions = self._embed_in(params, batch)
        T = x.shape[1]

        def period_body(x, period_params):
            for i, kind in enumerate(self.period):
                x, _ = _apply_block_full(
                    kind, period_params[i], cfg, x, positions, self.shard, T
                )
            return x, None

        body = jax.checkpoint(period_body) if self.remat else period_body
        x, _ = jax.lax.scan(body, x, params["blocks"], unroll=self.scan_unroll)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        return self._unembed(params, x)

    def loss_fn(self, params, batch) -> tuple[jax.Array, dict]:
        logits = self.forward(params, batch)  # (B, T, V) fp32
        labels = batch["labels"]
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = logz - gold
        mask = batch.get("loss_mask", jnp.ones_like(nll))
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss, {"loss": loss, "tokens": jnp.sum(mask)}

    # ---- serving ------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        if cfg.attention == "mla":
            width, dt = (batch, max_len, cfg.kv_lora_rank + cfg.qk_rope_dim), dtype_of(cfg)
            cache = {dense_prefix(i): jnp.zeros(width, dt) for i in range(cfg.first_dense)}
            cache[STACK] = jnp.zeros((cfg.n_periods,) + width, dt)
            return cache
        one_period = tuple(
            _init_block_cache(kind, cfg, batch, max_len) for kind in self.period
        )
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_periods,) + x.shape),
            one_period,
        )

    def prefill(self, params, batch, max_len: int):
        """Run the prompt; returns (cache, logits (B, T, V))."""
        cfg = self.cfg
        if cfg.attention == "mla":
            x, cache, _ = self._ckpt_prefill(params, batch, max_len)
            return cache, self._ckpt_logits(params, x)
        x, positions = self._embed_in(params, batch)
        T = x.shape[1]

        def period_body(x, period_params):
            entries = []
            for i, kind in enumerate(self.period):
                x, entry = _apply_block_full(
                    kind, period_params[i], cfg, x, positions, self.shard, max_len
                )
                entries.append(entry)
            return x, tuple(entries)

        x, cache = jax.lax.scan(
            period_body, x, params["blocks"], unroll=self.scan_unroll
        )
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        return cache, self._unembed(params, x)

    def decode_step(self, params, cache, tokens: jax.Array, pos: jax.Array):
        """One token step. tokens (B,) int32, pos scalar absolute position.
        Returns (logits (B, V), new cache)."""
        cfg = self.cfg
        if cfg.attention == "mla":
            return self._ckpt_decode_step(params, cache, tokens, pos)
        x_t = jnp.take(params["embed"], tokens, axis=0)  # (B, d)
        B = x_t.shape[0]
        positions_t = jnp.broadcast_to(pos[None, None], (B, 1))
        if cfg.rope == "mrope":
            positions_t = jnp.broadcast_to(positions_t[..., None], (B, 1, 3))

        def period_body(x_t, inp):
            period_params, period_cache = inp
            new_entries = []
            for i, kind in enumerate(self.period):
                x_t, entry = _apply_block_step(
                    kind, period_params[i], cfg, x_t, period_cache[i], pos, positions_t
                )
                new_entries.append(entry)
            return x_t, tuple(new_entries)

        x_t, new_cache = jax.lax.scan(
            period_body, x_t, (params["blocks"], cache), unroll=self.scan_unroll
        )
        x_t = rms_norm(x_t, params["ln_f"], cfg.norm_eps)
        logits = self._unembed(params, x_t)
        return logits, new_cache

    def _ckpt_decode_step(self, params, cache, tokens, pos):
        cfg = self.cfg
        x_t = jnp.take(params[EMBED], tokens, axis=0)
        new = {}
        for i in range(cfg.first_dense):
            prefix = dense_prefix(i)
            x_t, new[prefix] = _ckpt_block_step(
                _layer(params, prefix), cfg, x_t, cache[prefix], pos, moe=False
            )

        def body(x_t, inp):
            p, layer_cache = inp
            return _ckpt_block_step(p, cfg, x_t, layer_cache, pos, moe=True)

        x_t, new[STACK] = jax.lax.scan(
            body, x_t, (_layer(params, STACK), cache[STACK]), unroll=self.scan_unroll
        )
        return self._ckpt_logits(params, rms_norm(x_t, params[NORM], cfg.norm_eps)), new
