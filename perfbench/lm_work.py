"""The work of one prefill call of a DeepSeek-V3 stack, from the widths in
its configuration file (the Hugging Face ``config.json`` keys) and the
call's ``batch`` and ``seq`` (``overrides``).

Multiply-adds count as two operations. Per token and layer: MLA's four
projections (q, kv_a, kv_b, o), the dense SwiGLU on the leading layers,
and on the MoE layers the router, the shared experts and the routed
experts at num_experts_per_tok x held / router-width experts a token (the
share of the routed work this chip does). Attention counts causal
(query, key) pairs: per pair and head, a (nope + rope)-wide score and a
v-wide value. The head runs at the last position only.
"""

from __future__ import annotations


def prefill_ops(config: dict) -> float:
    c, o = config, config["overrides"]
    batch, seq = o["batch"], o["seq"]
    d, H, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    n, e, v = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    layers, dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    router = c["deployment"]["n_routed_experts"]
    proj = d * H * (n + e) + d * (r + e) + r * H * (n + v) + H * v * d
    experts = c["n_shared_experts"] + c["num_experts_per_tok"] * c["n_routed_experts"] / router
    moe = 3 * d * c["moe_intermediate_size"] * experts + d * router
    per_token = layers * proj + dense * 3 * d * c["intermediate_size"] + (layers - dense) * moe
    pairs = batch * seq * (seq + 1) / 2
    attention = layers * pairs * H * (n + e + v)
    return 2.0 * (batch * seq * per_token + attention + batch * d * c["vocab_size"])
