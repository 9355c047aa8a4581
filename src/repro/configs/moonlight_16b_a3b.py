"""moonlight-16b-a3b — DeepSeek-V3 block at hidden 2048
[https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json].

27L, d_model 2048, 16 heads of multi-head latent attention (kv_lora_rank
512, q_lora_rank none, qk_nope 128 + qk_rope 64, v 128, rope_theta 5e4),
one leading dense SwiGLU layer of width 11264, then 26 MoE layers: 64
routed experts of width 1408, top-6, 2 shared experts, a sigmoid router
with a score-correction bias (noaux_tc, one group), normalised top-k
weights times 2.446. rms_norm_eps 1e-5, untied vocab 163840, context 8192.
"""

from repro.models.config import ArchConfig


def config() -> ArchConfig:
    return ArchConfig(
        name="moonlight-16b-a3b",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        head_dim=128,
        d_ff=1408,
        vocab=163840,
        attention="mla",
        kv_lora_rank=512,
        qk_rope_dim=64,
        v_head_dim=128,
        rope_theta=5e4,
        n_experts=64,
        top_k=6,
        first_dense=1,
        dense_d_ff=11264,
        n_shared_experts=2,
        router="sigmoid",
        routed_scale=2.446,
        norm_eps=1e-5,
        attn_chunk=512,
        score_dtype="bfloat16",
        notes="MLA latent cache; 64 experts top-6 + 2 shared, sigmoid router",
    )


def smoke_config() -> ArchConfig:
    return ArchConfig(
        name="moonlight-16b-a3b-smoke",
        family="moe",
        n_layers=3,
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        head_dim=16,
        d_ff=32,
        vocab=256,
        attention="mla",
        kv_lora_rank=32,
        qk_rope_dim=8,
        v_head_dim=16,
        rope_theta=5e4,
        n_experts=8,
        top_k=3,
        first_dense=1,
        dense_d_ff=128,
        n_shared_experts=2,
        router="sigmoid",
        routed_scale=2.446,
        attn_chunk=16,
    )
