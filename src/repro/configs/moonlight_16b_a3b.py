"""moonlight-16b-a3b [moe]: 27L d=2048, MLA (16 heads, latent 512, rope 64,
nope 128, v 128), 1 dense layer (d_ff 11264) then 26 MoE layers of 64
routed experts (width 1408, top-6, sigmoid scores with a correction bias,
renormalised and scaled by 2.446) plus 2 shared, vocab=163840.

[https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json;
model_type deepseek_v3]
"""
from ..arch.config import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe", n_layers=27, d_model=2048,
    n_heads=16, n_kv_heads=16, d_ff=1408, vocab_size=163840, head_dim=192,
    rope_theta=50000.0, norm_eps=1e-5,
    n_experts=64, n_experts_active=6, n_shared_experts=2, shared_d_ff=2816,
    router="sigmoid", norm_topk=True, routed_scale=2.446,
    n_dense_layers=1, dense_d_ff=11264,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    notes="full attention -> long_500k skipped",
)


def smoke_config() -> ArchConfig:
    return ArchConfig(
        name="moonlight-smoke", family="moe", n_layers=3, d_model=32,
        n_heads=2, n_kv_heads=2, d_ff=16, vocab_size=256, head_dim=24,
        rope_theta=50000.0, norm_eps=1e-5,
        n_experts=16, n_experts_active=4, n_shared_experts=2, shared_d_ff=32,
        router="sigmoid", norm_topk=True, routed_scale=2.446,
        n_dense_layers=1, dense_d_ff=64,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16,
    )
