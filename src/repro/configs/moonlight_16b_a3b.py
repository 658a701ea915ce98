"""Moonlight-16B-A3B — DeepSeek-V3 blocks: MLA, a leading dense layer, 64
routed experts top-6 by sigmoid score + correction bias, 2 shared experts
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]."""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    vocab=163840,
    n_heads=16,
    n_kv_heads=16,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    d_ff=11264,
    first_dense_layers=1,
    n_experts=64,
    experts_per_token=6,
    d_expert=1408,
    n_shared_experts=2,
    routed_scale=2.446,
    moe_impl="pallas",
)

SMOKE = ModelConfig(
    name="moonlight-smoke",
    family="moe",
    n_layers=3,
    d_model=64,
    vocab=512,
    n_heads=4,
    n_kv_heads=4,
    kv_lora_rank=32,
    qk_nope_head_dim=32,
    qk_rope_head_dim=16,
    v_head_dim=32,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    d_ff=128,
    first_dense_layers=1,
    n_experts=16,
    experts_per_token=4,
    d_expert=32,
    n_shared_experts=1,
    routed_scale=2.446,
    experts_held=4,
    dtype="float32",
)
