"""DeepSeek-V2-Lite: multi-head latent attention with a YaRN-scaled
rotary key, one leading dense layer, then 64 routed experts (top-6,
gates not renormalised) and 2 shared experts in each of 26 layers
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json]."""

from .base import ModelConfig, MoESpec, YarnSpec

YARN = YarnSpec(factor=40.0, original_max_position=4096, beta_fast=32.0,
                beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=10944,
        vocab=102400,
        rope_theta=10_000.0,
        moe=MoESpec(num_experts=64, top_k=6, d_ff=1408, n_shared=2,
                    norm_topk=False, dropless=True),
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_scaling=YARN,
        first_dense_layers=1,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-smoke",
        family="moe",
        n_layers=3,
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        d_ff=128,
        vocab=256,
        moe=MoESpec(num_experts=8, top_k=3, d_ff=32, n_shared=2,
                    norm_topk=False, dropless=True),
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        rope_scaling=YARN,
        first_dense_layers=1,
    )
