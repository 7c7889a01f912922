"""deepseek-v2-236b [moe]: MLA + fine-grained MoE (arXiv:2405.04434).

Published values from
https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json:
60L d_model=5120 128H; MLA q_lora 1536, kv_lora 512, rope 64 (YaRN:
factor 40 over 4096 original positions, beta_fast 32, beta_slow 1,
mscale = mscale_all_dim = 0.707, rope_theta 1e4), nope 128, v 128;
expert d_ff=1536, vocab=102400, untied; 2 shared + 160 routed experts,
top-6, group-limited greedy over 8 groups keeping 3, softmax scores,
top-k weights not renormalised and scaled by 16; rms_norm_eps 1e-6.
Layer 0 uses a dense FFN (d_ff 12288) per the published config.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    num_layers=60,
    d_model=5120,
    num_heads=128,
    num_kv_heads=128,          # MLA: latent cache; kv head count == q heads
    d_ff=1536,
    vocab_size=102400,
    head_dim=128,              # v head dim; qk dims come from MLAConfig
    activation="silu_glu",
    norm="rmsnorm",
    norm_eps=1e-6,
    rope_theta=10000.0,
    tie_embeddings=False,
    mla=MLAConfig(
        kv_lora_rank=512,
        q_lora_rank=1536,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_factor=40.0,
        rope_original_max_positions=4096,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_mscale=0.707,
        rope_mscale_all_dim=0.707,
    ),
    moe=MoEConfig(
        num_experts=160,
        top_k=6,
        expert_ff=1536,
        num_shared_experts=2,
        shared_ff=1536,
        expert_groups=8,
        top_k_groups=3,
        routed_scale=16.0,
        renormalize_top_k=False,
    ),
    dense_layer_prefix=1,
    dense_prefix_ff=12288,
)
