"""nemotron-3-nano-30b-a3b [hybrid]: Mamba-2 mixers, sparse experts and
GQA attention, one mixer a block (Nemotron-H, arXiv:2504.03624).

Published values from
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json
(``model_type`` nemotron_h): 52 blocks in the hybrid_override_pattern
below, each x + mixer(RMSNorm(x)) with eps 1e-5; d_model 2688, vocab
131072, untied head.

  M  Mamba-2: 64 heads of 64 (inner 4096, not expand x d_model), state 128
     in 8 B/C groups, depthwise conv of width 4 with a bias, chunk 128, a
     gated RMSNorm per group of 512, no projection biases.
  E  128 routed experts, top-6, sigmoid scores chosen on score + a
     correction bias, weighted by the scores alone, renormalised and
     scaled by 2.5; each expert down(relu(up x)^2) of width 1856, plus one
     shared expert of that form, width 3712.
  *  GQA, 32 query and 2 KV heads of 128, no bias, no positional encoding:
     the NemotronH modelling code applies no rotary embedding (the
     config's rope_theta and partial_rotary_factor are read by nothing),
     so rope_theta is 0 here.
"""
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

CONFIG = ModelConfig(
    name="nemotron-3-nano-30b-a3b",
    family="hybrid",
    num_layers=52,
    d_model=2688,
    num_heads=32,
    num_kv_heads=2,
    d_ff=1856,
    vocab_size=131072,
    head_dim=128,
    activation="relu2",
    norm="rmsnorm",
    norm_eps=1e-5,
    rope_theta=0.0,
    tie_embeddings=False,
    layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    ssm=SSMConfig(state_dim=128, head_dim=64, num_heads=64, n_groups=8,
                  conv_width=4, conv_bias=True, chunk=128),
    moe=MoEConfig(
        num_experts=128,
        top_k=6,
        expert_ff=1856,
        num_shared_experts=1,
        shared_ff=3712,
        routed_scale=2.5,
        renormalize_top_k=True,
        score="sigmoid",
    ),
)
