"""deepseek-v2 — MLA attention, DeepSeekMoE with shared experts.

[hf:deepseek-ai/DeepSeek-V2 config.json; arXiv:2405.04434]
60L d_model=5120 128H MLA (q_lora 1536, kv_lora 512, nope 128, rope 64,
v 128) YaRN x40; layer 0 dense d_ff=12288, then 160 routed experts of
1536, top-6 over the best 3 of 8 groups, weights x16, plus 2 shared
experts; vocab=102400 untied
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2",
    family="moe",
    num_layers=60,
    d_model=5120,
    num_heads=128,
    num_kv_heads=128,
    head_dim=128,
    d_ff=12288,
    vocab_size=102400,
    rope_theta=10_000.0,
    head_pad_multiple=1,
    num_experts=160,
    experts_per_token=6,
    n_group=8,
    topk_group=3,
    routed_scaling_factor=16.0,
    norm_topk_prob=False,
    n_shared_experts=2,
    moe_d_ff=1536,
    first_k_dense=1,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
)
