"""Qwen3-MoE 30B-A3B [https://huggingface.co/Qwen/Qwen3-30B-A3B/blob/main/config.json].

48L d_model=2048 32H (GQA kv=4, head_dim 128, per-head QK-norm, no bias)
per-expert d_ff=768 vocab=151936 untied, MoE 128 experts top-8 in every
layer (renormalised top-k gates), router aux loss weight 0.001.
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="qwen3-moe-30b-a3b",
    arch_type="moe",
    num_layers=48,
    d_model=2048,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=0,
    vocab_size=151936,
    activation="swiglu",
    qk_norm=True,
    rope_theta=1e6,
    norm_eps=1e-6,
    tie_embeddings=False,
    moe=MoEConfig(num_experts=128, top_k=8, expert_d_ff=768,
                  router_aux_weight=0.001),
    source="https://huggingface.co/Qwen/Qwen3-30B-A3B/blob/main/config.json",
)
