"""Mellum2-12B-A2.5B — MoE code LM with window and full attention
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct; config.json].

28L, d_model 2304, 32 heads (GQA kv=4) head_dim 128, vocab 98304
(untied), RMSNorm eps 1e-6.  ``layer_types`` repeats (sliding, sliding,
sliding, full) seven times, window 1024.  Full layers use YaRN RoPE
(theta 5e5, factor 16 over 8192 original positions, beta 32 / 1,
attention factor 1.2772588722239782); sliding layers plain RoPE at
theta 5e5.  Every FFN is sparse: 64 SwiGLU experts of width 896, top-8,
renormalised, no shared expert.

``config()`` holds every expert; ``ep4_config()`` holds one chip's share
of a deployment that divides each layer's experts over 4 chips (experts
0-15; the router still scores all 64).
"""
import dataclasses
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="mellum2-12b-a2.5b", family="moe",
        num_layers=28, d_model=2304, num_heads=32, num_kv_heads=4,
        head_dim=128, d_ff=896, vocab_size=98304,
        pattern=(("local_attn", "moe"),) * 3 + (("attn", "moe"),),
        window=1024, num_experts=64, top_k=8,
        rope_theta=500000.0, yarn_factor=16.0, yarn_original_max_pos=8192,
        yarn_beta_fast=32.0, yarn_beta_slow=1.0,
        yarn_attention_factor=1.2772588722239782,
        mlp="swiglu", norm="rmsnorm", use_rope=True, tie_embeddings=False,
    )


def ep4_config() -> ModelConfig:
    return dataclasses.replace(config(), num_experts=16, router_experts=64,
                               expert_first=0)


def smoke_config() -> ModelConfig:
    """Two pattern units; 4 of 8 routed experts held, top-2."""
    return dataclasses.replace(
        config(), num_layers=8, d_model=64, num_heads=4, num_kv_heads=2,
        head_dim=16, d_ff=32, vocab_size=128, window=16, num_experts=4,
        router_experts=8, top_k=2)
