"""Mellum2-12B-A2.5B [hf:JetBrains/Mellum2-12B-A2.5B-Instruct, config.json]:
a code model of 28 layers, GQA 32 query / 4 key-value heads of 128, three
sliding-window layers (window 1024) then one full-attention layer, seven
times; every layer's FFN is 64 experts of width 896, top-8 with the softmax
renormalized over the 8, no shared expert; plain RoPE (theta 5e5) on the
sliding layers, YaRN (factor 16 from 8192 positions) on the full ones.

Held here: experts 0-15 of each layer, one chip's share of the model served
expert-parallel over the four chips of a v5e-4 host (16 experts per chip,
attention, router, norms, embedding and head on every chip).
"""
from .base import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b", family="moe",
    n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4, d_head=128,
    d_ff=896, vocab=98304,
    n_experts=64, top_k=8, experts_held=16,
    window=1024, layer_types=("sliding", "sliding", "sliding", "full"),
    rope_theta=5e5,
    yarn=Yarn(factor=16.0, original_max_position_embeddings=8192, beta_fast=32.0,
              beta_slow=1.0, attention_factor=1.2772588722239782),
    norm_eps=1e-6,
)
