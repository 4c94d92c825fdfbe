"""Mellum2-12B-A2.5B [hf:JetBrains/Mellum2-12B-A2.5B-Instruct]: GQA with
three sliding-window layers (window 1024, default RoPE) to every full
layer (YaRN RoPE), and a sparse MoE in every layer: 64 experts of
width 896, top-8, softmax router with renormalised top-k, no shared
expert.

``CONFIG`` is one chip's share of a four-chip expert-parallel
deployment: every layer's 64 experts divided over the 4 chips, 16
each, with attention data-parallel and all 28 layers on every chip.
This chip holds experts 0-15 of every layer; the router keeps its 64
outputs."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="mellum2-12b", family="moe", n_layers=28, d_model=2304,
    n_heads=32, n_kv_heads=4, head_dim=128, d_ff=896, vocab=98304,
    activation="swiglu", rope_theta=500000.0, sliding_window=1024,
    layer_kinds=("window", "window", "window", "full"),
    yarn=(16.0, 8192, 32.0, 1.0, 1.2772588722239782),
    n_experts=64, top_k=8, moe_layer_period=1, n_experts_held=16)

# the period of 4 kept; a window smaller than the test prompts
SMOKE = CONFIG.with_(n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
                     head_dim=16, d_ff=32, vocab=256, sliding_window=8,
                     yarn=(16.0, 64, 32.0, 1.0, 1.2772588722239782),
                     n_experts=16, top_k=4, n_experts_held=4, remat=False)
