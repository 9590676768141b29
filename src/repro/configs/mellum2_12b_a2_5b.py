"""mellum2-12b-a2.5b [moe]: 28L d_model=2304 32H (GQA kv=4, head_dim 128)
vocab=98304, 64 SiLU-gated experts of width 896, top-8 renormalised, no
shared expert.  Layers repeat sliding, sliding, sliding, full: a 1024-token
window with default rope (theta 5e5) on the sliding layers, YaRN (factor 16
over 8192 original positions) on the full ones.
[hf: JetBrains/Mellum2-12B-A2.5B-Instruct config.json]"""
from repro.configs.base import ArchConfig, Yarn

CONFIG = ArchConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=896,            # expert width; no layer is dense
    vocab_size=98304,
    n_experts=64,
    moe_topk=8,
    window=1024,
    layer_pattern=("L", "L", "L", "G"),
    mlp_kind="swiglu",
    pos="rope",
    rope_theta=500000.0,
    yarn=Yarn(factor=16.0, original_max_positions=8192, beta_fast=32.0,
              beta_slow=1.0, attention_factor=1.2772588722239782),
    norm="rmsnorm",
    source="[hf: JetBrains/Mellum2-12B-A2.5B-Instruct]",
)
