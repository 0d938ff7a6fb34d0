"""granite-moe-1b-a400m [moe]: 32 experts top-8, dropless
[hf:ibm-granite/granite-3.0-1b-a400m-base].
24L d_model=1024 16H(kv=8) expert d_ff=512 vocab=49155; rms_norm_eps=1e-6;
embedding x12, attention scores x0.015625, attention and MoE outputs x0.22
before each residual add, logits / 6.  Departure: a separate lm_head
(published: tied)."""

import dataclasses

from ..config import ModelConfig

CONFIG = ModelConfig(
    name="granite-moe-1b-a400m",
    family="moe",
    n_layers=24,
    d_model=1024,
    n_heads=16,
    n_kv_heads=8,
    d_ff=512,
    vocab_size=49155,
    block_pattern=("moe",),
    n_experts=32,
    n_experts_active=8,
    moe_d_ff=512,
    norm_eps=1e-6,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=6.0,
)

# attention_multiplier: 1/sqrt(d_head) at the smoke head width of 16
SMOKE = dataclasses.replace(
    CONFIG, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=64,
    vocab_size=512, n_experts=8, n_experts_active=2, moe_d_ff=64,
    attention_multiplier=0.25)
