"""granite-4.0-h-small [hybrid moe]: Mamba2 and NoPE attention layers in one
stack, each followed by a dropless MoE of 72 experts (top-10) and a shared
SwiGLU expert [hf:ibm-granite/granite-4.0-h-small].
40L d_model=4096 32H(kv=8) head_dim=128, NoPE; Mamba2 128 heads x 64,
d_state=128, d_conv=4, n_groups=1, expand 2, conv bias; expert d_ff=768,
shared d_ff=1536; vocab=100352; rms_norm_eps=1e-5; embedding x12,
attention scores x0.0078125, mixer and MLP outputs x0.22 before each
residual add, logits / 16.  The layer pattern has period 10: five Mamba2
layers, one attention layer, four Mamba2 layers.

``PUBLISHED`` is the model; ``CONFIG`` is what one chip serves of it when
8 chips share each layer by expert parallelism and 4 pipeline stages hold
10 layers each: one whole period of the pattern (layers 0-9) and experts
0-8 of each layer's 72, with the full vocabulary and a separate lm_head
(published: tied)."""

import dataclasses

from ..config import ModelConfig

PUBLISHED = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=768,
    vocab_size=100352,
    block_pattern=("mamba2_moe",) * 5 + ("moe",) + ("mamba2_moe",) * 4,
    d_head=128,
    rope=False,
    n_experts=72,
    n_experts_active=10,
    moe_d_ff=768,
    shared_d_ff=1536,
    ssm_state=128,
    ssm_conv=4,
    ssm_expand=2,
    ssm_head_dim=64,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    attention_multiplier=0.0078125,
    residual_multiplier=0.22,
    logits_scaling=16.0,
)

CONFIG = dataclasses.replace(PUBLISHED, n_layers=10, experts_held=9)

# both mixer kinds, two shares of experts (4 of 8 held) and the shared
# expert; attention_multiplier: 1/sqrt(d_head) at the smoke head width of 16
SMOKE = dataclasses.replace(
    CONFIG, n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
    d_ff=32, vocab_size=512, n_experts=8, n_experts_active=3, moe_d_ff=32,
    experts_held=4, shared_d_ff=48, ssm_state=16, ssm_head_dim=16,
    block_pattern=("mamba2_moe", "mamba2_moe", "moe", "mamba2_moe"),
    attention_multiplier=0.25)
