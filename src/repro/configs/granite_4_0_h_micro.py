"""Granite-4.0-H-Micro 3B (ibm-granite/granite-4.0-h-micro, model type
``granitemoehybrid`` without experts): 36 Mamba2 layers and 4 NoPE GQA
attention layers (indices 5, 15, 25, 35), a SwiGLU MLP after every mixer,
and Granite's embedding, residual, attention and logit multipliers."""
from .base import ModelConfig, reduced

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100352,
    rope_theta=1e4,
    nope=True,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_conv_width=4,
    ssm_chunk=256,
    layer_types=_PERIOD * 4,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    tie_embeddings=True,
)
SMOKE = reduced(CONFIG)
