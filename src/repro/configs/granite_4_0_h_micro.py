"""granite-4.0-h-micro [hybrid] — 40L d_model=2048 vocab=100352, tied
embeddings; Mamba-2 layers with attention at layers 5, 15, 25 and 35 (four
periods of 5 Mamba, 1 attention, 4 Mamba), each layer with its own SwiGLU
MLP of width 8192.
[https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json,
model_type granitemoehybrid]

Mamba-2 mixer: 64 heads of 64 (d_inner 4096), d_state 128, one group, conv
4 with bias, chunk 256, gated RMSNorm, no projection bias. Attention: GQA
32 query and 8 KV heads of 64, no position embedding (``nope``), softmax
scale ``attention_multiplier`` 1/64. Embeddings x12, each residual branch
x0.22, logits /8. Published with bfloat16 weights.
"""
import jax.numpy as jnp

from .base import ModelConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,                   # shared_intermediate_size: every layer
    vocab_size=100352,
    rms_eps=1e-5,
    rope_theta=10000,            # published, unused under nope
    tie_embeddings=True,
    ssm_state=128,
    ssm_expand=2,                # d_inner = 4096 -> 64 heads of 64
    ssm_headdim=64,
    ssm_conv=4,
    ssm_chunk=256,
    ssm_ngroups=1,
    layer_types=_PERIOD * 4,
    position_embedding_type="nope",
    attention_multiplier=0.015625,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    dtype=jnp.bfloat16,
    param_dtype=jnp.bfloat16,
)
