"""granite-4.0-h-micro [hybrid]: 40L d_model=2048 vocab=100352, tied -- 36
Mamba2 layers (64 heads of 64, state 128, 1 group, conv 4, chunk 256) and 4
GQA attention layers (32 query / 8 KV heads of 64, no positional encoding)
at layers 5, 15, 25, 35; a SwiGLU MLP (8192) after every mixer; muP
multipliers: embeddings x12, residual branches x0.22, logits /8, attention
scores x1/64. [huggingface.co/ibm-granite/granite-4.0-h-micro, config.json,
model_type granitemoehybrid, no experts]

The published ``rope_theta`` (10000) goes unused under
``position_embedding_type`` "nope": ``rope_theta=0`` here. ``attn_chunk``
sends 2048-token attention through the blocked ``chunked_sdpa``: the dense
path's f32 scores ([8, 32, 2048, 2048], 4.3 GB for an 8-row batch) do not
fit beside the training state on one 16 GB chip.
"""
from repro.config import ModelConfig

PATTERN = tuple("attention" if i % 10 == 5 else "mamba" for i in range(40))

CONFIG = ModelConfig(
    name="granite_4_0_h_micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100352,
    rope_theta=0.0,
    norm_eps=1e-5,
    act="swiglu",
    tie_embeddings=True,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_groups=1,
    ssm_conv_width=4,
    ssm_expand=2,
    ssm_chunk=256,
    layer_pattern=PATTERN,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    attention_scale=0.015625,
    attn_chunk=512,
)

SMOKE = ModelConfig(
    name="granite_h_smoke",
    family="hybrid",
    num_layers=3,
    d_model=64,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    d_ff=128,
    vocab_size=512,
    rope_theta=0.0,
    tie_embeddings=True,
    ssm_state=16,
    ssm_head_dim=16,
    ssm_groups=1,
    ssm_chunk=8,
    layer_pattern=("mamba", "attention", "mamba"),
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    attention_scale=1 / 16,
)
