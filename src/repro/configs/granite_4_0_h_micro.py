"""Granite-4.0-H-Micro: 40 layers of hidden 2048, Mamba2 mixers with GQA
attention at layers 5, 15, 25 and 35, a dense SwiGLU MLP in every layer
(source: https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json).

Every width and count is the published one.  Numeric formats: weights
and activations in bfloat16, matmuls summing in float32; the SSM state,
its decays and dt in float32 (``nn/ssm.py``)."""
from repro.models.config import ModelConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,                     # shared_intermediate_size
    vocab_size=100352,
    ssm_state=128,
    ssm_heads=64,                  # of width 64: expand 2 -> d_inner 4096
    d_inner=4096,
    ssm_groups=1,
    conv_kernel=4,
    ssm_chunk=256,
    layer_types=_PERIOD * 4,
    position_embedding="nope",
    attention_multiplier=0.015625,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    norm_eps=1e-5,
    tie_embeddings=True,
    attn_chunk=512,
    remat=False,
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json",
)
