"""granite-4.0-h-micro — interleaved Mamba-2 and NoPE GQA attention layers
(9:1), every layer followed by a SwiGLU MLP; muP-style multipliers on the
embedding, the residual branches, the softmax and the logits.
[hf:ibm-granite/granite-4.0-h-micro; hf]"""
from repro.configs.base import ModelConfig, register

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

GRANITE_4_0_H_MICRO = register(ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100352,
    ssm_state_dim=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_conv_width=4,
    ssm_chunk=256,
    ssm_num_groups=1,
    layer_pattern=_PERIOD * 4,
    position_embedding="nope",
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    source="[hf:ibm-granite/granite-4.0-h-micro; hf]",
))
