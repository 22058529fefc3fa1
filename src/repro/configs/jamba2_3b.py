"""AI21-Jamba2-3B — hybrid Mamba-1 + attention (1:13), dense SiLU MLPs.

[hf: ai21labs/AI21-Jamba2-3B config.json]
28L d_model=2560, Mamba d_inner=5120 d_state=16 d_conv=4 dt_rank=160 (conv
bias on, projection biases off), attention on layers ``i % 14 == 7`` (2 of
28; 20 query heads, 1 KV head, head size 128, no positional encoding),
``num_experts`` 1 so every layer has a dense MLP of width 8192, RMSNorm eps
1e-6, tied embeddings over a 65,536-token vocabulary.

Served precision (the stream step, ``models/jamba.py``): matrices and the
embedding in bf16, matmul inputs in bf16 with float32 accumulation; norms,
softmax, the SSM recurrence and its state in float32; conv state and KV
cache in bf16.
"""
from repro.config.core import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="jamba2-3b",
    family="jamba",
    num_layers=28,
    d_model=2560,
    num_heads=20,
    num_kv_heads=1,
    d_ff=8192,
    vocab_size=65_536,
    norm="rmsnorm",
    norm_eps=1e-6,
    activation="swiglu",
    tie_embeddings=True,
    ssm=SSMConfig(d_state=16, d_conv=4, expand=2, dt_rank=160),
    attn_every=14,
    attn_offset=7,
    max_seq_len=262_144,
    subquadratic=True,
    param_dtype="bfloat16",
)


def reduced() -> ModelConfig:
    """One period of 14 layers (13 Mamba + 1 attention) at test widths."""
    return ModelConfig(
        name="jamba2-3b-reduced",
        family="jamba",
        num_layers=14,
        d_model=64,
        num_heads=4,
        num_kv_heads=1,
        d_ff=128,
        vocab_size=512,
        norm="rmsnorm",
        norm_eps=1e-6,
        activation="swiglu",
        tie_embeddings=True,
        ssm=SSMConfig(d_state=4, d_conv=4, expand=2, dt_rank=8),
        attn_every=14,
        attn_offset=7,
        subquadratic=True,
    )
