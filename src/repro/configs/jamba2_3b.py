"""jamba2-3b — AI21-Jamba2-3B: hybrid Mamba-1 + attention, dense MLP.

[hf:ai21labs/AI21-Jamba2-3B config.json]
28L d_model=2560 20H (kv=1, head_dim 128) d_ff=8192 vocab=65536, tied
embeddings, rms_norm_eps 1e-6.

Layer pattern (attn_layer_period 14, attn_layer_offset 7): layers 7 and
21 are attention, the other 26 Mamba-1 (d_state 16, d_conv 4, expand 2,
dt_rank 160, conv bias, no projection bias).  ``num_experts`` is 1, so
every layer has a dense gated SiLU MLP.  Attention uses no positional
encoding.
"""
from repro.configs.base import ModelConfig, SSMConfig, register

CONFIG = register(ModelConfig(
    name="jamba2-3b",
    family="hybrid",
    n_layers=28,
    d_model=2560,
    n_heads=20,
    n_kv_heads=1,
    head_dim=128,
    d_ff=8192,
    vocab_size=65536,
    rope_theta=0.0,          # Jamba attention layers use no positional encoding
    tie_embeddings=True,
    act_fn="silu",
    norm_eps=1e-6,
    attn_every=14,
    attn_offset=7,
    ssm=SSMConfig(kind="mamba1", d_state=16, d_conv=4, expand=2,
                  dt_rank=160),
    source="https://huggingface.co/ai21labs/AI21-Jamba2-3B",
))
