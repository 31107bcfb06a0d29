"""Operations and bytes of a Jamba model's serving work (the selective
scan kernel, prefill and decode steps), computed from the shapes in its
``bench/configs`` file alone.

Nothing here imports the program: a later change to the kernel or to
the model cannot change what its roofline share is measured against.
The matmul counts are two operations per multiply-add of every
projection (the embedding lookup is none); the scan's and the conv's
elementwise work is not matmul work and is not counted.
"""
from __future__ import annotations

import math
from typing import Iterable, Tuple

DTYPE_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
               "u8": 1}


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return (d, h, cfg["num_key_value_heads"], hd, cfg["intermediate_size"],
            cfg["mamba_expand"] * d, cfg["mamba_d_state"],
            cfg["mamba_dt_rank"], cfg["mamba_d_conv"])


def n_attention(cfg: dict) -> int:
    """Layers ``i`` with ``i % attn_layer_period == attn_layer_offset``."""
    return sum(1 for i in range(cfg["num_hidden_layers"])
               if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"])


def n_mamba(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - n_attention(cfg)


def mamba_params(cfg: dict) -> int:
    """One Mamba-1 mixer: in_proj, conv taps and bias, x_proj, the dt/B/C
    norms, dt_proj and its bias, A_log, D and out_proj."""
    d, _h, _kv, _hd, _f, di, n, r, k = _dims(cfg)
    return (d * 2 * di + k * di + di + di * (r + 2 * n) + (r + 2 * n)
            + r * di + di + di * n + di + di * d)


def attention_params(cfg: dict) -> int:
    d, h, kv, hd, *_ = _dims(cfg)
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def weights(cfg: dict) -> int:
    """Every parameter: the mixers, one gated MLP and two norms a layer,
    the tied embedding and the final norm."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 3 * d * f + 2 * d
    return (n_mamba(cfg) * mamba_params(cfg)
            + n_attention(cfg) * attention_params(cfg)
            + cfg["num_hidden_layers"] * per_layer
            + cfg["vocab_size"] * d + d)


def _linear_flops_per_token(cfg: dict) -> int:
    d, h, kv, hd, f, di, n, r, _k = _dims(cfg)
    mamba = d * 2 * di + di * (r + 2 * n) + r * di + di * d
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    return 2 * (n_mamba(cfg) * mamba + n_attention(cfg) * attn
                + cfg["num_hidden_layers"] * 3 * d * f)


def _attn_flops(cfg: dict, n_pairs: int) -> int:
    """QK^T and PV over ``n_pairs`` (query, key) pairs, all attention
    layers."""
    _d, h, _kv, hd, *_ = _dims(cfg)
    return n_attention(cfg) * 2 * 2 * h * hd * n_pairs


def _head_flops(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_flops(cfg: dict, batch: int, seq: int) -> int:
    """Causal prefill of ``batch`` prompts of ``seq`` tokens, with the
    LM head at the last position only (the one that samples)."""
    pairs = seq * (seq + 1) // 2
    return batch * (seq * _linear_flops_per_token(cfg)
                    + _attn_flops(cfg, pairs) + _head_flops(cfg))


def decode_flops(cfg: dict, batch: int, ctx: int) -> int:
    """One decode step of ``batch`` sequences whose new token attends
    ``ctx`` positions (itself included)."""
    return batch * (_linear_flops_per_token(cfg) + _attn_flops(cfg, ctx)
                    + _head_flops(cfg))


def generate_flops(cfg: dict, batch: int, prompt: int, new_tokens: int
                   ) -> int:
    """A ``generate`` call: prefill samples token 0, then
    ``new_tokens - 1`` decode steps."""
    return prefill_flops(cfg, batch, prompt) + sum(
        decode_flops(cfg, batch, prompt + i + 1)
        for i in range(new_tokens - 1))


def decode_bytes(cfg: dict, batch: int, ctx: int, dtype_bytes: int = 2
                 ) -> int:
    """HBM bytes one decode step must move: every weight, the tied
    embedding read as the LM head, the attention layers' KV cache of
    ``ctx - 1`` earlier positions read plus one position written, and
    every Mamba layer's f32 state and conv tail (the last ``d_conv - 1``
    inputs) read and written."""
    _d, _h, kv, hd, _f, di, n, _r, k = _dims(cfg)
    kv_bytes = dtype_bytes * n_attention(cfg) * batch * ctx * 2 * kv * hd
    state = n_mamba(cfg) * batch * (4 * di * n + dtype_bytes * (k - 1) * di)
    return dtype_bytes * weights(cfg) + kv_bytes + 2 * state


def array_bytes(shapes: Iterable[Tuple[str, Tuple[int, ...]]]) -> int:
    """Bytes of arrays given as (type, dimensions), ``("f32", (8, 128))``."""
    return sum(DTYPE_BYTES[t] * math.prod(dims) for t, dims in shapes)
