"""Operations and bytes the benchmark's kernels and model steps need,
computed from their shapes alone, and the table of peaks they are held
against.

Nothing here imports the program: a later change to a kernel or to the
model cannot change what its roofline share is measured against.
"""
from __future__ import annotations

import json
import os

from bench.reference.probes import LINE_BYTES

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``.  A device that is not in
    the table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


# -- probe kernels --------------------------------------------------------


def read_hbm_bytes(rows: int) -> int:
    """``read_hbm`` streams every (rows, 128) f32 element once and
    stores one f32 sum."""
    return rows * LINE_BYTES + 4


def chase_hbm_loads(n_steps: int, n_chains: int = 1) -> int:
    """``chase_hbm`` makes one dependent single-line load per step of
    each chain it walks."""
    return n_steps * n_chains


# -- qwen2-style dense decoder --------------------------------------------


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, kv, hd, cfg["intermediate_size"], cfg["num_hidden_layers"]


def layer_params(cfg: dict) -> int:
    """Weights of one decoder layer: q/k/v with bias, o, gated MLP and
    two norms."""
    d, h, kv, hd, f, _ = _dims(cfg)
    attn = d * h * hd + 2 * d * kv * hd + h * hd + 2 * kv * hd + h * hd * d
    return attn + 3 * d * f + 2 * d


def _linear_flops_per_token(cfg: dict) -> int:
    d, h, kv, hd, f, n = _dims(cfg)
    per_layer = 2 * (d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f)
    return n * per_layer


def _attn_flops(cfg: dict, n_pairs: int) -> int:
    """QK^T and PV over ``n_pairs`` (query, key) pairs, all layers."""
    _d, h, _kv, hd, _f, n = _dims(cfg)
    return n * 2 * 2 * h * hd * n_pairs


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
    """HBM bytes one decode step must move: every layer's weights, the
    tied embedding read as the LM head, and the KV cache of ``ctx - 1``
    earlier positions read plus one position written."""
    d, _h, kv, hd, _f, n = _dims(cfg)
    weights = n * layer_params(cfg) + cfg["vocab_size"] * d + d
    kv_bytes = n * batch * ctx * 2 * kv * hd
    return dtype_bytes * (weights + kv_bytes)
