"""Seeded qwen2 weights and a plain float32 qwen2 forward.

The weights are the benchmark's input: made from ``--seed`` by the
functions below, one layer at a time from a key of its own, in bfloat16
(the type they are served in).  The serving driver lays the same values
out as the program wants them; the reference below makes each layer's
weights again from the seed and never sees the program's arrays.

The forward follows the Qwen2 architecture as published (arXiv:2407.10671,
Hugging Face ``Qwen2ForCausalLM``): RMSNorm, rotary embeddings on the
rotate-half convention, grouped-query attention with q/k/v bias, a gated
SiLU MLP, and the LM head tied to the embedding.  It runs in float32 at
``jax.default_matmul_precision("highest")``, one layer per call and one
block of queries at a time, so that it fits beside nothing else on one
chip.  It has no cache and no batching tricks: every position attends
every earlier one.  One departure, of parametrisation only: a norm's
weight is stored as its offset from one, and the forward adds the one
back in float32.

``quant="fp8"`` rounds every matmul operand to float8 (e4m3, one scale
per tensor) before the float32 product: the control that a precision
one step below the served bfloat16 must fail.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# the order in which a layer's tensors draw their keys; never reorder
LAYER_TENSORS = ("ln1", "q_w", "q_b", "k_w", "k_b", "v_w", "v_b", "o_w",
                 "ln2", "gate_w", "up_w", "down_w")
NORM_STD, BIAS_STD, EMBED_STD = 0.05, 0.02, 0.02


def dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, kv, hd, cfg["intermediate_size"]


def base_key(seed: int):
    """A key from any whole number below 2**64: the low and high 32 bits
    go in separately, so seeds past 32 bits stay distinct."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std
            ).astype(jnp.bfloat16)


def layer_weights(key, layer, cfg: dict) -> Dict[str, jnp.ndarray]:
    """Layer ``layer``'s bf16 weights, each matrix laid out (in, out)."""
    d, h, kv, hd, f = dims(cfg)
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    ks = dict(zip(LAYER_TENSORS, jax.random.split(lk, len(LAYER_TENSORS))))
    return {
        "ln1": _normal(ks["ln1"], (d,), NORM_STD),
        "q_w": _normal(ks["q_w"], (d, h * hd), 1 / math.sqrt(d)),
        "q_b": _normal(ks["q_b"], (h * hd,), BIAS_STD),
        "k_w": _normal(ks["k_w"], (d, kv * hd), 1 / math.sqrt(d)),
        "k_b": _normal(ks["k_b"], (kv * hd,), BIAS_STD),
        "v_w": _normal(ks["v_w"], (d, kv * hd), 1 / math.sqrt(d)),
        "v_b": _normal(ks["v_b"], (kv * hd,), BIAS_STD),
        "o_w": _normal(ks["o_w"], (h * hd, d), 1 / math.sqrt(h * hd)),
        "ln2": _normal(ks["ln2"], (d,), NORM_STD),
        "gate_w": _normal(ks["gate_w"], (d, f), 1 / math.sqrt(d)),
        "up_w": _normal(ks["up_w"], (d, f), 1 / math.sqrt(d)),
        "down_w": _normal(ks["down_w"], (f, d), 1 / math.sqrt(f)),
    }


def embedding(key, cfg: dict) -> jnp.ndarray:
    """The (vocab, hidden) bf16 embedding, also the LM head."""
    return _normal(jax.random.fold_in(key, 2),
                   (cfg["vocab_size"], cfg["hidden_size"]), EMBED_STD)


def final_norm(key, cfg: dict) -> jnp.ndarray:
    return _normal(jax.random.fold_in(key, 3), (cfg["hidden_size"],),
                   NORM_STD)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fp8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, quant: str):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b)


def _rmsnorm(x, offset, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + offset.astype(jnp.float32))


def _rope(x, positions, theta):
    """x: (B, S, heads, hd); rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _layer(x, key, layer, *, cfg: dict, q_block: int, quant: str):
    d, h, kv, hd, _f = dims(cfg)
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     layer_weights(key, layer, cfg))
    b, s, _ = x.shape
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(s)
    hn = _rmsnorm(x, w["ln1"], eps)
    q = (_mm(hn, w["q_w"], quant) + w["q_b"]).reshape(b, s, h, hd)
    k = (_mm(hn, w["k_w"], quant) + w["k_b"]).reshape(b, s, kv, hd)
    v = (_mm(hn, w["v_w"], quant) + w["v_b"]).reshape(b, s, kv, hd)
    q = _rope(q, pos, cfg["rope_theta"])
    k = _rope(k, pos, cfg["rope_theta"])
    rep = h // kv                       # query head i reads kv head i // rep
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)

    nq = s // q_block

    def attend(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block, 1)
        qpos = i * q_block + jnp.arange(q_block)
        sc = jnp.einsum("bqhd,bkhd->bhqk",
                        _fp8(qs) if quant == "fp8" else qs,
                        _fp8(k) if quant == "fp8" else k) / math.sqrt(hd)
        sc = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if quant == "fp8":
            p = _fp8(p)
        return jnp.einsum("bhqk,bkhd->bqhd", p,
                          _fp8(v) if quant == "fp8" else v)

    att = jax.lax.map(attend, jnp.arange(nq))          # (nq, B, qb, H, hd)
    att = jnp.moveaxis(att, 0, 1).reshape(b, s, h * hd)
    x = x + _mm(att, w["o_w"], quant)
    hn = _rmsnorm(x, w["ln2"], eps)
    g = _mm(hn, w["gate_w"], quant)
    u = _mm(hn, w["up_w"], quant)
    return x + _mm(jax.nn.silu(g) * u, w["down_w"], quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "q_block",
                                             "quant"))
def _layer_jit(x, key, layer, *, cfg_items, q_block, quant):
    with jax.default_matmul_precision("highest"):
        return _layer(x, key, layer, cfg=dict(cfg_items), q_block=q_block,
                      quant=quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _embed_jit(tokens, key, *, cfg_items, quant):
    del quant
    return embedding(key, dict(cfg_items)).astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _head_jit(x, key, *, cfg_items, quant):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        e = embedding(key, cfg).astype(jnp.float32)
        hn = _rmsnorm(x, final_norm(key, cfg), cfg["rms_norm_eps"])
        if quant == "fp8":
            hn, e = _fp8(hn), _fp8(e)
        return jnp.einsum("bsd,vd->bsv", hn, e)


def _items(cfg: dict):
    keep = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "rope_theta", "rms_norm_eps")
    return tuple((k, cfg[k]) for k in keep if k in cfg)


def logits_at(seed: int, cfg: dict, tokens, positions: Sequence[int], *,
              quant: str = "f32", q_block: int = 0):
    """Float32 logits (B, len(positions), vocab) of the sequences
    ``tokens`` (B, S) at ``positions``, every position attending all
    earlier ones.  Layer by layer: one layer's weights exist at a
    time."""
    items = _items(cfg)
    key = base_key(seed)
    tokens = np.asarray(tokens, np.int32)
    s = tokens.shape[1]
    # pad to whole query blocks at the end: causal attention keeps the
    # padding out of every earlier position
    q_block = q_block or min(512, s)
    s_pad = -(-s // q_block) * q_block
    tokens = np.pad(tokens, ((0, 0), (0, s_pad - s)))
    x = _embed_jit(jnp.asarray(tokens), key, cfg_items=items, quant=quant)
    for layer in range(cfg["num_hidden_layers"]):
        x = _layer_jit(x, key, jnp.int32(layer), cfg_items=items,
                       q_block=q_block, quant=quant)
    sel = x[:, jnp.asarray(positions, jnp.int32)]
    return _head_jit(sel, key, cfg_items=items, quant=quant)
