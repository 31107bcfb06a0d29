"""Seeded Jamba weights and a plain float32 Jamba forward.

The weights are the benchmark's input: made from ``--seed`` by the
functions below, one layer at a time from keys of its own, in bfloat16
(the type they are served in).  The serving driver lays the same values
out as the program wants them; the reference below makes each layer's
weights again from the seed and never sees the program's arrays.

The forward follows Jamba as published (arXiv:2403.19887, Hugging Face
``JambaForCausalLM``): pre-norm residual blocks (``input_layernorm`` ->
mixer, ``pre_ff_layernorm`` -> MLP), a final norm, and the LM head tied
to the embedding.  Layer ``i`` is attention where ``i %
attn_layer_period == attn_layer_offset`` and a Mamba-1 mixer elsewhere;
every layer's MLP is the dense gated SiLU MLP (``num_experts`` 1).  The
attention layers have no positional encoding and no bias.  The mixer
(``JambaMambaMixer``)::

    x, z  = in_proj(h)                      (no bias)
    x     = silu(causal depthwise conv(x) + conv_bias)
    dt, B, C = split(x_proj(x), [dt_rank, N, N]), each RMS-normed
    delta = softplus(dt_proj(dt))           (dt_proj has a bias)
    s_t   = exp(delta_t * A) * s_{t-1} + (delta_t * x_t) B_t,  A = -exp(A_log)
    y_t   = s_t . C_t + D * x_t
    out   = out_proj(y * silu(z))

It runs in float32 at ``jax.default_matmul_precision("highest")``, one
layer per call, attention one block of queries at a time, and the scan
as a ``lax.scan`` over positions, one position a step: no kernel, cache
or chunking.  One departure, of parametrisation only: a norm's weight is
stored as its offset from one, and the forward adds the one back in
float32.

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

from bench.reference.qwen2 import _fp8, _mm, _rmsnorm, base_key

# the order in which a layer's tensors draw their keys; never reorder
MAMBA_TENSORS = ("ln1", "in_w", "conv_w", "conv_b", "x_proj_w", "dt_norm",
                 "b_norm", "c_norm", "dt_proj_w", "dt_proj_b", "out_w",
                 "ln2", "gate_w", "up_w", "down_w")
ATTN_TENSORS = ("ln1", "q_w", "k_w", "v_w", "o_w", "ln2", "gate_w", "up_w",
                "down_w")
NORM_STD, BIAS_STD, EMBED_STD = 0.05, 0.02, 0.02
DT_MIN, DT_MAX = 1e-3, 1e-1


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "f": cfg["intermediate_size"],
            "di": cfg["mamba_expand"] * d, "n": cfg["mamba_d_state"],
            "r": cfg["mamba_dt_rank"], "k": cfg["mamba_d_conv"]}


def is_attention(cfg: dict, layer: int) -> bool:
    return layer % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std
            ).astype(jnp.bfloat16)


def _mlp(ks, d, f):
    return {"ln2": _normal(ks["ln2"], (d,), NORM_STD),
            "gate_w": _normal(ks["gate_w"], (d, f), 1 / math.sqrt(d)),
            "up_w": _normal(ks["up_w"], (d, f), 1 / math.sqrt(d)),
            "down_w": _normal(ks["down_w"], (f, d), 1 / math.sqrt(f))}


def layer_weights(key, layer, cfg: dict, attention: bool
                  ) -> Dict[str, jnp.ndarray]:
    """Layer ``layer``'s bf16 weights (``attention`` is
    :func:`is_attention` of it), each matrix laid out (in, out);
    ``conv_w`` is (d_conv, d_inner), tap j acting on position
    t - (d_conv - 1) + j."""
    m = dims(cfg)
    d, di, n, r = m["d"], m["di"], m["n"], m["r"]
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    if attention:
        h, kv, hd = m["h"], m["kv"], m["hd"]
        ks = dict(zip(ATTN_TENSORS, jax.random.split(lk, len(ATTN_TENSORS))))
        return {"ln1": _normal(ks["ln1"], (d,), NORM_STD),
                "q_w": _normal(ks["q_w"], (d, h * hd), 1 / math.sqrt(d)),
                "k_w": _normal(ks["k_w"], (d, kv * hd), 1 / math.sqrt(d)),
                "v_w": _normal(ks["v_w"], (d, kv * hd), 1 / math.sqrt(d)),
                "o_w": _normal(ks["o_w"], (h * hd, d), 1 / math.sqrt(h * hd)),
                **_mlp(ks, d, m["f"])}
    ks = dict(zip(MAMBA_TENSORS, jax.random.split(lk, len(MAMBA_TENSORS))))
    dt = jnp.exp(jax.random.uniform(ks["dt_proj_b"], (di,), jnp.float32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    a = jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (di, n))
    return {"ln1": _normal(ks["ln1"], (d,), NORM_STD),
            "in_w": _normal(ks["in_w"], (d, 2 * di), 1 / math.sqrt(d)),
            "conv_w": _normal(ks["conv_w"], (m["k"], di),
                              1 / math.sqrt(m["k"])),
            "conv_b": _normal(ks["conv_b"], (di,), BIAS_STD),
            "x_proj_w": _normal(ks["x_proj_w"], (di, r + 2 * n),
                                1 / math.sqrt(di)),
            "dt_norm": _normal(ks["dt_norm"], (r,), NORM_STD),
            "b_norm": _normal(ks["b_norm"], (n,), NORM_STD),
            "c_norm": _normal(ks["c_norm"], (n,), NORM_STD),
            "dt_proj_w": _normal(ks["dt_proj_w"], (r, di), 1 / math.sqrt(r)),
            # the inverse softplus of dt, so that softplus gives dt back
            "dt_proj_b": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.bfloat16),
            "A_log": jnp.log(a).astype(jnp.bfloat16),
            "D": jnp.ones((di,), jnp.bfloat16),
            "out_w": _normal(ks["out_w"], (di, d), 1 / math.sqrt(di)),
            **_mlp(ks, d, m["f"])}


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


def _mlp_out(x, w, eps, quant):
    hn = _rmsnorm(x, w["ln2"], eps)
    g = _mm(hn, w["gate_w"], quant)
    u = _mm(hn, w["up_w"], quant)
    return x + _mm(jax.nn.silu(g) * u, w["down_w"], quant)


def _mamba(x, w, *, cfg: dict, quant: str):
    m = dims(cfg)
    di, n, r, k = m["di"], m["n"], m["r"], m["k"]
    eps = cfg["rms_norm_eps"]
    s = x.shape[1]
    hn = _rmsnorm(x, w["ln1"], eps)
    xz = _mm(hn, w["in_w"], quant)
    xs, z = xz[..., :di], xz[..., di:]
    xp = jnp.pad(xs, ((0, 0), (k - 1, 0), (0, 0)))
    xs = jax.nn.silu(sum(xp[:, j:j + s] * w["conv_w"][j] for j in range(k))
                     + w["conv_b"])
    dbc = _mm(xs, w["x_proj_w"], quant)
    dt = _rmsnorm(dbc[..., :r], w["dt_norm"], eps)
    bm = _rmsnorm(dbc[..., r:r + n], w["b_norm"], eps)
    cm = _rmsnorm(dbc[..., r + n:], w["c_norm"], eps)
    delta = jax.nn.softplus(_mm(dt, w["dt_proj_w"], quant) + w["dt_proj_b"])
    a = -jnp.exp(w["A_log"])

    def step(state, inp):
        x_t, d_t, b_t, c_t = inp                     # (B, di), (B, N)
        state = (jnp.exp(d_t[:, :, None] * a) * state
                 + (d_t * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(
        step, jnp.zeros((x.shape[0], di, n), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (xs, delta, bm, cm)), unroll=8)
    y = jnp.moveaxis(y, 0, 1) + w["D"] * xs
    x = x + _mm(y * jax.nn.silu(z), w["out_w"], quant)
    return _mlp_out(x, w, eps, quant)


def _attention(x, w, *, cfg: dict, q_block: int, quant: str):
    m = dims(cfg)
    h, kv, hd = m["h"], m["kv"], m["hd"]
    b, s, _ = x.shape
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(s)
    hn = _rmsnorm(x, w["ln1"], eps)
    q = _mm(hn, w["q_w"], quant).reshape(b, s, h, hd)
    k = _mm(hn, w["k_w"], quant).reshape(b, s, kv, hd)
    v = _mm(hn, w["v_w"], quant).reshape(b, s, kv, hd)
    rep = h // kv                       # query head i reads kv head i // rep
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    if quant == "fp8":
        k, v = _fp8(k), _fp8(v)

    def attend(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block, 1)
        qpos = i * q_block + jnp.arange(q_block)
        sc = jnp.einsum("bqhd,bkhd->bhqk", _fp8(qs) if quant == "fp8"
                        else qs, k) / math.sqrt(hd)
        sc = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          _fp8(p) if quant == "fp8" else p, v)

    att = jax.lax.map(attend, jnp.arange(s // q_block))
    att = jnp.moveaxis(att, 0, 1).reshape(b, s, h * hd)
    x = x + _mm(att, w["o_w"], quant)
    return _mlp_out(x, w, eps, quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "attention",
                                             "q_block", "quant"))
def _layer_jit(x, key, layer, *, cfg_items, attention, q_block, quant):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(jnp.float32),
                         layer_weights(key, layer, cfg, attention))
        if attention:
            return _attention(x, w, cfg=cfg, q_block=q_block, quant=quant)
        return _mamba(x, w, cfg=cfg, quant=quant)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed_jit(tokens, key, *, cfg_items):
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
            "vocab_size", "rms_norm_eps", "attn_layer_period",
            "attn_layer_offset", "mamba_d_state", "mamba_d_conv",
            "mamba_expand", "mamba_dt_rank")
    return tuple((k, cfg[k]) for k in keep if k in cfg)


def logits_at(seed: int, cfg: dict, tokens, positions: Sequence[int], *,
              quant: str = "f32", q_block: int = 0):
    """Float32 logits (B, len(positions), vocab) of the sequences
    ``tokens`` (B, S) at ``positions``, every position seeing all
    earlier ones.  Layer by layer: one layer's weights exist at a
    time."""
    items = _items(cfg)
    key = base_key(seed)
    tokens = np.asarray(tokens, np.int32)
    s = tokens.shape[1]
    # pad to whole query blocks at the end: causal attention and the
    # scan keep the padding out of every earlier position
    q_block = q_block or min(512, s)
    s_pad = -(-s // q_block) * q_block
    tokens = np.pad(tokens, ((0, 0), (0, s_pad - s)))
    x = _embed_jit(jnp.asarray(tokens), key, cfg_items=items)
    for layer in range(cfg["num_hidden_layers"]):
        x = _layer_jit(x, key, jnp.int32(layer), cfg_items=items,
                       attention=is_attention(cfg, layer), q_block=q_block,
                       quant=quant)
    sel = x[:, jnp.asarray(positions, jnp.int32)]
    return _head_jit(sel, key, cfg_items=items, quant=quant)
