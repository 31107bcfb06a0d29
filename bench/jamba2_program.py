"""The seeded Jamba weights laid out as the program's serving engine
takes them, made on the device in one jitted call.

The values are those of :mod:`bench.reference.jamba2`'s generator; only
the layout is the program's: attention matrices split by head, the
mixer's input projection split into its x and z halves, and the layers
of each position of the period stacked for the period scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import jamba2 as ref


def program_config(cfg: dict):
    """The program's registered config for ``cfg['name']``, checked
    against the sizes the benchmark's file states."""
    from repro.configs.base import get_config
    mc = get_config(cfg["name"])
    m = ref.dims(cfg)
    if not cfg["mamba_conv_bias"] or cfg["mamba_proj_bias"] or \
            cfg["num_experts"] != 1:
        raise ValueError("the program's Jamba mixer has a conv bias, no "
                         "projection bias and a dense MLP")
    want = {"n_layers": cfg["num_hidden_layers"], "d_model": m["d"],
            "n_heads": m["h"], "n_kv_heads": m["kv"], "head_dim": m["hd"],
            "d_ff": m["f"], "vocab_size": cfg["vocab_size"],
            "norm_eps": cfg["rms_norm_eps"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "act_fn": cfg["hidden_act"], "param_dtype": cfg["torch_dtype"],
            "attn_every": cfg["attn_layer_period"],
            "attn_offset": cfg["attn_layer_offset"], "rope_theta": 0.0,
            "moe": None, "ssm_kind": "mamba1", "d_state": m["n"],
            "d_conv": m["k"], "expand": cfg["mamba_expand"],
            "dt_rank": m["r"]}
    got = {k: getattr(mc, k, None) for k in want}
    got.update({"ssm_kind": mc.ssm.kind, "d_state": mc.ssm.d_state,
                "d_conv": mc.ssm.d_conv, "expand": mc.ssm.expand,
                "dt_rank": mc.ssm.dt_rank})
    if got != want:
        raise ValueError(f"the program's {cfg['name']} config differs from "
                         f"bench/configs: {got} != {want}")
    return mc


def _layout(w, cfg: dict, attention: bool, n: int):
    """One position's weights, stacked over its ``n`` layers."""
    m = ref.dims(cfg)
    d, di = m["d"], m["di"]
    mlp = {"w_in": w["up_w"], "w_gate": w["gate_w"], "w_out": w["down_w"]}
    if attention:
        h, kv, hd = m["h"], m["kv"], m["hd"]
        return {"ln1": w["ln1"], "ln2": w["ln2"], "mlp": mlp,
                "attn": {"wq": w["q_w"].reshape(n, d, h, hd),
                         "wk": w["k_w"].reshape(n, d, kv, hd),
                         "wv": w["v_w"].reshape(n, d, kv, hd),
                         "wo": w["o_w"].reshape(n, h, hd, d)}}
    return {"ln1": w["ln1"], "ln2": w["ln2"], "mlp": mlp,
            "ssm": {"w_xz": w["in_w"].reshape(n, d, 2, di),
                    "conv_w": w["conv_w"], "conv_b": w["conv_b"],
                    "w_dbc": w["x_proj_w"], "dt_norm": w["dt_norm"],
                    "b_norm": w["b_norm"], "c_norm": w["c_norm"],
                    "w_dt": w["dt_proj_w"], "dt_bias": w["dt_proj_b"],
                    "A_log": w["A_log"], "ssm_D": w["D"],
                    "w_ssm_out": w["out_w"]}}


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _make(key, *, cfg_items):
    cfg = dict(cfg_items)
    period = cfg["attn_layer_period"]
    n = cfg["num_hidden_layers"] // period
    scan = {}
    for p in range(period):
        attention = ref.is_attention(cfg, p)
        w = jax.vmap(lambda i: ref.layer_weights(key, i, cfg, attention))(
            p + period * jnp.arange(n))
        scan[f"p{p}"] = _layout(w, cfg, attention, n)
    return {"embed": ref.embedding(key, cfg),
            "final_norm": ref.final_norm(key, cfg), "scan": scan}


def make_params(seed: int, cfg: dict, mc):
    """The program's parameter pytree for ``mc``, from ``seed``; its
    structure, shapes and types are checked against the program's own
    initialiser, traced only (nothing of it runs)."""
    from repro.models import lm
    if mc.padded_vocab != cfg["vocab_size"] or \
            cfg["num_hidden_layers"] % cfg["attn_layer_period"]:
        raise ValueError("the layout takes whole periods and a vocabulary "
                         "of whole 256-row blocks")
    params = _make(ref.base_key(seed), cfg_items=ref._items(cfg))
    want = jax.eval_shape(lambda k: lm.init_params(mc, k),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if got != want:
        raise ValueError("the seeded weights do not have the layout of "
                         f"the program's parameters: {got} != {want}")
    return params
