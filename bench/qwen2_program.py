"""The seeded qwen2 weights laid out as the program's serving engine
takes them, made on the device in one jitted call.

The values are those of :mod:`bench.reference.qwen2`'s generator; only
the layout is the program's: attention matrices split by head, every
layer stacked for the layer scan, the embedding padded to the program's
vocabulary rows.  The padding rows are zero, so their logits are zero
and no greedy token falls on them while any real logit is positive.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import qwen2 as ref


def program_config(cfg: dict):
    """The program's registered config for ``cfg['name']``, checked
    against the sizes the benchmark's file states."""
    from repro.configs.base import get_config
    mc = get_config(cfg["name"])
    want = {"n_layers": cfg["num_hidden_layers"],
            "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "d_ff": cfg["intermediate_size"],
            "vocab_size": cfg["vocab_size"],
            "rope_theta": cfg["rope_theta"], "norm_eps": cfg["rms_norm_eps"],
            "qkv_bias": cfg["attention_bias"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "act_fn": cfg["hidden_act"], "param_dtype": cfg["torch_dtype"]}
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        raise ValueError(f"the program's {cfg['name']} config differs from "
                         f"bench/configs: {got} != {want}")
    return mc


@functools.partial(jax.jit, static_argnames=("cfg_items", "padded_vocab"))
def _make(key, *, cfg_items, padded_vocab):
    cfg = dict(cfg_items)
    d, h, kv, hd, _f = ref.dims(cfg)
    n = cfg["num_hidden_layers"]
    lw = jax.vmap(lambda i: ref.layer_weights(key, i, cfg))(jnp.arange(n))
    emb = ref.embedding(key, cfg)
    emb = jnp.pad(emb, ((0, padded_vocab - emb.shape[0]), (0, 0)))
    layer = {
        "ln1": lw["ln1"],
        "attn": {"wq": lw["q_w"].reshape(n, d, h, hd),
                 "bq": lw["q_b"].reshape(n, h, hd),
                 "wk": lw["k_w"].reshape(n, d, kv, hd),
                 "bk": lw["k_b"].reshape(n, kv, hd),
                 "wv": lw["v_w"].reshape(n, d, kv, hd),
                 "bv": lw["v_b"].reshape(n, kv, hd),
                 "wo": lw["o_w"].reshape(n, h, hd, d)},
        "ln2": lw["ln2"],
        "mlp": {"w_in": lw["up_w"], "w_gate": lw["gate_w"],
                "w_out": lw["down_w"]},
    }
    return {"embed": emb, "final_norm": ref.final_norm(key, cfg),
            "scan": {"p0": layer}}


def make_params(seed: int, cfg: dict, mc):
    """The program's parameter pytree for ``mc``, from ``seed``; its
    structure, shapes and types are checked against the program's own
    initialiser, traced only (nothing of it runs)."""
    from repro.models import lm
    params = _make(ref.base_key(seed), cfg_items=ref._items(cfg),
                   padded_vocab=mc.padded_vocab)
    want = jax.eval_shape(lambda k: lm.init_params(mc, k),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if got != want:
        raise ValueError("the seeded weights do not have the layout of "
                         f"the program's parameters: {got} != {want}")
    return params
