"""GPipe-style pipeline parallelism over a "stage" mesh axis.

The assignment's fixed production mesh is (data, model) — PP is not part
of the 40-cell baseline — but a 1000-node deployment wants a stage axis
for cross-pod scaling, so the machinery is here as a first-class,
tested feature.

Mapping (DESIGN.md §6): one stage per mesh slice along ``stage``; the
schedule is plain GPipe — microbatches march left to right, activations
hop stages via ``jax.lax.ppermute`` (TPU-native neighbour exchange on the
ICI torus), and the whole schedule is a single ``lax.scan`` of
``n_micro + n_stages - 1`` ticks inside one ``shard_map``.  Bubble
fraction is the textbook (S-1)/(T+S-1); pick n_micro >> n_stages.

``apply_stage(stage_params, x)`` is user code (e.g. a slab of decoder
layers); it must be shape-preserving, which all our decoder stacks are.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_schedule(apply_stage: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   *, n_stages: int, n_micro: int, axis: str = "stage"):
    """Returns per_device(params_stage, x_micro) -> y_micro to be run
    under shard_map over the ``axis`` mesh dimension.

    params_stage: this stage's parameters (already sharded by stage).
    x_micro: (n_micro, mb, ...) — meaningful on stage 0 only.
    Returns (n_micro, mb, ...) — meaningful on the last stage only.
    """
    if n_micro < 1 or n_stages < 1:
        raise ValueError((n_micro, n_stages))
    ticks = n_micro + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def per_device(params_stage, x_micro):
        stage = jax.lax.axis_index(axis)
        # params arrive stacked (n_stages, ...); this shard holds 1 stage
        params_stage = jax.tree.map(lambda a: a[0], params_stage)
        mb_shape = x_micro.shape[1:]
        out0 = jnp.zeros_like(x_micro)

        def tick(carry, t):
            act, out = carry
            # 1) receive the neighbour's activation (stage s gets s-1's)
            act_in = jax.lax.ppermute(act, axis, perm) if perm else act
            # 2) stage 0 injects microbatch t instead
            feed = jax.lax.dynamic_index_in_dim(
                x_micro, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
            act_in = jnp.where(stage == 0, feed, act_in)
            # 3) compute when this stage has live data: s <= t < s + n_micro
            live = (t >= stage) & (t < stage + n_micro)
            y = apply_stage(params_stage, act_in)
            act_out = jnp.where(live, y, act_in)
            # 4) the last stage banks finished microbatch t - (S-1)
            mb_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            bank = live & (stage == n_stages - 1)
            upd = jnp.where(
                bank, act_out,
                jax.lax.dynamic_index_in_dim(out, mb_idx, 0, False))
            out = jax.lax.dynamic_update_index_in_dim(out, upd, mb_idx, 0)
            return (act_out, out), None

        act0 = jnp.zeros(mb_shape, x_micro.dtype)
        # the carry becomes device-varying after ppermute: mark it so
        act0, out0 = jax.lax.pvary((act0, out0), (axis,))
        (_, out), _ = jax.lax.scan(
            tick, (act0, out0), jnp.arange(ticks, dtype=jnp.int32))
        # only the last stage banked anything (zeros elsewhere): reduce to
        # make the result replicated across stages
        return jax.lax.psum(out, axis)

    return per_device


def make_gpipe(mesh: Mesh, apply_stage, *, n_micro: int,
               axis: str = "stage",
               x_spec: P = P(None), params_spec: P = None):
    """shard_map-wrapped GPipe runner on ``mesh`` (must carry ``axis``)."""
    if params_spec is None:
        params_spec = P(axis)
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    per_device = gpipe_schedule(apply_stage, n_stages=n_stages,
                                n_micro=n_micro, axis=axis)
    return jax.shard_map(per_device, mesh=mesh,
                         in_specs=(params_spec, x_spec), out_specs=x_spec)


def reference_pipeline(apply_stage, params_all, x_micro):
    """Oracle: run every stage sequentially on one device.

    params_all: (n_stages, ...) stacked stage params; x_micro (n_micro, ...).
    """
    n_stages = jax.tree.leaves(params_all)[0].shape[0]

    def run_micro(x):
        for s in range(n_stages):
            p = jax.tree.map(lambda a: a[s], params_all)
            x = apply_stage(p, x)
        return x

    return jax.vmap(run_micro)(x_micro)
