"""Sharding rules: logical axis names -> mesh PartitionSpecs.

Parallelism map (see DESIGN.md §6):
  * batch        -> ("pod", "data") (whatever exists and divides)
  * TP (model)   -> d_ff, vocab, attention heads (when divisible), experts,
                    SSM heads
  * SP           -> KV sequence over "model" for archs whose KV head count
                    does not divide the model axis; KV-cache sequence over
                    ("data","model") for the batch=1 long-context shape
  * ZeRO-1       -> optimizer state additionally over "data"

All rules degrade to replication when a dimension does not divide the mesh
axis, so reduced CPU configs (1 device) use the same code path.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axes_size(mesh: Mesh, axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        n *= mesh_axis_size(mesh, a)
    return n


def _maybe(axis, dim_size: int, mesh: Mesh):
    """Return `axis` if dim_size divides the axis size, else None."""
    if axis is None:
        return None
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    sz = _axes_size(mesh, axes)
    if sz > 1 and dim_size % sz == 0:
        return axis
    return None


@dataclass(frozen=True)
class ShardingRules:
    """Resolved sharding decisions for one (arch, mesh, shape-kind)."""
    mesh: Mesh
    cfg: ModelConfig
    batch: Tuple[str, ...]          # axes for the batch dim
    tp: str = "model"               # tensor-parallel axis
    attn_mode: str = "seq"          # "head" (KV heads TP) | "seq" (KV seq SP)
    kv_seq_axes: Tuple[str, ...] = ()   # axes sharding the KV-cache seq dim
    # seq mode with Q heads divisible by tp: shard wq/wo (and q
    # activations) over Q heads even though KV heads cannot shard —
    # Megatron column/row attention with replicated (small, GQA) KV.
    # Removes the replicated-attention-weight f32 grad buffers.
    q_heads_tp: bool = False

    # ---- parameter specs -------------------------------------------------
    def param_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        """Spec for a parameter leaf. `path` is a '/'-joined name."""
        m, mesh = self.tp, self.mesh
        leaf = path.split("/")[-1]
        if leaf in ("embed", "unembed"):
            return P(_maybe(m, shape[0], mesh), None)    # (vocab, d_model)
        if leaf in ("wq", "wo"):
            # (D, H, hd) / (H, hd, D): shard flattened head dim when possible
            h_dim = 1 if leaf == "wq" else 0
            return self._head_spec(shape, h_dim)
        if leaf in ("wk", "wv"):
            return self._head_spec(shape, 1)
        if leaf in ("w_in", "w_gate"):
            return P(None, _maybe(m, shape[-1], mesh))   # (D, F)
        if leaf == "w_out":
            return P(_maybe(m, shape[0], mesh), None)    # (F, D)
        if leaf == "router":
            return P(None, None)
        if leaf in ("we_in", "we_gate"):                 # (E, D, F)
            return P(_maybe(m, shape[0], mesh), None, None)
        if leaf == "we_out":                             # (E, F, D)
            return P(_maybe(m, shape[0], mesh), None, None)
        if leaf == "w_zxbcdt":                           # (D, zxbcdt)
            return P(None, _maybe(m, shape[-1], mesh))
        if leaf == "w_xz":                               # (D, 2, d_inner)
            return P(None, None, _maybe(m, shape[-1], mesh))
        if leaf == "w_dbc":                              # (d_inner, R+2N)
            return P(_maybe(m, shape[0], mesh), None)
        if leaf == "w_dt":                               # (R, d_inner)
            return P(None, _maybe(m, shape[-1], mesh))
        if leaf == "w_ssm_out":                          # (d_inner, D)
            return P(_maybe(m, shape[0], mesh), None)
        if leaf == "conv_w":                             # (K, channels)
            return P(None, _maybe(m, shape[-1], mesh))
        if leaf in ("A_log", "dt_bias", "ssm_D"):  # (H,) | (d_inner[, N])
            return P(_maybe(m, shape[0], mesh))
        if leaf == "ssm_norm":                           # (d_inner,)
            return P(_maybe(m, shape[0], mesh))
        # norms, biases, small vectors: replicate
        return P(*([None] * len(shape)))

    def _head_spec(self, shape: Tuple[int, ...], h_dim: int) -> P:
        mesh = self.mesh
        spec = [None] * len(shape)
        if self.attn_mode == "head":
            spec[h_dim] = _maybe(self.tp, shape[h_dim], mesh)
        elif self.q_heads_tp and shape[h_dim] == self.cfg.n_heads:
            # wq/wo only (their head dim is n_heads; wk/wv have n_kv_heads
            # which does not divide tp in this mode)
            spec[h_dim] = _maybe(self.tp, shape[h_dim], mesh)
        return P(*spec)

    # ---- activation specs ------------------------------------------------
    def hidden_spec(self) -> P:
        """(B, S, D) residual-stream activations."""
        return P(self.batch if self.batch else None, None, None)

    def ffn_spec(self) -> P:
        """(B, S, F) intermediate activations (TP over F)."""
        return P(self.batch if self.batch else None, None, self.tp)

    def q_spec(self) -> P:
        """(B, S, H, hd)."""
        h = self.tp if self.attn_mode == "head" else None
        return P(self.batch if self.batch else None, None, h, None)

    def kv_spec(self) -> P:
        """(B, S, KV, hd) — sequence-sharded in "seq" mode."""
        if self.attn_mode == "head":
            return P(self.batch if self.batch else None, None, self.tp, None)
        s = self.kv_seq_axes if self.kv_seq_axes else None
        return P(self.batch if self.batch else None, s, None, None)

    def kv_cache_spec(self) -> P:
        """(B, S, KV, hd) persistent cache."""
        return self.kv_spec()

    def logits_spec(self) -> P:
        """(B, S, V) — vocab TP."""
        return P(self.batch if self.batch else None, None, self.tp)

    # ---- attention train-path specs (blocked / one-shot, see
    # models/attention.py) ------------------------------------------------
    def blocked_q_spec(self, nb: int) -> P:
        """(B, nb, block, KV, G, hd): blocks over tp when they divide."""
        if self.attn_mode == "head":
            kv = self.cfg.n_kv_heads
            return P(self.batch if self.batch else None, None, None,
                     _maybe(self.tp, kv, self.mesh), None, None)
        return P(self.batch if self.batch else None,
                 _maybe(self.tp, nb, self.mesh), None, None, None, None)

    def blocked_kv_spec(self, nb: int) -> P:
        """(B, nb, ext, KV, hd)."""
        if self.attn_mode == "head":
            kv = self.cfg.n_kv_heads
            return P(self.batch if self.batch else None, None, None,
                     _maybe(self.tp, kv, self.mesh), None)
        return P(self.batch if self.batch else None,
                 _maybe(self.tp, nb, self.mesh), None, None, None)

    def q_seq_spec(self) -> P:
        """(B, S, H, hd) q activations for the one-shot train path:
        heads-TP when possible (fully local attention, Megatron-style),
        else sequence-sharded."""
        if self.attn_mode == "head" or self.q_heads_tp:
            return P(self.batch if self.batch else None, None, self.tp,
                     None)
        return P(self.batch if self.batch else None, self.tp, None, None)

    def kv_rep_spec(self) -> P:
        """(B, S, KV, hd) replicated over tp (gathered once per layer)."""
        if self.attn_mode == "head":
            return P(self.batch if self.batch else None, None, self.tp,
                     None)
        return P(self.batch if self.batch else None, None, None, None)

    def ssm_state_spec(self) -> P:
        """(B, H, P, N) recurrent state — heads TP; Mamba-1's
        (B, d_inner, N) — channels TP."""
        if self.cfg.ssm is not None and self.cfg.ssm.kind == "mamba1":
            di = self.cfg.ssm.d_inner(self.cfg.d_model)
            return P(self.batch if self.batch else None,
                     _maybe(self.tp, di, self.mesh), None)
        h = _maybe(self.tp, self.cfg.ssm.n_heads(self.cfg.d_model),
                   self.mesh) if self.cfg.ssm else self.tp
        return P(self.batch if self.batch else None, h, None, None)

    def dispatch_spec(self) -> P:
        """(G, E, cap, D) MoE dispatch buffer — experts TP."""
        e = self.cfg.moe.n_experts if self.cfg.moe else 0
        return P(self.batch if self.batch else None,
                 _maybe(self.tp, e, self.mesh), None, None)


def make_rules(cfg: ModelConfig, mesh: Mesh, *, global_batch: int,
               shape_kind: str = "train") -> ShardingRules:
    b_axes = []
    remaining = global_batch
    for a in batch_axes(mesh):
        sz = mesh_axis_size(mesh, a)
        if remaining % sz == 0 and remaining >= sz:
            b_axes.append(a)
            remaining //= sz
    tp_size = mesh_axis_size(mesh, "model")
    if cfg.n_kv_heads and tp_size > 1 and cfg.n_kv_heads % tp_size == 0:
        attn_mode = "head"
        kv_seq: Tuple[str, ...] = ()
        q_heads_tp = False
    else:
        attn_mode = "seq"
        kv_seq = ("model",) if tp_size > 1 else ()
        # batch=1 long-context: spread the KV sequence over spare batch axes
        unused = tuple(a for a in batch_axes(mesh) if a not in b_axes)
        kv_seq = unused + kv_seq
        q_heads_tp = bool(cfg.n_heads and tp_size > 1
                          and cfg.n_heads % tp_size == 0)
    return ShardingRules(mesh=mesh, cfg=cfg, batch=tuple(b_axes),
                         attn_mode=attn_mode, kv_seq_axes=kv_seq,
                         q_heads_tp=q_heads_tp)


def logical_to_spec(rules: ShardingRules, tree, path_prefix: str = ""):
    """Map a param pytree to a pytree of PartitionSpecs by leaf path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    specs = {}
    for kp, leaf in flat:
        path = "/".join(_key_str(k) for k in kp)
        specs[path] = rules.param_spec(path, leaf.shape)
    # rebuild tree
    def _build(kp, leaf):
        path = "/".join(_key_str(k) for k in kp)
        return specs[path]
    return jax.tree_util.tree_map_with_path(_build, tree)


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def constrain(x, mesh: Mesh, spec: P):
    """with_sharding_constraint that is a no-op off-mesh / on 1 device."""
    if mesh is None or mesh.size == 1 or isinstance(
            mesh, jax.sharding.AbstractMesh) and False:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    except ValueError:
        return x
