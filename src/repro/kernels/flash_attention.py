"""Flash attention (online-softmax, causal + sliding-window), Pallas TPU.

The model-side perf-critical kernel: blockwise attention that never
materialises the (Sq, Sk) score matrix in HBM.  Supports GQA natively via
the KV-head index map (no repeated-KV materialisation) and gemma-style
sliding windows via block skipping — an out-of-window KV block is never
DMA'd at all, which is what makes local-attention layers O(S·W) in both
FLOPs *and* bytes.

Layout: q (B, H, Sq, D); k, v (B, KVH, Sk, D); H % KVH == 0.
Grid (B, H, nq, nk), nk innermost/sequential; m/l/acc live in VMEM
scratch and persist across the nk loop (standard TPU flash schedule).
Accumulation is f32 regardless of input dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
LANE = 128


def _flash_body(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                sm_scale: float, block_q: int, block_k: int, n_k: int,
                causal: bool, window: int, sk_valid: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # --- static-ish block skip predicates (computed on grid indices) -----
    run = jnp.bool_(True)
    if causal:
        # lowest kv pos in this block must not exceed highest q pos
        run = run & (ik * block_k <= iq * block_q + block_q - 1)
    if window:
        # highest kv pos must be within the window of the lowest q pos
        run = run & (ik * block_k + block_k - 1 > iq * block_q - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = jnp.bool_(jnp.ones((block_q, block_k), jnp.bool_))
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window:
            mask = mask & (k_pos > q_pos - window)
        if sk_valid % block_k:                 # padded kv tail block
            mask = mask & (k_pos < sk_valid)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                          # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)     # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
        p = jnp.exp(s - m_new)                         # (bq, bk)
        p = jnp.where(mask, p, 0.0)

        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * alpha + jnp.sum(p, -1, keepdims=True),
            l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, d)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    causal: bool = True, window: int = 0,
    sm_scale: Optional[float] = None,
    block_q: int = 128, block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """q: (B,H,Sq,D); k,v: (B,KVH,Sk,D) -> (B,H,Sq,D).

    Sequences need not divide the block shape: q/k/v are zero-padded up
    to the block grid and the padded kv tail is masked inside the kernel
    (an out-of-range score block contributes exp(-inf) = 0), so the
    result is bit-for-bit independent of the tiling.
    """
    b, h, sq, d = q.shape
    _, kvh, sk, _ = k.shape
    assert h % kvh == 0, (h, kvh)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pad_q = -sq % block_q
    pad_k = -sk % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sq_p, sk_p = sq + pad_q, sk + pad_k
    n_q, n_k = sq_p // block_q, sk_p // block_k
    scale = sm_scale if sm_scale is not None else d ** -0.5

    grid = (b, h, n_q, n_k)
    body = functools.partial(
        _flash_body, sm_scale=scale, block_q=block_q, block_k=block_k,
        n_k=n_k, causal=causal, window=window, sk_valid=sk)

    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, qq, kk, kvh=kvh, h=h:
                         (bb, hh * kvh // h, kk, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, qq, kk, kvh=kvh, h=h:
                         (bb, hh * kvh // h, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANE), jnp.float32),   # m
            pltpu.VMEM((block_q, LANE), jnp.float32),   # l
            pltpu.VMEM((block_q, d), jnp.float32),      # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq] if pad_q else out
