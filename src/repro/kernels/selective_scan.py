"""Mamba-1 selective scan: the diagonal state-space recurrence of one
layer over a whole prompt, with the state kept in VMEM.

    s_t = exp(dt_t * A) * s_{t-1} + (dt_t * x_t) B_t        (per channel)
    y_t = s_t . C_t

x, dt: (batch, T, D); A: (D, N); B, C: (batch, T, N); state (batch, D, N).

The grid runs over (batch, channel block, time chunk), time last and
sequential.  A block's state lives in a (N, block_d) f32 VMEM scratch
across its time chunks: channels on the lanes and the N states on the
sublanes, so that one step updates the state with whole vregs.  The
steps go eight at a time: one aligned (8, block_d) load of x and dt and
(8, N) of B and C, eight updates, each turning its row of B and C into
a column by a masked lane reduction, and one aligned store of eight
rows of y.  No (token, channel, state)
tensor is ever written to HBM: the kernel reads x, dt, B, C and the
initial state and writes y and the final state, which is what makes a
chunked prefill possible (the final state of one call is the initial
state of the next).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_D = 512
DEFAULT_BLOCK_T = 256


def _body(x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref, y_ref, sT_ref,
          s_scr, x_scr, *, block_t: int):
    n = a_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = s0_ref[...]

    # one f32 copy of the chunk's x: bf16 packs two rows a sublane, so
    # eight rows of it cannot be loaded alone
    x_scr[...] = x_ref[...].astype(jnp.float32)

    a = a_ref[...]                                   # (N, Dc)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))

    def column(row):
        """(1, N) -> (N, 1)."""
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def group(g, s):
        t0 = pl.multiple_of(g * 8, 8)
        dtb = dt_ref[pl.ds(t0, 8), :]                # (8, Dc)
        xb = x_scr[pl.ds(t0, 8), :]
        bb = b_ref[pl.ds(t0, 8), :]                  # (8, N)
        cb = c_ref[pl.ds(t0, 8), :]
        ys = []
        for i in range(8):
            dt = dtb[i:i + 1]
            s = (jnp.exp(a * dt) * s
                 + column(bb[i:i + 1]) * (dt * xb[i:i + 1]))
            ys.append(jnp.sum(s * column(cb[i:i + 1]), axis=0,
                              keepdims=True))
        y_ref[pl.ds(t0, 8), :] = jnp.concatenate(ys, axis=0)
        return s

    s = jax.lax.fori_loop(0, block_t // 8, group, s_scr[...])
    s_scr[...] = s

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        sT_ref[...] = s


def _pick(total: int, want: int, align: int) -> int:
    """The largest divisor of ``total`` that is at most ``want`` and a
    multiple of ``align``; ``total`` itself when none is."""
    for blk in range(min(want, total), 0, -1):
        if total % blk == 0 and blk % align == 0:
            return blk
    return total


def selective_scan(x, dt, A, B, C, init_state, *,
                   block_d: int = DEFAULT_BLOCK_D,
                   block_t: int = DEFAULT_BLOCK_T,
                   interpret: bool = False):
    """Returns (y (batch, T, D) f32, final state (batch, D, N) f32).

    ``A`` is the negative real diagonal ``-exp(A_log)``.  Time is padded
    to whole chunks with dt = 0, an identity step."""
    bsz, t, d = x.shape
    n = A.shape[1]
    block_d = _pick(d, block_d, 128)
    block_t = min(block_t, -(-t // 8) * 8)
    pad = (-t) % block_t
    if pad:
        x, dt, B, C = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                       for v in (x, dt, B, C))
    tp = t + pad
    f32 = jnp.float32
    row = lambda b_, d_, t_: (b_, t_, d_)            # noqa: E731
    small = lambda b_, d_, t_: (b_, t_, 0)           # noqa: E731
    state = lambda b_, d_, t_: (b_, 0, d_)           # noqa: E731
    y, s_t = pl.pallas_call(
        functools.partial(_body, block_t=block_t),
        grid=(bsz, d // block_d, tp // block_t),
        in_specs=[
            pl.BlockSpec((None, block_t, block_d), row),
            pl.BlockSpec((None, block_t, block_d), row),
            pl.BlockSpec((n, block_d), lambda b_, d_, t_: (0, d_)),
            pl.BlockSpec((None, block_t, n), small),
            pl.BlockSpec((None, block_t, n), small),
            pl.BlockSpec((None, n, block_d), state),
        ],
        out_specs=[
            pl.BlockSpec((None, block_t, block_d), row),
            pl.BlockSpec((None, n, block_d), state),
        ],
        out_shape=[jax.ShapeDtypeStruct((bsz, tp, d), f32),
                   jax.ShapeDtypeStruct((bsz, n, d), f32)],
        scratch_shapes=[pltpu.VMEM((n, block_d), f32),
                        pltpu.VMEM((block_t, block_d), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(x, dt.astype(f32), A.T.astype(f32), B.astype(f32), C.astype(f32),
      jnp.swapaxes(init_state, 1, 2).astype(f32))
    return y[:, :t], jnp.swapaxes(s_t, 1, 2)
