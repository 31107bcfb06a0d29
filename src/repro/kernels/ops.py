"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU (the CPU runs the Pallas
interpreter) and False on a TPU, so the same call sites work in both.
A TPU process never runs a kernel in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import chase as _chase
from repro.kernels import compute_probe as _probe
from repro.kernels import flash_attention as _flash
from repro.kernels import selective_scan as _scan
from repro.kernels import stream as _stream


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interp(override: Optional[bool]) -> bool:
    if on_tpu():
        if override:
            raise ValueError("interpret mode requested in a TPU process; "
                             "kernels run compiled on the chip")
        return False
    return True if override is None else override


# --- stream ------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def stream_read(x, *, block_rows: int = 512, interpret: Optional[bool] = None):
    return _stream.read_hbm(x, block_rows=block_rows,
                            interpret=_interp(interpret))


@functools.partial(jax.jit,
                   static_argnames=("rows", "block_rows", "interpret"))
def stream_write(*, rows: int, block_rows: int = 512,
                 interpret: Optional[bool] = None):
    return _stream.write_hbm(rows, block_rows=block_rows,
                             interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def stream_rmw(x, *, block_rows: int = 512,
               interpret: Optional[bool] = None):
    return _stream.rmw_hbm(x, block_rows=block_rows,
                           interpret=_interp(interpret))


@functools.partial(jax.jit,
                   static_argnames=("rows", "block_rows", "interpret"))
def stream_write_seeded(seed, *, rows: int, block_rows: int = 512,
                        interpret: Optional[bool] = None):
    return _stream.write_hbm_seeded(seed, rows, block_rows=block_rows,
                                    interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def stream_copy(x, *, block_rows: int = 512,
                interpret: Optional[bool] = None):
    return _stream.copy_hbm(x, block_rows=block_rows,
                            interpret=_interp(interpret))


@functools.partial(jax.jit,
                   static_argnames=("scalar", "block_rows", "interpret"))
def stream_triad(b, c, *, scalar: float = 3.0, block_rows: int = 512,
                 interpret: Optional[bool] = None):
    return _stream.triad_hbm(b, c, scalar=scalar, block_rows=block_rows,
                             interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("read_fraction",
                                             "block_rows", "interpret"))
def stream_mixed(x, *, read_fraction: float, block_rows: int = 512,
                 interpret: Optional[bool] = None):
    return _stream.mixed_hbm(x, read_fraction=read_fraction,
                             block_rows=block_rows,
                             interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("repeats", "interpret"))
def vmem_read(x, *, repeats: int = 16, interpret: Optional[bool] = None):
    return _stream.read_vmem(x, repeats=repeats,
                             interpret=_interp(interpret))


@functools.partial(jax.jit,
                   static_argnames=("rows", "repeats", "interpret"))
def vmem_write(*, rows: int, repeats: int = 16,
               interpret: Optional[bool] = None):
    return _stream.write_vmem(rows, repeats=repeats,
                              interpret=_interp(interpret))


# --- chase -------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_steps", "interpret"))
def chase_vmem(buf, *, n_steps: int, interpret: Optional[bool] = None):
    return _chase.chase_vmem(buf, n_steps=n_steps,
                             interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("n_steps", "interpret"))
def chase_hbm(buf, *, n_steps: int, interpret: Optional[bool] = None):
    return _chase.chase_hbm(buf, n_steps=n_steps,
                            interpret=_interp(interpret))


make_chain = _chase.make_chain
chain_buffer = _chase.chain_buffer
make_strided_chain = _chase.make_strided_chain
strided_chain_buffer = _chase.strided_chain_buffer
lay_lines = _chase.lay_lines


# --- compute probe -------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("iters", "interpret"))
def mxu_probe(a, *, iters: int = 64, interpret: Optional[bool] = None):
    return _probe.mxu_probe(a, iters=iters, interpret=_interp(interpret))


# --- flash attention -----------------------------------------------------------


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "sm_scale", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    sm_scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None):
    return _flash.flash_attention(
        q, k, v, causal=causal, window=window, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=_interp(interpret))


# --- selective scan ----------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(x, dt, A, B, C, init_state, *,
                   interpret: Optional[bool] = None):
    """Mamba-1 prefill scan: (y (batch, T, D) f32, final state)."""
    return _scan.selective_scan(x, dt, A, B, C, init_state,
                                interpret=_interp(interpret))
