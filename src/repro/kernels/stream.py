"""Sequential bandwidth microbenchmark kernels (the paper's r/w/s/x/y).

TPU adaptation of MEMSCOPE's assembly bandwidth test benches.  On the
ZCU102 the distinction is cacheable vs. non-cacheable *instructions*; on a
TPU the "cache" is VMEM (software-managed), so the distinction becomes a
**BlockSpec choice**:

* ``*_hbm``  — grid over HBM blocks, each block DMA'd into VMEM exactly
  once (the non-cacheable analog: every byte travels HBM<->VMEM).
* ``*_vmem`` — a single VMEM-resident block iterated ``repeats`` times by
  an inner ``fori_loop`` (the cacheable analog: traffic stays on-chip).

Ops:
  read   (r/s)  sum-reduce each block (result returned so XLA can't DCE).
  write  (w/x)  write a constant to each block; with write-allocate
                semantics the destination is also an *input* (aliased), so
                the line is read before written — MEMSCOPE's ``x``.
  stream (y)    pure write, destination never read — MEMSCOPE's ``dc zva``
                write-streaming (write-no-allocate).
  copy / triad  STREAM-style composites used by the validation benchmark.

All kernels use (block_rows, 128) f32 blocks (lane-aligned for the VPU).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
DEFAULT_BLOCK_ROWS = 512  # 512*128*4B = 256 KiB per block

# VMEM-resident kernels hold their whole buffer in one block.  The
# largest buffer they are handed (workloads.VMEM_KERNEL_BYTES) plus the
# compiler's own scratch must fit under this scoped-VMEM limit, which
# stays below the 128 MiB a v5e TensorCore has.
VMEM_LIMIT_BYTES = 100 << 20


def _vmem_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _grid_blocks(n_rows: int, block_rows: int) -> int:
    assert n_rows % block_rows == 0, (n_rows, block_rows)
    return n_rows // block_rows


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------


def _read_body(x_ref, acc_ref):
    # one scalar accumulator in SMEM across the sequential grid: the TPU
    # stores scalars only to SMEM, and an (n, 1) VMEM output would break
    # the (8, 128) block tiling
    @pl.when(pl.program_id(0) == 0)
    def _():
        acc_ref[0, 0] = jnp.float32(0.0)

    acc_ref[0, 0] += jnp.sum(x_ref[...], dtype=jnp.float32)


def _write_body(o_ref, *, value: float):
    o_ref[...] = jnp.full_like(o_ref, value)


def _write_seeded_body(seed_ref, o_ref, *, value: float):
    # the stored value depends on the (1,1) seed operand, so the store
    # traffic carries a dataflow edge from whatever produced the seed —
    # one extra scalar read total, still a pure write stream per line
    o_ref[...] = jnp.full_like(o_ref, value) + seed_ref[0, 0]


def _rmw_body(x_ref, o_ref):
    # write-allocate analog: the line is read, modified, written back
    o_ref[...] = x_ref[...] + 1.0


def _copy_body(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _triad_body(b_ref, c_ref, o_ref, *, scalar: float):
    o_ref[...] = b_ref[...] + scalar * c_ref[...]


def _read_vmem_body(x_ref, acc_ref, *, repeats: int):
    def step(i, acc):
        # rotate a tiny offset so the loop is not hoisted; all traffic VMEM
        return acc + jnp.sum(x_ref[...], dtype=jnp.float32) + i * 0.0

    acc_ref[0, 0] = jax.lax.fori_loop(0, repeats, step, jnp.float32(0.0))


def _write_vmem_body(o_ref, *, repeats: int):
    def step(i, _):
        o_ref[...] = jnp.full_like(o_ref, i.astype(jnp.float32))
        return 0

    jax.lax.fori_loop(0, repeats, step, 0)


# ---------------------------------------------------------------------------
# pallas_call wrappers (HBM-streaming variants: grid over blocks)
# ---------------------------------------------------------------------------


def read_hbm(x: jnp.ndarray, *, block_rows: int = DEFAULT_BLOCK_ROWS,
             interpret: bool = False) -> jnp.ndarray:
    """Sum x by streaming every block through VMEM once. x: (R, 128) f32."""
    n = _grid_blocks(x.shape[0], block_rows)
    return pl.pallas_call(
        _read_body,
        grid=(n,),
        in_specs=[pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret,
    )(x)[0, 0]


def write_hbm(shape_rows: int, *, value: float = 1.0,
              block_rows: int = DEFAULT_BLOCK_ROWS,
              interpret: bool = False) -> jnp.ndarray:
    """Write-streaming (y): pure stores, destination never read."""
    n = _grid_blocks(shape_rows, block_rows)
    return pl.pallas_call(
        functools.partial(_write_body, value=value),
        grid=(n,),
        in_specs=[],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((shape_rows, LANE), jnp.float32),
        interpret=interpret,
    )()


def write_hbm_seeded(seed: jnp.ndarray, shape_rows: int, *,
                     value: float = 1.0,
                     block_rows: int = DEFAULT_BLOCK_ROWS,
                     interpret: bool = False) -> jnp.ndarray:
    """Write-streaming (y) with a dataflow anchor: identical store
    traffic to :func:`write_hbm`, but the stored value depends on the
    (1, 1) f32 ``seed`` operand.  The SPMD backend uses this so a pure
    write activity cannot be hoisted above the rung's start barrier —
    ``write_hbm`` takes no operands at all, which leaves the measured
    region structurally unfenced (see ``measured_region_is_fenced``)."""
    n = _grid_blocks(shape_rows, block_rows)
    return pl.pallas_call(
        functools.partial(_write_seeded_body, value=value),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((shape_rows, LANE), jnp.float32),
        interpret=interpret,
    )(seed)


def rmw_hbm(x: jnp.ndarray, *, block_rows: int = DEFAULT_BLOCK_ROWS,
            interpret: bool = False) -> jnp.ndarray:
    """Write-allocate (x): every line read, modified, written back."""
    n = _grid_blocks(x.shape[0], block_rows)
    return pl.pallas_call(
        _rmw_body,
        grid=(n,),
        in_specs=[pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x)


def copy_hbm(x: jnp.ndarray, *, block_rows: int = DEFAULT_BLOCK_ROWS,
             interpret: bool = False) -> jnp.ndarray:
    n = _grid_blocks(x.shape[0], block_rows)
    return pl.pallas_call(
        _copy_body,
        grid=(n,),
        in_specs=[pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x)


def triad_hbm(b: jnp.ndarray, c: jnp.ndarray, *, scalar: float = 3.0,
              block_rows: int = DEFAULT_BLOCK_ROWS,
              interpret: bool = False) -> jnp.ndarray:
    n = _grid_blocks(b.shape[0], block_rows)
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_triad_body, scalar=scalar),
        grid=(n,),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        interpret=interpret,
    )(b, c)


def mixed_hbm(x: jnp.ndarray, *, read_fraction: float,
              value: float = 1.0, block_rows: int = DEFAULT_BLOCK_ROWS,
              interpret: bool = False,
              seed: Optional[jnp.ndarray] = None):
    """Mixed read/write stream: ``read_fraction`` of the blocks are
    sum-reduced (pure read traffic), the rest are written (pure store
    traffic) — nothing else touches memory, so the realized read:write
    line ratio IS the configured one.  Interleave order is irrelevant
    to a bandwidth mix, so the split is by row range.

    Returns (read_sum, written): read_sum keeps the read traffic live
    under DCE; written is the store destination.

    The ratio is realized at whole-block granularity; when the buffer
    holds few blocks at the requested block size, the block size is
    reduced (to the largest row-count divisor giving >= 8 blocks) so a
    small buffer cannot silently degenerate to a pure read or write.

    ``seed`` (optional (1, 1) f32): route the write half through
    :func:`write_hbm_seeded` so the store traffic carries a dataflow
    edge from the seed — required when the mix runs inside a fenced
    SPMD measured region (a no-operand write kernel could be hoisted
    above the start barrier).
    """
    assert 0.0 <= read_fraction <= 1.0
    rows = x.shape[0]
    if 0.0 < read_fraction < 1.0 and rows // block_rows < 8:
        block_rows = next(b for b in range(max(1, rows // 8), 0, -1)
                          if rows % b == 0)
    n = _grid_blocks(rows, block_rows)
    n_r = max(0, min(n, int(round(n * read_fraction))))
    if 0.0 < read_fraction < 1.0 and n >= 2:
        # an extreme but genuine mix keeps >= 1 block of each kind
        n_r = max(1, min(n - 1, n_r))
    n_w = n - n_r
    acc = jnp.float32(0.0)
    out = jnp.zeros((0, LANE), jnp.float32)
    if n_r:
        acc = read_hbm(x[:n_r * block_rows], block_rows=block_rows,
                       interpret=interpret)
    if n_w:
        if seed is not None:
            out = write_hbm_seeded(seed, n_w * block_rows, value=value,
                                   block_rows=block_rows,
                                   interpret=interpret)
        else:
            out = write_hbm(n_w * block_rows, value=value,
                            block_rows=block_rows, interpret=interpret)
    return acc, out


# ---------------------------------------------------------------------------
# VMEM-resident variants (cacheable analog)
# ---------------------------------------------------------------------------


def read_vmem(x: jnp.ndarray, *, repeats: int = 16,
              interpret: bool = False) -> jnp.ndarray:
    """Re-read a VMEM-resident buffer `repeats` times (one DMA in)."""
    return pl.pallas_call(
        functools.partial(_read_vmem_body, repeats=repeats),
        in_specs=[pl.BlockSpec(x.shape, lambda: (0, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        compiler_params=_vmem_params(),
        interpret=interpret,
    )(x)[0, 0]


def write_vmem(shape_rows: int, *, repeats: int = 16,
               interpret: bool = False) -> jnp.ndarray:
    """Re-write a VMEM-resident buffer `repeats` times (one DMA out)."""
    return pl.pallas_call(
        functools.partial(_write_vmem_body, repeats=repeats),
        in_specs=[],
        out_specs=pl.BlockSpec((shape_rows, LANE), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((shape_rows, LANE), jnp.float32),
        compiler_params=_vmem_params(),
        interpret=interpret,
    )()
