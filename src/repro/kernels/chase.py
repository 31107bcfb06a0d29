"""Data-dependent pointer-chase latency kernels (the paper's l / m).

MEMSCOPE measures round-trip latency by ensuring exactly one outstanding
memory transaction: the next address is only known once the previous load
returns.  The buffer is initialised as a single permutation *cycle*
(Sattolo's algorithm — the TPU-native equivalent of the paper's
Appendix-A swap-based shuffle: full coverage, no repeats, unprefetchable).

Two TPU-native variants:

* ``chase_vmem`` (strategy ``l``) — the chain lives in a VMEM-resident
  block; an inner ``fori_loop`` performs truly dependent loads
  (``idx = buf[idx]``).  Measures on-chip (VMEM) load-to-use latency.
* ``chase_hbm``  (strategy ``m``) — the chain lives in HBM
  (``memory_space=ANY``); every step issues a single-line DMA
  HBM->VMEM, waits for it, and reads the next index from the landed
  line.  One outstanding transaction by construction — this is the
  ``dc civac`` non-cacheable chase, adapted to a software-managed
  memory hierarchy.

Line layout: (n_lines, 128) int32 — one 512-byte lane-row per "cache
line"; element [i, 0] holds the successor of line i.

Chains are built in two halves.  ``make_chain`` draws the Sattolo
permutation on the host in bulk: every step's swap target in one
``Generator.integers`` call, the swaps replayed with array operations.
It returns, bit for bit, the permutation that the textbook loop draws
from the same seed, which is what the chase's checksum is checked
against (a different cycle is a wrong result, not a faster one).
``lay_lines`` then writes the line layout on the device from the
successors, so only 4 bytes a line cross from the host.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.stream import VMEM_LIMIT_BYTES

LANE = 128


# ---------------------------------------------------------------------------
# Chain initialisation (the paper's Fig. 16, steps 1-3)
# ---------------------------------------------------------------------------


def make_chain(n_lines: int, seed: int = 0) -> np.ndarray:
    """Sattolo cyclic permutation: following next[i] from 0 visits every
    line exactly once before returning to 0.

    Bit for bit the permutation of the loop ``for i in n-1 .. 1: j =
    rng.integers(0, i); swap p[i], p[j]``, and it must stay so: a
    chase's final index is checked exactly against that loop's cycle.
    No per-line Python step: one call draws every ``j`` in the loop's
    order (array bounds draw exactly as the scalar calls do), and the
    swaps are replayed in closed form.  Position i is final after step
    i, and holds what position j[i] held just before it: the value that
    the next larger step g(i) aiming at j[i] put there, else j[i]
    itself.  A step k leaves at j[k] what position k held before step
    k: V(k), which is V(f(k)) for the smallest step f(k) aiming at k,
    else k.  Line 0 ends holding V(0)."""
    rng = np.random.default_rng(seed)
    if n_lines < 2:
        return np.arange(n_lines, dtype=np.int32)
    j = np.zeros(n_lines, np.int64)
    j[:0:-1] = rng.integers(0, np.arange(n_lines - 1, 0, -1))
    # steps grouped by target, ascending within a group
    steps = np.argsort(j[1:], kind="stable") + 1
    target = j[steps]
    first = np.ones(n_lines - 1, bool)
    first[1:] = target[1:] != target[:-1]
    # V by pointer doubling along k -> f(k); f(k) > k, so the longest
    # path (n - 1 hops) is resolved after bit_length(n - 1) rounds
    v = np.arange(n_lines)
    v[target[first]] = steps[first]
    for _ in range((n_lines - 1).bit_length()):
        v = v[v]
    p = j.copy()
    later = ~first[1:]               # steps[a + 1] is g(steps[a])
    p[steps[:-1][later]] = v[steps[1:][later]]
    p[0] = v[0]
    return p.astype(np.int32)


def chain_buffer(n_lines: int, seed: int = 0) -> np.ndarray:
    """(n_lines, 128) int32 buffer with the successor in lane 0, on the
    host (``lay_lines`` builds the same layout on the device)."""
    buf = np.zeros((n_lines, LANE), np.int32)
    buf[:, 0] = make_chain(n_lines, seed)
    return buf


def make_strided_chain(n_lines: int, stride: int) -> np.ndarray:
    """Deterministic strided cycle: next[i] = (i + stride') mod n with
    stride' the smallest value >= stride coprime to n, so the walk still
    visits every line exactly once.  Unlike the Sattolo shuffle the hop
    distance is CONSTANT — the strided-chase traffic shape: predictable
    distance, no spatial locality beyond the stride."""
    if n_lines == 1:
        return np.zeros(1, np.int32)
    s = max(1, stride) % n_lines or 1
    while math.gcd(s, n_lines) != 1:
        s += 1
        if s >= n_lines:
            s = 1
            break
    return ((np.arange(n_lines) + s) % n_lines).astype(np.int32)


def strided_chain_buffer(n_lines: int, stride: int) -> np.ndarray:
    """(n_lines, 128) int32 strided-cycle buffer (successor in lane 0)."""
    buf = np.zeros((n_lines, LANE), np.int32)
    buf[:, 0] = make_strided_chain(n_lines, stride)
    return buf


@jax.jit
def lay_lines(succ: jnp.ndarray) -> jnp.ndarray:
    """(..., n_lines) int32 successors -> the (..., n_lines, 128) line
    layout with the successor in lane 0, written on the device in one
    elementwise pass (a lane-0 scatter compiles to a transposed copy
    of the whole buffer, then a pad)."""
    return jnp.where(jnp.arange(LANE) == 0, succ[..., None], 0)


# ---------------------------------------------------------------------------
# VMEM chase (l)
# ---------------------------------------------------------------------------


def _chase_vmem_body(x_ref, o_ref, *, n_steps: int):
    def step(_, idx):
        return x_ref[idx, 0]

    o_ref[0, 0] = jax.lax.fori_loop(0, n_steps, step, jnp.int32(0))


def chase_vmem(buf: jnp.ndarray, *, n_steps: int,
               interpret: bool = False) -> jnp.ndarray:
    """buf: (n_lines, 128) int32, VMEM-resident. Returns the final index
    (data-dependent on every intermediate load)."""
    return pl.pallas_call(
        functools.partial(_chase_vmem_body, n_steps=n_steps),
        in_specs=[pl.BlockSpec(buf.shape, lambda: (0, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(buf)[0, 0]


# ---------------------------------------------------------------------------
# HBM chase (m): one line DMA'd per dependent step
# ---------------------------------------------------------------------------


def _chase_hbm_body(x_hbm_ref, o_ref, line_ref, sem, *, n_steps: int):
    g = pl.program_id(0)

    def step(_, idx):
        cp = pltpu.make_async_copy(
            x_hbm_ref.at[g, pl.ds(idx, 1)], line_ref, sem)
        cp.start()
        cp.wait()
        return line_ref[0, 0]

    o_ref[g] = jax.lax.fori_loop(0, n_steps, step, jnp.int32(0))


def chase_hbm(buf: jnp.ndarray, *, n_steps: int,
              interpret: bool = False) -> jnp.ndarray:
    """buf: (n_lines, 128) int32 staying in HBM; exactly one outstanding
    single-line DMA at any time.  A stacked (G, n_lines, 128) buffer
    walks its G chains one after another (one grid step each) and
    returns their G final indices: the TPU lowering cannot ``vmap`` a
    kernel whose operand stays in HBM, so batching lives here."""
    stacked = buf.reshape((-1,) + buf.shape[-2:])
    g = stacked.shape[0]
    out = pl.pallas_call(
        functools.partial(_chase_hbm_body, n_steps=n_steps),
        grid=(g,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((g,), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, LANE), jnp.int32),
                        pltpu.SemaphoreType.DMA],
        interpret=interpret,
    )(stacked)
    return out if buf.ndim == 3 else out[0]
