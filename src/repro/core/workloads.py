"""Workload Library — registry of micro-benchmark activities (Table I).

Each workload is keyed by its access-strategy letter and binds a memory
pool + buffer size to a runnable activity.  Workloads carry:

* a **buffer initialiser** (the paper's configurable init: sequential
  ints for bandwidth sanity-checking, a Sattolo chain for latency);
* an **executable** (jit'd Pallas kernel, interpret=True off-TPU) used by
  the ``interpret``/``tpu`` backends;
* the **queueing-class parameters** (strategy letter, traffic multiplier,
  MLP) consumed by the ``simulate`` backend.

The cacheable strategies (r/w/l) become VMEM-resident kernels when the
buffer fits the VMEM budget and HBM-streaming kernels otherwise — the
software-managed-hierarchy analog of "whether the buffer fits in L2",
which is exactly how the paper's Fig. 5 buffer-size sweeps behave.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.core.devicetree import MemoryNode
from repro.core.pools import Allocation, MemoryPool
from repro.kernels import ops

LANE = 128
LINE_BYTES = LANE * 4          # one (1,128) f32 row = 512 B "line"
VMEM_BUDGET = 64 << 20         # "cache size": cacheable buffers <= this
                               # are VMEM-resident (the L2-fit analog)
# the largest buffer a VMEM-resident kernel is handed: one whole-buffer
# block, double-buffered when vmapped, must fit the kernels' scoped
# VMEM limit (kernels.stream.VMEM_LIMIT_BYTES) on a v5e
VMEM_KERNEL_BYTES = 32 << 20
_VMEM_KERNELS = ("r", "w", "l")   # strategies with a VMEM-resident kernel


@dataclass
class WorkloadResult:
    strategy: str
    pool: str
    buffer_bytes: int
    iters: int
    bytes_moved: int           # useful bytes touched (all iters)
    elapsed_ns: float          # wall time (interpret/tpu backends)
    transactions: int          # dependent loads for latency workloads
    # the kernel's own result on its last call, normalised per pass:
    # the buffer sum for reads, the final chain index for chases
    checksum: Optional[float] = None
    # the memory kind the kernel's operand (or, for pure writes, its
    # destination) actually lived in, as JAX reports it
    memory_kind: Optional[str] = None
    # the seed of the Sattolo chain a pointer chase walked
    chain_seed: Optional[int] = None

    @property
    def bandwidth_gbps(self) -> float:
        if self.elapsed_ns <= 0:
            return 0.0
        return self.bytes_moved / self.elapsed_ns

    @property
    def latency_ns(self) -> float:
        if self.transactions <= 0:
            return 0.0
        return self.elapsed_ns / self.transactions


@dataclass
class Workload:
    """A bound activity: strategy letter + pool + buffer."""
    strategy: str
    pool: MemoryPool
    buffer_bytes: int
    description: str
    run_fn: Callable[[int], WorkloadResult]
    alloc: Optional[Allocation] = None
    is_memory_bound: bool = True

    def run(self, iters: int = 500) -> WorkloadResult:
        return self.run_fn(iters)

    def release(self) -> None:
        if self.alloc is not None:
            with spans.span("inputs"):
                self.pool.free(self.alloc)
            self.alloc = None

    @property
    def node(self) -> MemoryNode:
        return self.pool.node


# ---------------------------------------------------------------------------
# Buffer initialisers (paper: "Configurable Buffer Initialization")
# ---------------------------------------------------------------------------


def bw_buffer_init(shape, dtype):
    """Sequential integers — lets experiments sanity-check corruption."""
    n = int(np.prod(shape))
    return jnp.arange(n, dtype=jnp.float32).reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Factory
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[..., Workload]] = {}


def register_strategy(letter: str):
    def deco(fn):
        _REGISTRY[letter] = fn
        return fn
    return deco


def strategies() -> Dict[str, str]:
    return {k: (v.__doc__ or "").strip().splitlines()[0]
            for k, v in sorted(_REGISTRY.items())}


def make_workload(strategy: str, pool: MemoryPool, buffer_bytes: int,
                  **kw) -> Workload:
    if strategy not in _REGISTRY:
        raise KeyError(
            f"unknown access strategy {strategy!r}; have "
            f"{sorted(_REGISTRY)}")
    return _REGISTRY[strategy](pool, buffer_bytes, **kw)


def resolve_strategy(strategy: str, shape=None) -> str:
    """The strategy letter a (strategy, TrafficShape) pair actually
    executes as: mixed shapes run the ``b`` mixed-stream workload,
    strided shapes the ``t`` strided chase, everything else the plain
    strategy.  The single source of truth for this mapping — the
    batched group measurement below and the coordinator's spmd branch
    builder both consume it, so every backend executes the same kernel
    class for a given spec."""
    kind = getattr(shape, "kind", "steady") if shape is not None \
        else "steady"
    return {"mixed": "b", "strided": "t"}.get(kind, strategy)


def make_shaped_workload(strategy: str, pool: MemoryPool, buffer_bytes: int,
                         shape=None, **kw) -> Workload:
    """Bind a (strategy, TrafficShape) pair to an executable workload.

    Steady shapes resolve to the plain strategy; mixed ratios map onto
    the ``b`` mixed-stream workload, strided shapes onto the ``t``
    strided chase, and bursty shapes wrap the base workload with
    duty-cycled accounting (the off phase is pure idle, so the
    time-averaged bandwidth scales by the duty cycle)."""
    with spans.span("inputs"):
        if shape is None or getattr(shape, "is_steady", True):
            return make_workload(strategy, pool, buffer_bytes, **kw)
        if shape.kind == "mixed":
            return make_workload("b", pool, buffer_bytes,
                                 read_fraction=shape.read_fraction, **kw)
        if shape.kind == "strided":
            return make_workload("t", pool, buffer_bytes,
                                 stride=shape.stride, **kw)
        if shape.kind == "burst":
            wl = make_workload(strategy, pool, buffer_bytes, **kw)
            return _duty_cycled(wl, shape.duty_cycle)
        raise KeyError(f"unknown traffic shape kind {shape.kind!r}")


def _duty_cycled(wl: Workload, duty: float) -> Workload:
    import dataclasses
    base_run = wl.run_fn

    def run(iters):
        res = base_run(iters)
        idle_ns = res.elapsed_ns * (1.0 - duty) / duty
        return dataclasses.replace(res, elapsed_ns=res.elapsed_ns + idle_ns)

    wl.run_fn = run
    wl.description = f"{wl.description} (duty={duty:g})"
    return wl


# ---------------------------------------------------------------------------
# Batched group measurement (the matrix runner's fast path)
# ---------------------------------------------------------------------------

# observer strategies whose measured pass maps over a stacked input
# array, so G same-shape scenarios collapse into ONE jit'd vmapped
# dispatch (read-like paths; chases keep per-member Sattolo chains) —
# write-like paths and the deterministic strided chase ('t', whose
# members are bit-identical) carry no distinct batched input, so their
# group measures once and shares the result.
_VMAP_READS = ("r", "s", "c", "x", "b")
_VMAP_CHASES = ("l", "m")


# batched measurement stacks member buffers into one array; cap the
# stack so a big group cannot out-allocate the device (the naive path
# only ever holds ONE member buffer)
_BATCH_BYTES_CAP = 1 << 30


def measure_group(strategy: str, pool: MemoryPool, buffer_bytes: int,
                  n_members: int, iters: int, *, shape=None,
                  seeds: Optional[list] = None,
                  member_pools: Optional[list] = None,
                  stats=None, programs: Optional[dict] = None
                  ) -> Tuple[list, int]:
    """Measure ``n_members`` same-signature observers with jit'd
    ``vmap`` passes over the stacked member buffers (chases keep
    per-member chains, so different seeds/strides stay distinct).

    ``member_pools`` (optional, len ``n_members``) supports
    *heterogeneous* groups: observers from different pools whose
    placement lands in the same physical memory (the caller groups by
    :meth:`MemoryPool.effective_memory_kind`, so this never stacks
    buffers that would really live in different memories).  Each
    member's result is labeled with its own pool name.

    ``programs`` (optional) is the caller's table of the measured
    pass's ``jit(vmap(...))`` programs (see :func:`_vmapped`): a
    coordinator passes its own, so back-to-back sweeps build each
    program once.  Without it every chunk builds its program afresh.
    ``stats`` (the coordinator's ``DispatchStats``, optional) counts
    the programs built in ``programs_built`` and those found in
    ``programs`` in ``program_cache_hits``.

    Returns ``(results, n_dispatches)``.  Normally one dispatch covers
    the whole group; groups whose stacked footprint would exceed the
    batch byte cap or the pool's free space split into chunks (the
    naive path only ever holds ONE member buffer, so the batched path
    must not out-allocate it unboundedly), each chunk one dispatch.
    The group's wall time is split evenly (members are identical up to
    buffer content, and on hardware they run as concurrent engines of
    one fused pass)."""
    strat = resolve_strategy(strategy, shape)
    if strat not in _VMAP_READS + _VMAP_CHASES:
        # write-like path stacks no buffers: one measurement serves
        # the whole group regardless of member size
        chunk = n_members
    else:
        member_bytes = _rows(buffer_bytes) * LINE_BYTES
        budget = min(_BATCH_BYTES_CAP, max(pool.available, member_bytes))
        chunk = max(1, min(n_members, budget // member_bytes))
    results: list = []
    dispatches = 0
    for start in range(0, n_members, chunk):
        g = min(chunk, n_members - start)
        results.extend(_measure_chunk(
            strategy, pool, buffer_bytes, g, iters, shape=shape,
            seeds=(seeds[start:start + g] if seeds is not None
                   else list(range(start, start + g))),
            pool_names=([p.node.name for p in
                         member_pools[start:start + g]]
                        if member_pools is not None else None),
            stats=stats, programs=programs))
        dispatches += 1
    return results, dispatches


def _measure_chunk(strategy: str, pool: MemoryPool, buffer_bytes: int,
                   n_members: int, iters: int, *, shape=None,
                   seeds: Optional[list] = None,
                   pool_names: Optional[list] = None, stats=None,
                   programs: Optional[dict] = None) -> list:
    rows = _rows(buffer_bytes)
    g = n_members
    names = pool_names or [pool.node.name] * g
    vmem = _fits_vmem(buffer_bytes) or pool.node.kind == "vmem"
    blk = min(512, rows)
    strat = resolve_strategy(strategy, shape)

    duty = shape.duty_cycle if (shape is not None
                                and shape.kind == "burst") else 1.0

    if strat in _VMAP_CHASES:
        seeds = seeds or list(range(g))
        with spans.span("inputs"):
            bufs = chase_operand(pool, rows, seeds)
        steps = chase_steps(rows)
        if strat == "l" and vmem:
            batched = _vmapped(ops.chase_vmem, bufs, programs, stats,
                               n_steps=steps)
        else:
            # the HBM chase walks a stacked buffer's chains one after
            # another inside one kernel
            batched = functools.partial(ops.chase_hbm, n_steps=steps)
        t, out = _timed(batched, bufs, iters=max(1, iters // 10))
        # /g: the g chains execute back-to-back within the pass
        # (test_batched_chase_latency_matches_naive guards it)
        per = (t / g) / duty
        kind = bufs.sharding.memory_kind
        with spans.span("readback"):
            ends = np.asarray(out)
        return [WorkloadResult(strat, name, buffer_bytes, iters,
                               rows * LINE_BYTES, per, transactions=steps,
                               checksum=float(c), memory_kind=kind,
                               chain_seed=sd)
                for name, c, sd in zip(names, ends, seeds)]

    if strat in _VMAP_READS:
        # every member streams the same content, so one plain reference
        # checks every member's checksum
        with spans.span("inputs"):
            x = pool.place(jnp.broadcast_to(
                bw_buffer_init((rows, LANE), jnp.float32), (g, rows, LANE)))
        scale = 1.0
        useful = rows * LINE_BYTES
        if strat == "b":
            rf = (shape.read_fraction
                  if shape is not None and shape.kind == "mixed" else 0.5)
            kernel, static = ops.stream_mixed, dict(read_fraction=rf,
                                                    block_rows=blk)
        elif strat == "c":
            kernel, static = ops.stream_copy, dict(block_rows=blk)
            useful = 2 * rows * LINE_BYTES
        elif strat == "x":
            kernel, static = ops.stream_rmw, dict(block_rows=blk)
            useful = 2 * rows * LINE_BYTES
        elif vmem and strat == "r":
            kernel, static = ops.vmem_read, dict(repeats=8)
            scale = 1.0 / 8.0               # 8 on-chip re-reads per call
        else:
            kernel, static = ops.stream_read, dict(block_rows=blk)
        batched = _vmapped(kernel, x, programs, stats, **static)
        t, out = _timed(batched, x, iters=iters)
        t *= scale
        per = (t / g) / duty
        sums = _member_checksums(strat, out) * scale
        kind = x.sharding.memory_kind
        return [WorkloadResult(strat, name, buffer_bytes, iters,
                               useful * iters, per * iters, 0,
                               checksum=float(c), memory_kind=kind)
                for name, c in zip(names, sums)]

    # write-like paths (w/x/y/i...): no batched input array — one
    # measurement, shared by every identical member (relabeled with
    # each member's own pool for heterogeneous groups).
    wl = make_shaped_workload(strategy, pool, buffer_bytes, shape)
    try:
        res = wl.run(iters)
    finally:
        wl.release()
    import dataclasses
    return [res if name == res.pool else dataclasses.replace(res, pool=name)
            for name in names]


def chase_operand(pool: MemoryPool, rows: int, seeds) -> jax.Array:
    """The stacked ``(len(seeds), rows, 128)`` int32 chase operand in
    ``pool``'s memory: each seed's Sattolo successors drawn on the host,
    their line layout written on the device."""
    return pool.place(ops.lay_lines(
        np.stack([ops.make_chain(rows, s) for s in seeds])))


def _vmapped(kernel, operand, programs: Optional[dict], stats,
             **static) -> Callable:
    """The ``jit(vmap(kernel))`` program over the stacked ``operand``.

    ``programs`` keeps one program per kernel, static arguments, and
    operand shape, dtype and memory kind: everything that defines the
    compiled program, so a hit traces, lowers and compiles nothing.
    Strategies that run the same kernel at the same arguments share
    one entry.  A miss builds the program and, given ``programs``,
    keeps it.  ``stats`` counts misses in ``programs_built`` and hits
    in ``program_cache_hits``."""
    key = (kernel, tuple(sorted(static.items())), operand.shape,
           operand.dtype, operand.sharding.memory_kind)
    fn = programs.get(key) if programs is not None else None
    if fn is not None:
        if stats is not None:
            stats.program_cache_hits += 1
        return fn
    fn = jax.jit(jax.vmap(functools.partial(kernel, **static)))
    if programs is not None:
        programs[key] = fn
    if stats is not None:
        stats.programs_built += 1
    return fn


def _member_checksums(strat: str, out) -> np.ndarray:
    """Per-member checksum of a vmapped stream pass: the read sum, plus
    the destination's sum where the kernel writes one."""
    with spans.span("readback"):
        if strat == "b":
            acc, written = out
            return (np.asarray(acc, np.float64)
                    + np.asarray(jnp.sum(written, axis=(1, 2)), np.float64))
        if strat in ("c", "x"):
            return np.asarray(jnp.sum(out, axis=(1, 2)), np.float64)
        return np.asarray(out, np.float64)


def chase_steps(rows: int) -> int:
    """Dependent loads per chase pass: one short of the full cycle, so
    the final index (the predecessor of line 0) checks the walk."""
    return max(1, rows - 1)


def _rows(buffer_bytes: int) -> int:
    rows = max(1, buffer_bytes // LINE_BYTES)
    # keep divisible by the largest block we use
    block = 512 if rows >= 512 else rows
    return (rows // block) * block or rows


def rows_for(buffer_bytes: int) -> int:
    """Public spelling of the buffer->line-rows mapping every backend
    shares (block-aligned row count for a byte budget); the spmd rung
    builder and the batched measured pass must agree on it exactly."""
    return _rows(buffer_bytes)


def _timed(fn, *args, iters: int, **kw) -> Tuple[float, Any]:
    """Median-of-3 wall time for `iters` back-to-back calls (ns), and
    the last call's output."""
    with spans.span("build"):
        out = jax.block_until_ready(fn(*args, **kw))   # compile + warm
    samples = []
    with spans.span("timed"):
        for _ in range(3):
            t0 = time.perf_counter_ns()
            for _ in range(iters):
                out = fn(*args, **kw)
            jax.block_until_ready(out)
            samples.append((time.perf_counter_ns() - t0) / iters)
    return float(np.median(samples)), out


def _fits_vmem(buffer_bytes: int) -> bool:
    """Executable-kernel residency choice: what a VMEM-resident kernel
    may hold on the chip."""
    return buffer_bytes <= VMEM_KERNEL_BYTES


def models_as_vmem(buffer_bytes: int) -> bool:
    """Modeling-side 'fits the cache' rule (the Fig. 5 sweep knee)."""
    return buffer_bytes < VMEM_BUDGET


def refusal(strategy: str, pool: MemoryPool,
            buffer_bytes: int) -> Optional[str]:
    """Why ``strategy`` cannot run as a compiled kernel on ``pool`` at
    ``buffer_bytes``, or None when it can.  The idle loop touches no
    memory, so it runs everywhere."""
    if strategy == "i":
        return None
    if pool.node.kind == "peer":
        return ("no probe kernel reaches another chip's memory; its "
                "operands would live in local HBM")
    if pool.node.kind == "vmem":
        if strategy not in _VMEM_KERNELS:
            return (f"strategy {strategy!r} has no VMEM-resident kernel; "
                    f"on the vmem pool it would stream HBM")
        if buffer_bytes > VMEM_KERNEL_BYTES:
            return (f"{buffer_bytes} B exceeds the "
                    f"{VMEM_KERNEL_BYTES >> 20} MiB a VMEM-resident "
                    f"kernel may hold")
    if pool.effective_memory_kind() == "pinned_host":
        return ("no probe kernel can take a pinned_host operand: the TPU "
                "compiler has no DMA from host memory into VMEM "
                "('Unimplemented DMA from host to vmem'), and the Pallas "
                "interpreter cannot mix host and device memory")
    return None


def _done(strategy: str, pool: MemoryPool, buffer_bytes: int, iters: int,
          nbytes: int, t: float, out, *, src=None, transactions: int = 0
          ) -> WorkloadResult:
    """One registry workload's result, stamped with the kernel's output
    checksum (the sum over every array of ``out``) and the memory kind
    of its operand (``src``) or, for pure writes, of its destination."""
    where = src if src is not None else out
    with spans.span("readback"):
        checksum = sum(float(jnp.sum(o)) for o in jax.tree.leaves(out))
    return WorkloadResult(
        strategy, pool.node.name, buffer_bytes, iters, nbytes, t,
        transactions, checksum=checksum,
        memory_kind=where.sharding.memory_kind)


def _operand(alloc: Allocation, init, shape, dtype):
    """The allocation's placed array.  A pool with no memory kind (vmem)
    places nothing, so its operand is made in default memory."""
    return alloc.array if alloc.array is not None else init(shape, dtype)


# ---- bandwidth strategies ---------------------------------------------------


@register_strategy("r")
def _mk_r(pool, buffer_bytes, **kw):
    """sequential reads (cacheable) — read bandwidth"""
    rows = _rows(buffer_bytes)
    alloc = pool.alloc((rows, LANE), jnp.float32, init=bw_buffer_init,
                       tag="bw:r")
    x = _operand(alloc, bw_buffer_init, (rows, LANE), jnp.float32)
    vmem = _fits_vmem(buffer_bytes) or pool.node.kind == "vmem"

    def run(iters):
        if vmem:
            t, out = _timed(ops.vmem_read, x, repeats=8, iters=iters)
            t, out = t / 8, out / 8
        else:
            t, out = _timed(ops.stream_read, x, block_rows=min(512, rows),
                            iters=iters)
        return _done("r", pool, buffer_bytes, iters,
                     rows * LINE_BYTES * iters, t * iters, out, src=x)

    return Workload("r", pool, buffer_bytes,
                    "sequential cacheable read", run, alloc)


@register_strategy("w")
def _mk_w(pool, buffer_bytes, **kw):
    """sequential writes (cacheable, write-allocate) — write bandwidth"""
    rows = _rows(buffer_bytes)
    alloc = pool.alloc((rows, LANE), jnp.float32, tag="bw:w")
    vmem = _fits_vmem(buffer_bytes) or pool.node.kind == "vmem"

    def run(iters):
        if vmem:
            t, out = _timed(ops.vmem_write, rows=rows, repeats=8,
                            iters=iters)
            t = t / 8
        else:
            t, out = _timed(ops.stream_write, rows=rows,
                            block_rows=min(512, rows), iters=iters)
        return _done("w", pool, buffer_bytes, iters,
                     rows * LINE_BYTES * iters, t * iters, out)

    return Workload("w", pool, buffer_bytes,
                    "sequential cacheable write", run, alloc)


@register_strategy("s")
def _mk_s(pool, buffer_bytes, **kw):
    """non-cacheable sequential read (always streams from the module)"""
    rows = _rows(buffer_bytes)
    alloc = pool.alloc((rows, LANE), jnp.float32, init=bw_buffer_init,
                       tag="bw:s")
    x = _operand(alloc, bw_buffer_init, (rows, LANE), jnp.float32)

    def run(iters):
        t, out = _timed(ops.stream_read, x, block_rows=min(512, rows),
                        iters=iters)
        return _done("s", pool, buffer_bytes, iters,
                     rows * LINE_BYTES * iters, t * iters, out, src=x)

    return Workload("s", pool, buffer_bytes, "non-cacheable read", run,
                    alloc)


@register_strategy("x")
def _mk_x(pool, buffer_bytes, **kw):
    """non-cacheable write (write-allocate: line read+written)"""
    rows = _rows(buffer_bytes)
    alloc = pool.alloc((rows, LANE), jnp.float32, init=bw_buffer_init,
                       tag="bw:x")
    x = _operand(alloc, bw_buffer_init, (rows, LANE), jnp.float32)

    def run(iters):
        t, out = _timed(ops.stream_rmw, x, block_rows=min(512, rows),
                        iters=iters)
        return _done("x", pool, buffer_bytes, iters,
                     2 * rows * LINE_BYTES * iters, t * iters, out, src=x)

    return Workload("x", pool, buffer_bytes,
                    "non-cacheable write (allocate)", run, alloc)


@register_strategy("y")
def _mk_y(pool, buffer_bytes, **kw):
    """write-streaming (no write-allocate — the dc zva analog)"""
    rows = _rows(buffer_bytes)
    alloc = pool.alloc((rows, LANE), jnp.float32, tag="bw:y")

    def run(iters):
        t, out = _timed(ops.stream_write, rows=rows,
                        block_rows=min(512, rows), iters=iters)
        return _done("y", pool, buffer_bytes, iters,
                     rows * LINE_BYTES * iters, t * iters, out)

    return Workload("y", pool, buffer_bytes, "write-streaming", run, alloc)


@register_strategy("c")
def _mk_c(pool, buffer_bytes, **kw):
    """copy stream (read every line, write it elsewhere) — STREAM copy"""
    rows = _rows(buffer_bytes)
    alloc = pool.alloc((rows, LANE), jnp.float32, init=bw_buffer_init,
                       tag="bw:c")
    x = _operand(alloc, bw_buffer_init, (rows, LANE), jnp.float32)

    def run(iters):
        t, out = _timed(ops.stream_copy, x, block_rows=min(512, rows),
                        iters=iters)
        return _done("c", pool, buffer_bytes, iters,
                     2 * rows * LINE_BYTES * iters, t * iters, out, src=x)

    return Workload("c", pool, buffer_bytes, "copy stream", run, alloc)


@register_strategy("b")
def _mk_mixed(pool, buffer_bytes, *, read_fraction: float = 0.5, **kw):
    """mixed read/write blocks at a configurable r:w ratio"""
    rows = _rows(buffer_bytes)
    alloc = pool.alloc((rows, LANE), jnp.float32, init=bw_buffer_init,
                       tag="bw:b")
    x = _operand(alloc, bw_buffer_init, (rows, LANE), jnp.float32)
    rf = max(0.0, min(1.0, read_fraction))

    def run(iters):
        t, out = _timed(ops.stream_mixed, x, read_fraction=rf,
                        block_rows=min(512, rows), iters=iters)
        # the checksum is the read sum plus the sum of what was written
        return _done("b", pool, buffer_bytes, iters,
                     rows * LINE_BYTES * iters, t * iters, out, src=x)

    return Workload("b", pool, buffer_bytes,
                    f"mixed r/w stream (rf={rf:g})", run, alloc)


def _chase_workload(letter: str, pool, buffer_bytes: int, chain, kernel,
                    description: str, seed: Optional[int] = None
                    ) -> Workload:
    """A pointer-chase workload whose chain buffer is placed through the
    pool (so a latency is measured in the pool's own memory).  ``chain``
    gives a row count's successors; their line layout is built on the
    device."""
    rows = _rows(buffer_bytes)

    def init(_shape, _dtype):
        return ops.lay_lines(chain(rows))

    alloc = pool.alloc((rows, LANE), jnp.int32, init=init,
                       tag=f"lat:{letter}")
    buf = _operand(alloc, init, (rows, LANE), jnp.int32)
    steps = chase_steps(rows)

    def run(iters):
        t, out = _timed(kernel, buf, n_steps=steps,
                        iters=max(1, iters // 10))
        res = _done(letter, pool, buffer_bytes, iters, rows * LINE_BYTES,
                    t, out, src=buf, transactions=steps)
        res.chain_seed = seed
        return res

    return Workload(letter, pool, buffer_bytes, description, run, alloc)


@register_strategy("t")
def _mk_strided(pool, buffer_bytes, *, stride: int = 8, **kw):
    """strided pointer chase (constant hop distance, non-cacheable)"""
    return _chase_workload(
        "t", pool, buffer_bytes,
        lambda rows: ops.make_strided_chain(rows, stride), ops.chase_hbm,
        f"strided pointer-chase (x{stride})")


# ---- latency strategies -----------------------------------------------------


@register_strategy("l")
def _mk_l(pool, buffer_bytes, *, seed: int = 0, **kw):
    """data-dependent pointer chase (cacheable) — latency"""
    vmem = _fits_vmem(buffer_bytes) or pool.node.kind == "vmem"
    return _chase_workload(
        "l", pool, buffer_bytes,
        lambda rows: ops.make_chain(rows, seed),
        ops.chase_vmem if vmem else ops.chase_hbm,
        "pointer-chase latency", seed)


@register_strategy("m")
def _mk_m(pool, buffer_bytes, *, seed: int = 0, **kw):
    """non-cacheable pointer chase — module latency"""
    return _chase_workload(
        "m", pool, buffer_bytes,
        lambda rows: ops.make_chain(rows, seed), ops.chase_hbm,
        "non-cacheable pointer-chase", seed)


# ---- memory-idle -------------------------------------------------------------


@register_strategy("i")
def _mk_idle(pool, buffer_bytes, **kw):
    """memory-idle MXU busy loop (zero memory traffic)"""
    # the identity keeps every power exact whatever the MXU's input
    # precision, so the checksum (the trace, 128) checks the kernel
    a = jnp.eye(128, dtype=jnp.float32)

    def run(iters):
        t, out = _timed(lambda aa: ops.mxu_probe(aa, iters=64), a,
                        iters=iters)
        return WorkloadResult("i", pool.node.name, 0, iters, 0, t * iters,
                              0, checksum=float(jnp.sum(out)))

    return Workload("i", pool, 0, "memory-idle busy loop", run, None,
                    is_memory_bound=False)
