"""Workload library — the access strategies of Table I and the one
measured pass that runs them.

``STRATEGIES`` holds, per access-strategy letter, everything a
measurement of it needs: its HBM-streaming kernel and, for the
cacheable strategies (r/w/l), a VMEM-resident kernel; the static
arguments each takes; the operand it is handed (the paper's
configurable buffer initialisation: sequential ints for bandwidth
sanity-checking, a Sattolo chain for latency); the lines it moves per
buffer line; and whether members of a group carry distinct stacked
operands or share one measurement.  :func:`measure_group` measures any
number of same-signature observers of any letter from that table.

The cacheable strategies become VMEM-resident kernels when the buffer
fits the VMEM budget and HBM-streaming kernels otherwise — the
software-managed-hierarchy analog of "whether the buffer fits in L2",
which is exactly how the paper's Fig. 5 buffer-size sweeps behave.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.core.pools import MemoryPool
from repro.kernels import ops

LANE = 128
LINE_BYTES = LANE * 4          # one (1,128) f32 row = 512 B "line"
VMEM_BUDGET = 64 << 20         # "cache size": cacheable buffers <= this
                               # are VMEM-resident (the L2-fit analog)
# the largest buffer a VMEM-resident kernel is handed: one whole-buffer
# block, double-buffered when vmapped, must fit the kernels' scoped
# VMEM limit (kernels.stream.VMEM_LIMIT_BYTES) on a v5e
VMEM_KERNEL_BYTES = 32 << 20
BLOCK_ROWS = 512               # rows of one (rows, 128) stream block


def block_rows(rows: int) -> int:
    """The stream kernels' block for a buffer of ``rows`` lines."""
    return min(BLOCK_ROWS, rows)


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


# ---------------------------------------------------------------------------
# The strategy table
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Kernel:
    """A probe kernel as the measured pass calls it: ``ops.<name>``,
    looked up at call time, with the static arguments named in
    ``takes`` filled in per measurement (``rows``, ``block_rows``,
    ``n_steps``, ``read_fraction``) and those in ``fixed`` constant.
    ``repeats`` in ``fixed`` is the passes one call makes over the
    buffer: the time is per pass, and so is the output of a kernel that
    adds up its passes (``summed``).  A stacked operand is vmapped over
    unless the kernel walks the stack itself (``vmapped=False``)."""
    name: str
    takes: Tuple[str, ...] = ()
    fixed: Tuple[Tuple[str, Any], ...] = ()
    summed: bool = False
    vmapped: bool = True

    @property
    def fn(self) -> Callable:
        return getattr(ops, self.name)

    @property
    def passes(self) -> int:
        return dict(self.fixed).get("repeats", 1)


@dataclass(frozen=True)
class Strategy:
    """One access strategy.  ``buffer`` is what its kernel is handed:
    ``"lines"`` (the sequential float buffer), ``"chain"`` (a Sattolo
    successor chain per member seed), ``"strided"`` (the constant-hop
    chain of ``stride``), ``"identity"`` (the MXU loop's 128x128
    matrix), or None (the kernel makes its own output)."""
    description: str
    hbm: Kernel
    vmem: Optional[Kernel] = None      # VMEM-resident kernel (r w l)
    buffer: Optional[str] = None
    lines: int = 1                     # lines moved per buffer line
    stride: int = 0                    # default hop of a strided chase

    @property
    def chase(self) -> bool:
        """A dependent-load walk: its time is per transaction."""
        return self.buffer in ("chain", "strided")

    @property
    def stacked(self) -> bool:
        """Members carry distinct operands, stacked into one pass;
        otherwise one measurement serves every member of a group."""
        return self.buffer in ("lines", "chain")


_READ = Kernel("stream_read", ("block_rows",))
_WRITE = Kernel("stream_write", ("rows", "block_rows"))
_CHASE = Kernel("chase_hbm", ("n_steps",), vmapped=False)

STRATEGIES: Dict[str, Strategy] = {
    "r": Strategy("sequential cacheable read", _READ,
                  Kernel("vmem_read", fixed=(("repeats", 8),),
                         summed=True), buffer="lines"),
    "s": Strategy("non-cacheable read", _READ, buffer="lines"),
    "w": Strategy("sequential cacheable write", _WRITE,
                  Kernel("vmem_write", ("rows",), (("repeats", 8),))),
    "y": Strategy("write-streaming (no write-allocate)", _WRITE),
    "x": Strategy("non-cacheable write (allocate: read and write)",
                  Kernel("stream_rmw", ("block_rows",)), buffer="lines",
                  lines=2),
    "c": Strategy("copy stream", Kernel("stream_copy", ("block_rows",)),
                  buffer="lines", lines=2),
    "b": Strategy("mixed read/write stream",
                  Kernel("stream_mixed", ("read_fraction", "block_rows")),
                  buffer="lines"),
    "l": Strategy("pointer-chase latency (cacheable)", _CHASE,
                  Kernel("chase_vmem", ("n_steps",)), buffer="chain"),
    "m": Strategy("non-cacheable pointer chase", _CHASE, buffer="chain"),
    "t": Strategy("strided pointer chase", _CHASE, buffer="strided",
                  stride=8),
    "i": Strategy("memory-idle MXU busy loop",
                  Kernel("mxu_probe", fixed=(("iters", 64),)),
                  buffer="identity", lines=0),
}


def resolve_strategy(strategy: str, shape=None) -> str:
    """The strategy letter a (strategy, TrafficShape) pair actually
    executes as: mixed shapes run the ``b`` mixed-stream workload,
    strided shapes the ``t`` strided chase, everything else the plain
    strategy.  The single source of truth for this mapping — the
    measured pass and the spmd branch builder both consume it, so every
    backend executes the same kernel class for a given spec."""
    kind = getattr(shape, "kind", "steady") if shape is not None \
        else "steady"
    return {"mixed": "b", "strided": "t"}.get(kind, strategy)


def chase_stride(shape) -> int:
    """The hop of a strided chase: a strided shape's own, else the
    ``t`` strategy's default."""
    if getattr(shape, "kind", None) == "strided":
        return shape.stride
    return STRATEGIES["t"].stride


def bw_buffer_init(shape, dtype):
    """Sequential integers — lets experiments sanity-check corruption."""
    n = int(np.prod(shape))
    return jnp.arange(n, dtype=jnp.float32).reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# The measured pass
# ---------------------------------------------------------------------------

# a stacked pass holds every member's buffer at once; cap the stack so
# a big group cannot out-allocate the device
_BATCH_BYTES_CAP = 1 << 30


def measure_group(strategy: str, pool: MemoryPool, buffer_bytes: int,
                  n_members: int, iters: int, *, shape=None,
                  seeds: Optional[list] = None,
                  member_pools: Optional[list] = None,
                  stats=None, programs: Optional[dict] = None
                  ) -> Tuple[list, int]:
    """Measure ``n_members`` same-signature observers.  Strategies with
    stacked operands run one pass over the stacked member buffers
    (chases keep per-member chains, so different seeds stay distinct);
    the others measure once and share the result.

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
    the whole group; stacked groups whose footprint would exceed the
    batch byte cap or the pool's free space split into chunks, each
    chunk one dispatch.  A stacked group's wall time is split evenly
    (members are identical up to buffer content, and on hardware they
    run as concurrent engines of one fused pass)."""
    if not STRATEGIES[resolve_strategy(strategy, shape)].stacked:
        chunk = n_members
    else:
        member_bytes = rows_for(buffer_bytes) * LINE_BYTES
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
                   n_members: int, iters: int, *, shape, seeds: list,
                   pool_names: Optional[list], stats,
                   programs: Optional[dict]) -> list:
    strat = resolve_strategy(strategy, shape)
    entry = STRATEGIES[strat]
    rows = rows_for(buffer_bytes)
    g = n_members if entry.stacked else 1
    names = pool_names or [pool.node.name] * n_members
    vmem = _fits_vmem(buffer_bytes) or pool.node.kind == "vmem"
    kernel = entry.vmem if (vmem and entry.vmem is not None) else entry.hbm
    duty = shape.duty_cycle if (shape is not None
                                and shape.kind == "burst") else 1.0

    x = _operand(entry, pool, rows, seeds, shape)
    have = {"rows": rows, "block_rows": block_rows(rows),
            "n_steps": chase_steps(rows),
            "read_fraction": (shape.read_fraction if shape is not None
                              and shape.kind == "mixed" else 0.5)}
    static = {**{a: have[a] for a in kernel.takes}, **dict(kernel.fixed)}
    if entry.stacked and kernel.vmapped:
        fn = _vmapped(kernel.fn, x, programs, stats, **static)
    else:
        fn = functools.partial(kernel.fn, **static)
    calls = max(1, iters // 10) if entry.chase else iters
    t, out = _timed(fn, *(() if x is None else (x,)), iters=calls)
    sums = _checksums(out, entry.stacked)
    if kernel.summed:
        sums = sums / kernel.passes
    # /g: the g members execute back-to-back within the pass
    # (test_batched_chase_latency_matches_naive guards it for chases)
    per = t / kernel.passes / g / duty
    if entry.chase:
        nbytes, elapsed, tx = rows * LINE_BYTES, per, chase_steps(rows)
    else:
        nbytes = entry.lines * rows * LINE_BYTES * iters
        elapsed, tx = per * iters, 0
    # the idle loop touches no memory: no buffer, no memory kind
    kind = (None if not entry.lines
            else (x if x is not None else out).sharding.memory_kind)

    def result(name, checksum, seed):
        return WorkloadResult(
            strat, name, buffer_bytes if entry.lines else 0, iters, nbytes,
            elapsed, tx, checksum=float(checksum), memory_kind=kind,
            chain_seed=seed)

    if not entry.stacked:
        # one measurement serves every member, labeled with its pool
        return [result(name, sums[0], None) for name in names]
    return [result(name, c, sd if entry.buffer == "chain" else None)
            for name, c, sd in zip(names, sums, seeds)]


def _operand(entry: Strategy, pool: MemoryPool, rows: int, seeds,
             shape) -> Optional[jax.Array]:
    """The operand ``entry``'s kernel is handed, in ``pool``'s memory:
    stacked over the members for stacked strategies."""
    if entry.buffer is None:
        return None
    with spans.span("inputs"):
        if entry.buffer == "lines":
            # every member streams the same content, so one plain
            # reference checks every member's checksum
            return pool.place(jnp.broadcast_to(
                bw_buffer_init((rows, LANE), jnp.float32),
                (len(seeds), rows, LANE)))
        if entry.buffer == "chain":
            return chase_operand(pool, rows, seeds)
        if entry.buffer == "strided":
            return pool.place(ops.lay_lines(
                ops.make_strided_chain(rows, chase_stride(shape))))
        # the identity keeps every power exact whatever the MXU's input
        # precision, so the checksum (the trace, 128) checks the kernel
        return jnp.eye(LANE, dtype=jnp.float32)


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


def _checksums(out, stacked: bool) -> np.ndarray:
    """Each member's checksum: the sum of every array the kernel
    returned, over all but the leading member axis of a stacked pass
    (a read's sum, plus the destination's sum where it writes one; a
    chase's final index).  A shared measurement gives one."""
    with spans.span("readback"):
        total = np.zeros(1)
        for o in jax.tree.leaves(out):
            if not stacked:
                o = jnp.sum(o)
            elif o.ndim > 1:
                o = jnp.sum(o, axis=tuple(range(1, o.ndim)))
            total = total + np.asarray(o, np.float64)
        return total


def chase_steps(rows: int) -> int:
    """Dependent loads per chase pass: one short of the full cycle, so
    the final index (the predecessor of line 0) checks the walk."""
    return max(1, rows - 1)


def rows_for(buffer_bytes: int) -> int:
    """The buffer->line-rows mapping every backend shares: whole blocks
    of a byte budget; the spmd rung builder and the measured pass must
    agree on it exactly."""
    rows = max(1, buffer_bytes // LINE_BYTES)
    return (rows // block_rows(rows)) * block_rows(rows) or rows


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
        if STRATEGIES[strategy].vmem is None:
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
