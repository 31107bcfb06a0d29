"""program — stage 2 of the spmd execution pipeline.

Turns a :class:`~repro.core.exec.plan.PlannedDispatch` into a traced,
fence-verified, operand-placed :class:`CompiledProgram`: the per-engine
branch activities (Pallas kernel library or pure-jnp traffic loops),
the operand arrays, the fused SPMD program builders, and
:func:`build_ladder_entry` tying them together (trace once, feed the
same jaxpr to the structural fence walk and the AOT compile).

The psum sandwich invariants (module docstring of
:mod:`repro.core.coordinator`) are enforced here; width-packed
dispatches replace the global all-reduce with grouped collectives
(``compat.psum_grouped``) so each engine subset keeps its OWN sandwich.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core.exec.fence import measured_region_is_fenced
from repro.core.exec.plan import (PlannedDispatch, effective_duty,
                                  merge_probe_operand_roles)
from repro.core.workloads import LINE_BYTES, resolve_strategy


class CompileError(RuntimeError):
    """The compiler refused a program: deterministic, so the resilience
    layer re-raises it rather than retrying or degrading."""


def compile_traced(traced, what: str):
    """``traced.lower().compile()``; a refusal raises CompileError."""
    try:
        return traced.lower().compile()
    except Exception as exc:
        raise CompileError(f"{what}: {exc}") from exc


_SPMD_CHASES = ("l", "m", "t")      # latency walks: dependent gathers
_SPMD_STREAM_2X = ("c", "x")        # copy/rmw touch two lines per line


def build_rung_operands(roles, n_eng: int,
                        rows_max: int) -> Tuple[np.ndarray, np.ndarray]:
    """Per-engine operands for one SPMD program: a float stream buffer
    and an int chase chain (seeded by engine index), padded to the
    widest role.  Operands are fully determined by the role layout, so
    cached programs can reuse their placed arrays verbatim."""
    from repro.kernels import ops as kops

    xf = np.broadcast_to(
        np.arange(rows_max * LINE_BYTES // 4, dtype=np.float32)
        .reshape(rows_max, LINE_BYTES // 4),
        (n_eng, rows_max, LINE_BYTES // 4)).copy()
    xi = np.zeros((n_eng, rows_max, LINE_BYTES // 4), np.int32)
    for e, (strategy, shape, rows, _ri) in enumerate(roles):
        if resolve_strategy(strategy, shape) in _SPMD_CHASES:
            if resolve_strategy(strategy, shape) == "t":
                chain = kops.strided_chain_buffer(
                    rows, getattr(shape, "stride", 8) or 8)
            else:
                chain = kops.chain_buffer(rows, seed=e)
            xi[e, :rows, :chain.shape[1]] = chain
    return xf, xi


def spmd_branch_fn(strategy: str, shape, rows: int, iters: int,
                   activity: str = "jnp"):
    """Per-engine activity for one SPMD rung: ``(xf, xi) -> f32``.

    All branches take the SAME operand pair and return a scalar so
    ``lax.switch`` can fuse them; each closes over its own static row
    count and iteration budget.  Loop bodies either carry the buffer or
    re-issue it with the carry through ``optimization_barrier`` so XLA
    cannot hoist the memory traffic out of the loop.

    ``activity="pallas"`` builds the branch from the real kernel
    library (:mod:`repro.kernels.stream` / ``chase``: mixed-stream,
    copy, seeded write streams, strided/Sattolo chases — compiled on
    TPU, interpret-mode elsewhere); ``"jnp"`` builds pure-jnp traffic
    loops instead."""
    strat = resolve_strategy(strategy, shape)
    n = max(1, int(round(iters * effective_duty(shape))))

    if activity == "pallas" and strategy != "i":
        return _pallas_branch_fn(strat, shape, rows, n)

    if strategy == "i":
        def idle(xf, xi):
            def body(_, acc):
                return acc * 0.999 + 1.0
            # seeded from the fenced operand: even idle engines enter
            # their spin only after the start barrier
            return jax.lax.fori_loop(0, n * 8, body, xf[0, 0] * 1e-30)
        return idle

    if strat in _SPMD_CHASES:
        def chase(xf, xi):
            chain = xi[:rows, 0]

            def step(_, idx):
                return chain[idx]

            def cycle(_, carry):
                idx, acc = carry
                idx = jax.lax.fori_loop(0, rows, step, idx)
                return idx, acc + idx.astype(jnp.float32)

            _, acc = jax.lax.fori_loop(
                0, n, cycle, (jnp.int32(0), jnp.float32(0.0)))
            return acc
        return chase

    if strat in ("w", "y"):
        def write(xf, xi):
            def body(_, x):
                return x + 1.0
            x = jax.lax.fori_loop(0, n, body, xf[:rows])
            return x[0, 0]
        return write

    if strat in ("c", "x", "b"):
        def readwrite(xf, xi):
            def body(_, x):
                return x * 1.0000001 + 0.25
            x = jax.lax.fori_loop(0, n, body, xf[:rows])
            return x[0, 0]
        return readwrite

    def read(xf, xi):
        x = xf[:rows]

        def body(_, acc):
            # re-issued buffer, paired with the carry: a barrier on the
            # loop-invariant buffer alone is hoisted out of the loop
            # with the read, leaving one pass for all n
            xx, _ = jax.lax.optimization_barrier((x, acc))
            return acc * 0.5 + jnp.sum(xx)

        return jax.lax.fori_loop(0, n, body, jnp.float32(0.0))
    return read


def _pallas_branch_fn(strat: str, shape, rows: int, n: int):
    """Pallas-kernel edition of one rung activity (resolved strategy
    letter ``strat``, ``n`` active passes): the branch's memory traffic
    is the real kernel library, not a jnp stand-in.  Every branch keeps
    a dataflow edge from its (barrier-fenced) operands into each
    kernel call — carried loop state where the kernel's output feeds
    the next pass (copy/rmw/seeded write), re-issue through an
    ``optimization_barrier`` paired with the carry where it cannot
    (reads, mixed streams, chases) — so the
    extended jaxpr fence check can verify every ``pallas_call``
    consumes fenced data."""
    from repro.kernels import chase as _kchase
    from repro.kernels import ops as kops
    from repro.kernels import stream as _kstream
    from repro.core.workloads import _fits_vmem

    interp = not kops.on_tpu()
    blk = min(512, rows)

    if strat in _SPMD_CHASES:
        vmem = strat == "l" and _fits_vmem(rows * LINE_BYTES)
        kern = _kchase.chase_vmem if vmem else _kchase.chase_hbm

        def chase(xf, xi):
            buf = xi[:rows]

            def cycle(_, acc):
                # re-issued with the carry: one dependent full
                # traversal per pass, not hoistable out of the loop
                bb, _ = jax.lax.optimization_barrier((buf, acc))
                idx = kern(bb, n_steps=rows, interpret=interp)
                return acc + idx.astype(jnp.float32)

            return jax.lax.fori_loop(0, n, cycle, jnp.float32(0.0))
        return chase

    if strat == "y":
        def write_stream(xf, xi):
            def body(_, acc):
                # the seed depends on the previous pass, serialising
                # the passes; the kernel's stores depend on the seed
                seed = xf[:1, :1] + acc * 1e-30
                out = _kstream.write_hbm_seeded(
                    seed, rows, block_rows=blk, interpret=interp)
                return acc * 0.5 + out[0, 0]

            return jax.lax.fori_loop(0, n, body, jnp.float32(0.0))
        return write_stream

    if strat in ("w", "x"):
        def rmw(xf, xi):
            def body(_, x):
                # write-allocate: read + write back, carried so pass
                # t+1 depends on pass t's stores — deliberate for 'w'
                # too (a cacheable write allocates the line).  Useful-
                # bytes stays the registry's convention: 'w' counts
                # written lines (1x), 'x' both (2x, _SPMD_STREAM_2X).
                return _kstream.rmw_hbm(x, block_rows=blk,
                                        interpret=interp)

            x = jax.lax.fori_loop(0, n, body, xf[:rows])
            return x[0, 0]
        return rmw

    if strat == "c":
        def copy(xf, xi):
            def body(_, x):
                return _kstream.copy_hbm(x, block_rows=blk,
                                         interpret=interp)

            x = jax.lax.fori_loop(0, n, body, xf[:rows])
            return x[0, 0]
        return copy

    if strat == "b":
        rf = (shape.read_fraction
              if getattr(shape, "kind", None) == "mixed" else 0.5)

        def mixed(xf, xi):
            x = xf[:rows]

            def body(_, acc):
                xx, _ = jax.lax.optimization_barrier((x, acc))
                # the seed fences the write half of the mix (its store
                # kernel consumes no other operand)
                s, out = _kstream.mixed_hbm(
                    xx, read_fraction=rf, block_rows=blk,
                    interpret=interp, seed=xx[:1, :1])
                # consume one written row: keeps the store kernel live
                # under DCE without re-reading the whole destination
                return acc * 0.5 + s + jnp.sum(out[:1])

            return jax.lax.fori_loop(0, n, body, jnp.float32(0.0))
        return mixed

    def read(xf, xi):                   # r / s: pure read stream
        x = xf[:rows]

        def body(_, acc):
            xx, _ = jax.lax.optimization_barrier((x, acc))
            return acc * 0.5 + _kstream.read_hbm(xx, block_rows=blk,
                                                 interpret=interp)

        return jax.lax.fori_loop(0, n, body, jnp.float32(0.0))
    return read


def build_rung_program(n_engines: int, branch_fns, engine_branch):
    """One fused SPMD rung over an ("engine",) mesh.

    Returns ``(mesh, f)`` with ``f(xf, xi) -> (per_engine_out, barrier)``
    jit-compiled: engine ``e`` runs ``branch_fns[engine_branch[e]]`` on
    its shard of the operands.  The measured region is *provably*
    sandwiched (invariants 1-4 of the coordinator docstring):

      start — every engine all-reduces a token derived from its live
          operand data (psum #1; a constant token would fold away at
          trace time), and the operands are re-issued through
          ``optimization_barrier`` together with that token, so every
          activity's operands carry a dataflow dependency on the
          collective: XLA cannot schedule measured work before the
          barrier completes;
      stop — the activity outputs are all-reduced (psum #2) into the
          returned barrier value, so the dispatch only retires after
          every engine's activity finished, and the next rung (a new
          dispatch) cannot begin until the host unblocks.

    :func:`measured_region_is_fenced` asserts the start edge
    structurally (jaxpr dataflow), which the tests pin down.
    """
    from jax.sharding import PartitionSpec as P

    devs = jax.devices()[:n_engines]
    mesh = compat.make_mesh_from_devices(devs, ("engine",))
    table = jnp.asarray(list(engine_branch), jnp.int32)

    def per_engine(xf, xi):
        xf, xi = xf[0], xi[0]
        # barrier #1: data-derived token, all-reduced into operands
        token = jax.lax.psum(xf[0, 0] + xi[0, 0].astype(xf.dtype),
                             "engine")
        xf, xi, token = jax.lax.optimization_barrier((xf, xi, token))
        eng = jax.lax.axis_index("engine")
        out = jax.lax.switch(table[eng], branch_fns, xf, xi)
        # barrier #2: consumes every engine's finished activity.  (The
        # start token is alive through the operands' barrier edge; only
        # the stop psum — statically replicated — is returned.)
        done = jax.lax.psum(out, "engine")
        return out[None], done

    # check_vma=False: pallas_call has no replication rule, so Pallas
    # rungs cannot trace under the checker; the stop psum still
    # replicates `done` at runtime
    f = jax.shard_map(per_engine, mesh=mesh,
                      in_specs=(P("engine"), P("engine")),
                      out_specs=(P("engine"), P()), check_vma=False)
    return mesh, jax.jit(f)


def _subset_layout(n_engines: int, subsets):
    """(psum groups, clock-leader mask) of a packed mesh: each declared
    subset is its own barrier group with its first engine stamping the
    clock; leftover engines form one extra group (``axis_index_groups``
    must partition the whole axis) whose idle spin barriers only with
    itself.  Unpacked programs get ``groups=None`` (global psum) and
    engine 0 as the only leader — the same program text serves both."""
    if not subsets:
        leaders = np.zeros(n_engines, np.int32)
        leaders[0] = 1
        return None, leaders
    groups = [tuple(int(i) for i in s) for s in subsets]
    members = {i for g in groups for i in g}
    leftover = tuple(i for i in range(n_engines) if i not in members)
    if leftover:
        groups.append(leftover)
    leaders = np.zeros(n_engines, np.int32)
    for s in subsets:
        leaders[int(s[0])] = 1
    return tuple(groups), leaders


def build_ladder_program(n_engines: int, branch_fns, branch_table,
                         samples: int = 3, donate: bool = False,
                         subsets=None):
    """The WHOLE contention ladder as one fused SPMD dispatch.

    ``branch_table`` is a (K, n_engines) int table: scan step for rung
    ``k`` runs ``branch_fns[branch_table[k][e]]`` on engine ``e``'s
    shard.  Each rung is repeated ``samples`` times, and EVERY repeat
    is its own psum sandwich — the scanned edition of
    :func:`build_rung_program`'s spin-lock-sandwich invariants:

      start — every sample's token psum is derived from live operand
          data AND the loop carry (a loop-invariant psum would be
          hoisted out of the scan), and the operands are re-issued with
          an exact-zero contribution from the start timestamp, so no
          engine's measured work can begin before the barrier completed
          and the stamp's buffer was actually filled;
      stop — the activity outputs are all-reduced (psum #2) and the
          carry value-consumes the stop timestamp, so sample s+1's
          start barrier cannot open until sample s fully retired —
          invariant 4, enforced in-dispatch by dataflow instead of a
          host round-trip per rung.

    ``subsets`` width-packs the dispatch: both psums become grouped
    collectives (``compat.psum_grouped``) with one group per declared
    engine subset, so each subset runs an INDEPENDENT sandwich — the
    ladders packed side by side neither wait for each other's barriers
    nor observe each other's stamps — and each subset's first engine
    stamps its own clock pairs.  Unpacked programs (``subsets=None``)
    keep the global psum and engine-0 clock: the degenerate one-subset
    geometry.

    Per-rung elapsed time comes from ``compat.device_clock`` stamp
    pairs taken inside the dispatch (each leader's stop stamp follows
    its group's stop psum, i.e. its SLOWEST engine's finish), returned
    as ``(n_eng, K*samples, 2)`` int32 ``[s, ns]`` arrays alongside the
    per-engine activity outputs.  Returns ``(mesh, fn)`` with
    ``fn(xf, xi) -> (outs, t0s, t1s, xf, xi)``; the operands are
    passed through (and donated when ``donate=True``) so callers can
    cache and rebind them without any host->device re-transfer."""
    from jax.sharding import PartitionSpec as P

    devs = jax.devices()[:n_engines]
    mesh = compat.make_mesh_from_devices(devs, ("engine",))
    table = np.repeat(np.asarray(branch_table, np.int32),
                      int(samples), axis=0)
    table_j = jnp.asarray(table)
    groups, leader_mask = _subset_layout(n_engines, subsets)
    leaders_j = jnp.asarray(leader_mask)

    def per_engine(xf, xi):
        xf, xi = xf[0], xi[0]
        eng = jax.lax.axis_index("engine")

        def clock(dep):
            # only each subset's LEADER engine pays the stamp cost
            # (callback stamps are host round-trips); its siblings
            # still serialize on it via the carry -> token psum below
            return jax.lax.cond(leaders_j[eng] == 1,
                                compat.device_clock,
                                lambda _d: jnp.zeros((2,), jnp.int32),
                                dep)

        def step(carry, row):
            # barrier #1: data-derived, carry-dependent, reduced over
            # this engine's subset (globally when unpacked)
            token = compat.psum_grouped(
                xf[0, 0] + xi[0, 0].astype(xf.dtype) + carry * 1e-30,
                "engine", groups)
            t0 = clock(token)
            # thread the start stamp into every operand as an EXACT
            # zero: min(t, 0) == 0 at runtime (monotonic clock parts
            # are non-negative) but XLA cannot fold it away — the
            # activity cannot start until the stamp exists.  A
            # scheduling-only edge is not enough: the callback
            # fallback fills its result buffer asynchronously.
            z = jnp.minimum(t0[0] + t0[1], 0)
            xf_, xi_, _tok = jax.lax.optimization_barrier(
                (xf + z.astype(xf.dtype), xi + z, token))
            out = jax.lax.switch(row[eng], branch_fns, xf_, xi_)
            # barrier #2: consumes every subset engine's finished
            # activity
            done = compat.psum_grouped(out, "engine", groups)
            t1 = clock(done)
            # the carry value-consumes the stop stamp: the next
            # sample's start barrier waits for this one to retire
            carry = (done * 1e-30
                     + jnp.minimum(t1[0] + t1[1], 0).astype(xf.dtype))
            return carry, (out, t0, t1)

        _c, (outs, t0s, t1s) = jax.lax.scan(step, jnp.float32(0.0),
                                            table_j)
        return outs[None], t0s[None], t1s[None], xf[None], xi[None]

    f = jax.shard_map(per_engine, mesh=mesh,
                      in_specs=(P("engine"), P("engine")),
                      out_specs=(P("engine", None), P("engine", None, None),
                                 P("engine", None, None), P("engine"),
                                 P("engine")), check_vma=False)
    kw = {"donate_argnums": (0, 1)} if donate else {}
    return mesh, jax.jit(f, **kw)


def build_scenario_program(n_engines: int, n_stressors: int,
                           main_fn, stress_fn, idle_fn):
    """Returns f(main_x, stress_x) -> (main_out, barrier) running under
    ``shard_map`` over an ("engine",) mesh: engine 0 = observed, engines
    1..n_stressors = stress, rest idle.  The measured region is fenced by
    two psum barriers (invariants 1-4 above) — and the fence is
    dataflow-enforced: the start psum is derived from live operand data
    and re-issued into the operands via ``optimization_barrier``, so
    the activities cannot be hoisted above it (the historical version
    computed a psum nothing depended on, which JAX folds away at trace
    time — invariant 1 was unenforced)."""
    from jax.sharding import PartitionSpec as P

    devs = jax.devices()[:n_engines]
    mesh = compat.make_mesh_from_devices(devs, ("engine",))

    def per_engine(main_x, stress_x):
        eng = jax.lax.axis_index("engine")
        # barrier #1: every engine signals ready before measurement
        # starts, and the measured operands depend on the collective
        seed = (jnp.ravel(main_x)[0].astype(jnp.float32)
                + jnp.ravel(stress_x)[0].astype(jnp.float32))
        ready = jax.lax.psum(seed, "engine")
        main_x, stress_x, ready = jax.lax.optimization_barrier(
            (main_x, stress_x, ready))

        def run_main(m, _s):
            return main_fn(m)

        def run_stress(_m, s):
            return stress_fn(s)

        def run_idle(_m, s):
            return idle_fn(s)

        branch = jnp.where(eng == 0, 0,
                           jnp.where(eng <= n_stressors, 1, 2))
        # operands positional: the `operand=` kwarg is lint-rejected
        # deprecated drift (tests/test_compat.py)
        out = jax.lax.switch(branch, [run_main, run_stress, run_idle],
                             main_x, stress_x)
        # barrier #2: `done` consumes every engine's finished activity
        # output; only the statically-replicated stop psum is returned
        # (`ready` stays alive through the operand barrier edge)
        done = jax.lax.psum(jnp.ravel(out)[0].astype(jnp.float32),
                            "engine")
        return out, done

    f = jax.shard_map(per_engine, mesh=mesh,
                      in_specs=(P("engine"), P("engine")),
                      out_specs=(P("engine"), P()))
    return mesh, f


# ---------------------------------------------------------------------------
# Built programs
# ---------------------------------------------------------------------------


class CompiledProgram:
    """One built ladder program with its placed operands — the cache
    entry the dispatcher runs.  Kept list-indexable (``entry[3]``,
    ``entry[3:5]``, item assignment) because the LRU treats entries
    generically: eviction deletes the operand buffers by position, and
    donated dispatches rebind them in place."""

    _FIELDS = ("mesh", "call", "fenced", "xf", "xi", "aot")
    __slots__ = _FIELDS

    def __init__(self, mesh, call, fenced, xf, xi, aot):
        self.mesh = mesh
        self.call = call
        self.fenced = fenced
        self.xf = xf
        self.xi = xi
        self.aot = aot

    def __len__(self) -> int:
        return len(self._FIELDS)

    def __iter__(self):
        return (getattr(self, f) for f in self._FIELDS)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [getattr(self, f) for f in self._FIELDS[i]]
        return getattr(self, self._FIELDS[i])

    def __setitem__(self, i, value):
        setattr(self, self._FIELDS[i], value)


def build_ladder_entry(planned: PlannedDispatch, n_eng: int,
                       activity: str, samples: int,
                       stats) -> CompiledProgram:
    """Build, fence-verify, place and AOT-compile one planned
    dispatch's fused ladder program.

    The planned rung table is expanded to the full mesh: width-packed
    dispatches tile the subset-width roles across ``n_subsets``
    disjoint engine slices (leftover engines idle in their own barrier
    group) and scan-stack ``waves`` repeats; unpacked group dispatches
    reduce to the leading-scenario-axis stacking (one wave per
    ladder).  Probe batches (``planned.probe``) carry their scan rows
    verbatim — already at full packed width, one heterogeneous row per
    step, no tiling — and seed operands from the MERGED role layout so
    one operand set serves every row (``merge_probe_operand_roles``).
    The program is traced exactly ONCE (``jit(...).trace``):
    the same trace feeds the structural fence walk — packed dispatches
    pass their subsets so EVERY subset's sandwich is verified
    independently — and ``lower().compile()``."""

    idle_iters = planned.rungs[0][0][3]
    full_rungs = []
    for roles in planned.rungs:
        row = (list(roles) if planned.probe
               else list(roles) * planned.n_subsets)
        while len(row) < n_eng:
            row.append(("i", None, 1, idle_iters))
        full_rungs.append(tuple(row))

    if planned.probe:
        op_roles = merge_probe_operand_roles(full_rungs)
        rows_max = max(r[2] for row in full_rungs for r in row)
    else:
        op_roles = full_rungs[-1]
        rows_max = max(r[2] for r in op_roles)
    xf, xi = build_rung_operands(op_roles, n_eng, rows_max)
    branch_fns: List = []
    branch_of: Dict[Tuple, int] = {}
    table = np.zeros((len(full_rungs), n_eng), np.int32)
    for k, roles in enumerate(full_rungs):
        for e, sig in enumerate(roles):
            if sig not in branch_of:
                branch_of[sig] = len(branch_fns)
                branch_fns.append(spmd_branch_fn(
                    *sig, activity=activity))
            table[k, e] = branch_of[sig]
    if planned.waves > 1 and not planned.probe:
        # the leading scenario axis: wave w's rungs are scan steps
        # [w*K, (w+1)*K) — every stacked rung keeps its own psum
        # sandwich and stamp pair, and the scan carry serializes wave
        # w+1 behind wave w exactly like rung k+1 behind rung k
        # (invariant 4, across the whole group).  Probe batches list
        # every wave's row explicitly, so their table stacks as-is.
        table = np.tile(table, (planned.waves, 1))
    subsets = planned.subsets()
    mesh, fn = build_ladder_program(
        n_eng, branch_fns, table, samples=samples,
        donate=compat.donation_supported(), subsets=subsets)
    # commit the operands onto the mesh BEFORE tracing: the AOT
    # executable is specialized to the placed shardings, and the
    # fence walk sees the same program the dispatch runs
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, P("engine"), memory_kind=planned.kind)
    xf = jax.device_put(xf, sharding)
    xi = jax.device_put(xi, sharding)
    jax.block_until_ready((xf, xi))
    traced = fn.trace(xf, xi)
    # provenance records the VERIFIED fence state of every scanned
    # rung of every stacked ladder — including, for packed programs,
    # per-subset isolation of every psum sandwich — not an assertion
    fenced = measured_region_is_fenced(fn, xf, xi, jaxpr=traced.jaxpr,
                                       subsets=subsets)
    compiled = compile_traced(
        traced, f"spmd ladder program ({planned.group} ladders)")
    stats.programs_built += 1
    stats.aot_compiles += 1
    return CompiledProgram(mesh, compiled, fenced, xf, xi, True)
