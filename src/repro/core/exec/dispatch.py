"""dispatch — stage 3 of the spmd execution pipeline.

Owns everything between a built program and its numbers: the
coordinator-level program/operand LRU (:class:`ProgramCache`), AOT
compile + persistent-cache opt-in, donation rebind, the
host-synchronous dispatch itself, and the
(waves, subsets, rungs, samples) clock decode mapping each stacked
ladder's stamp pairs back to per-rung elapsed medians.
"""
from __future__ import annotations

import contextlib
import hashlib
import time as _time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from repro.core.exec.fence import measured_region_is_fenced
from repro.core.exec.plan import PlannedDispatch
from repro.core.exec.program import (CompiledProgram, build_ladder_entry,
                                     build_rung_operands,
                                     build_rung_program, compile_traced,
                                     spmd_branch_fn)


def _fault_site(key: Tuple) -> str:
    """Stable fault-injection site id for a program cache key.  The
    key's repr is deterministic (frozen dataclasses and primitives
    only), so the same dispatch gets the same site in every process —
    which is what makes a seeded fault schedule byte-reproducible."""
    return hashlib.sha256(repr(key).encode()).hexdigest()[:16]


@dataclass
class DispatchStats:
    """Execution accounting for the matrix runner: the batched runner's
    claim ("fewer dispatches than the per-point loop") and the spmd
    backend's claim ("one fused SPMD dispatch per ladder rung") are
    checked against these numbers in the tests."""
    n_scenarios: int = 0            # ScenarioSpecs in the matrix
    n_ladders: int = 0              # (spec, observer, buffer) ladders
    measure_dispatches: int = 0     # timed executable measurement passes
    model_evals: int = 0            # queueing-network solves
    spmd_rungs: int = 0             # ladder rungs executed on the mesh
    # host-blocking spmd program executions: the sweep-batched path
    # does ONE per same-signature ladder GROUP (~ one per distinct
    # program signature per sweep) — width-packed or not: a packed
    # dispatch running P ladders side by side still counts ONE — the
    # fused ladder path one per ladder, the legacy path 4 per RUNG
    # (warm + 3 timed); benchmarks/perf_harness.py holds each
    # contender to its number
    host_sync_dispatches: int = 0
    # programs reused from the coordinator: compiled spmd programs
    # (+ placed operands) from its LRU cache — across rungs, ladders,
    # AND back-to-back run_matrix calls — and the measured pass's
    # jit(vmap(...)) programs it keeps (workloads.measure_group)
    program_cache_hits: int = 0
    # sweep-level megabatching: distinct role-program signatures this
    # run stacked ladders under (0 on the non-batched paths)
    spmd_groups: int = 0
    # programs built this run (misses of the coordinator's programs):
    # spmd programs traced + compiled, and the measured pass's
    # jit(vmap(...)) programs the coordinator did not hold yet;
    # and how many spmd programs went through the AOT
    # lower().compile() pipeline — together with
    # host_sync_dispatches these make the dispatch-vs-compile
    # attribution in BENCH_spmd.json explicit
    programs_built: int = 0
    aot_compiles: int = 0
    # JAX's backend compiles during run_matrix (count_compiles): real
    # XLA compiles, and executables loaded from the persistent cache
    xla_compiles: int = 0
    cache_loads: int = 0
    # engine-subset width-packing: ladders that ran side by side on a
    # disjoint engine subset of a packed dispatch, and the widest
    # subset used (0 when nothing packed this run)
    packed_ladders: int = 0
    subset_width: int = 0
    # the resilience layer (exec.resilience): faults consumed from the
    # injector, failed attempts retried, ladders that finished BELOW
    # their planned dispatch level, ladders that fell all the way to
    # the modeled floor, quality-gate re-measurements (each one is an
    # extra honest host_sync_dispatch) + rungs still noisy after them,
    # and ladders restored from a sweep journal instead of re-executed
    faults_injected: int = 0
    retried_dispatches: int = 0
    degraded_ladders: int = 0
    modeled_floor_ladders: int = 0
    noisy_remeasures: int = 0
    noisy_rungs: int = 0
    resumed_ladders: int = 0

    def resilience_clean(self) -> bool:
        """True while no fault, retry, degradation or re-measurement
        has perturbed the dispatch accounting — the strict
        one-sync-per-group equalities only hold then."""
        return not (self.faults_injected or self.retried_dispatches
                    or self.degraded_ladders or self.noisy_remeasures)


BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@contextlib.contextmanager
def count_compiles(stats: DispatchStats) -> Iterator[None]:
    """Count JAX's backend compiles while entered into ``stats``.  JAX
    records one backend-compile event per executable it compiles or
    loads from the persistent cache, and a cache-hit event inside it
    for each load, so the loads are the hits and the XLA compiles the
    rest.  The listeners are JAX's process-wide ones: compiles of
    another thread in the same interval count too."""
    seen = {BACKEND_COMPILE_EVENT: 0, CACHE_HIT_EVENT: 0}

    def on_event(event: str, *_args, **_kw) -> None:
        if event in seen:
            seen[event] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        jax.monitoring.unregister_event_duration_listener(on_event)
        stats.cache_loads += seen[CACHE_HIT_EVENT]
        stats.xla_compiles += (seen[BACKEND_COMPILE_EVENT]
                               - seen[CACHE_HIT_EVENT])


class ProgramCache:
    """LRU over built spmd programs + their placed operands, keyed by
    program signature.  Entries are mutable (lists or
    :class:`CompiledProgram`s): donated dispatches rebind the operand
    arrays in place.  The cap is a MEMORY bound: eviction eagerly
    deletes the evicted entry's device buffers — dropping only the
    dict entry would leave the placed (and possibly donation-aliased)
    operands alive on the devices until Python GC got around to
    them."""

    def __init__(self, cap: int):
        assert cap >= 1, cap
        self.cap = cap
        self.entries: "OrderedDict[Tuple, Any]" = OrderedDict()

    def get(self, key: Tuple, stats: Optional[DispatchStats] = None):
        entry = self.entries.get(key)
        if entry is not None:
            self.entries.move_to_end(key)
            if stats is not None:
                stats.program_cache_hits += 1
        return entry

    def put(self, key: Tuple, entry) -> None:
        self.entries[key] = entry
        self.entries.move_to_end(key)
        while len(self.entries) > self.cap:
            _k, evicted = self.entries.popitem(last=False)
            for arr in evicted[3:5]:
                delete = getattr(arr, "delete", None)
                if delete is not None:
                    try:
                        delete()
                    except Exception:
                        pass        # already consumed by donation


class Dispatcher:
    """Stage 3: run planned dispatches.  Holds the program LRU and the
    per-coordinator dispatch knobs (sample count, fault seam); the
    coordinator facade delegates here."""

    def __init__(self, cache_cap: int, samples: int, faults=None):
        assert samples >= 1, samples
        self.cache = ProgramCache(cache_cap)
        self.samples = samples
        # the fault-injection seam (exec.resilience.FaultInjector or
        # None): consulted at the compile / dispatch / decode sites of
        # both dispatch paths.  Deterministic — draws are pure hashes
        # of (seed, site, phase, attempt) — and duck-typed, so this
        # module never imports the resilience layer
        self.faults = faults

    def _fault(self, site: str, phase: str, stats: DispatchStats):
        """Consult the fault-injection seam.  Raising phases
        ("compile"/"dispatch") raise the injector's fault; the
        "decode" phase returns the fault kind so the caller can
        corrupt the decoded timings instead (a corrupted-timing fault
        must produce bad VALUES — detection is the resilience layer's
        validator, not an exception)."""
        if self.faults is None:
            return None
        kind = self.faults.check(site, phase)
        if kind is not None:
            stats.faults_injected += 1
            if phase != "decode":
                raise self.faults.error(kind, site)
        return kind

    # -- the fused/batched/packed path ---------------------------------

    def run_planned(self, planned: PlannedDispatch, n_eng: int,
                    activity: str, mode: str, stats: DispatchStats,
                    ) -> Tuple[np.ndarray, np.ndarray, bool, bool]:
        """Execute one planned dispatch: build (or fetch) its program,
        run it with ONE host-synchronous call, and decode each stacked
        ladder's in-dispatch stamp pairs.  Returns
        ``(med, spread, fenced, aot)`` with ``med``/``spread`` of
        shape (group, n_scen) nanoseconds."""
        key = planned.cache_key(mode, n_eng, activity, self.samples)
        site = _fault_site(key)
        entry = self.cache.get(key, stats)
        if entry is None:
            self._fault(site, "compile", stats)
            entry = build_ladder_entry(planned, n_eng, activity,
                                       self.samples, stats)
            self.cache.put(key, entry)
        aot = entry[5]
        _mesh, call, fenced, xf, xi = entry[:5]
        self._fault(site, "dispatch", stats)
        out = jax.block_until_ready(call(xf, xi))
        stats.host_sync_dispatches += 1
        stats.measure_dispatches += 1
        stats.spmd_rungs += planned.group * planned.n_scen
        if planned.packed:
            stats.packed_ladders += planned.group
            stats.subset_width = max(stats.subset_width,
                                     planned.subset_width)
        # donated dispatch consumed the cached operands; rebind the
        # returned (aliased in place where donation is real) arrays
        entry[3], entry[4] = out[3], out[4]
        # each subset's LEADER engine is its observer: its [s, ns]
        # stamp pairs bracket each scanned sandwich, stop stamp taken
        # after the subset's stop psum (i.e. when its SLOWEST engine
        # finished — paper invariant 3).  Ladder g ran in wave g//P on
        # subset g%P; the trailing spare subsets of a ragged last wave
        # executed but are not decoded.
        t0s = np.asarray(out[1])
        t1s = np.asarray(out[2])
        k, s = planned.n_scen, self.samples
        med = np.zeros((planned.group, k))
        spread = np.zeros((planned.group, k), np.int64)
        for g in range(planned.group):
            wave, subset = planned.member_slot(g)
            lead = subset * planned.subset_width
            t0 = t0s[lead].reshape(planned.waves, k, s, 2)[wave]
            t1 = t1s[lead].reshape(planned.waves, k, s, 2)[wave]
            d = ((t1[..., 0].astype(np.int64) - t0[..., 0])
                 * 1_000_000_000 + (t1[..., 1] - t0[..., 1]))
            med[g] = np.median(d, axis=1)
            spread[g] = d.max(axis=1) - d.min(axis=1)
        if self._fault(site, "decode", stats):
            med = -np.abs(med)      # corrupted timings: non-positive
        return med, spread, fenced, aot

    # -- the legacy per-rung path ---------------------------------------

    def run_rung(self, roles, n_eng: int, activity: str,
                 kind: Optional[str], stats: DispatchStats,
                 ) -> Tuple[float, bool, int, bool]:
        """One rung, one fused program — all branches of a single
        ``shard_map`` dispatch whose measured region sits between the
        two psum barriers of ``build_rung_program`` (the returned bool
        is the structurally *verified* fence state of this rung's
        program, the final int the spread of the host wall-time
        samples).

        The wall time of the dispatch is the measured region: host
        ``perf_counter_ns`` around ``block_until_ready``, median of
        ``samples`` — which costs 1 + ``samples`` host round-trips per
        rung (4 at the default) and includes Python dispatch jitter.
        The fused ladder path replaces both; this path is kept for
        comparison (``benchmarks/perf_harness.py``) and as the
        fallback where no in-dispatch timestamp source exists."""
        roles = tuple(roles)
        rows_max = max(r[2] for r in roles)
        # the kind joins the cache key: identical role programs from
        # differently-placed pools must not share operands
        key = ("rung", n_eng, activity, kind, roles)
        site = _fault_site(key)
        entry = self.cache.get(key, stats)

        if entry is not None:
            # operands are fully determined by the cache key (chain
            # seeds are engine indices): reuse the placed arrays too —
            # no host-side rebuild, no repeated host->device transfer
            _mesh, fn, fenced, xf, xi, aot = entry
        else:
            self._fault(site, "compile", stats)
            xf, xi = build_rung_operands(roles, n_eng, rows_max)
            branch_fns: List = []
            engine_branch: List[int] = []
            branch_of: Dict[Tuple, int] = {}
            for sig in roles:
                if sig not in branch_of:
                    branch_of[sig] = len(branch_fns)
                    branch_fns.append(spmd_branch_fn(
                        *sig, activity=activity))
                engine_branch.append(branch_of[sig])
            mesh, fn = build_rung_program(n_eng, branch_fns,
                                          engine_branch)
            # commit the operands onto the mesh BEFORE the measured
            # region: a host array would be re-transferred inside
            # every timed call, and the transfer (which scales with
            # the widest role, not the observer) would dominate the
            # measurement
            from jax.sharding import NamedSharding, PartitionSpec as P
            sharding = NamedSharding(mesh, P("engine"), memory_kind=kind)
            xf = jax.device_put(xf, sharding)
            xi = jax.device_put(xi, sharding)
            jax.block_until_ready((xf, xi))
            # one trace serves the fence walk AND the AOT compile; the
            # rung programs carry no host callbacks, so with a
            # persistent cache enabled the compile is also reused
            # across processes.  provenance records the VERIFIED fence
            # state, not an assertion
            traced = fn.trace(xf, xi)
            fenced = measured_region_is_fenced(fn, xf, xi,
                                               jaxpr=traced.jaxpr)
            fn = compile_traced(traced, f"spmd rung program {key!r}")
            stats.programs_built += 1
            stats.aot_compiles += 1
            aot = True
            self.cache.put(key, CompiledProgram(mesh, fn, fenced,
                                                xf, xi, aot))
        self._fault(site, "dispatch", stats)
        jax.block_until_ready(fn(xf, xi))          # warm
        samples = []
        for _ in range(self.samples):
            t0 = _time.perf_counter_ns()
            jax.block_until_ready(fn(xf, xi))
            samples.append(_time.perf_counter_ns() - t0)
        stats.host_sync_dispatches += 1 + self.samples
        stats.measure_dispatches += 1
        stats.spmd_rungs += 1
        elapsed = float(np.median(samples))
        if self._fault(site, "decode", stats):
            elapsed = -abs(elapsed)     # corrupted timing: non-positive
        return elapsed, fenced, int(max(samples) - min(samples)), aot
