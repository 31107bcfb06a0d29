"""Core Coordinator — scenario ladders with the barrier "sandwich".

Mirrors the paper's §III-D: an *Experiment Instantiator* validates the
configuration and binds workloads; a *Multi-Engine Synchronizer* enforces
the four measurement invariants.  On a TPU slice the synchronizer is an
SPMD program over a 1-D "engine" mesh where engine 0 runs the main
activity and engines 1..k the stress activity — the measured region is
sandwiched between two all-reduce barriers, the collective analog of the
paper's spin-lock sandwich:

  (1) measurement starts only after every engine passed the start
      barrier (psum #1);
  (2) the scenario is stable: one fused SPMD program, lockstep engines;
  (3) the stop barrier (psum #2) completes only after every engine's
      activity finished — measurement closes before teardown;
  (4) the next scenario is a new program dispatch, which cannot begin
      until the previous one fully retired (host blocks on the result).

Backends: ``simulate`` (closed queueing network, repro.core.simulate),
``interpret`` (executes the observed activity's Pallas kernels in
interpret mode; contended rungs fall back to the model), ``tpu`` (same
code path on real hardware), and ``spmd`` — which *executes*
contention ladders on an ("engine",) mesh: observer + coupled sibling
observers + live stressor engines, rung activities from the real
Pallas kernel library (or pure-jnp traffic loops on request),
measured region dataflow-fenced between two psum barriers.

The spmd machinery itself lives in :mod:`repro.core.exec` as an
explicit plan -> build -> dispatch -> assemble pipeline (see that
package's docstring for the module map); this class is the thin facade
tying the stages together and the home of the queueing-network model.
The default dispatch mode (``spmd_dispatch="batched"``) applies
SWEEP-LEVEL megabatching — the planner groups ladders by role-program
signature and every group executes as ONE stacked dispatch — and, when
the mesh is wide enough (``spmd_pack="auto"``), the planner's
engine-subset width-packing transform additionally runs several
same-signature shallow ladders SIDE BY SIDE on disjoint engine subsets
of that one dispatch, each subset with its own grouped-psum sandwich.
Programs are AOT-compiled once per signature; the persistent compile
cache is process-wide and placed by the entry points
(``compat.use_compile_cache``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax

from repro.core import simulate as sim
from repro.core import spans
from repro.core.devicetree import Platform, detect_platform
from repro.core.exec import journal as exec_journal
from repro.core.exec import plan as exec_plan
from repro.core.exec import resilience as exec_resilience
from repro.core.exec.assemble import (MatrixResult, ScenarioResult,
                                      ScenarioRun, assemble_runs)
from repro.core.exec.dispatch import (Dispatcher, DispatchStats,
                                      count_compiles)
from repro.core.exec.fence import measured_region_is_fenced
from repro.core.exec.program import (build_ladder_program,
                                     build_rung_program,
                                     build_scenario_program)
from repro.core.pools import MemoryPool, PoolManager
from repro.core.scenarios import (ObserverSpec, ScenarioSpec, StressorSpec,
                                  TrafficShape)
from repro.core.workloads import (STRATEGIES, WorkloadResult,
                                  measure_group, refusal)

__all__ = [
    "ActivitySpec", "CoreCoordinator", "DispatchStats",
    "ExperimentConfig", "ExperimentResult", "MatrixResult",
    "ScenarioResult", "ScenarioRun", "ValidationError",
    "build_ladder_program", "build_rung_program",
    "build_scenario_program", "measured_region_is_fenced",
]

# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ActivitySpec:
    strategy: str              # Table-I letter
    pool: str                  # pool name ("hbm", "host", ...)
    buffer_bytes: int
    # optional traffic-shape parameters (ScenarioSpec DSL; the defaults
    # reproduce the seed's steady streams exactly)
    read_fraction: Optional[float] = None   # mixed r/w ratio
    duty_cycle: float = 1.0                 # bursty/duty-cycled
    stride: int = 1                         # strided pointer-chase

    def describe(self) -> str:
        return f"({self.strategy},{self.pool},{self.buffer_bytes >> 10}K)"

    def shape(self) -> Optional[TrafficShape]:
        """The TrafficShape these fields encode (None = steady)."""
        if self.read_fraction is not None:
            # surface grid points carry BOTH a mix and a duty cycle —
            # dropping the duty here would silently rebuild a hotter
            # shape than the one that ran
            return TrafficShape(kind="mixed",
                                read_fraction=self.read_fraction,
                                duty_cycle=self.duty_cycle)
        if self.duty_cycle < 1.0:
            return TrafficShape(kind="burst", duty_cycle=self.duty_cycle)
        if self.stride > 1:
            return TrafficShape(kind="strided", stride=self.stride)
        return None

    @staticmethod
    def from_stressor(s: StressorSpec) -> "ActivitySpec":
        return ActivitySpec(
            s.strategy, s.pool, s.buffer_bytes,
            read_fraction=(s.shape.read_fraction
                           if s.shape.kind == "mixed" else None),
            duty_cycle=s.shape.duty_cycle,
            stride=s.shape.stride)


@dataclass(frozen=True)
class ExperimentConfig:
    main: ActivitySpec
    stress: ActivitySpec
    iters: int = 500
    scenarios: Optional[int] = None      # default: platform.n_engines


@dataclass
class ExperimentResult:
    config: ExperimentConfig
    scenarios: List[ScenarioResult] = field(default_factory=list)

    bandwidth_curve = ScenarioRun.bandwidth_curve
    latency_curve = ScenarioRun.latency_curve


class ValidationError(ValueError):
    pass


# ---------------------------------------------------------------------------


class CoreCoordinator:
    # compiled spmd programs kept per coordinator (LRU): fused ladder
    # programs are expensive to trace, and back-to-back run_matrix
    # calls must not re-trace/re-transfer what they just built.  Each
    # entry also holds its placed operands, so the cap is a MEMORY
    # bound; the legacy per-rung path needs K entries per ladder where
    # the fused paths need one (raise ``spmd_cache_cap`` to trade
    # memory for re-compiles).
    _SPMD_CACHE_CAP = 32

    def __init__(self, pool_mgr: Optional[PoolManager] = None,
                 platform: Optional[Platform] = None,
                 backend: str = "auto",
                 spmd_activity: str = "pallas",
                 spmd_dispatch: str = "batched",
                 spmd_samples: int = 3,
                 spmd_cache_cap: Optional[int] = None,
                 spmd_pack: str = "auto",
                 faults=None,
                 retry: Optional[exec_resilience.RetryPolicy] = None,
                 quality="auto"):
        self.platform = platform or detect_platform()
        self.pools = pool_mgr or PoolManager(self.platform)
        if backend == "auto":
            backend = "tpu" if jax.default_backend() == "tpu" else "simulate"
        assert backend in ("simulate", "interpret", "tpu", "spmd"), backend
        # what fills the spmd backend's rung measured regions: real
        # Pallas kernels ("pallas") or pure-jnp traffic loops ("jnp"),
        # stamped into ``execution["activity"]``
        assert spmd_activity in ("pallas", "jnp"), spmd_activity
        # sweep dispatch granularity: "batched" (default) stacks
        # same-signature ladders into ONE dispatch per group, "ladder"
        # fuses one ladder per dispatch, "rung" is the legacy
        # one-dispatch-per-rung path.  The timing source lands in
        # ``execution["timing_source"]``.
        assert spmd_dispatch in ("batched", "ladder", "rung"), spmd_dispatch
        assert spmd_samples >= 1, spmd_samples
        # engine-subset width-packing (the planner transform): "auto"
        # packs same-signature shallow ladders side by side whenever
        # the mesh is at least twice a ladder's width ("off" disables;
        # bools accepted).  Packing changes no dispatch-count
        # accounting — a packed dispatch still counts ONE host sync
        # for its whole group — it trades scan waves for mesh width.
        if isinstance(spmd_pack, bool):
            spmd_pack = "auto" if spmd_pack else "off"
        assert spmd_pack in ("auto", "off"), spmd_pack
        self.backend = backend
        self.spmd_activity = spmd_activity
        self.spmd_dispatch = spmd_dispatch
        self.spmd_samples = spmd_samples
        self.spmd_pack = spmd_pack
        self.spmd_cache_cap = (spmd_cache_cap if spmd_cache_cap
                               is not None else self._SPMD_CACHE_CAP)
        assert self.spmd_cache_cap >= 1, self.spmd_cache_cap
        # resilience wiring (exec.resilience): deterministic fault
        # injection (None reads REPRO_FAULT_SPEC), retry/degradation
        # policy, and the per-rung measurement quality gate
        self.fault_spec = exec_resilience.resolve_faults(faults)
        self.retry_policy = retry or exec_resilience.RetryPolicy()
        self.quality_gate = exec_resilience.resolve_gate(quality)
        # the batched measured pass's jit(vmap(...)) programs, kept for
        # the coordinator's lifetime (workloads.measure_group)
        self._measured_programs: Dict[Tuple, Any] = {}
        # stage 3 of the exec pipeline: program/operand LRU, AOT
        # compile, dispatch + decode
        self._dispatcher = Dispatcher(self.spmd_cache_cap, spmd_samples,
                                      faults=(self.fault_spec.injector()
                                              if self.fault_spec
                                              else None))

    # -- spmd program cache (LRU, coordinator lifetime; the storage
    # -- lives on the Dispatcher, these delegates are the stable API) --
    @property
    def _spmd_programs(self):
        return self._dispatcher.cache.entries

    def _program_cache_get(self, key: Tuple,
                           stats: Optional[DispatchStats] = None):
        return self._dispatcher.cache.get(key, stats)

    def _program_cache_put(self, key: Tuple, entry) -> None:
        self._dispatcher.cache.put(key, entry)

    # -- Experiment Instantiator ----------------------------------------
    def validate(self, cfg: ExperimentConfig) -> None:
        for which, spec in (("main", cfg.main), ("stress", cfg.stress)):
            if spec.strategy not in STRATEGIES:
                raise ValidationError(
                    f"{which}: unknown strategy {spec.strategy!r}")
            pool = self.pools.pool(spec.pool)   # raises PoolError if absent
            if spec.strategy != "i" and spec.buffer_bytes > pool.available:
                raise ValidationError(
                    f"{which}: buffer {spec.buffer_bytes}B exceeds free "
                    f"space in pool {spec.pool} ({pool.available}B)")
            if which == "main":
                self._check_runnable(which, spec.strategy, pool,
                                     spec.buffer_bytes)
        if cfg.iters <= 0:
            raise ValidationError("iters must be positive")
        n = cfg.scenarios if cfg.scenarios is not None \
            else self.platform.n_engines
        if not 1 <= n <= self.platform.n_engines:
            raise ValidationError(
                f"scenarios must be in [1, {self.platform.n_engines}]")

    def refusal(self, strategy: str, pool: str,
                buffer_bytes: int) -> Optional[str]:
        """Why this backend cannot run ``strategy`` on ``pool`` at
        ``buffer_bytes`` (None when it can; the model runs anything)."""
        if self.backend == "simulate":
            return None
        p = self.pools.pool(pool)
        if (self.backend == "spmd" and p.node.kind == "vmem"
                and strategy != "i"):
            return ("spmd rung kernels stream their operands from the "
                    "pool's memory, and VMEM holds no operands")
        return refusal(strategy, p, buffer_bytes)

    def _check_runnable(self, where: str, strategy: str, pool: MemoryPool,
                        buffer_bytes: int) -> None:
        """Refuse a (pool, strategy) pair whose kernel cannot target the
        pool on this backend, instead of measuring another memory under
        the pool's name."""
        why = self.refusal(strategy, pool.node.name, buffer_bytes)
        if why is not None:
            raise ValidationError(
                f"{where}: strategy {strategy!r} cannot run on pool "
                f"{pool.node.name!r} ({self.backend} backend): {why}")

    # -- scenario ladder ----------------------------------------------------
    def run(self, cfg: ExperimentConfig) -> ExperimentResult:
        """The ladder of one observer against one stressor: a one-spec
        :meth:`run_matrix`."""
        self.validate(cfg)
        main, stress = cfg.main, cfg.stress
        spec = ScenarioSpec(
            f"{main.describe()}-vs-{stress.describe()}",
            ObserverSpec(main.strategy, main.pool, (main.buffer_bytes,),
                         main.shape() or TrafficShape()),
            (StressorSpec(stress.strategy, stress.pool, stress.buffer_bytes,
                          stress.shape() or TrafficShape()),),
            iters=cfg.iters,
            max_stressors=(None if cfg.scenarios is None
                           else cfg.scenarios - 1))
        run, = self.run_matrix([spec]).runs
        return ExperimentResult(cfg, scenarios=run.scenarios)

    # -- cache semantics ------------------------------------------------------
    _CACHEABLE = ("r", "w", "l", "c", "b")

    def _model_node(self, spec: ActivitySpec, pool: MemoryPool,
                    other: Optional[ActivitySpec] = None,
                    other_engines: int = 0):
        """Where does this activity's traffic actually land?

        Cacheable strategies on small buffers hit the platform's cache
        (transparent shared L2 on the ZCU102; software-managed private
        VMEM residency on v5e) — UNLESS, for a *shared* cache, the
        combined cacheable footprint exceeds it (inter-engine evictions,
        the red case of Fig. 12)."""
        node = pool.node
        if node.kind in ("vmem", "cache"):
            return node
        if spec.strategy not in self._CACHEABLE:
            return node

        cache_name = getattr(self.platform, "cache_node", None)
        if cache_name:                     # transparent shared cache
            cache = self.platform.memories[cache_name]
            if spec.buffer_bytes > cache.size_bytes:
                return node
            footprint = spec.buffer_bytes
            if other is not None and other.strategy in self._CACHEABLE:
                other_pool = self.pools.pool(other.pool)
                if other_pool.node.kind not in ("vmem", "cache"):
                    footprint += other_engines * other.buffer_bytes
            return cache if footprint <= cache.size_bytes else node

        # v5e: private VMEM residency, no cross-engine eviction
        from repro.core.workloads import models_as_vmem
        vmem = self.platform.memories.get("vmem")
        if vmem is not None and models_as_vmem(spec.buffer_bytes):
            return vmem
        return node

    # ==================================================================
    # ScenarioSpec matrix execution (the v2 characterization engine)
    # ==================================================================

    def validate_spec(self, spec: ScenarioSpec) -> None:
        # exact-duplicate observers would alias one curve key per
        # buffer and silently overwrite each other's ladders in
        # CurveDB — reject up front (observers differing in ANY field
        # are legitimate twins and key distinctly via the buf= suffix)
        seen = set()
        for obs in spec.observers:
            if obs in seen:
                raise ValidationError(
                    f"{spec.name}: duplicate observer "
                    f"({obs.pool}:{obs.strategy}"
                    f"{'@' + obs.shape.tag() if obs.shape.tag() else ''}, "
                    f"buffers={obs.buffers}) — its curves would alias "
                    f"the first occurrence's keys")
            seen.add(obs)
        for obs in spec.observers:
            if obs.strategy not in STRATEGIES:
                raise ValidationError(
                    f"{spec.name}: unknown observer strategy "
                    f"{obs.strategy!r}")
            pool = self.pools.pool(obs.pool)
            for b in obs.buffers:
                if obs.strategy != "i" and b > pool.available:
                    raise ValidationError(
                        f"{spec.name}: observer buffer {b}B exceeds pool "
                        f"{obs.pool} ({pool.available}B free)")
                self._check_runnable(spec.name, obs.strategy, pool, b)
        for s in spec.stressors:
            if s.strategy not in STRATEGIES:
                raise ValidationError(
                    f"{spec.name}: unknown stressor strategy "
                    f"{s.strategy!r}")
            pool = self.pools.pool(s.pool)
            if self.backend == "spmd":      # only spmd executes stressors
                self._check_runnable(spec.name, s.strategy, pool,
                                     s.buffer_bytes)
        if spec.iters <= 0:
            raise ValidationError(f"{spec.name}: iters must be positive")
        if spec.max_stressors is not None and not (
                0 <= spec.max_stressors < self.platform.n_engines):
            raise ValidationError(
                f"{spec.name}: max_stressors out of "
                f"[0, {self.platform.n_engines})")

    def _obs_activity(self, observer: ObserverSpec,
                      buffer_bytes: int) -> ActivitySpec:
        sh = observer.shape
        return ActivitySpec(
            observer.strategy, observer.pool, buffer_bytes,
            read_fraction=(sh.read_fraction if sh.kind == "mixed"
                           else None),
            duty_cycle=sh.duty_cycle, stride=sh.stride)

    def _model_spec_scenario(self, spec: ScenarioSpec,
                             observer: ObserverSpec, buffer_bytes: int,
                             k: int) -> Tuple[float, float, float]:
        """Model one rung: one observer + k stress engines distributed
        round-robin over the stressor ensemble — plus, for a *coupled*
        multi-observer scenario, one always-on single-engine class per
        sibling observer (:func:`sim.co_observer_class`), exactly like
        the spmd backend's executed rungs.  ``spec.coupled=False``
        keeps the historical stressor-only semantics."""
        obs_act = self._obs_activity(observer, buffer_bytes)
        obs_pool = self.pools.pool(observer.pool)
        first = spec.stressors[0] if spec.stressors else None
        obs_node = self._model_node(
            obs_act, obs_pool,
            other=ActivitySpec.from_stressor(first) if first else None,
            other_engines=k)
        classes = [sim.ActivityClass(
            "obs", obs_node, obs_act.strategy, 1,
            read_fraction=obs_act.read_fraction,
            duty_cycle=obs_act.duty_cycle, stride=obs_act.stride)]
        for j, sib in enumerate(self._coupled_siblings(spec, observer)):
            if sib.strategy == "i":
                continue
            act = self._obs_activity(sib, sib.buffers[0])
            node = self._model_node(act, self.pools.pool(sib.pool),
                                    other=obs_act, other_engines=1)
            classes.append(sim.co_observer_class(
                f"co{j}", node, act.strategy,
                read_fraction=act.read_fraction,
                duty_cycle=act.duty_cycle, stride=act.stride))
        m = len(spec.stressors)
        if k and m:
            share = [k // m + (1 if j < k % m else 0) for j in range(m)]
            for j, (s, e) in enumerate(zip(spec.stressors, share)):
                if e == 0 or s.strategy == "i":
                    continue
                act = ActivitySpec.from_stressor(s)
                node = self._model_node(act, self.pools.pool(s.pool),
                                        other=obs_act, other_engines=1)
                classes.append(sim.ActivityClass(
                    f"stress{j}", node, s.strategy, e,
                    read_fraction=act.read_fraction,
                    duty_cycle=act.duty_cycle, stride=act.stride))
        res = sim.simulate_scenario(self.platform, classes)
        obs = res.get("obs")
        stress_bw = sum((r.bw_gbps for n, r in res.items()
                         if n.startswith("stress")), 0.0)
        return (obs.bw_gbps if obs else 0.0,
                obs.lat_ns if obs else 0.0,
                stress_bw)

    @staticmethod
    def _coupled_siblings(spec: ScenarioSpec,
                          observer: ObserverSpec) -> Tuple[ObserverSpec, ...]:
        """The sibling observers sharing this observer's measured
        region (the logic lives on :meth:`ScenarioSpec.coupled_siblings`
        so the sweep-level grouping signature can reuse it)."""
        return spec.coupled_siblings(observer)

    def _ladder_depth(self, spec: ScenarioSpec) -> int:
        mesh = self._spmd_engines() if self.backend == "spmd" else None
        return exec_plan.ladder_depth(spec, self.platform.n_engines,
                                      mesh)

    def run_matrix(self, specs: List[ScenarioSpec], *,
                   batched: bool = True, journal=None) -> MatrixResult:
        """Execute a scenario matrix.

        The measured observer pass is where executable backends spend
        their dispatches; ``batched=True`` groups same-signature
        observers (strategy, shape, row count, residency, effective
        memory placement) and measures each group with ONE pass,
        where ``batched=False`` gives every ladder its own.
        Multi-observer scenarios contribute one ladder per (observer,
        buffer) and their observers join the same signature groups.

        Backends: ``simulate``/``interpret``/``tpu`` model the
        contention ladder per rung (interpret/tpu additionally measure
        the uncontended observer); ``spmd`` *executes* every rung and
        its curves carry ``source == "executed"``.  On the spmd
        backend ``batched=True`` (with ``spmd_dispatch="batched"``)
        applies SWEEP-LEVEL megabatching: the planner stacks
        same-signature ladders into ONE dispatch per group — and
        width-packs shallow groups onto disjoint engine subsets
        (``spmd_pack``) — so a sweep costs ~one host-synchronous
        dispatch per distinct signature; ``batched=False`` degrades to
        one fused dispatch per ladder.  Every curve's ``execution``
        provenance records the backend, executed-vs-modeled rungs,
        effective ``coupled`` state, the rung ``activity``, and — for
        spmd — ``batched``/``group_size``/``aot`` plus the
        width-packing slot ``packed``/``subset_width``/
        ``subset_index``.

        Execution is resilient (see :mod:`repro.core.exec.resilience`):
        a failed dispatch retries with backoff, degrades down the
        packed->batched->ladder->rung->modeled ladder isolated to its
        signature group, and noisy rungs re-measure under the quality
        gate; pass ``journal=<path>`` (spmd fused paths) to make the
        sweep crash-resumable via a :class:`SweepJournal` sidecar."""
        if journal is not None and self.backend != "spmd":
            raise ValidationError(
                "journal= requires the spmd backend (other backends "
                "model and have nothing to resume)")
        for spec in specs:
            self.validate_spec(spec)
        triples = [(spec, obs, b) for spec in specs
                   for obs in spec.observers for b in obs.buffers]
        stats = DispatchStats(n_scenarios=len(specs),
                              n_ladders=len(triples))

        measured: Dict[int, WorkloadResult] = {}
        executed: Dict[Tuple[int, int], WorkloadResult] = {}
        fenced_by_triple: Dict[int, bool] = {}
        timing_by_triple: Dict[int, Dict[str, Any]] = {}
        with count_compiles(stats):
            if self.backend in ("interpret", "tpu"):
                # the measured pass runs the real Pallas kernel library
                activity = "pallas"
                measured = self._measure_triples(triples, batched, stats)
            elif self.backend == "spmd":
                activity = self.spmd_activity
                executed, fenced_by_triple, timing_by_triple = \
                    self._execute_spmd(triples, stats, activity,
                                       batched=batched, journal=journal)
            else:
                activity = "none"       # nothing executes on this backend
            with spans.span("assemble", ladders=len(triples)):
                runs = assemble_runs(
                    triples, backend=self.backend, activity=activity,
                    stats=stats, depth_fn=self._ladder_depth,
                    model_fn=self._model_spec_scenario, measured=measured,
                    executed=executed, fenced_by_triple=fenced_by_triple,
                    timing_by_triple=timing_by_triple,
                    n_engines=(self._spmd_engines()
                               if self.backend == "spmd" else None),
                    operand_kinds_fn=(self._operand_memory_kinds
                                      if self.backend == "spmd" else None))
        return MatrixResult(runs=runs, stats=stats)

    def _operand_memory_kinds(self, spec: ScenarioSpec,
                              obs: ObserverSpec) -> List[str]:
        return sorted(
            {self.pools.pool(p).effective_memory_kind() or "default"
             for p in ([obs.pool]
                       + [o.pool for o in
                          self._coupled_siblings(spec, obs)]
                       + [s.pool for s in spec.stressors])})

    def _measure_triples(self, triples, batched: bool,
                         stats: DispatchStats) -> Dict[int, WorkloadResult]:
        """The measured observer pass over all (spec, observer, buffer)
        triples (uncontended: single real device).  Batched grouping
        comes from the SAME planner as the spmd backend
        (:func:`repro.core.exec.plan.observer_groups`); unbatched, each
        triple is a group of one."""
        measured: Dict[int, WorkloadResult] = {}
        if batched:
            with spans.span("plan", ladders=len(triples)):
                groups = list(exec_plan.observer_groups(
                    triples, self.pools).values())
        else:
            groups = [[i] for i in range(len(triples))]
        for group, idxs in enumerate(groups):
            spec, obs, buf = triples[idxs[0]]
            member_pools = [self.pools.pool(triples[i][1].pool)
                            for i in idxs]
            with spans.measurement(strategy=obs.strategy, bytes=buf,
                                   members=len(idxs), group=group):
                results, dispatches = measure_group(
                    obs.strategy, member_pools[0], buf, len(idxs),
                    spec.iters, shape=obs.shape, member_pools=member_pools,
                    stats=stats, programs=self._measured_programs)
            stats.measure_dispatches += dispatches
            for i, res in zip(idxs, results):
                measured[i] = res
        return measured

    # -- the spmd backend: executable multi-engine contention -----------

    def _spmd_engines(self) -> int:
        return max(1, min(self.platform.n_engines, len(jax.devices())))

    def _spmd_group_key(self, spec: ScenarioSpec, obs: ObserverSpec,
                        buf: int) -> Tuple:
        """Sweep-level grouping key (see
        :func:`repro.core.exec.plan.group_key`)."""
        return exec_plan.group_key(spec, obs, buf, self.pools)

    # rung role expansion (see exec.plan.rung_roles)
    _rung_roles = staticmethod(exec_plan.rung_roles)

    def _execute_spmd(
        self, triples, stats: DispatchStats, activity: str = "jnp",
        batched: bool = True, journal=None,
    ) -> Tuple[Dict[Tuple[int, int], WorkloadResult], Dict[int, bool],
               Dict[int, Dict[str, Any]]]:
        """Execute every (spec, observer, buffer) triple's contention
        ladder on the engine mesh through the exec pipeline: the
        planner builds a DispatchPlan (one dispatch per same-signature
        group when ``spmd_dispatch="batched"``, per triple under
        ``"ladder"``), width-packing re-plans shallow groups onto
        disjoint engine subsets, and the resilient executor
        (:mod:`repro.core.exec.journal`) builds, fence-verifies, runs,
        retries/degrades and optionally journals each planned dispatch
        (``"rung"`` is the legacy host-clocked one-dispatch-per-rung
        path).  Returns per-(triple, rung) observer results,
        per-triple verified fence state, and per-triple timing
        provenance."""
        n_eng = self._spmd_engines()
        if n_eng < 2:
            raise ValidationError(
                "spmd backend needs >= 2 devices; start the process with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                "(CPU container) or run on a real multi-device slice")
        dispatch = self.spmd_dispatch
        if dispatch == "batched" and not batched:
            dispatch = "ladder"       # megabatching explicitly disabled
        if dispatch in ("batched", "ladder"):
            plan = exec_plan.build_plan(
                triples, n_eng, self.pools, self.platform.n_engines,
                grouped=(dispatch == "batched"))
            if dispatch == "batched":
                stats.spmd_groups += len(plan.dispatches)
                if self.spmd_pack == "auto":
                    plan = exec_plan.pack_engine_subsets(plan)
            return exec_journal.execute_plan(
                self._dispatcher, plan, n_eng=n_eng, activity=activity,
                mode=dispatch, stats=stats, policy=self.retry_policy,
                gate=self.quality_gate, journal=journal)
        if journal is not None:
            raise ValidationError(
                "journal= needs a fused dispatch path "
                "(spmd_dispatch='batched' or 'ladder'), not 'rung'")
        return exec_journal.execute_rung_path(
            self._dispatcher, triples, n_eng=n_eng, activity=activity,
            stats=stats, depth_fn=self._ladder_depth, pools=self.pools,
            policy=self.retry_policy, gate=self.quality_gate)
