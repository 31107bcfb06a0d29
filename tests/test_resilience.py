"""Resilient sweep execution (PR 9): fault spec parsing, the
deterministic injector, the retry-with-degradation ladder
(packed -> batched -> fused ladder -> per-rung -> modeled), the
measurement quality gate, GroupExecutionError context, atomic
CurveDB.save, and crash-resumable sweep journals.

The ladder tests drive :func:`repro.core.exec.resilience.run_group`
with REAL DispatchPlans (the planner is pure data) and a scripted
FakeDispatcher, so every degradation step is exercised fast and
deterministically without a device mesh.  End-to-end chaos behaviour
on a real mesh runs in forced-device subprocesses at the bottom.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.exec import journal as exec_journal
from repro.core.exec import plan as exec_plan
from repro.core.exec import resilience as res
from repro.core.exec.dispatch import DispatchStats
from repro.core.pools import PoolManager
from repro.core.scenarios import ObserverSpec, ScenarioSpec, StressorSpec

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
N_DEV = max(2, int(os.environ.get("REPRO_SPMD_DEVICES", "8")))

BUF = 1 << 16
NOOP = lambda _s: None          # noqa: E731 — retry backoff stub


def _spec(name, buf=BUF, ostrat="r", K=1):
    return ScenarioSpec(name, ObserverSpec(ostrat, "hbm", (buf,)),
                        (StressorSpec("w", "hbm", buf),), iters=3,
                        max_stressors=K)


def _plan(names=("a", "b", "c", "d"), n_eng=8, packed=False, buf=BUF):
    pm = PoolManager()
    triples = [(s, s.observer, buf) for s in (_spec(n, buf) for n in names)]
    plan = exec_plan.build_plan(triples, n_eng, pm,
                                pm.platform.n_engines)
    if packed:
        plan = exec_plan.pack_engine_subsets(plan)
    return plan


class FakeDispatcher:
    """Scripted Dispatcher stand-in.  ``behaviors`` is a queue consumed
    one element per run_planned/run_rung call:

    - ``"ok"``            good timings
    - ``"corrupt"``       non-positive timings (validation fault)
    - ``("noisy", s)``    good timings with sample spread ``s``
    - a fault-kind string (``"timeout"`` ...)  raises InjectedFault
    - an exception instance                    raised verbatim

    When the queue drains, ``default`` repeats forever.
    """

    def __init__(self, behaviors=(), default="ok", samples=3):
        self.behaviors = list(behaviors)
        self.default = default
        self.samples = samples
        self.planned_calls = []
        self.rung_calls = []

    def _next(self):
        b = self.behaviors.pop(0) if self.behaviors else self.default
        if isinstance(b, BaseException):
            raise b
        if isinstance(b, str) and b in res.FAULT_KINDS:
            raise res.InjectedFault(b, "fake-site")
        return b

    def run_planned(self, planned, n_eng, activity, mode, stats):
        self.planned_calls.append(planned)
        b = self._next()
        g, k = planned.group, planned.n_scen
        stats.host_sync_dispatches += 1
        stats.measure_dispatches += 1
        stats.spmd_rungs += g * k
        if planned.packed:
            stats.packed_ladders += g
        if b == "corrupt":
            return (np.full((g, k), -1.0), np.zeros((g, k)), True, True)
        spread = b[1] if isinstance(b, tuple) else 10.0
        return (np.full((g, k), 1000.0), np.full((g, k), float(spread)),
                True, True)

    def run_rung(self, roles, n_eng, activity, kind, stats):
        self.rung_calls.append(roles)
        b = self._next()
        stats.host_sync_dispatches += 1 + self.samples
        if b == "corrupt":
            return (-5.0, True, 3, True)
        return (2000.0, True, 3, True)


def _run(disp, plan, policy=None, gate=None, stats=None):
    stats = stats or DispatchStats()
    outs = []
    for planned in plan.dispatches:
        outs.extend(res.run_group(
            disp, planned, n_eng=plan.n_engines, activity="jnp",
            mode="batched", stats=stats,
            policy=policy or res.RetryPolicy(backoff_s=0, sleep=NOOP),
            gate=gate))
    return outs, stats


# ---------------------------------------------------------------------------
# FaultSpec: parsing, env resolution, validation
# ---------------------------------------------------------------------------


def test_fault_spec_parse_spellings():
    s = res.FaultSpec.parse("mixed=0.4,seed=7")
    assert s.seed == 7
    assert all(s.rate(k) == pytest.approx(0.1) for k in res.FAULT_KINDS)
    s = res.FaultSpec.parse("compile=0.5,corrupt=0.25")
    assert (s.compile_error, s.corrupt_timing) == (0.5, 0.25)
    assert s.runtime_error == s.timeout == 0.0
    # explicit rates win over the mixed remainder
    s = res.FaultSpec.parse("mixed=0.8,timeout=0.0")
    assert s.timeout == 0.0 and s.compile_error == pytest.approx(0.2)


def test_fault_spec_rejects_garbage():
    with pytest.raises(ValueError):
        res.FaultSpec.parse("bogus=1")
    with pytest.raises(ValueError):
        res.FaultSpec.parse("compile")            # no '='
    with pytest.raises(ValueError):
        res.FaultSpec(compile_error=1.5)          # rate outside [0, 1]
    with pytest.raises(ValueError):
        res.FaultSpec(timeout=-0.1)


def test_fault_spec_from_env_and_resolution():
    E = res.ENV_FAULT_SPEC
    assert res.FaultSpec.from_env({}) is None
    for off in ("", "0", "off", "none", "OFF"):
        assert res.FaultSpec.from_env({E: off}) is None
    s = res.FaultSpec.from_env({E: "mixed=0.25,seed=3"})
    assert s.seed == 3 and s.rate("timeout") == pytest.approx(0.0625)

    # coordinator-side resolution
    assert res.resolve_faults(False) is None
    assert res.resolve_faults("off") is None
    assert res.resolve_faults(None, environ={}) is None
    assert res.resolve_faults(None, environ={E: "timeout=1"}).timeout == 1
    assert res.resolve_faults("runtime=0.5").runtime_error == 0.5
    assert res.resolve_faults(s) is s
    with pytest.raises(TypeError):
        res.resolve_faults(123)


def test_quality_gate_resolution():
    assert isinstance(res.resolve_gate(None), res.QualityGate)
    assert isinstance(res.resolve_gate("auto"), res.QualityGate)
    assert res.resolve_gate(False) is None
    assert res.resolve_gate("off") is None
    g = res.QualityGate(rel_spread=2.0)
    assert res.resolve_gate(g) is g
    with pytest.raises(TypeError):
        res.resolve_gate(1.0)


def test_injector_determinism_and_rates():
    spec = res.FaultSpec.parse("mixed=0.5,seed=11")
    a, b = spec.injector(), spec.injector()
    visits = [(f"site{i % 7}", ph) for i in range(300)
              for ph in ("compile", "dispatch", "decode")]
    seq_a = [a.check(s, p) for s, p in visits]
    seq_b = [b.check(s, p) for s, p in visits]
    assert seq_a == seq_b                     # same seed, same schedule
    fired = [k for k in seq_a if k]
    assert all(k in res.FAULT_KINDS for k in fired)
    # mixed=0.5 splits 0.125/kind; a phase draws only its own kinds
    # (compile: 0.125, dispatch: 0.25, decode: 0.125) -> ~1/6 a visit
    frac = len(fired) / len(seq_a)
    assert 0.08 < frac < 0.28

    # a different seed reshuffles the schedule
    c = res.FaultSpec.parse("mixed=0.5,seed=12").injector()
    assert [c.check(s, p) for s, p in visits] != seq_a

    # rate edges: 0 never fires, 1 always fires the phase's kind
    z = res.FaultSpec(seed=5).injector()
    assert all(z.check("s", "dispatch") is None for _ in range(50))
    one = res.FaultSpec(compile_error=1.0, seed=5).injector()
    assert all(one.check("s", "compile") == "compile_error"
               for _ in range(50))

    # a retry (same site, next attempt) sees a FRESH draw
    spec = res.FaultSpec(timeout=0.5, seed=0)
    inj = spec.injector()
    seq = [inj.check("retry-site", "dispatch") for _ in range(40)]
    assert "timeout" in seq and None in seq


def test_injector_classification_helpers():
    assert res.classify_fault(res.InjectedFault("timeout", "s")) == \
        "timeout"
    assert res.classify_fault(TimeoutError("t")) == "timeout"
    assert res.classify_fault(RuntimeError("x")) == "runtime_error"


# ---------------------------------------------------------------------------
# run_group: retry, quality gate, and every degradation level
# ---------------------------------------------------------------------------


def test_zero_fault_path_exact_accounting():
    disp = FakeDispatcher()
    outs, st = _run(disp, _plan(packed=True))
    assert len(outs) == 4
    for o in outs:
        assert o.med == [1000.0, 1000.0]
        t = o.timing
        assert t["timing_source"] == "callback"
        assert t["dispatches"] == 1 and t["remeasures"] == 0
        assert t["attempts"] == 1 and t["degraded_from"] is None
        assert t["fault_kind"] is None and t["noisy"] is False
    assert st.resilience_clean()
    assert st.host_sync_dispatches == 1       # one packed dispatch


def test_retry_recovers_without_degradation():
    disp = FakeDispatcher(behaviors=["timeout", "ok"])
    outs, st = _run(disp, _plan(packed=True))
    assert st.retried_dispatches == 1 and st.degraded_ladders == 0
    for o in outs:
        assert o.timing["attempts"] == 2
        assert o.timing["degraded_from"] is None
        assert o.timing["fault_kind"] == "timeout"   # noted, recovered
        assert o.med == [1000.0, 1000.0]


def test_corrupt_timing_detected_and_retried():
    disp = FakeDispatcher(behaviors=["corrupt", "ok"])
    outs, st = _run(disp, _plan(packed=True))
    assert st.retried_dispatches == 1
    for o in outs:
        assert o.timing["fault_kind"] == "corrupt_timing"
        assert all(m > 0 for m in o.med)


def test_packed_degrades_to_unpacked():
    # packed dispatch fails once; the unpacked re-plan succeeds
    disp = FakeDispatcher(behaviors=["runtime_error", "ok"])
    pol = res.RetryPolicy(retries=0, backoff_s=0, sleep=NOOP)
    outs, st = _run(disp, _plan(packed=True), policy=pol)
    assert [d.packed for d in disp.planned_calls] == [True, False]
    assert st.degraded_ladders == 4
    for o in outs:
        assert o.timing["timing_source"] == "callback"
        assert o.timing["degraded_from"] == "packed"
        assert o.timing["attempts"] == 2
        assert o.med == [1000.0, 1000.0]


def test_batched_split_isolates_failure_to_one_ladder():
    # the 4-ladder group dispatch fails; after the split, ladder 'c'
    # keeps failing and lands on the host-timed per-rung floor while
    # a, b, d recover as single fused ladders
    disp = FakeDispatcher(behaviors=[
        "runtime_error",                      # group dispatch
        "ok", "ok",                           # singles a, b
        "runtime_error",                      # single c -> rung floor
        "ok", "ok",                           # c rung 0, rung 1
        "ok"])                                # single d
    pol = res.RetryPolicy(retries=0, backoff_s=0, sleep=NOOP)
    outs, st = _run(disp, _plan(packed=False), policy=pol)
    by_name = {o.entry.spec.name: o for o in outs}
    for n in ("a", "b", "d"):
        t = by_name[n].timing
        assert t["timing_source"] == "callback"
        assert t["degraded_from"] == "batched" and t["group_size"] == 1
    c = by_name["c"].timing
    assert c["timing_source"] == "host"
    assert c["degraded_from"] == "batched"
    assert c["fault_kind"] == "runtime_error"
    assert c["attempts"] == 4          # group + single + 2 rungs
    assert by_name["c"].med == [2000.0, 2000.0]
    assert st.degraded_ladders == 4 and st.modeled_floor_ladders == 0


def test_full_ladder_to_modeled_floor():
    # every dispatch AND every rung faults: packed -> unpacked ->
    # split -> per-rung -> modeled, isolating nothing but losing
    # nothing either (one outcome per entry, med=None)
    disp = FakeDispatcher(default="timeout")
    outs, st = _run(disp, _plan(packed=True))
    assert len(outs) == 4
    for o in outs:
        assert o.med == [None, None]
        assert o.fenced is False
        assert o.timing["timing_source"] == "none"
        assert o.timing["degraded_from"] == "packed"
        assert o.timing["fault_kind"] == "timeout"
    assert st.modeled_floor_ladders == 4
    assert st.degraded_ladders == 4
    assert not st.resilience_clean()


def test_rung_floor_partial_rung_loss():
    # single-ladder plan degraded to rungs: rung 0 measures, rung 1
    # exhausts retries and is modeled; the ladder keeps rung 0
    disp = FakeDispatcher(behaviors=[
        "runtime_error", "runtime_error",     # fused ladder, retry
        "ok",                                 # rung 0
        "timeout", "timeout"])                # rung 1, retry -> None
    outs, st = _run(disp, _plan(names=("solo",), packed=False))
    (o,) = outs
    assert o.med == [2000.0, None]
    assert o.timing["timing_source"] == "host"
    assert o.timing["degraded_from"] == "ladder"
    assert st.modeled_floor_ladders == 0      # something still measured
    assert st.degraded_ladders == 1


def test_degrade_disabled_goes_straight_to_floor():
    disp = FakeDispatcher(default="timeout")
    pol = res.RetryPolicy(retries=0, degrade=False, backoff_s=0,
                          sleep=NOOP)
    outs, st = _run(disp, _plan(packed=True), policy=pol)
    assert all(o.med == [None, None] for o in outs)
    assert len(disp.planned_calls) == 1       # no ladder walked
    assert st.modeled_floor_ladders == 4 and st.degraded_ladders == 0


def test_modeled_floor_disabled_raises_group_error():
    disp = FakeDispatcher(default="timeout")
    pol = res.RetryPolicy(retries=0, degrade=False, modeled_floor=False,
                          backoff_s=0, sleep=NOOP)
    with pytest.raises(res.GroupExecutionError):
        _run(disp, _plan(packed=True), policy=pol)


def test_backoff_is_capped_exponential():
    slept = []
    pol = res.RetryPolicy(retries=4, backoff_s=0.05, backoff_cap_s=0.15,
                          sleep=slept.append)
    disp = FakeDispatcher(behaviors=["timeout"] * 4 + ["ok"])
    _run(disp, _plan(names=("solo",)), policy=pol)
    assert slept == [0.05, 0.1, 0.15, 0.15]   # doubled, then capped


def test_non_retryable_carries_group_context():
    disp = FakeDispatcher(behaviors=[ValueError("bad roles table")])
    with pytest.raises(res.GroupExecutionError) as ei:
        _run(disp, _plan(packed=True))
    err = ei.value
    msg = str(err)
    for name in ("a", "b", "c", "d"):
        assert f"'{name}'" in msg             # every member spec named
    assert "hbm:r" in msg and str(BUF) in msg
    assert isinstance(err.cause, ValueError)
    assert err.context.startswith("dispatch group")
    assert isinstance(err.__cause__, ValueError)
    assert len(disp.planned_calls) == 1       # no retry, no degradation


def test_quality_gate_remeasures_and_keeps_calmer_set():
    gate = res.QualityGate(rel_spread=2.0, remeasure=2, min_spread_ns=1.0)
    disp = FakeDispatcher(behaviors=[("noisy", 5000.0), "ok"])
    outs, st = _run(disp, _plan(packed=True), gate=gate)
    assert st.noisy_remeasures == 1 and st.noisy_rungs == 0
    # logical counters stay stable; the honest cost is host syncs
    assert st.measure_dispatches == 1 and st.host_sync_dispatches == 2
    for o in outs:
        t = o.timing
        assert t["noisy"] is False and t["remeasures"] == 1
        assert t["dispatches"] == 2
        assert max(t["rung_time_spread_ns"]) <= 10


def test_quality_gate_flags_stubbornly_noisy_rungs():
    gate = res.QualityGate(rel_spread=2.0, remeasure=2, min_spread_ns=1.0)
    disp = FakeDispatcher(default=("noisy", 5000.0))
    outs, st = _run(disp, _plan(packed=True), gate=gate)
    assert st.noisy_remeasures == 2           # budget spent
    assert st.noisy_rungs == 8                # 4 ladders x 2 rungs
    for o in outs:
        assert o.timing["noisy"] is True
        assert o.timing["noisy_rungs"] == [0, 1]
        assert o.med == [1000.0, 1000.0]      # still persisted, flagged


def test_quality_gate_off_never_remeasures():
    disp = FakeDispatcher(default=("noisy", 1e9))
    outs, st = _run(disp, _plan(packed=True), gate=None)
    assert st.noisy_remeasures == 0 and st.noisy_rungs == 0
    assert all(o.timing["noisy"] is False for o in outs)


# ---------------------------------------------------------------------------
# Atomic CurveDB.save
# ---------------------------------------------------------------------------


def _tiny_db():
    from repro.core.characterize import CurveDB, CurvePoint, Surface
    db = CurveDB(platform="test")
    key = CurveDB.key("hbm", "r", "hbm", "w")
    db.surfaces[key] = Surface.from_points(
        [CurvePoint(n_stressors=0, bandwidth_gbps=10.0, latency_ns=100.0),
         CurvePoint(n_stressors=1, bandwidth_gbps=5.0, latency_ns=200.0)])
    return db


def test_curvedb_save_is_atomic(tmp_path, monkeypatch):
    from repro.core import characterize
    db = _tiny_db()
    path = str(tmp_path / "curves.json")
    db.save(path)
    before = open(path).read()

    # a fault mid-serialisation must leave the old file byte-intact
    def boom(*a, **kw):
        raise res.InjectedFault("runtime_error", "curvedb-save")
    monkeypatch.setattr(characterize.json, "dump", boom)
    with pytest.raises(res.InjectedFault):
        db.save(path)
    assert open(path).read() == before
    # ...and no temp litter survives the failed attempt
    assert [p for p in os.listdir(tmp_path)
            if p.startswith(".curvedb-")] == []
    monkeypatch.undo()
    rt = characterize.CurveDB.load(path)
    assert set(rt.surfaces) == set(db.surfaces)


# ---------------------------------------------------------------------------
# SweepJournal: crash-resume at the unit level
# ---------------------------------------------------------------------------


def _exec(plan, disp, journal, stats=None):
    stats = stats or DispatchStats()
    maps = exec_journal.execute_plan(
        disp, plan, n_eng=plan.n_engines, activity="jnp", mode="batched",
        stats=stats, policy=res.RetryPolicy(backoff_s=0, sleep=NOOP),
        gate=None, journal=journal)
    return maps, stats


def test_journal_resume_is_value_equal_and_free(tmp_path):
    plan = _plan(names=("a", "b"), packed=False, buf=BUF)
    jpath = str(tmp_path / "sweep.journal")
    maps1, st1 = _exec(plan, FakeDispatcher(), jpath)
    assert st1.resumed_ladders == 0

    # resume from the complete journal: zero dispatches, equal values
    disp2 = FakeDispatcher(default=RuntimeError("must not dispatch"))
    maps2, st2 = _exec(plan, disp2, jpath)
    assert disp2.planned_calls == []
    assert st2.resumed_ladders == 2
    assert st2.host_sync_dispatches == 0
    executed1, fenced1, timing1 = maps1
    executed2, fenced2, timing2 = maps2
    assert fenced1 == fenced2 and timing1 == timing2
    assert set(executed1) == set(executed2)
    for k in executed1:
        assert executed1[k] == executed2[k]   # exact float round-trip


def test_journal_rejects_foreign_fingerprint(tmp_path):
    jpath = str(tmp_path / "sweep.journal")
    _exec(_plan(names=("a", "b")), FakeDispatcher(), jpath)
    with pytest.raises(ValueError, match="different sweep"):
        _exec(_plan(names=("a", "zzz")), FakeDispatcher(), jpath)


def test_killed_sweep_resumes_skipping_finished_groups(tmp_path):
    # distinct buffers -> distinct signatures -> three groups
    pm = PoolManager()
    triples = [(s, s.observer, s.observer.buffers[0])
               for s in (_spec("a", BUF), _spec("b", 2 * BUF),
                         _spec("c", 4 * BUF))]
    plan = exec_plan.build_plan(triples, 8, pm, pm.platform.n_engines)
    assert len(plan.dispatches) == 3
    jpath = str(tmp_path / "sweep.journal")

    # the sweep dies mid-flight after journaling the first group
    disp = FakeDispatcher(behaviors=["ok", KeyboardInterrupt()])
    with pytest.raises(KeyboardInterrupt):
        _exec(plan, disp, jpath)
    assert len(disp.planned_calls) == 2       # group 2 died un-journaled

    # resume: group 1 restores, groups 2+3 execute
    disp2 = FakeDispatcher()
    maps2, st2 = _exec(plan, disp2, jpath)
    assert st2.resumed_ladders == 1
    assert len(disp2.planned_calls) == 2
    executed2, fenced2, _t = maps2
    assert len(fenced2) == 3                  # every ladder present
    assert {i for i, _k in executed2} == {0, 1, 2}

    # third run resumes everything — the journal is now complete
    disp3 = FakeDispatcher(default=RuntimeError("no"))
    maps3, st3 = _exec(plan, disp3, jpath)
    assert st3.resumed_ladders == 3 and disp3.planned_calls == []
    assert maps3[0] == maps2[0] and maps3[2] == maps2[2]


def test_journal_skips_torn_tail_line(tmp_path):
    plan = _plan(names=("a", "b"))
    jpath = str(tmp_path / "sweep.journal")
    _exec(plan, FakeDispatcher(), jpath)
    with open(jpath, "a") as f:
        f.write('{"entries": [{"key": "torn')  # crash mid-append
    disp = FakeDispatcher(default=RuntimeError("no"))
    _maps, st = _exec(plan, disp, jpath)
    assert st.resumed_ladders == 2            # intact prefix restored
    assert disp.planned_calls == []


def test_journal_records_modeled_floor_outcomes(tmp_path):
    # even fully-degraded groups journal (med=None round-trips), so a
    # resume does not retry known-dead work
    plan = _plan(names=("solo",))
    jpath = str(tmp_path / "sweep.journal")
    _maps, st1 = _exec(plan, FakeDispatcher(default="timeout"), jpath)
    assert st1.modeled_floor_ladders == 1
    disp2 = FakeDispatcher(default=RuntimeError("no"))
    maps2, st2 = _exec(plan, disp2, jpath)
    assert st2.resumed_ladders == 1 and disp2.planned_calls == []
    _executed, _fenced, timing = maps2
    assert timing[0]["timing_source"] == "none"
    assert timing[0]["fault_kind"] == "timeout"


# ---------------------------------------------------------------------------
# End-to-end on a real mesh (forced-device subprocesses)
# ---------------------------------------------------------------------------


def run_forced(body: str, n_devices: int = N_DEV, timeout: int = 480,
               extra_env=None) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n_devices}"
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("SUBPROC_OK")
    """)
    env = dict(os.environ, PYTHONPATH=SRC, **(extra_env or {}))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    assert "SUBPROC_OK" in r.stdout
    return r.stdout


def test_chaos_sweep_completes_with_every_curve():
    """A mixed-fault sweep on the real mesh finishes with EVERY curve
    present — faults retry or degrade, never silently drop points —
    and the resilience trail lands in provenance + CurveDB meta."""
    out = run_forced("""
    import json
    from repro.core.coordinator import CoreCoordinator
    from repro.core.characterize import curvedb_from_result
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec, TrafficShape)

    BUF = 64 << 10
    specs = [ScenarioSpec(f"chaos-{o}-{s}-{dc}",
                          ObserverSpec(o, "hbm", (BUF,)),
                          (StressorSpec(s, "hbm", BUF,
                                        TrafficShape.burst(dc)),),
                          iters=3, max_stressors=1)
             for o in ("r", "w") for s in ("r", "w")
             for dc in (0.5, 1.0)]
    coord = CoreCoordinator(backend="spmd",
                            faults="mixed=0.35,seed=7", quality="off")
    res = coord.run_matrix(specs)
    assert len(res.runs) == len(specs), "a faulted curve went missing"
    for run in res.runs:
        ex = run.execution
        assert ex["attempts"] >= 1
        assert "degraded_from" in ex and "fault_kind" in ex
        assert all(s.modeled_bw_gbps > 0 for s in run.scenarios)
    db = curvedb_from_result(res, coord.platform.name, backend="spmd")
    meta = db.meta
    print("FAULTS", json.dumps({
        k: meta[k] for k in ("faults_injected", "retried_dispatches",
                             "degraded_ladders", "modeled_floor_ladders")}))
    assert meta["faults_injected"] > 0, "chaos seed injected nothing"
    assert len(db.surfaces) > 0
    """)
    faults = json.loads(out.split("FAULTS ", 1)[1].splitlines()[0])
    assert faults["faults_injected"] > 0


def test_sweep_journal_end_to_end_resume():
    """Real-mesh crash/resume: a sweep that dies mid-flight resumes
    from its journal, re-executing only unfinished groups, and the
    journaled prefix restores value-identically; a second resume of
    the complete journal executes nothing and reproduces the CurveDB
    byte-for-byte."""
    run_forced("""
    import json, os, tempfile
    from repro.core.coordinator import CoreCoordinator
    from repro.core.characterize import characterize_matrix
    from repro.core.exec import journal as exec_journal
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)

    BUF = 64 << 10
    specs = [ScenarioSpec(f"jrn-{i}", ObserverSpec(o, "hbm", (BUF,)),
                          (StressorSpec(s, "hbm", BUF),),
                          iters=3, max_stressors=1)
             for i, (o, s) in enumerate(
                 [("r", "w"), ("w", "w"), ("r", "y")])]
    tmp = tempfile.mkdtemp()
    jpath = os.path.join(tmp, "sweep.journal")

    # crash after the first journaled group
    real_record = exec_journal.SweepJournal.record
    calls = {"n": 0}
    def dying_record(self, planned, outcomes):
        real_record(self, planned, outcomes)
        calls["n"] += 1
        if calls["n"] >= 1:
            raise KeyboardInterrupt("simulated mid-sweep crash")
    exec_journal.SweepJournal.record = dying_record
    coord = CoreCoordinator(backend="spmd", faults=False, quality="off")
    try:
        characterize_matrix(coord, specs, journal=jpath)
        raise SystemExit("sweep should have crashed")
    except KeyboardInterrupt:
        pass
    finally:
        exec_journal.SweepJournal.record = real_record
    with open(jpath) as f:
        prefix = [json.loads(l) for l in f.read().splitlines()[1:]]
    assert len(prefix) == 1

    # resume: finishes the sweep, restoring the journaled group
    # (which may stack several same-signature ladders)
    db1 = characterize_matrix(coord, specs, journal=jpath)
    assert db1.meta["resumed_ladders"] == len(prefix[0]["entries"])
    assert len(db1.surfaces) >= 1

    # a complete journal makes the next run pure restore, value-equal
    db2 = characterize_matrix(coord, specs, journal=jpath)
    assert db2.meta["resumed_ladders"] == len(specs)
    assert db2.meta["measure_dispatches"] == 0
    def doc(db):
        d = {k.to_string(): s.to_dict()
             for k, s in db.surfaces.items()}
        return json.dumps(d, sort_keys=True)
    assert doc(db1) == doc(db2)
    """)


def test_env_fault_spec_reaches_dispatcher():
    """REPRO_FAULT_SPEC wires chaos into a default-constructed
    coordinator (the CI chaos leg's contract), and faults=False
    overrides it for hermetic runs."""
    run_forced("""
    from repro.core.coordinator import CoreCoordinator
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)
    c = CoreCoordinator(backend="spmd")
    assert c.fault_spec is not None and c.fault_spec.seed == 7
    assert c._dispatcher.faults is not None
    off = CoreCoordinator(backend="spmd", faults=False)
    assert off.fault_spec is None and off._dispatcher.faults is None

    BUF = 64 << 10
    spec = ScenarioSpec("envchaos", ObserverSpec("r", "hbm", (BUF,)),
                        (StressorSpec("w", "hbm", BUF),), iters=3,
                        max_stressors=1)
    res = c.run_matrix([spec])
    assert len(res.runs) == 1       # chaos on, curve still complete
    """, extra_env={"REPRO_FAULT_SPEC": "mixed=0.3,seed=7"})


def test_compiler_refusal_is_raised_not_degraded():
    """A program the compiler refuses is refused every time: the
    resilience layer re-raises it with the group's context instead of
    retrying, degrading to another program, or modeling the curve."""
    from repro.core.exec.program import CompileError
    disp = FakeDispatcher(default=CompileError("kernel ran out of VMEM"))
    with pytest.raises(res.GroupExecutionError) as ei:
        _run(disp, _plan(packed=True))
    assert isinstance(ei.value.cause, CompileError)
    assert len(disp.planned_calls) == 1       # no retry, no degradation


def test_compile_traced_names_the_refused_program():
    from repro.core.exec.program import CompileError, compile_traced

    class _Refused:
        def lower(self):
            raise RuntimeError("Mosaic failed to compile TPU kernel")

    with pytest.raises(CompileError, match="rung program 7.*Mosaic"):
        compile_traced(_Refused(), "rung program 7")
