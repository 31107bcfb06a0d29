"""The adversarial worst-case search and its planner transform.

Main-process tests exercise ``plan.probe_batch`` as pure data (no mesh
needed) and the search loop on the deterministic modeled path; the
multi-device execution — per-probe psum sandwiches in the stacked
dispatch, one host sync per probe batch, the full search loop — runs in
subprocesses with forced host devices (the main pytest process must
keep seeing one device; see conftest).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core.characterize import (AXIS_N, CurveDB, Surface, SurfaceAxis,
                                     SurfaceKey)
from repro.core.coordinator import CoreCoordinator
from repro.core.exec import plan as exec_plan
from repro.core.scenarios import (ObserverSpec, ScenarioSpec, StressorSpec,
                                  TrafficShape)
from repro.core.search import (DEFAULT_ARMS, SearchArm, SearchSpec,
                               WORSTCASE_QUALIFIER, worst_case_search)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
BUF = 64 << 10


@pytest.fixture(scope="module")
def coord():
    return CoreCoordinator(backend="simulate")


def _spec(strat="b", rw=0.5, ir=1.0, stride=16, iters=8, max_stressors=3):
    if strat == "t":
        shape = TrafficShape(kind="strided", stride=stride, duty_cycle=ir)
    else:
        shape = TrafficShape.traffic(rw, ir)
    return ScenarioSpec(
        name=f"probe.hbm.r|hbm.{strat}@{shape.tag()}",
        observer=ObserverSpec("r", "hbm", (BUF,)),
        stressors=(StressorSpec(strat, "hbm", BUF, shape),),
        iters=iters, max_stressors=max_stressors)


def _probes(specs_ks):
    return [(s, s.observer, BUF, k) for s, k in specs_ks]


# ---------------------------------------------------------------------------
# probe_batch: pure planning, no mesh required
# ---------------------------------------------------------------------------


def test_probe_batch_packs_slots_and_idle_fills_ragged_wave(coord):
    probes = _probes([(_spec(rw=rw), 1) for rw in
                      (0.0, 0.25, 0.5, 0.75, 1.0)])
    planned = exec_plan.probe_batch(probes, 8, coord.pools,
                                    coord.platform.n_engines)
    assert planned.probe and planned.packed
    assert (planned.subset_width, planned.n_subsets,
            planned.waves, planned.n_scen) == (2, 4, 2, 1)
    assert planned.group == 5
    # probe g runs in wave g // P on subset g % P
    assert planned.member_slot(0) == (0, 0)
    assert planned.member_slot(4) == (1, 0)
    # every row spans the full packed width; the ragged last wave
    # idle-fills its three spare slots
    assert all(len(row) == 8 for row in planned.rungs)
    last = planned.rungs[-1]
    assert all(r[0] == "i" for r in last[2:])
    assert last[0][0] != "i"        # probe 4's observer is live


def test_probe_batch_degenerate_slot_is_global(coord):
    # a probe needing the whole mesh forces the one-slot geometry:
    # one probe per wave behind a global psum sandwich
    probes = _probes([(_spec(), 3), (_spec(rw=1.0), 3)])
    planned = exec_plan.probe_batch(probes, 4, coord.pools,
                                    coord.platform.n_engines)
    assert planned.probe and not planned.packed
    assert (planned.subset_width, planned.n_subsets,
            planned.waves) == (4, 1, 2)
    assert planned.subsets() is None


def test_probe_batch_rejects_out_of_depth_rungs(coord):
    with pytest.raises(ValueError, match="ladder depth"):
        exec_plan.probe_batch(_probes([(_spec(max_stressors=2), 3)]),
                              8, coord.pools, coord.platform.n_engines)
    with pytest.raises(ValueError, match="at least one probe"):
        exec_plan.probe_batch([], 8, coord.pools,
                              coord.platform.n_engines)


def test_probe_batch_rejects_conflicting_chase_chains(coord):
    # probes 0 and 4 share slot 0 across waves: one operand cannot
    # seed both an 8-stride and a 64-stride chain
    probes = _probes([(_spec("t", stride=8), 1)] * 4
                     + [(_spec("t", stride=64), 1)])
    with pytest.raises(ValueError, match="conflicting chase chains"):
        exec_plan.probe_batch(probes, 8, coord.pools,
                              coord.platform.n_engines)
    # the same stride everywhere shares one chain legally
    ok = _probes([(_spec("t", stride=8), 1)] * 5)
    planned = exec_plan.probe_batch(ok, 8, coord.pools,
                                    coord.platform.n_engines)
    assert planned.probe and planned.waves == 2


def test_merge_probe_operand_roles_covers_every_engine():
    chase = ("l", None, 8, 4)
    stream = ("r", None, 16, 4)
    idle = ("i", None, 1, 4)
    rows = [(chase, stream), (stream, idle)]
    merged = exec_plan.merge_probe_operand_roles(rows)
    # engine 0 keeps its chain-seeding chase; engine 1 the widest
    # chain-free role; never-covered positions materialize as idle
    assert merged[0] == chase and merged[1] == stream
    merged = exec_plan.merge_probe_operand_roles([(idle, idle)])
    assert merged == [idle, idle]


def test_probe_batch_cache_key_and_packing_pass_through(coord):
    probes = _probes([(_spec(), 1), (_spec(rw=1.0), 1)])
    planned = exec_plan.probe_batch(probes, 8, coord.pools,
                                    coord.platform.n_engines)
    key = planned.cache_key("batched", 8, "jnp", 3)
    assert key[-2] is True          # the probe flag is part of identity
    # width-packing must not re-plan an already-packed probe batch
    plan = exec_plan.DispatchPlan(8, (planned,))
    packed = exec_plan.pack_engine_subsets(plan)
    assert packed.dispatches[0] is planned


# ---------------------------------------------------------------------------
# The search loop (modeled path: deterministic, single device)
# ---------------------------------------------------------------------------


def _envelope_bytes(result):
    return json.dumps(
        {k.to_string(): s.to_dict() for k, s in result.envelope.items()},
        sort_keys=True).encode()


def test_search_is_seed_deterministic(coord):
    spec = SearchSpec(iterations=5, batch=3, max_stressors=3, seed=11)
    a = worst_case_search(coord, spec, execute=False)
    b = worst_case_search(coord, spec, execute=False)
    assert _envelope_bytes(a) == _envelope_bytes(b)
    assert [t["candidates"] for t in a.trace] == \
        [t["candidates"] for t in b.trace]
    # a different seed explores a different trajectory
    c = worst_case_search(
        coord, SearchSpec(iterations=5, batch=3, max_stressors=3,
                          seed=12), execute=False)
    assert [t["candidates"] for t in a.trace] != \
        [t["candidates"] for t in c.trace]


def test_search_save_load_search_is_idempotent(coord, tmp_path):
    """The satellite property test: searching against a database, then
    against its save->load round-trip, yields byte-identical
    envelopes."""
    db = CurveDB(platform="test")
    db.surfaces[SurfaceKey("hbm", "r", "hbm", "b")] = Surface(
        axes=(SurfaceAxis(AXIS_N, (0.0, 1.0, 3.0)),),
        bandwidth_gbps=[90.0, 55.0, 30.0], latency_ns=[0.0, 0.0, 0.0])
    db.surfaces[SurfaceKey("hbm", "l", "hbm", "b")] = Surface(
        axes=(SurfaceAxis(AXIS_N, (0.0, 1.0, 3.0)),),
        bandwidth_gbps=[1.0, 1.0, 1.0], latency_ns=[120.0, 300.0, 700.0])
    spec = SearchSpec(iterations=4, batch=2, max_stressors=3, seed=5)
    first = worst_case_search(coord, spec, db, execute=False)
    path = os.path.join(tmp_path, "db.json")
    first.install(db)
    db.save(path)
    reloaded = CurveDB.load(path)
    # the installed envelope round-tripped under its qualified key
    key = SurfaceKey("hbm", "r", "hbm", "b",
                     qualifier=WORSTCASE_QUALIFIER)
    assert reloaded.surfaces[key].to_dict() == \
        db.surfaces[key].to_dict()
    second = worst_case_search(coord, spec, reloaded, execute=False)
    assert _envelope_bytes(first) == _envelope_bytes(second)


def test_search_envelope_is_worst_per_stressor_count(coord):
    spec = SearchSpec(iterations=6, batch=3, max_stressors=3, seed=2)
    r = worst_case_search(coord, spec, execute=False)
    for key, surf in r.envelope.items():
        assert key.qualifier == WORSTCASE_QUALIFIER
        assert surf.axes[0].name == AXIS_N
        prov = surf.provenance["worstcase"]
        assert prov["seed"] == 2 and len(prov["acquisition_trace"]) == 6
        for i, n in enumerate(surf.axes[0].values):
            same_n = [p for p in r.points
                      if p.obs_strat == key.obs_strat
                      and p.n_stressors == int(n)]
            if key.obs_strat == "l":
                assert surf.latency_ns[i] == pytest.approx(
                    max(p.latency_ns for p in same_n))
            else:
                assert surf.bandwidth_gbps[i] == pytest.approx(
                    min(p.bandwidth_gbps for p in same_n))
    # worst() agrees with the provenance record
    worst = r.worst("r")
    key = SurfaceKey("hbm", "r", "hbm", "b",
                     qualifier=WORSTCASE_QUALIFIER)
    assert r.envelope[key].provenance["worstcase"]["worst"] == \
        worst.to_dict()


def test_search_bandit_plays_every_arm_then_exploits(coord):
    spec = SearchSpec(iterations=len(DEFAULT_ARMS) + 2, batch=2,
                      max_stressors=3, seed=1)
    r = worst_case_search(coord, spec, execute=False)
    played = [t["arm"] for t in r.trace]
    assert sorted(played[:len(DEFAULT_ARMS)]) == \
        sorted(a.label() for a in DEFAULT_ARMS)
    # exploitation rounds replay known arms
    assert set(played[len(DEFAULT_ARMS):]) <= set(played)


def test_search_arm_shapes_honour_coordinates():
    assert SearchArm("t", 32).shape(0.5, 0.5) == TrafficShape(
        kind="strided", stride=32, duty_cycle=0.5)
    assert SearchArm("y").shape(0.5, 1.0) == TrafficShape.steady()
    assert SearchArm("y").shape(0.5, 0.5).duty_cycle == 0.5
    assert SearchArm("b").shape(0.75, 0.5) == TrafficShape.traffic(
        0.75, 0.5)
    assert SearchArm("b").read_fraction(0.75) == 0.75
    assert SearchArm("y").read_fraction(0.75) is None


# ---------------------------------------------------------------------------
# Multi-device execution (subprocess: forced host devices)
# ---------------------------------------------------------------------------


def _run_forced(code: str, sentinel: str, devices: int = 4):
    preamble = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \\
            "--xla_force_host_platform_device_count={devices}"
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c",
                        preamble + textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=480,
                       env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    assert sentinel in r.stdout


def test_probe_batch_dispatch_fences_every_probe():
    """The stacked probe dispatch is ONE host sync whose program
    carries a verified psum sandwich for every probe slot — and the
    packed fence is NOT valid for any other mesh partition."""
    _run_forced("""
        import jax
        from repro.core.coordinator import CoreCoordinator
        from repro.core.exec import plan as exec_plan
        from repro.core.exec import program as exec_program
        from repro.core.exec.dispatch import DispatchStats
        from repro.core.exec.fence import measured_region_is_fenced
        from repro.core.exec.program import build_ladder_entry
        from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                          StressorSpec, TrafficShape)

        # keep the trace the program was compiled from (an AOT
        # executable cannot be re-walked with different subsets below)
        traces = []
        _compile = exec_program.compile_traced

        def _keep_trace(traced, what):
            traces.append(traced)
            return _compile(traced, what)

        exec_program.compile_traced = _keep_trace

        BUF = 64 << 10
        coord = CoreCoordinator(backend="spmd")

        def spec_for(rw):
            shape = TrafficShape.traffic(rw, 1.0)
            return ScenarioSpec(
                name=f"p@{shape.tag()}",
                observer=ObserverSpec("r", "hbm", (BUF,)),
                stressors=(StressorSpec("b", "hbm", BUF, shape),),
                iters=8, max_stressors=3)

        probes = [(s, s.observer, BUF, 1)
                  for s in (spec_for(0.0), spec_for(0.5),
                            spec_for(1.0))]
        planned = exec_plan.probe_batch(probes, 4, coord.pools,
                                        coord.platform.n_engines)
        assert planned.packed and planned.n_subsets == 2
        stats = DispatchStats()
        entry = build_ladder_entry(planned, 4, "jnp", 2, stats)
        assert entry.fenced
        # the packed probe program's sandwich is per-subset: the same
        # program is NOT a fence for a different partition
        assert not measured_region_is_fenced(
            None, jaxpr=traces[-1].jaxpr, subsets=((0, 2), (1, 3)))
        med, _s, fenced, aot = coord._dispatcher.run_planned(
            planned, 4, "jnp", "batched", stats)
        assert fenced and aot
        assert stats.host_sync_dispatches == 1
        assert med.shape == (3, 1) and (med > 0).all()
        print("PROBE_FENCE_OK")
    """, "PROBE_FENCE_OK")


def test_worst_case_search_one_dispatch_per_iteration():
    """Acceptance: each search iteration is exactly one host-sync
    batched dispatch, asserted via DispatchStats on a live mesh."""
    _run_forced("""
        import jax
        from repro.core.coordinator import CoreCoordinator
        from repro.core.search import SearchSpec, worst_case_search

        coord = CoreCoordinator(backend="spmd")
        spec = SearchSpec(iterations=3, batch=2, max_stressors=2,
                          seed=9, buffer_bytes=64 << 10, iters=8)
        r = worst_case_search(coord, spec)
        assert r.executed and r.fenced
        assert r.stats.host_sync_dispatches == \\
            spec.iterations + r.stats.noisy_remeasures
        assert sum(t["host_sync_dispatches"] for t in r.trace) == \\
            r.stats.host_sync_dispatches
        assert {k.obs_strat for k in r.envelope} == {"r", "l"}
        assert all(k.qualifier == "worstcase" for k in r.envelope)
        print("SEARCH_DISPATCH_OK")
    """, "SEARCH_DISPATCH_OK")
