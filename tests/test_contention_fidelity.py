"""Contention-fidelity suite (ISSUE 3): the spmd backend's curves must
stay honest as the rung activities get real.

Covers three fidelity claims:

* **Backend consistency** — the same scenario run on the ``interpret``
  and ``spmd`` backends produces the same curve keys and the same
  (deterministic) modeled ladder, and the modeled ladder is monotone on
  both: executing rungs must not change what the curves *mean*.
* **Co-observer coupling** — a coupled multi-observer scenario shifts
  each observer's curve versus the uncoupled baseline (the sibling is
  live inside the measured region / queueing network), and CurveDB
  provenance records ``coupled`` and ``activity`` for every curve.
* **Fenced Pallas activities** — with rung activities promoted from jnp
  loops to real Pallas kernels, ``measured_region_is_fenced`` still
  verifies the barrier dataflow edge, now *through* the ``pallas_call``
  boundary: a kernel fed only by constants (a no-operand write stream)
  is rejected even though the switch output downstream still depends on
  the fence.

Multi-device execution happens in forced-device subprocesses (the main
pytest process must keep seeing ONE device); the device count follows
the ``REPRO_SPMD_DEVICES`` env var so CI can exercise a 2-device and an
8-device mesh (see .github/workflows/ci.yml).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

# CI matrix knob: how many host devices the spmd subprocesses force
N_DEV = max(2, int(os.environ.get("REPRO_SPMD_DEVICES", "8")))


def run_forced(body: str, n_devices: int = N_DEV, timeout: int = 480) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n_devices}"
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("SUBPROC_OK")
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    assert "SUBPROC_OK" in r.stdout
    return r.stdout


# ---------------------------------------------------------------------------
# spmd vs interpret: same scenario, same curve identity, sane ladder
# ---------------------------------------------------------------------------


def test_spmd_vs_interpret_consistency():
    """The same ScenarioSpec on both executable backends: identical
    curve keys, identical modeled rung values (the queueing network is
    deterministic and backend-independent), monotone modeled ladder,
    and executed spmd points present and positive for every rung the
    mesh could hold."""
    run_forced("""
    import jax
    from repro.core.coordinator import CoreCoordinator
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)

    BUF = 64 << 10
    K = 3
    spec = ScenarioSpec(
        "consistency", ObserverSpec("r", "hbm", (BUF,)),
        (StressorSpec("w", "hbm", BUF),), iters=3, max_stressors=K)

    n_dev = len(jax.devices())
    interp = CoreCoordinator(backend="interpret").run_matrix([spec])
    spmd = CoreCoordinator(backend="spmd").run_matrix([spec])

    # curve identity agrees
    assert [r.key for r in interp.runs] == [r.key for r in spmd.runs]
    ri, rs = interp.runs[0], spmd.runs[0]
    # the spmd ladder is capped at the rungs its mesh can hold;
    # interpret models the full requested depth
    depth = max(1, min(K + 1, n_dev))
    assert len(ri.scenarios) == K + 1
    assert len(rs.scenarios) == depth

    # the modeled rung values are backend-independent (common prefix)
    for si, ss in zip(ri.scenarios, rs.scenarios):
        assert si.modeled_bw_gbps == ss.modeled_bw_gbps
        assert si.modeled_lat_ns == ss.modeled_lat_ns
    # ...and the modeled ladder is monotone (bw down, latency up)
    bws = [s.modeled_bw_gbps for s in ri.scenarios]
    lats = [s.modeled_lat_ns for s in ri.scenarios]
    assert all(b <= a * 1.0001 for a, b in zip(bws, bws[1:]))
    assert all(b >= a * 0.9999 for a, b in zip(lats, lats[1:]))

    # the spmd backend executed every rung the mesh could hold, and
    # the executed points are real measurements
    assert rs.execution["executed_rungs"] == list(range(depth))
    assert rs.execution["activity"] in ("pallas", "jnp")
    assert rs.execution["fenced"]
    for s in rs.scenarios:
        assert s.source == "executed"
        assert s.main.elapsed_ns > 0
        assert s.main.bandwidth_gbps > 0
    print("consistency OK on", n_dev, "devices")
    """)


def test_coupled_execution_on_mesh():
    """Coupled multi-observer spmd execution: every sibling occupies a
    live engine inside each observer's rung, so the executable ladder
    depth shrinks by one engine per sibling; provenance records
    coupled/activity per curve; the jnp fallback activity is selectable
    and stamps itself honestly."""
    run_forced("""
    import jax
    from repro.core.coordinator import CoreCoordinator
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)

    BUF = 64 << 10
    K = 3
    obs = (ObserverSpec("r", "hbm", (BUF,)),
           ObserverSpec("l", "hbm", (BUF,)))
    stress = (StressorSpec("w", "hbm", BUF),)
    coupled = ScenarioSpec("coupled", obs, stress, iters=3,
                           max_stressors=K)
    uncoupled = ScenarioSpec("uncoupled", obs, stress, iters=3,
                             max_stressors=K, coupled=False)

    n_dev = len(jax.devices())
    c = CoreCoordinator(backend="spmd")
    res = c.run_matrix([coupled, uncoupled])
    assert res.stats.n_ladders == 4

    depth_c = max(1, min(K + 1, n_dev - 1))   # 1 engine per sibling
    depth_u = max(1, min(K + 1, n_dev))
    for run in res.runs:
        ex = run.execution
        assert ex["fenced"]
        assert ex["activity"] in ("pallas", "jnp")
        if run.spec.name == "coupled":
            assert ex["coupled"] is True
            assert ex["executed_rungs"] == list(range(depth_c))
        else:
            assert ex["coupled"] is False
            assert ex["executed_rungs"] == list(range(depth_u))
        for s in run.scenarios:
            if s.source == "executed":
                assert s.main.elapsed_ns > 0

    # forcing the jnp fallback stamps the provenance honestly
    cj = CoreCoordinator(backend="spmd", spmd_activity="jnp")
    resj = cj.run_matrix([coupled])
    assert all(r.execution["activity"] == "jnp" for r in resj.runs)
    assert all(r.execution["fenced"] for r in resj.runs)
    print("coupled execution OK on", n_dev, "devices")
    """)


# ---------------------------------------------------------------------------
# Coupling shifts curves (deterministic: the queueing model)
# ---------------------------------------------------------------------------


def test_coupled_vs_uncoupled_curves_differ_under_load():
    """A live sibling bandwidth observer inside the measured region
    must cost the observer bandwidth at EVERY rung — including rung 0,
    where the uncoupled scenario sees no contention at all.  Modeled
    backend: deterministic, so the comparison is exact."""
    from repro.core.characterize import characterize_matrix
    from repro.core.coordinator import CoreCoordinator

    coupled, uncoupled = _twin_specs()
    c = CoreCoordinator(backend="simulate")
    db_c = characterize_matrix(c, [coupled])
    db_u = characterize_matrix(c, [uncoupled])
    key = "hbm:r|hbm:w"
    bw_c = [p.bandwidth_gbps for p in db_c.curves[key]]
    bw_u = [p.bandwidth_gbps for p in db_u.curves[key]]
    lat_c = [p.latency_ns for p in db_c.curves[key]]
    lat_u = [p.latency_ns for p in db_u.curves[key]]
    assert all(cc < uu for cc, uu in zip(bw_c, bw_u))
    assert all(cc > uu for cc, uu in zip(lat_c, lat_u))
    # provenance records which semantics produced each curve
    assert db_c.provenance[key]["execution"]["coupled"] is True
    assert db_u.provenance[key]["execution"]["coupled"] is False
    assert db_c.provenance[key]["coupled"] is True
    assert db_u.provenance[key]["coupled"] is False


def test_coupling_term_in_scenario_ladder():
    """The queueing model's standalone ladder API carries the same
    co-observer term: a coupled sibling read stream depresses the
    observer at every rung."""
    from repro.core import simulate as sim
    from repro.core.devicetree import TPU_V5E

    node = TPU_V5E.node("hbm")
    plain = sim.scenario_ladder(
        TPU_V5E, obs_node=node, obs_strategy="r", stress_node=node,
        stress_strategy="w", max_stressors=3)
    coupled = sim.scenario_ladder(
        TPU_V5E, obs_node=node, obs_strategy="r", stress_node=node,
        stress_strategy="w", max_stressors=3,
        co_observers=[(node, "r")])
    for p, q in zip(plain, coupled):
        assert q["obs"].bw_gbps < p["obs"].bw_gbps
        assert "co0" in q and "co0" not in p


def test_uncoupled_spec_roundtrips_and_defaults_coupled():
    """``coupled`` is part of the spec identity: it round-trips through
    dicts, and absent keys (pre-coupling spec files) default to the new
    coupled semantics."""
    import json

    from repro.core.scenarios import ScenarioSpec

    coupled, uncoupled = _twin_specs()
    for spec in (coupled, uncoupled):
        back = ScenarioSpec.from_dict(json.loads(json.dumps(
            spec.to_dict())))
        assert back == spec and back.coupled == spec.coupled
    legacy = coupled.to_dict()
    del legacy["coupled"]
    assert ScenarioSpec.from_dict(legacy).coupled is True


def _twin_specs():
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)
    BUF = 1 << 20
    obs = (ObserverSpec("r", "hbm", (BUF,)),
           ObserverSpec("r", "host", (BUF,)))
    stress = (StressorSpec("w", "hbm", BUF),)
    return (ScenarioSpec("twin-c", obs, stress, iters=5, max_stressors=3),
            ScenarioSpec("twin-u", obs, stress, iters=5, max_stressors=3,
                         coupled=False))


def test_duplicate_observers_rejected():
    """Two observers identical in every field would alias one curve key
    per buffer and silently overwrite each other's ladders in CurveDB —
    validate_spec must reject the spec up front (twins differing in any
    field, e.g. buffer ladders, stay legal and key distinctly)."""
    from repro.core.coordinator import CoreCoordinator, ValidationError
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)

    BUF = 1 << 20
    o = ObserverSpec("r", "hbm", (BUF,))
    dup = ScenarioSpec("dup", (o, ObserverSpec("r", "hbm", (BUF,))),
                       (StressorSpec("w", "hbm", BUF),), iters=5)
    c = CoreCoordinator(backend="simulate")
    with pytest.raises(ValidationError, match="duplicate observer"):
        c.validate_spec(dup)
    # same instance listed twice is the same duplicate
    with pytest.raises(ValidationError, match="duplicate observer"):
        c.validate_spec(ScenarioSpec(
            "dup2", (o, o), (StressorSpec("w", "hbm", BUF),), iters=5))
    # differing buffer ladders remain legal
    c.validate_spec(ScenarioSpec(
        "ok", (o, ObserverSpec("r", "hbm", (2 * BUF,))),
        (StressorSpec("w", "hbm", BUF),), iters=5))


def test_coupled_siblings_resolve_for_reconstructed_observers():
    """_coupled_siblings drops exactly one occurrence of the measured
    observer — including for a deserialized (equal, non-identical)
    observer — so twins differing only in buffers still see each
    other."""
    from repro.core.coordinator import CoreCoordinator
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)

    BUF = 1 << 20
    a = ObserverSpec("r", "hbm", (BUF,))
    b = ObserverSpec("r", "hbm", (2 * BUF,))
    spec = ScenarioSpec("twins", (a, b),
                        (StressorSpec("w", "hbm", BUF),), iters=5)
    sib = CoreCoordinator._coupled_siblings
    assert sib(spec, a) == (b,)
    assert sib(spec, b) == (a,)
    # reconstructed equal observer resolves by value
    assert sib(spec, ObserverSpec("r", "hbm", (BUF,))) == (b,)
    assert sib(spec, ObserverSpec("r", "hbm", (2 * BUF,))) == (a,)


# ---------------------------------------------------------------------------
# Pallas rung activities keep the fence (jaxpr check crosses pallas_call)
# ---------------------------------------------------------------------------

ROWS = 16


def _operands(n_eng: int):
    xf = np.ones((n_eng, ROWS, 128), np.float32)
    xi = np.zeros((n_eng, ROWS, 128), np.int32)
    xi[:, :ROWS, 0] = np.roll(np.arange(ROWS), 1)     # a valid cycle
    return xf, xi


@pytest.mark.parametrize("strategy", ["r", "w", "y", "c", "b", "l", "t"])
def test_pallas_branch_fns_execute_and_stay_fenced(strategy):
    """Every Pallas rung activity traces under the rung program, runs
    to a finite result, and the measured region remains structurally
    fenced — the dataflow edge from the start-barrier psum reaches
    every pallas_call's operands."""
    from repro.core.coordinator import (_spmd_branch_fn,
                                        build_rung_program,
                                        measured_region_is_fenced)
    from repro.core.scenarios import TrafficShape

    shape = {"b": TrafficShape.mixed(1, 1),
             "t": TrafficShape.strided(4)}.get(strategy)
    fns = [_spmd_branch_fn(strategy, shape, ROWS, 2, activity="pallas")]
    _mesh, f = build_rung_program(1, fns, [0])
    xf, xi = _operands(1)
    out, _barrier = f(xf, xi)
    assert np.isfinite(np.asarray(out)).all()
    assert measured_region_is_fenced(f, xf, xi)


def test_pallas_activity_programs_contain_pallas_calls():
    """The promoted rung program really is pallas_call-backed (and the
    jnp fallback really is not): the activity provenance claim is
    structural, not a label."""
    import jax

    from repro.core.coordinator import _spmd_branch_fn, build_rung_program

    def has_pallas(activity):
        fns = [_spmd_branch_fn("r", None, ROWS, 2, activity=activity)]
        _mesh, f = build_rung_program(1, fns, [0])
        return "pallas_call" in str(jax.make_jaxpr(f)(*_operands(1)))

    assert has_pallas("pallas")
    assert not has_pallas("jnp")


def test_fence_checker_rejects_unfenced_pallas_kernel():
    """A pallas_call fed only by constants (write_hbm takes no operands
    at all) is real memory traffic with NO dataflow edge from the start
    barrier — XLA may hoist it above the fence.  The extended checker
    must reject it even though the switch output downstream still
    depends on the barrier through other equations."""
    from repro.core.coordinator import (build_rung_program,
                                        measured_region_is_fenced)
    from repro.kernels import stream as _kstream

    def unfenced(xf, xi):
        out = _kstream.write_hbm(ROWS, block_rows=ROWS, interpret=True)
        return out[0, 0] + xf[0, 0] * 0.0     # "depends" on the fence

    _mesh, f = build_rung_program(1, [unfenced], [0])
    assert not measured_region_is_fenced(f, *_operands(1))


def test_mixed_stream_write_half_needs_the_seed():
    """Regression (found by the extended checker): the mixed stream's
    write half is a no-operand kernel, so an unseeded mix inside the
    measured region is structurally unfenced; the seeded mix routes the
    stores through write_hbm_seeded and restores the edge."""
    import jax
    import jax.numpy as jnp

    from repro.core.coordinator import (build_rung_program,
                                        measured_region_is_fenced)
    from repro.kernels import stream as _kstream

    def mk(seeded):
        def mixed(xf, xi):
            x = jax.lax.optimization_barrier(xf[:ROWS])
            s, out = _kstream.mixed_hbm(
                x, read_fraction=0.5, block_rows=ROWS // 8,
                interpret=True, seed=x[:1, :1] if seeded else None)
            return s + jnp.sum(out[:1])
        return mixed

    _m, f_seeded = build_rung_program(1, [mk(True)], [0])
    _m, f_bare = build_rung_program(1, [mk(False)], [0])
    xf, xi = _operands(1)
    assert measured_region_is_fenced(f_seeded, xf, xi)
    assert not measured_region_is_fenced(f_bare, xf, xi)


# ---------------------------------------------------------------------------
# Fused whole-ladder dispatch (ISSUE 4): accounting, equivalence, cache
# ---------------------------------------------------------------------------


def test_fused_dispatch_accounting():
    """DispatchStats under fusion: the fused path blocks the host ONCE
    per (triple, ladder) — versus 4 per RUNG on the legacy path — and
    the execution provenance records the timing source, the per-rung
    sample spreads, and the per-ladder dispatch count."""
    run_forced("""
    import jax
    from repro.core.coordinator import CoreCoordinator
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)

    BUF = 64 << 10
    K = 2
    spec = ScenarioSpec(
        "acct", (ObserverSpec("r", "hbm", (BUF,)),
                 ObserverSpec("w", "hbm", (BUF,))),
        (StressorSpec("w", "hbm", BUF),), iters=3, max_stressors=K)
    n_dev = len(jax.devices())
    depth = max(1, min(K + 1, n_dev - 1))     # 1 engine per sibling

    fused = CoreCoordinator(backend="spmd").run_matrix([spec])
    st = fused.stats
    assert st.n_ladders == 2
    assert st.spmd_rungs == 2 * depth
    assert st.measure_dispatches == st.n_ladders          # 1 per ladder
    # quality-gate re-measures (rare: a real noise event during the
    # run) each add one honest host sync on top of the 1-per-ladder
    assert st.host_sync_dispatches == st.n_ladders + st.noisy_remeasures
    for run in fused.runs:
        ex = run.execution
        assert ex["timing_source"] == "callback"
        assert ex["dispatches"] == 1 + ex["remeasures"]
        assert ex["attempts"] == 1 and ex["degraded_from"] is None
        assert ex["samples"] == 3
        assert len(ex["rung_time_spread_ns"]) == depth
        assert all(s >= 0 for s in ex["rung_time_spread_ns"])

    legacy = CoreCoordinator(backend="spmd",
                             spmd_dispatch="rung").run_matrix([spec])
    st = legacy.stats
    assert st.spmd_rungs == 2 * depth
    assert st.measure_dispatches == 2 * depth             # K per ladder
    assert st.host_sync_dispatches == 4 * 2 * depth       # warm + 3 timed
    for run in legacy.runs:
        ex = run.execution
        assert ex["timing_source"] == "host"
        assert ex["dispatches"] == 4 * depth
        assert len(ex["rung_time_spread_ns"]) == depth
    print("accounting OK on", n_dev, "devices")
    """)


def test_fused_vs_per_rung_curve_equivalence():
    """The fused whole-ladder dispatch must produce the SAME curves as
    the legacy per-rung path: identical keys, every rung executed and
    fenced on both, and the measured timings within a (generous — this
    is shared-CPU wall time on tiny budgets) agreement band."""
    run_forced("""
    import jax
    from repro.core.coordinator import CoreCoordinator
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)

    BUF = 128 << 10
    K = 3
    spec = ScenarioSpec(
        "equiv", ObserverSpec("r", "hbm", (BUF,)),
        (StressorSpec("w", "hbm", BUF),), iters=20, max_stressors=K)
    n_dev = len(jax.devices())
    depth = max(1, min(K + 1, n_dev))

    fused = CoreCoordinator(backend="spmd").run_matrix([spec])
    legacy = CoreCoordinator(backend="spmd",
                             spmd_dispatch="rung").run_matrix([spec])
    assert [r.key for r in fused.runs] == [r.key for r in legacy.runs]
    rf, rl = fused.runs[0], legacy.runs[0]
    assert rf.execution["fenced"] and rl.execution["fenced"]
    assert rf.execution["executed_rungs"] == list(range(depth))
    assert rl.execution["executed_rungs"] == list(range(depth))
    for sf, sl in zip(rf.scenarios, rl.scenarios):
        assert sf.source == sl.source == "executed"
        assert sf.main.strategy == sl.main.strategy
        assert sf.main.bytes_moved == sl.main.bytes_moved
        assert sf.main.elapsed_ns > 0 and sl.main.elapsed_ns > 0
        ratio = sf.main.elapsed_ns / sl.main.elapsed_ns
        assert 1 / 50 < ratio < 50, (sf.n_stressors, ratio)
    print("equivalence OK on", n_dev, "devices")
    """)


def test_batched_sweep_equivalence_and_accounting():
    """Sweep-level megabatching (ISSUE 5): a mixed sweep whose ladders
    repeat a role-program signature costs ONE host-synchronous dispatch
    per distinct signature — and produces curves IDENTICAL in keys,
    resolved strategies, bytes and fence state to the same sweep with
    batching off (one fused dispatch per ladder)."""
    run_forced("""
    import jax
    from repro.core.coordinator import CoreCoordinator, ValidationError
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)

    BUF = 64 << 10
    K = 2

    def mk(name, pool, iters):
        return ScenarioSpec(name, ObserverSpec("r", pool, (BUF,)),
                            (StressorSpec("w", "hbm", BUF),),
                            iters=iters, max_stressors=K)

    # 4 ladders, 2 distinct signatures: ladders whose roles and pools'
    # effective memory kinds match stack whatever the spec is called,
    # while differing iteration budgets MUST split
    specs = [mk("a", "hbm", 3), mk("b", "hbm", 3),
             mk("c", "hbm", 5), mk("d", "hbm", 5)]
    n_dev = len(jax.devices())
    depth = max(1, min(K + 1, n_dev))

    c = CoreCoordinator(backend="spmd")
    if c.pools.pool("host").effective_memory_kind() == "pinned_host":
        # host memory is its own kind here, as on a chip, and no rung
        # kernel takes a host operand: refused, not measured in HBM
        try:
            c.run_matrix([mk("h", "host", 3)])
            raise AssertionError("host observer was not refused")
        except ValidationError:
            pass
    bat = c.run_matrix(specs)
    st = bat.stats
    assert st.n_ladders == 4
    assert st.spmd_groups == 2
    # one per SIGNATURE (+ any rare quality-gate re-measures)
    assert st.host_sync_dispatches == 2 + st.noisy_remeasures
    assert st.measure_dispatches == 2
    assert st.spmd_rungs == 4 * depth          # every rung executed
    assert st.programs_built == 2              # one program per group
    for run in bat.runs:
        ex = run.execution
        assert ex["batched"] is True
        assert ex["group_size"] == 2
        assert ex["timing_source"] == "callback"
        assert ex["dispatches"] == 1 + ex["remeasures"]
        assert ex["fenced"]
        assert isinstance(ex["aot"], bool)

    # batching off: same coordinator API, one fused dispatch per ladder
    unb = CoreCoordinator(backend="spmd").run_matrix(specs,
                                                     batched=False)
    assert unb.stats.host_sync_dispatches == \
        4 + unb.stats.noisy_remeasures           # one per LADDER
    assert unb.stats.spmd_groups == 0
    assert [r.key for r in bat.runs] == [r.key for r in unb.runs]
    for rb, ru in zip(bat.runs, unb.runs):
        assert ru.execution["batched"] is False
        assert ru.execution["group_size"] == 1
        assert ru.execution["fenced"]
        assert rb.execution["executed_rungs"] \
            == ru.execution["executed_rungs"]
        for sb, su in zip(rb.scenarios, ru.scenarios):
            assert sb.source == su.source == "executed"
            assert sb.main.strategy == su.main.strategy
            assert sb.main.bytes_moved == su.main.bytes_moved
            assert sb.main.elapsed_ns > 0 and su.main.elapsed_ns > 0
    print("batched equivalence OK on", n_dev, "devices")
    """)


def test_packed_sweep_accounting_and_equivalence():
    """Engine-subset width-packing (ISSUE 7): a sweep of narrow
    same-signature ladders runs them SIDE BY SIDE on disjoint engine
    subsets of one dispatch — the accounting must show the packing
    (stats.packed_ladders / subset_width, per-curve subset slots), the
    dispatch count must stay at one per signature, and the curves must
    be IDENTICAL in keys, bytes and fence state to the same sweep with
    packing forced off."""
    run_forced("""
    import jax
    from repro.core.coordinator import CoreCoordinator
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)

    BUF = 64 << 10

    def mk(name):
        # max_stressors=1 -> 2-rung, width-2 ladders (observer +
        # one stressor engine); all four share one signature
        return ScenarioSpec(name, ObserverSpec("r", "hbm", (BUF,)),
                            (StressorSpec("w", "hbm", BUF),),
                            iters=3, max_stressors=1)

    specs = [mk(n) for n in "abcd"]
    n_dev = len(jax.devices())
    depth = max(1, min(2, n_dev))
    width = depth                   # 1 observer + (depth-1) scenarios
    # the planner packs iff a full second subset fits
    n_subsets = min(n_dev // width, 4) if n_dev >= 2 * width else 1

    c = CoreCoordinator(backend="spmd")
    res = c.run_matrix(specs)
    st = res.stats
    assert st.n_ladders == 4
    assert st.spmd_groups == 1                 # one signature
    assert st.host_sync_dispatches == \
        1 + st.noisy_remeasures                # ...one dispatch
    assert st.programs_built == 1
    assert st.spmd_rungs == 4 * depth          # every rung executed
    if n_subsets > 1:
        assert st.packed_ladders == 4
        assert st.subset_width == width
    else:
        assert st.packed_ladders == 0
    seen_subsets = set()
    for run in res.runs:
        ex = run.execution
        assert ex["batched"] is True
        assert ex["group_size"] == 4
        assert ex["fenced"]
        assert ex["packed"] is (n_subsets > 1)
        assert ex["subset_width"] == (width if n_subsets > 1
                                      else n_dev)
        assert 0 <= ex["subset_index"] < n_subsets
        seen_subsets.add(ex["subset_index"])
    # packed ladders really occupy DISTINCT subsets of the mesh
    assert len(seen_subsets) == min(n_subsets, 4)

    # packing off: same sweep, same grouping, scan-stacked instead
    off = CoreCoordinator(backend="spmd", spmd_pack="off")
    unp = off.run_matrix(specs)
    assert unp.stats.packed_ladders == 0
    assert unp.stats.host_sync_dispatches == \
        1 + unp.stats.noisy_remeasures
    assert [r.key for r in res.runs] == [r.key for r in unp.runs]
    for rp, ru in zip(res.runs, unp.runs):
        assert ru.execution["packed"] is False
        assert ru.execution["fenced"]
        assert rp.execution["executed_rungs"] \\
            == ru.execution["executed_rungs"]
        for sp, su in zip(rp.scenarios, ru.scenarios):
            assert sp.source == su.source == "executed"
            assert sp.main.strategy == su.main.strategy
            assert sp.main.bytes_moved == su.main.bytes_moved
            assert sp.main.elapsed_ns > 0 and su.main.elapsed_ns > 0
            ratio = sp.main.elapsed_ns / su.main.elapsed_ns
            assert 1 / 50 < ratio < 50, (rp.key, sp.n_stressors,
                                         ratio)
    print("packed OK:", n_subsets, "subsets on", n_dev, "devices")
    """)


def test_lru_eviction_deletes_operand_buffers():
    """Satellite regression: the spmd program cache cap is a MEMORY
    bound — evicting an entry must delete its placed operand device
    buffers eagerly, not just drop the dict reference (a capped cache
    must not pin device memory for programs it no longer holds)."""
    import jax
    import jax.numpy as jnp

    from repro.core.coordinator import CoreCoordinator

    c = CoreCoordinator(backend="simulate", spmd_cache_cap=1)

    def entry():
        xf = jax.device_put(jnp.ones((4, 4), jnp.float32))
        xi = jax.device_put(jnp.zeros((4, 4), jnp.int32))
        return [None, None, True, xf, xi, False]

    e1, e2 = entry(), entry()
    c._program_cache_put(("k1",), e1)
    c._program_cache_put(("k2",), e2)
    assert list(c._spmd_programs) == [("k2",)]
    # the evicted entry's device buffers are gone NOW, not at GC time
    assert e1[3].is_deleted() and e1[4].is_deleted()
    # the resident entry's buffers are untouched
    assert not e2[3].is_deleted() and not e2[4].is_deleted()


def test_program_cache_reuse_across_run_matrix():
    """The spmd program cache lives on the COORDINATOR: a second
    run_matrix call reuses every compiled program (and its placed,
    donated operand buffers) instead of re-tracing, and the
    DispatchStats counter proves it."""
    run_forced("""
    import jax
    from repro.core.coordinator import CoreCoordinator
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)

    BUF = 64 << 10
    spec = ScenarioSpec(
        "cache", ObserverSpec("r", "hbm", (BUF,)),
        (StressorSpec("w", "hbm", BUF),), iters=3, max_stressors=2)

    depth = max(1, min(3, len(jax.devices())))
    # hermetic: a quality-gate re-measure re-dispatches a cached
    # program (one more cache hit), and under a loaded CPU the gate
    # fires at random, so it is pinned off with fault injection
    for mode, n_programs in (("batched", 1), ("ladder", 1),
                             ("rung", depth)):
        c = CoreCoordinator(backend="spmd", spmd_dispatch=mode,
                            faults=False, quality="off")
        first = c.run_matrix([spec])
        assert first.stats.program_cache_hits == 0
        assert first.stats.programs_built == n_programs
        again = c.run_matrix([spec])
        # every program the second run needs is already cached: ONE
        # stacked/whole-ladder program, or one per rung on the legacy
        # path
        assert again.stats.program_cache_hits == n_programs
        assert again.stats.programs_built == 0
        for run in again.runs:
            assert run.execution["fenced"]
            for s in run.scenarios:
                assert s.main.elapsed_ns > 0

    # spmd_cache_cap=1 under eviction churn (the per-rung path needs
    # `depth` programs): every eviction must delete the evicted
    # operand buffers, execution must stay correct, and the single
    # resident entry must keep live buffers
    c1 = CoreCoordinator(backend="spmd", spmd_dispatch="rung",
                         spmd_cache_cap=1, faults=False, quality="off")
    for _ in range(2):
        r1 = c1.run_matrix([spec])
        assert len(c1._spmd_programs) == 1
        live = next(iter(c1._spmd_programs.values()))
        assert not live[3].is_deleted() and not live[4].is_deleted()
        for run in r1.runs:
            assert run.execution["fenced"]
            for s in run.scenarios:
                assert s.main.elapsed_ns > 0
    print("cache reuse OK")
    """)


def test_spmd_ladder_refuses_pinned_single_device():
    """Regression: with XLA_FLAGS already pinning the host device count
    below 2, benchmarks.spmd_ladder used to re-exec itself with the
    same environment — unbounded process recursion.  It must fail fast
    with an actionable message instead."""
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    root = os.path.dirname(SRC)
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.spmd_ladder"],
        capture_output=True, text=True, timeout=240, env=env, cwd=root)
    assert r.returncode != 0
    assert "already pins" in r.stderr


def test_jnp_fallback_branches_still_fenced():
    """The compat fallback (pure-jnp loops) keeps the original fence
    guarantee — the checker extension must not regress it."""
    from repro.core.coordinator import (_spmd_branch_fn,
                                        build_rung_program,
                                        measured_region_is_fenced)

    fns = [_spmd_branch_fn("r", None, ROWS, 2, activity="jnp"),
           _spmd_branch_fn("w", None, ROWS, 2, activity="jnp")]
    _mesh, f = build_rung_program(1, fns, [0])
    assert measured_region_is_fenced(f, *_operands(1))
