"""ScenarioSpec DSL + CurveDB v2 + batched matrix runner.

Covers the ISSUE-1 acceptance criteria: spec round-trip serialization,
schema versioning (v1 curve files still load), the shaped smoke sweep on
the ``simulate`` backend, real-kernel execution on ``interpret``, and
the batched runner's dispatch advantage on a >= 64-scenario sweep.
"""
import json

import pytest

from repro.core.characterize import (CurveDB, CurvePoint, characterize,
                                     characterize_matrix)
from repro.core.coordinator import CoreCoordinator, ValidationError
from repro.core.placement import ContentionSpec, MemObject, PlacementAdvisor
from repro.core.scenarios import (DEFAULT_STRESS_SHAPES, ObserverSpec,
                                  ScenarioSpec, StressorSpec, TrafficShape,
                                  load_matrix, save_matrix, scenario_matrix)

BUF = 1 << 20


def _spec(name="s", ostrat="r", sstrat="w", shape=None,
          buffers=(BUF,)) -> ScenarioSpec:
    return ScenarioSpec(
        name=name,
        observer=ObserverSpec(ostrat, "hbm", tuple(buffers)),
        stressors=(StressorSpec(sstrat, "hbm", BUF,
                                shape or TrafficShape.steady()),),
        iters=5)


# ---------------------------------------------------------------------------
# TrafficShape
# ---------------------------------------------------------------------------


def test_traffic_shape_constructors_and_tags():
    assert TrafficShape.steady().tag() == ""
    # exactly-representable parameters keep the short 2-decimal form
    assert TrafficShape.mixed(1, 1).read_fraction == 0.5
    assert TrafficShape.mixed(1, 1).tag() == "rf0.50"
    assert TrafficShape.burst(0.5).tag() == "dc0.50"
    assert TrafficShape.strided(8).tag() == "st8"
    # non-terminating ratios widen until the spelling round-trips
    assert TrafficShape.mixed(2, 1).tag() == f"rf{2 / 3!r}"
    assert float(TrafficShape.mixed(2, 1).tag()[2:]) == 2 / 3


def test_burst_len_is_part_of_the_tag():
    """Regression: burst shapes differing only in burst_len aliased one
    key, so a burst-length sweep tripped the collision guard."""
    assert TrafficShape.burst(0.5).tag() == "dc0.50"          # default len
    assert TrafficShape.burst(0.5, 128).tag() == "dc0.50x128"
    c = CoreCoordinator(backend="simulate")
    db = characterize_matrix(c, [
        _spec("b64", shape=TrafficShape.burst(0.5, 64)),
        _spec("b128", shape=TrafficShape.burst(0.5, 128)),
    ])
    assert len(db.curves) == 2


def test_key_for_matches_for_equal_observers():
    """Regression: sibling detection compared by identity, so a
    reconstructed (equal, non-identical) observer got a spurious buf=
    suffix and missed the stored curve key."""
    spec = ScenarioSpec(
        "multi",
        (ObserverSpec("r", "hbm", (BUF,)),
         ObserverSpec("l", "host", (BUF,))),
        (StressorSpec("w", "hbm", BUF),), iters=5)
    stored = spec.key_for(spec.observers[1], BUF)
    rebuilt = spec.key_for(ObserverSpec("l", "host", (BUF,)), BUF)
    assert stored == rebuilt == "host:l|hbm:w"


def test_tag_precision_cannot_alias_distinct_ratios():
    """Regression: rf/dc spellings used to round to 2 decimals, so
    mixed(2,1) and mixed(67,33) aliased one CurveDB key and tripped the
    characterize_matrix collision guard."""
    a, b = TrafficShape.mixed(2, 1), TrafficShape.mixed(67, 33)
    assert a.read_fraction != b.read_fraction
    assert a.tag() != b.tag()
    assert TrafficShape.burst(2 / 3).tag() != TrafficShape.burst(0.67).tag()
    # ...and through the full matrix path: distinct keys, no collision
    c = CoreCoordinator(backend="simulate")
    db = characterize_matrix(c, [
        _spec("two-one", shape=TrafficShape.mixed(2, 1)),
        _spec("sixtyseven", shape=TrafficShape.mixed(67, 33)),
    ])
    assert len(db.curves) == 2


def test_traffic_shape_validation():
    with pytest.raises(ValueError):
        TrafficShape(kind="nope")
    with pytest.raises(ValueError):
        TrafficShape(kind="burst", duty_cycle=0.0)
    with pytest.raises(ValueError):
        TrafficShape(kind="mixed", read_fraction=1.5)
    with pytest.raises(ValueError):
        TrafficShape.strided(0)
    with pytest.raises(ValueError):
        TrafficShape.mixed(0, 0)


# ---------------------------------------------------------------------------
# ScenarioSpec round-trip
# ---------------------------------------------------------------------------


def test_spec_dict_roundtrip():
    spec = ScenarioSpec(
        name="shaped",
        observer=ObserverSpec("r", "hbm", (BUF, 2 * BUF)),
        stressors=(
            StressorSpec("w", "host", BUF, TrafficShape.burst(0.25)),
            StressorSpec("r", "hbm", BUF, TrafficShape.mixed(1, 2)),
            StressorSpec("m", "hbm", BUF, TrafficShape.strided(16)),
        ),
        iters=42, max_stressors=3)
    d = spec.to_dict()
    back = ScenarioSpec.from_dict(json.loads(json.dumps(d)))
    assert back == spec


def test_matrix_file_roundtrip(tmp_path):
    specs = scenario_matrix(pools=["hbm", "host"], buffer_bytes=BUF,
                            obs_strategies=("r", "l"),
                            stress_shapes=DEFAULT_STRESS_SHAPES, iters=5)
    p = str(tmp_path / "matrix.json")
    save_matrix(specs, p)
    assert load_matrix(p) == specs


def test_v1_compatible_keys():
    """Steady single-stressor scenarios must key exactly like the seed."""
    assert _spec().key() == "hbm:r|hbm:w"
    assert CurveDB.key("hbm", "r", "hbm", "w").to_string() == "hbm:r|hbm:w"
    shaped = _spec(shape=TrafficShape.burst(0.5))
    assert shaped.key() == "hbm:r|hbm:w@dc0.50"
    assert CurveDB.key("hbm", "r", "hbm", "w",
                       "dc0.50").to_string() == shaped.key()


def test_spec_validation():
    c = CoreCoordinator(backend="simulate")
    c.validate_spec(_spec())
    with pytest.raises(ValidationError):
        c.validate_spec(_spec(ostrat="z"))
    with pytest.raises(ValidationError):
        bad = ScenarioSpec("b", ObserverSpec("r", "hbm", (BUF,)),
                           iters=0)
        c.validate_spec(bad)


# ---------------------------------------------------------------------------
# CurveDB schema versioning
# ---------------------------------------------------------------------------


def test_curvedb_v3_roundtrip_with_provenance(tmp_path):
    c = CoreCoordinator(backend="simulate")
    specs = [_spec(), _spec("shaped", shape=TrafficShape.mixed(1, 1))]
    db = characterize_matrix(c, specs)
    assert db.schema == 3
    assert set(db.provenance) == set(db.curves)
    p = str(tmp_path / "v3.json")
    db.save(p)
    db2 = CurveDB.load(p)
    assert db2.schema == 3
    assert db2.curves.keys() == db.curves.keys()
    k = "hbm:r|hbm:w@rf0.50"
    assert ScenarioSpec.from_dict(db2.provenance[k]).stressors[0].shape \
        == TrafficShape.mixed(1, 1)
    assert db2.meta["model_evals"] > 0


def test_curvedb_v1_files_still_load(tmp_path):
    """A seed-format (schema-less) curve file must load and serve
    lookups, including the shaped-tag fallback to steady curves."""
    v1 = {"platform": "tpu-v5e",
          "curves": {"hbm:r|hbm:w": [
              {"n_stressors": 0, "bandwidth_gbps": 800.0,
               "latency_ns": 100.0},
              {"n_stressors": 1, "bandwidth_gbps": 400.0,
               "latency_ns": 200.0}],
              "hbm:l|hbm:w": [
              {"n_stressors": 0, "bandwidth_gbps": 1.0,
               "latency_ns": 390.0},
              {"n_stressors": 1, "bandwidth_gbps": 0.5,
               "latency_ns": 800.0}]}}
    p = str(tmp_path / "v1.json")
    with open(p, "w") as f:
        json.dump(v1, f)
    db = CurveDB.load(p)
    assert db.schema == 1
    assert db.provenance == {}
    assert db.effective_bw("hbm", 1) == 400.0
    # shaped lookup falls back to the steady curve on a v1 db
    assert db.effective_bw("hbm", 1, shape_tag="dc0.50") == 400.0
    assert db.effective_lat("hbm", 1, shape_tag="rf0.33") == 800.0


# ---------------------------------------------------------------------------
# Shaped smoke sweep (simulate backend physics)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def shaped_db():
    c = CoreCoordinator(backend="simulate")
    db = characterize(c, pools=["hbm", "host"],
                      obs_strategies=("r", "l"),
                      stress_strategies=("r", "w"),
                      stress_shapes=DEFAULT_STRESS_SHAPES, iters=5)
    return db, c


RF21 = TrafficShape.mixed(2, 1).tag()
RF11 = TrafficShape.mixed(1, 1).tag()
RF12 = TrafficShape.mixed(1, 2).tag()


def test_shaped_sweep_produces_new_curves(shaped_db):
    db, _ = shaped_db
    tags = {k.tag for k in db.surfaces if k.tag}
    assert {RF21, RF11, RF12, "dc0.50", "st8"} <= tags
    # copy stressor curves exist under the steady key format
    assert "hbm:r|hbm:c" in db.curves


def test_mixed_ratio_interpolates_read_write(shaped_db):
    """More write share in the mix -> more WAWB traffic -> lower
    observed bandwidth, bracketed by the pure-read and pure-write
    steady curves."""
    db, _ = shaped_db
    worst = -1
    bw_r = db.curves["hbm:r|hbm:r"][worst].bandwidth_gbps
    bw_21 = db.curves[f"hbm:r|hbm:r@{RF21}"][worst].bandwidth_gbps
    bw_11 = db.curves[f"hbm:r|hbm:r@{RF11}"][worst].bandwidth_gbps
    bw_12 = db.curves[f"hbm:r|hbm:r@{RF12}"][worst].bandwidth_gbps
    assert bw_r >= bw_21 >= bw_11 >= bw_12


def test_burst_stress_degrades_less_than_steady(shaped_db):
    db, _ = shaped_db
    steady = db.curves["hbm:r|hbm:w"]
    burst = db.curves["hbm:r|hbm:w@dc0.50"]
    assert burst[-1].bandwidth_gbps > steady[-1].bandwidth_gbps
    # both still monotonically degrade with stressor count
    bws = [p.bandwidth_gbps for p in burst]
    assert all(a >= b - 1e-9 for a, b in zip(bws, bws[1:]))


def test_strided_chase_modeled_distinctly():
    """The strided shape must reach the queueing model: a strided chase
    observer sees higher latency than a unit-stride chase (lost
    row-buffer/prefetch locality), so '@st8' curves are not duplicates
    of the steady chase curves."""
    from repro.core import simulate as sim
    from repro.core.devicetree import TPU_V5E
    node = TPU_V5E.node("hbm")
    plain = sim.simulate_scenario(
        TPU_V5E, [sim.ActivityClass("obs", node, "m", 1)])
    strided = sim.simulate_scenario(
        TPU_V5E, [sim.ActivityClass("obs", node, "m", 1, stride=8)])
    assert strided["obs"].lat_ns > plain["obs"].lat_ns
    # and through the full matrix path: a strided observer's modeled
    # latency curve sits above the unit-stride one
    c = CoreCoordinator(backend="simulate")
    runs = c.run_matrix([
        ScenarioSpec("plain", ObserverSpec("m", "hbm", (BUF,)),
                     (StressorSpec("w", "hbm", BUF),), iters=5),
        ScenarioSpec("strided", ObserverSpec(
            "m", "hbm", (BUF,), TrafficShape.strided(8)),
            (StressorSpec("w", "hbm", BUF),), iters=5),
    ]).runs
    lat_plain = [p[1] for p in runs[0].latency_curve()]
    lat_strided = [p[1] for p in runs[1].latency_curve()]
    assert all(s > p for s, p in zip(lat_strided, lat_plain))


def test_batched_chase_latency_matches_naive():
    """The batched chase pass splits group wall time /g — valid only if
    the g chains execute back-to-back within the vmapped pass.  Guard
    that assumption by comparing against the naive single-chase path."""
    from repro.core.pools import PoolManager
    from repro.core.workloads import make_workload, measure_group
    mgr = PoolManager()
    wl = make_workload("l", mgr.pool("hbm"), 64 << 10)
    try:
        naive = wl.run(10)
    finally:
        wl.release()
    batched, _ = measure_group("l", mgr.pool("hbm"), 64 << 10, 6, 10)
    # loose bound: wall-clock noise under full-suite load is real, but
    # a broken /g split (g=6 here) would still be ~6x off
    assert batched[0].latency_ns == pytest.approx(naive.latency_ns,
                                                  rel=1.0)


@pytest.mark.parametrize("pool_name", ["hbm", "host"])
@pytest.mark.parametrize("g", [1, 3])
def test_chase_operand_is_the_host_layout_in_the_pool_memory(pool_name, g):
    """The stacked chase operand built on the device holds what the
    host-side chain buffers hold, in the pool's own memory kind."""
    import jax
    import numpy as np

    from repro.core.devicetree import TPU_V5E
    from repro.core.pools import PoolManager
    from repro.core.workloads import chase_operand
    from repro.kernels.chase import chain_buffer
    pool = PoolManager(TPU_V5E).pool(pool_name)
    seeds = [5, 0, 11][:g]
    out = jax.block_until_ready(chase_operand(pool, 512, seeds))
    np.testing.assert_array_equal(
        np.asarray(out), np.stack([chain_buffer(512, s) for s in seeds]))
    assert out.sharding.memory_kind == (
        pool.effective_memory_kind()
        or jax.devices()[0].default_memory().kind)


@pytest.mark.parametrize("strategy", ["l", "m"])
def test_batched_chase_ends_where_the_reference_walk_ends(strategy):
    """The batched chase pass, fed the bulk-drawn chains, returns each
    member's reference final index."""
    from bench.reference import probes
    from repro.core.pools import PoolManager
    from repro.core.workloads import measure_group
    nbytes, seeds = 64 << 10, [3, 2**31 - 1]
    res, _ = measure_group(strategy, PoolManager().pool("hbm"), nbytes,
                           len(seeds), 10, seeds=seeds)
    vmem = probes.uses_vmem_kernel(nbytes, "hbm")
    assert [r.checksum for r in res] == [
        probes.checksum(strategy, nbytes, vmem, s) for s in seeds]
    assert [r.chain_seed for r in res] == seeds


def test_copy_stress_between_read_and_write(shaped_db):
    """Copy traffic (1.5 Tx/line) must hurt more than pure reads
    (1 Tx/line) and no more than allocating writes (2 Tx/line)."""
    db, _ = shaped_db
    bw_r = db.curves["hbm:r|hbm:r"][-1].bandwidth_gbps
    bw_c = db.curves["hbm:r|hbm:c"][-1].bandwidth_gbps
    bw_w = db.curves["hbm:r|hbm:w"][-1].bandwidth_gbps
    assert bw_w <= bw_c <= bw_r


def test_placement_consumes_shaped_curves(shaped_db):
    db, c = shaped_db
    adv = PlacementAdvisor(db, c.platform, pools=["hbm", "host"])
    obj = MemObject("heap", BUF, bytes_per_step=float(BUF))
    steady = adv.predict_ns(obj, "hbm", ContentionSpec(7, "hbm", "w"))
    burst = adv.predict_ns(
        obj, "hbm",
        ContentionSpec.shaped(7, "hbm", "w", TrafficShape.burst(0.5)))
    assert burst < steady          # duty-cycled stress hurts less


# ---------------------------------------------------------------------------
# Batched matrix runner on real (interpret-mode) kernels
# ---------------------------------------------------------------------------


def test_interpret_matrix_runs_real_kernels():
    c = CoreCoordinator(backend="interpret")
    specs = [
        ScenarioSpec("copy", ObserverSpec("c", "hbm", (64 << 10,)),
                     (StressorSpec("w", "hbm", 64 << 10),),
                     iters=2, max_stressors=1),
        ScenarioSpec("mixed", ObserverSpec(
            "r", "hbm", (64 << 10,), TrafficShape.mixed(1, 1)),
            (StressorSpec("w", "hbm", 64 << 10),),
            iters=2, max_stressors=1),
        ScenarioSpec("strided", ObserverSpec(
            "m", "hbm", (64 << 10,), TrafficShape.strided(8)),
            (StressorSpec("w", "hbm", 64 << 10),),
            iters=2, max_stressors=1),
    ]
    res = c.run_matrix(specs)
    for run in res.runs:
        assert run.scenarios[0].main.bytes_moved > 0
        assert run.scenarios[0].main.elapsed_ns > 0
    # strided chase reports per-transaction latency
    assert res.runs[2].scenarios[0].main.latency_ns > 0
    for p in c.pools.pools():
        assert p.allocated == 0


def test_batched_runner_fewer_dispatches_64():
    """>= 64-scenario sweep: the batched runner must dispatch
    demonstrably fewer measured passes than the per-point loop.  Pools
    that share one effective memory kind share its signature groups;
    a pool whose kind no probe kernel can take (host memory, listed as
    ``pinned_host`` here as on a chip) is refused, not measured."""
    c = CoreCoordinator(backend="interpret")
    pools = [p for p in ("hbm", "host", "vmem")
             if c.refusal("r", p, 64 << 10) is None]
    assert (("host" in pools) ==
            (c.pools.pool("host").effective_memory_kind() != "pinned_host"))
    kinds = {c.pools.pool(p).effective_memory_kind() for p in pools}
    specs = scenario_matrix(pools=pools,
                            buffer_bytes=64 << 10,
                            obs_strategies=("r", "w"),
                            stress_shapes=DEFAULT_STRESS_SHAPES[:8],
                            iters=2, max_stressors=1)
    assert len(specs) >= 64
    batched = c.run_matrix(specs, batched=True)
    naive = c.run_matrix(specs, batched=False)
    assert naive.stats.measure_dispatches == len(specs)
    assert batched.stats.measure_dispatches < naive.stats.measure_dispatches
    assert batched.stats.measure_dispatches <= 8 * len(kinds)
    # both modes measured every scenario
    assert batched.stats.n_scenarios == naive.stats.n_scenarios == len(specs)
    for run in batched.runs:
        assert run.scenarios[0].main.elapsed_ns > 0


def test_ladder_signature_grouping_never_merges_distinct_roles():
    """Sweep-level grouping soundness (ISSUE-5 satellite), on a concrete
    grid: any two (spec, observer, buffer) triples landing in one
    `_spmd_group_key` group must expand to IDENTICAL per-rung role
    tables at every mesh size, identical iteration budgets, and
    identical effective memory kinds — and every role-relevant field
    (strategy, shape, buffer, iters) must split groups.  Pools that
    differ only in name but share one effective memory kind are the
    ONLY legal merge."""
    coord = CoreCoordinator(backend="simulate")
    specs = []
    for strat in ("r", "w"):
        for pool in ("hbm", "host"):
            for iters in (5, 9):
                for shape in (TrafficShape.steady(),
                              TrafficShape.burst(0.5)):
                    for buf in (64 << 10, 128 << 10):
                        specs.append(ScenarioSpec(
                            f"g.{strat}.{pool}.{iters}."
                            f"{shape.tag() or 'steady'}.{buf}",
                            ObserverSpec(strat, pool, (buf,), shape),
                            (StressorSpec("w", "hbm", 64 << 10),),
                            iters=iters, max_stressors=2))
    triples = [(s, o, b) for s in specs for o in s.observers
               for b in o.buffers]
    groups = {}
    for t in triples:
        groups.setdefault(coord._spmd_group_key(*t), []).append(t)

    kinds_equal = (coord.pools.pool("hbm").effective_memory_kind()
                   == coord.pools.pool("host").effective_memory_kind())
    # 2 strategies x 2 iters x 2 shapes x 2 buffers always split; the
    # pool axis merges exactly when the effective kinds agree
    assert len(groups) == (16 if kinds_equal else 32)
    for members in groups.values():
        ref = members[0]
        for m in members[1:]:
            assert ref[0].iters == m[0].iters
            for n_eng in (2, 4):
                for k in range(min(3, n_eng)):
                    roles_ref, pools_ref = coord._rung_roles(
                        ref[0], ref[1], ref[2], k, n_eng)
                    roles_m, pools_m = coord._rung_roles(
                        m[0], m[1], m[2], k, n_eng)
                    assert roles_ref == roles_m     # identical tables
                    assert [coord.pools.pool(p).effective_memory_kind()
                            for p in pools_ref] \
                        == [coord.pools.pool(p).effective_memory_kind()
                            for p in pools_m]


def test_ladder_signature_covers_siblings_and_stressors():
    """The signature must split on everything outside the observer too:
    stressor ensembles, sibling observers, coupling, max_stressors."""
    BUF2 = 64 << 10
    obs = ObserverSpec("r", "hbm", (BUF2,))
    base = ScenarioSpec("base", obs, (StressorSpec("w", "hbm", BUF2),),
                        iters=5, max_stressors=2)
    sig = base.ladder_signature(obs, BUF2)
    # different stressor strategy / shape / buffer
    for s in (StressorSpec("y", "hbm", BUF2),
              StressorSpec("w", "hbm", BUF2, TrafficShape.burst(0.5)),
              StressorSpec("w", "hbm", 2 * BUF2)):
        other = ScenarioSpec("o", obs, (s,), iters=5, max_stressors=2)
        assert other.ladder_signature(obs, BUF2) != sig
    # a coupled sibling changes the signature; uncoupling removes it
    sib = ObserverSpec("l", "hbm", (BUF2,))
    multi = ScenarioSpec("m", (obs, sib),
                         (StressorSpec("w", "hbm", BUF2),),
                         iters=5, max_stressors=2)
    assert multi.ladder_signature(obs, BUF2) != sig
    unc = ScenarioSpec("u", (obs, sib), (StressorSpec("w", "hbm", BUF2),),
                       iters=5, max_stressors=2, coupled=False)
    assert unc.ladder_signature(obs, BUF2) == sig
    # ladder depth is part of the identity
    deeper = ScenarioSpec("d", obs, (StressorSpec("w", "hbm", BUF2),),
                          iters=5, max_stressors=3)
    assert deeper.ladder_signature(obs, BUF2) != sig
    # ...and pool names are deliberately NOT (the kind refinement in
    # _spmd_group_key handles placement)
    hosted = ScenarioSpec("h", ObserverSpec("r", "host", (BUF2,)),
                          (StressorSpec("w", "hbm", BUF2),),
                          iters=5, max_stressors=2)
    assert hosted.ladder_signature(hosted.observer, BUF2) == sig


def test_multi_observer_spec_roundtrip_and_keys():
    """A tuple of observers normalizes into observer + co_observers,
    round-trips through dicts, and keys one curve per observer."""
    spec = ScenarioSpec(
        "multi",
        (ObserverSpec("r", "hbm", (BUF,)),
         ObserverSpec("l", "host", (BUF,))),
        (StressorSpec("w", "hbm", BUF),), iters=5)
    assert spec.observer == ObserverSpec("r", "hbm", (BUF,))
    assert spec.co_observers == (ObserverSpec("l", "host", (BUF,)),)
    assert len(spec.observers) == 2
    # primary key stays v1-compatible; co-observer keys its own curve
    assert spec.key() == "hbm:r|hbm:w"
    assert spec.key_for(spec.observers[1]) == "host:l|hbm:w"
    back = ScenarioSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert back == spec
    with pytest.raises(ValueError):
        ScenarioSpec("empty", ())


def test_multi_observer_single_vmapped_pass():
    """Two observers measuring two pools whose placement lands in the
    same physical memory (equal effective memory kinds) collapse into
    ONE vmapped measured pass, each yielding its own correctly-labeled
    curve — here HBM split into two pools, as a partitioned deployment
    exports it.  A pool in another memory that no probe kernel can take
    (host memory) is refused with its reason."""
    import dataclasses

    from repro.core.devicetree import TPU_V5E
    from repro.core.pools import PoolManager
    hbm = TPU_V5E.memories["hbm"]
    plat = dataclasses.replace(TPU_V5E, memories={
        **TPU_V5E.memories,
        "hbm-b": dataclasses.replace(hbm, name="hbm-b",
                                     size_bytes=hbm.size_bytes // 2)})
    c = CoreCoordinator(PoolManager(plat), plat, backend="interpret")
    assert c.pools.pool("hbm-b").effective_memory_kind() == \
        c.pools.pool("hbm").effective_memory_kind()
    spec = ScenarioSpec(
        "multi",
        (ObserverSpec("r", "hbm", (64 << 10,)),
         ObserverSpec("r", "hbm-b", (64 << 10,))),
        (StressorSpec("w", "hbm", 64 << 10),),
        iters=2, max_stressors=1)
    res = c.run_matrix([spec])
    assert res.stats.n_ladders == 2
    assert res.stats.measure_dispatches == 1     # one pass, two pools
    keys = {run.key for run in res.runs}
    assert keys == {"hbm:r|hbm:w", "hbm-b:r|hbm:w"}
    for run in res.runs:
        assert run.scenarios[0].main.pool == run.observer.pool
        assert run.scenarios[0].main.elapsed_ns > 0
    # ...and per-observer curves land in CurveDB
    db = characterize_matrix(c, [spec])
    assert set(db.curves) == keys
    if c.pools.pool("host").effective_memory_kind() == "pinned_host":
        host = ScenarioSpec(
            "host", (ObserverSpec("r", "hbm", (64 << 10,)),
                     ObserverSpec("r", "host", (64 << 10,))),
            (StressorSpec("w", "hbm", 64 << 10),),
            iters=2, max_stressors=1)
        with pytest.raises(ValidationError, match="pinned_host"):
            c.run_matrix([host])


def test_multi_observer_same_pool_keys_do_not_alias():
    """Regression: two observers differing only in buffer size used to
    key the same curve ('hbm:r|hbm:w'), and the collision guard (which
    compared spec dicts — identical here) silently overwrote the first
    observer's curve with the second's."""
    spec = ScenarioSpec(
        "twin",
        (ObserverSpec("r", "hbm", (BUF,)),
         ObserverSpec("r", "hbm", (2 * BUF,))),
        (StressorSpec("w", "hbm", BUF),), iters=5, max_stressors=1)
    keys = {spec.key_for(o, o.buffers[0]) for o in spec.observers}
    assert keys == {f"hbm:r|hbm:w|buf={BUF}",
                    f"hbm:r|hbm:w|buf={2 * BUF}"}
    c = CoreCoordinator(backend="simulate")
    db = characterize_matrix(c, [spec])
    assert set(db.curves) == keys          # both curves survive
    for key in keys:
        assert db.provenance[key]["curve"]["buffer_bytes"] in (BUF,
                                                               2 * BUF)


def test_batched_groups_split_by_iters():
    """Regression: members of one signature group used to be measured
    (and stamped) at the group-max iteration budget.  Groups now split
    by iters, so every result carries its own spec's budget."""
    c = CoreCoordinator(backend="interpret")
    specs = [
        ScenarioSpec("short", ObserverSpec("r", "hbm", (64 << 10,)),
                     (StressorSpec("w", "hbm", 64 << 10),),
                     iters=2, max_stressors=1),
        ScenarioSpec("long", ObserverSpec("r", "hbm", (64 << 10,)),
                     (StressorSpec("y", "hbm", 64 << 10),),
                     iters=7, max_stressors=1),
    ]
    res = c.run_matrix(specs)
    stamps = {run.spec.name: run.scenarios[0].main.iters
              for run in res.runs}
    assert stamps == {"short": 2, "long": 7}


def test_dispatch_stats_count_scenarios_not_pairs():
    """Regression: n_scenarios used to count (spec, buffer) pairs; the
    ladder expansion now lives in n_ladders."""
    c = CoreCoordinator(backend="simulate")
    spec = ScenarioSpec(
        "ladder", ObserverSpec("r", "hbm", (BUF, 2 * BUF)),
        (StressorSpec("w", "hbm", BUF),), iters=5, max_stressors=1)
    multi = ScenarioSpec(
        "multi",
        (ObserverSpec("r", "hbm", (BUF,)),
         ObserverSpec("l", "host", (BUF,))),
        (StressorSpec("w", "hbm", BUF),), iters=5, max_stressors=1)
    res = c.run_matrix([spec, multi])
    assert res.stats.n_scenarios == 2          # two ScenarioSpecs...
    assert res.stats.n_ladders == 4            # ...expanding to 4 curves


def test_buffer_ladder_keys_are_distinct():
    c = CoreCoordinator(backend="simulate")
    spec = ScenarioSpec(
        "ladder", ObserverSpec("r", "hbm", (BUF, 2 * BUF)),
        (StressorSpec("w", "hbm", BUF),), iters=5, max_stressors=1)
    res = c.run_matrix([spec])
    keys = [r.key for r in res.runs]
    assert len(keys) == 2 and len(set(keys)) == 2
    assert all("buf=" in k for k in keys)
