"""MEMSCOPE core behaviour: pools, device tree, workloads, coordinator,
simulator physics, characterization, MLP, placement, user interface.

These tests assert the *paper's* qualitative findings hold in our
reproduction (Fig. 4-9 trends, Tables II/III MLP, Fig. 6/7 shared-queue
throttling, Fig. 13 write-stream collapse, Fig. 14 counter-intuitive
placement).
"""
import json

import numpy as np
import pytest

from repro.core import simulate as sim
from repro.core.characterize import CurveDB, characterize, mlp_table
from repro.core.coordinator import (ActivitySpec, CoreCoordinator,
                                    ExperimentConfig, ValidationError)
from repro.core.devicetree import (TPU_V5E, ZCU102, Platform,
                                   detect_platform, zcu102_partitioned)
from repro.core.interface import (MemscopeInterface, parse_experiment,
                                  parse_size)
from repro.core.placement import (ContentionSpec, MemObject,
                                  PlacementAdvisor, kv_cache_object)
from repro.core.pools import PoolError, PoolManager


# ---------------------------------------------------------------------------
# Device tree + pools
# ---------------------------------------------------------------------------


def test_detect_platform():
    p = detect_platform()
    assert p.name == "tpu-v5e"
    assert set(p.memories) == {"hbm", "vmem", "host", "peer"}
    assert detect_platform("zcu102").name == "zcu102"
    with pytest.raises(KeyError):
        detect_platform("nope")


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform = platform
        self.device_kind = device_kind


@pytest.mark.parametrize("kind,tree", [("TPU v5 lite", "tpu-v5e"),
                                       ("TPU v5e", "tpu-v5e"),
                                       ("TPU v9 imaginary", None)])
def test_detect_platform_keys_a_tpu_by_device_kind(monkeypatch, kind, tree):
    """On a TPU the tree comes from ``device_kind``; a kind the repo has
    no tree for is an error, never a default."""
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("tpu", kind)])
    if tree is None:
        with pytest.raises(KeyError, match="no device tree"):
            detect_platform()
    else:
        assert detect_platform().name == tree


@pytest.mark.parametrize("backend,pool,strategy,nbytes,refused", [
    ("simulate", "host", "r", 1 << 20, False),    # the model runs anything
    ("interpret", "hbm", "r", 1 << 20, False),
    ("interpret", "host", "i", 0, False),          # idle touches no memory
    ("interpret", "peer", "r", 1 << 20, True),     # no kernel reaches a peer
    ("interpret", "vmem", "r", 32 << 20, False),
    ("interpret", "vmem", "r", 33 << 20, True),    # beyond what compiles
    ("interpret", "vmem", "c", 1 << 20, True),     # no VMEM-resident copy
    ("spmd", "vmem", "r", 1 << 20, True),          # rungs stream operands
])
def test_refusal_of_pairs_a_backend_cannot_run(backend, pool, strategy,
                                               nbytes, refused):
    c = CoreCoordinator(backend=backend)
    assert (c.refusal(strategy, pool, nbytes) is not None) is refused


def test_host_pool_probes_are_refused_where_host_memory_is_real():
    """Where the device lists pinned_host, a host-pool probe would hand
    a kernel a host-memory operand, which no probe kernel can take: the
    coordinator refuses it with the reason instead of measuring HBM
    under the host's name, and characterize records the refusal."""
    c = CoreCoordinator(backend="interpret")
    if c.pools.pool("host").effective_memory_kind() != "pinned_host":
        pytest.skip("this device lists no pinned_host memory")
    assert "pinned_host" in c.refusal("r", "host", 1 << 20)
    with pytest.raises(ValidationError, match="cannot run on pool"):
        c.run(ExperimentConfig(ActivitySpec("r", "host", 64 << 10),
                               ActivitySpec("i", "hbm", 0), iters=1,
                               scenarios=1))
    db = characterize(c, pools=["hbm", "host"], buffer_bytes=64 << 10,
                      obs_strategies=("r",), stress_strategies=("w",),
                      iters=1)
    assert set(db.meta["refused"]) == {"host:r"}
    assert {k.obs_pool for k in db.surfaces} == {"hbm"}


def test_platform_json_roundtrip():
    p2 = Platform.from_json(TPU_V5E.to_json())
    assert p2.memories["hbm"].peak_bw_gbps == 819.0
    assert p2.n_engines == TPU_V5E.n_engines


def test_pool_alloc_free_capacity():
    mgr = PoolManager()
    pool = mgr.pool("hbm")
    a = pool.alloc((1024, 128), tag="t")
    assert pool.allocated == 1024 * 128 * 4
    pool.free(a)
    assert pool.allocated == 0
    with pytest.raises(PoolError):
        pool.free(a)                         # double free
    with pytest.raises(PoolError):
        mgr.pool("vmem").alloc((1 << 20, 128))   # exceeds 128 MiB
    with pytest.raises(PoolError):
        mgr.pool("nope")


def test_pool_ids_match_modules():
    mgr = PoolManager()
    for p in mgr.pools():
        assert mgr.pool(p.id) is p           # 1-to-1 id <-> module
    assert "pool" in mgr.status()


def test_upool_place():
    import jax.numpy as jnp
    mgr = PoolManager()
    up = mgr.upool("hbm")
    tree = {"x": jnp.ones((4, 4))}
    placed = up.place(tree)
    assert placed["x"].shape == (4, 4)
    assert up.name == "hbm"


@pytest.mark.parametrize("pool", ["hbm", "host"])
@pytest.mark.parametrize("with_mesh", [False, True])
def test_upool_place_returns_landed_arrays(pool, with_mesh):
    """A placement is complete when it returns: no copy into or out of
    host memory is still in flight when the caller drops the arrays."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro import compat
    up = PoolManager().upool(pool)
    tree = {"k": jnp.ones((64, 128)), "v": jnp.zeros((64, 128))}
    if with_mesh:
        mesh = compat.make_mesh((1,), ("x",), devices=jax.devices()[:1])
        placed = up.place(tree, mesh, {"k": P(), "v": P()})
    else:
        placed = up.place(tree)
    want = up.pool.effective_memory_kind() or "device"
    for leaf in jax.tree.leaves(placed):
        assert leaf.is_ready()
        assert leaf.sharding.memory_kind == want


# ---------------------------------------------------------------------------
# Simulator physics (the paper's findings)
# ---------------------------------------------------------------------------


def _bw_ladder(platform, mem, obs="r", stress="w"):
    res = sim.scenario_ladder(platform, obs_node=platform.node(mem),
                              obs_strategy=obs,
                              stress_node=platform.node(mem),
                              stress_strategy=stress)
    return [r["obs"].bw_gbps for r in res]


def test_bandwidth_monotonic_under_stress():
    """Fig. 4: observed bandwidth never increases with stressor count."""
    for mem in ("hbm", "host"):
        for stress in ("r", "w", "y"):
            bw = _bw_ladder(TPU_V5E, mem, "r", stress)
            assert all(b1 >= b2 - 1e-9 for b1, b2 in zip(bw, bw[1:])), \
                (mem, stress, bw)


def test_latency_monotonic_under_stress():
    """Fig. 5: observed latency never decreases with stressor count."""
    for mem in ("dram", "pl-dram"):
        res = sim.scenario_ladder(ZCU102, obs_node=ZCU102.node(mem),
                                  obs_strategy="l",
                                  stress_node=ZCU102.node(mem),
                                  stress_strategy="w")
        lat = [r["obs"].lat_ns for r in res]
        assert all(l1 <= l2 + 1e-9 for l1, l2 in zip(lat, lat[1:])), \
            (mem, lat)


def test_write_stress_worse_than_read_stress():
    """Fig. 4: (r,w) degrades more than (r,r) — WAWB write amplification."""
    bw_r = _bw_ladder(ZCU102, "dram", "r", "r")
    bw_w = _bw_ladder(ZCU102, "dram", "r", "w")
    assert bw_w[-1] < bw_r[-1]


def test_zcu102_mlp_matches_paper_tables():
    """Tables II/III: DRAM MLP ~4.5-4.9, PL-DRAM ~4.0-4.2 under stress."""
    plat = ZCU102
    for mem, lo, hi in (("dram", 3.0, 7.0), ("pl-dram", 2.5, 6.5)):
        res = sim.scenario_ladder(plat, obs_node=plat.node(mem),
                                  obs_strategy="l",
                                  stress_node=plat.node(mem),
                                  stress_strategy="r")
        lat = res[-1]["obs"].lat_ns
        bw = sim.scenario_ladder(plat, obs_node=plat.node(mem),
                                 obs_strategy="r",
                                 stress_node=plat.node(mem),
                                 stress_strategy="r")[-1]["obs"].bw_gbps
        mlp = lat * bw / plat.line_bytes
        assert lo <= mlp <= hi, (mem, mlp)


def test_heterogeneous_shared_queue_throttling():
    """Fig. 6/7: stressing the SLOW module degrades the FAST module's
    bandwidth (slow transactions hold shared CCI entries longer)."""
    plat = ZCU102
    alone = sim.scenario_ladder(
        plat, obs_node=plat.node("dram"), obs_strategy="s",
        stress_node=plat.node("pl-dram"), stress_strategy="i")[0]
    stressed = sim.scenario_ladder(
        plat, obs_node=plat.node("dram"), obs_strategy="s",
        stress_node=plat.node("pl-dram"), stress_strategy="x")[-1]
    assert stressed["obs"].bw_gbps < 0.9 * alone["obs"].bw_gbps
    # and the effect is asymmetric: PL-DRAM obs under DRAM stress suffers
    # proportionally less (paper Fig. 7 reverse case)
    pl_alone = sim.scenario_ladder(
        plat, obs_node=plat.node("pl-dram"), obs_strategy="s",
        stress_node=plat.node("dram"), stress_strategy="i")[0]
    pl_stressed = sim.scenario_ladder(
        plat, obs_node=plat.node("pl-dram"), obs_strategy="s",
        stress_node=plat.node("dram"), stress_strategy="x")[-1]
    drop_fast = stressed["obs"].bw_gbps / alone["obs"].bw_gbps
    drop_slow = pl_stressed["obs"].bw_gbps / pl_alone["obs"].bw_gbps
    assert drop_slow > drop_fast


def test_write_stream_bank_collapse():
    """Fig. 13: y-stress from >=2 engines collapses even cache-partitioned
    bandwidth; 1 stressor is comparable to the (r,w) case."""
    plat = zcu102_partitioned()
    obs = plat.node("pvtpool")
    ladder_w = sim.scenario_ladder(plat, obs_node=obs, obs_strategy="r",
                                   stress_node=plat.node("dram"),
                                   stress_strategy="w")
    ladder_y = sim.scenario_ladder(plat, obs_node=obs, obs_strategy="r",
                                   stress_node=plat.node("dram"),
                                   stress_strategy="y")
    bw_w = [r["obs"].bw_gbps for r in ladder_w]
    bw_y = [r["obs"].bw_gbps for r in ladder_y]
    assert bw_y[1] > 0.5 * bw_w[1]          # comparable at one stressor
    assert bw_y[3] < 0.25 * bw_w[3]         # collapse at three


def test_cache_partitioning_helps_miss_path_only():
    """Fig. 11/12: partitioning does NOT help when everyone hits (bank
    contention on the hit path), but DOES when stressors miss."""
    plat = zcu102_partitioned()
    # everyone hitting in the cache: partitioned obs still degrades
    hit_ladder = sim.scenario_ladder(
        plat, obs_node=plat.node("pvtpool"), obs_strategy="r",
        stress_node=plat.node("l2"), stress_strategy="r")
    hit_bw = [r["obs"].bw_gbps for r in hit_ladder]
    assert hit_bw[-1] < 0.8 * hit_bw[0]
    # stressors missing to DRAM, obs hits private partition: mild impact
    miss_ladder = sim.scenario_ladder(
        plat, obs_node=plat.node("pvtpool"), obs_strategy="r",
        stress_node=plat.node("dram"), stress_strategy="r")
    miss_bw = [r["obs"].bw_gbps for r in miss_ladder]
    assert miss_bw[-1] > hit_bw[-1]


# ---------------------------------------------------------------------------
# Coordinator + experiment structure
# ---------------------------------------------------------------------------


def test_coordinator_validation():
    c = CoreCoordinator(backend="simulate")
    good = ExperimentConfig(ActivitySpec("r", "hbm", 1 << 20),
                            ActivitySpec("w", "hbm", 1 << 20))
    c.validate(good)
    with pytest.raises(ValidationError):
        c.validate(ExperimentConfig(ActivitySpec("z", "hbm", 1),
                                    ActivitySpec("w", "hbm", 1)))
    with pytest.raises(ValidationError):
        c.validate(ExperimentConfig(
            ActivitySpec("r", "hbm", 1 << 20),
            ActivitySpec("w", "hbm", 1 << 20), iters=0))
    with pytest.raises(PoolError):
        c.validate(ExperimentConfig(ActivitySpec("r", "nope", 1),
                                    ActivitySpec("w", "hbm", 1)))


def test_scenario_ladder_structure():
    """§III-A: p scenarios, 0..p-1 stressors, teardown leaves pools clean."""
    c = CoreCoordinator(backend="simulate")
    res = c.run(ExperimentConfig(ActivitySpec("r", "hbm", 1 << 20),
                                 ActivitySpec("w", "hbm", 1 << 20)))
    assert [s.n_stressors for s in res.scenarios] == list(
        range(c.platform.n_engines))
    for p in c.pools.pools():
        assert p.allocated == 0              # post-experiment clean state
    curve = res.bandwidth_curve()
    assert curve[0][1] >= curve[-1][1]


def test_interpret_backend_runs_real_kernels():
    c = CoreCoordinator(backend="interpret")
    res = c.run(ExperimentConfig(ActivitySpec("r", "hbm", 256 << 10),
                                 ActivitySpec("i", "hbm", 0), iters=2,
                                 scenarios=1))
    assert res.scenarios[0].main.bytes_moved > 0
    assert res.scenarios[0].main.elapsed_ns > 0


# ---------------------------------------------------------------------------
# Characterization + placement (Fig. 14 loop)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def curve_db():
    c = CoreCoordinator(backend="simulate")
    return characterize(c, pools=["hbm", "host"],
                        obs_strategies=("r", "l"),
                        stress_strategies=("r", "w"), iters=5), c


def test_curvedb_roundtrip(curve_db, tmp_path):
    db, _ = curve_db
    p = str(tmp_path / "curves.json")
    db.save(p)
    db2 = CurveDB.load(p)
    assert db2.curves.keys() == db.curves.keys()
    k = next(iter(db.curves))
    assert db2.curves[k][0].bandwidth_gbps == db.curves[k][0].bandwidth_gbps


def test_mlp_table_renders(curve_db):
    db, c = curve_db
    txt = mlp_table(db, c.platform)
    assert "hbm" in txt and "MLP" in txt


def test_placement_prefers_uncontended_pool(curve_db):
    """Fig. 14: under heavy HBM stress, the advisor may place a
    latency-sensitive object in nominally-slower host memory."""
    db, c = curve_db
    adv = PlacementAdvisor(db, c.platform, pools=["hbm", "host"])
    obj = MemObject("heap", 1 << 20, bytes_per_step=1 << 20,
                    dependent_accesses=0.0)
    quiet = adv.advise([obj], ContentionSpec(0, "hbm", "w"))
    assert quiet.pool_of("heap") == "hbm"    # HBM wins uncontended
    # predicted cost under stress must rise
    stressed_cost = adv.predict_ns(obj, "hbm",
                                   ContentionSpec(7, "hbm", "w"))
    quiet_cost = adv.predict_ns(obj, "hbm", ContentionSpec(0, "hbm", "w"))
    assert stressed_cost > quiet_cost


def test_placement_capacity_fallback(curve_db):
    db, c = curve_db
    adv = PlacementAdvisor(db, c.platform, pools=["hbm", "host"])
    big = kv_cache_object("kv", 32 << 30, bytes_read_per_token=1 << 20)
    plan = adv.advise([big], ContentionSpec(0),
                      capacities={"hbm": 16 << 30, "host": 256 << 30})
    assert plan.pool_of("kv") == "host"      # does not fit HBM
    with pytest.raises(RuntimeError):
        adv.advise([MemObject("x", 1 << 40, 0.0)],
                   capacities={"hbm": 1, "host": 1})


def test_placement_pinning(curve_db):
    db, c = curve_db
    adv = PlacementAdvisor(db, c.platform, pools=["hbm", "host"])
    obj = MemObject("pinned", 1 << 10, 1.0, pinned_pool="host")
    assert adv.advise([obj]).pool_of("pinned") == "host"


# ---------------------------------------------------------------------------
# User interface (debugfs analog)
# ---------------------------------------------------------------------------


def test_parse_size():
    assert parse_size("4M") == 4 << 20
    assert parse_size("128K") == 128 << 10
    assert parse_size("1G") == 1 << 30
    assert parse_size("77") == 77
    with pytest.raises(ValueError):
        parse_size("4X")


def test_parse_experiment_roundtrip():
    cfg = parse_experiment("l,hbm,4M w,host,8K iters=100 scenarios=3")
    assert cfg.main == ActivitySpec("l", "hbm", 4 << 20)
    assert cfg.stress == ActivitySpec("w", "host", 8 << 10)
    assert cfg.iters == 100 and cfg.scenarios == 3
    with pytest.raises(ValueError):
        parse_experiment("r,hbm")
    with pytest.raises(ValueError):
        parse_experiment("r,hbm,1M w,hbm,1M bogus=1")


def test_interface_state_machine():
    iface = MemscopeInterface(CoreCoordinator(backend="simulate"))
    assert iface.write_cmd("start").startswith("ERR")
    iface.write_experiment("r,hbm,1M w,hbm,1M iters=5")
    assert iface.write_cmd("validate") == "OK valid"
    assert iface.write_cmd("start") == "OK complete"
    out = iface.read_results()
    assert "stressors" in out and "bw_GBps" in out
    assert iface.write_cmd("erase") == "OK erased"
    assert iface.read_results() == "(no results)"
    assert iface.write_cmd("reboot").startswith("ERR")
    assert "hbm" in iface.read_pools()
    iface.write_perfcount("WALL_NS,HLO_FLOPS")
    assert iface.read_perfcount() == "WALL_NS,HLO_FLOPS"
