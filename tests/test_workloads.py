"""The measured pass over the strategy table, and ``CoreCoordinator.run``
as a one-spec ``run_matrix``.

Every access strategy runs through ``measure_group`` on the interpret
backend at 64 KiB, where ``r``, ``w`` and ``l`` take their VMEM-resident
kernels and every other letter its only kernel; each member's bytes,
transactions, memory kind and checksum are held to the plain numpy
reference of ``bench/reference/probes.py``.
"""
import jax
import pytest

from bench.reference import probes
from repro.core.coordinator import (ActivitySpec, CoreCoordinator,
                                    ExperimentConfig)
from repro.core.pools import PoolManager
from repro.core.scenarios import (ObserverSpec, ScenarioSpec, StressorSpec,
                                  TrafficShape)
from repro.core.workloads import LINE_BYTES, STRATEGIES, measure_group

NBYTES = 64 << 10
ITERS = 2


def test_the_table_holds_every_letter():
    assert sorted(STRATEGIES) == sorted("rswyxcblmti")
    assert {k for k, s in STRATEGIES.items() if s.vmem} == set("rwl")
    assert {k for k, s in STRATEGIES.items() if s.chase} == set("lmt")
    assert {k for k, s in STRATEGIES.items() if s.stacked} == \
        set("rscxblm")
    assert {k for k, s in STRATEGIES.items() if s.lines == 2} == set("cx")


@pytest.mark.parametrize("members", [1, 3])
@pytest.mark.parametrize("strategy", list("rswyxcblmti"))
def test_every_strategy_measures_its_reference(strategy, members):
    pool = PoolManager().pool("hbm")
    results, dispatches = measure_group(strategy, pool, NBYTES, members,
                                        ITERS)
    assert dispatches == 1 and len(results) == members
    rows = probes.rows_for(NBYTES)
    vmem = probes.uses_vmem_kernel(NBYTES, pool.node.kind)
    kind = (pool.effective_memory_kind()
            or jax.devices()[0].default_memory().kind)
    entry = STRATEGIES[strategy]
    for seed, res in enumerate(results):
        assert res.strategy == strategy and res.pool == "hbm"
        assert res.elapsed_ns > 0
        ref = probes.checksum(strategy, NBYTES, vmem, seed)
        if entry.chase:
            assert res.bytes_moved == rows * LINE_BYTES
            assert res.transactions == probes.chase_steps(rows)
            assert res.checksum == ref          # a line index: exact
            assert res.chain_seed == (seed if strategy != "t" else None)
        else:
            assert res.bytes_moved == entry.lines * rows * LINE_BYTES * ITERS
            assert res.transactions == 0
            assert res.checksum == pytest.approx(ref, rel=1e-6)
        if strategy == "i":
            assert res.buffer_bytes == 0 and res.memory_kind is None
        else:
            assert res.buffer_bytes == NBYTES and res.memory_kind == kind


MIXED = TrafficShape(kind="mixed", read_fraction=0.25, duty_cycle=0.5)


@pytest.mark.parametrize("main,stress,shape,scenarios", [
    (ActivitySpec("r", "hbm", 1 << 20), ActivitySpec("w", "hbm", 1 << 20),
     TrafficShape.steady(), None),
    (ActivitySpec("l", "hbm", 256 << 20),
     ActivitySpec("b", "hbm", 64 << 20, read_fraction=0.25,
                  duty_cycle=0.5), MIXED, 5),
    (ActivitySpec("s", "host", 1 << 20),
     ActivitySpec("y", "hbm", 1 << 20, duty_cycle=0.5),
     TrafficShape.burst(0.5), 3),
    (ActivitySpec("m", "hbm", 4 << 20),
     ActivitySpec("t", "hbm", 4 << 20, stride=16), TrafficShape.strided(16),
     None),
    (ActivitySpec("r", "hbm", 1 << 20), ActivitySpec("i", "hbm", 0),
     TrafficShape.steady(), 4),
])
def test_run_is_the_one_spec_run_matrix(main, stress, shape, scenarios):
    """``run`` gives, rung by rung, what ``run_matrix`` gives for the
    one-observer, one-stressor spec written out by hand (a shaped
    stressor included)."""
    coord = CoreCoordinator(backend="simulate")
    res = coord.run(ExperimentConfig(main, stress, iters=7,
                                     scenarios=scenarios))
    spec = ScenarioSpec(
        "by-hand", ObserverSpec(main.strategy, main.pool,
                                (main.buffer_bytes,)),
        (StressorSpec(stress.strategy, stress.pool, stress.buffer_bytes,
                      shape),),
        iters=7, max_stressors=None if scenarios is None else scenarios - 1)
    run, = coord.run_matrix([spec]).runs
    depth = scenarios or coord.platform.n_engines
    assert [s.n_stressors for s in res.scenarios] == list(range(depth))
    assert len(run.scenarios) == depth
    for got, want in zip(res.scenarios, run.scenarios):
        assert got.modeled_bw_gbps == want.modeled_bw_gbps
        assert got.modeled_lat_ns == want.modeled_lat_ns
        assert got.stress_bw_gbps == want.stress_bw_gbps
    assert res.bandwidth_curve() == run.bandwidth_curve()
    assert res.latency_curve() == run.latency_curve()
