"""Scenario-matrix characterization v2 — the full shaped sweep.

Runs the declarative scenario matrix (mixed read/write ratios,
bursty/duty-cycled stress, copy streams, strided chases — on top of the
seed's steady ladder) end-to-end:

  1. >= 64-scenario sweep on the ``simulate`` backend -> CurveDB v2
     (schema-tagged, provenance-carrying), consumed by the placement
     advisor below;
  2. the same matrix class on the ``interpret`` backend, measuring real
     Pallas kernels, comparing the batched runner's dispatch count
     against the naive per-point loop;
  3. a placement decision driven by a *shaped* contention spec.
"""
from repro.core.characterize import characterize_matrix
from repro.core.coordinator import CoreCoordinator
from repro.core.placement import ContentionSpec, MemObject, PlacementAdvisor
from repro.core.scenarios import (DEFAULT_STRESS_SHAPES, TrafficShape,
                                  scenario_matrix)
from benchmarks.common import coordinator, print_table

BUF = 64 << 20


def main() -> list:
    # -- 1. shaped sweep, simulate backend --------------------------------
    coord = coordinator()
    specs = scenario_matrix(pools=["hbm", "host"], buffer_bytes=BUF,
                            obs_strategies=("r", "w", "l"),
                            stress_shapes=DEFAULT_STRESS_SHAPES,
                            iters=50)
    assert len(specs) >= 64, len(specs)
    db = characterize_matrix(coord, specs)
    rows = []
    for key in sorted(db.curves):
        pts = db.curves[key]
        rows.append({
            "scenario": key,
            "bw0_GBps": round(pts[0].bandwidth_gbps, 1),
            "bwN_GBps": round(pts[-1].bandwidth_gbps, 1),
            "latN_ns": round(pts[-1].latency_ns, 1),
        })
    print_table(f"scenario matrix ({len(specs)} scenarios, "
                f"CurveDB schema {db.schema})", rows[:16])
    print(f"... {len(rows) - 16} more curves; "
          f"meta={db.meta}")

    # shaped-physics headline checks
    def bw(key, k):
        return db.curves[key][k].bandwidth_gbps
    # a 50%-duty write burst degrades the observer less than steady writes
    assert bw("hbm:r|hbm:w@dc0.50", 7) > bw("hbm:r|hbm:w", 7)
    # more write share in the mix -> more WAWB amplification -> worse
    rf12, rf21 = TrafficShape.mixed(1, 2).tag(), TrafficShape.mixed(2, 1).tag()
    assert bw(f"hbm:r|hbm:r@{rf12}", 7) < bw(f"hbm:r|hbm:r@{rf21}", 7)

    # -- 2. batched vs naive dispatches, interpret backend ------------------
    # host memory is refused by every probe kernel; vmem shares hbm's
    # effective memory kind, so the two pools share signature groups
    ic = coordinator(backend="interpret")
    small = scenario_matrix(pools=["hbm", "vmem"], buffer_bytes=64 << 10,
                            obs_strategies=("r", "w"),
                            stress_shapes=DEFAULT_STRESS_SHAPES[:8],
                            iters=2, max_stressors=1)
    res_b = ic.run_matrix(small, batched=True)
    res_n = ic.run_matrix(small, batched=False)
    print(f"interpret sweep: {len(small)} scenarios -> "
          f"batched {res_b.stats.measure_dispatches} dispatches vs "
          f"naive {res_n.stats.measure_dispatches}")
    assert res_b.stats.measure_dispatches < res_n.stats.measure_dispatches

    # -- 3. placement under shaped contention -------------------------------
    adv = PlacementAdvisor(db, coord.platform, pools=["hbm", "host"])
    heap = MemObject("heap", 1 << 20, bytes_per_step=1 << 20)
    for shape in (TrafficShape.steady(), TrafficShape.burst(0.5),
                  TrafficShape.mixed(1, 2)):
        strat = "r" if shape.kind == "mixed" else "w"
        c = ContentionSpec.shaped(7, "hbm", strat, shape)
        t = adv.predict_ns(heap, "hbm", c)
        print(f"heap@hbm under {strat}{'@' + shape.tag() if shape.tag() else '':9s}"
              f" stress: {t / 1e3:8.1f} us/step")
    return rows


if __name__ == "__main__":
    main()
