"""Driver: characterization sweeps through the toolkit's normal path.

Set-up builds a ``CoreCoordinator`` on the configuration's backend and
the sweep's scenario matrix with ``characterize_specs`` (one observer
strategy per curve, on the configuration's pool, at every buffer size
of the traffic mix, against the configuration's stressor), then runs
one whole sweep to compile and warm every program.  The window runs
whole sweeps (``run_matrix`` then ``curvedb_from_result``, the body of
``characterize_matrix``), each with the scenarios in an order drawn
from the seed, until the time is up.

The check compares every probe kernel's output in the window with the
numpy reference, the memory kind of every operand with the pool's, and
counts ladders that degraded or fell to the queueing model and
bandwidths above the memory's peak.
"""
from __future__ import annotations

import dataclasses
import statistics
import sys
import time

import jax
import numpy as np

from bench.harness import BenchError, Check, Window
from bench.reference import probes

# limits, from the readings in PERF.md ("How correct is decided")
CHECKSUM_REL_LIMIT = 1e-3


class State:
    def __init__(self, coord, specs, rng, batched):
        self.coord = coord
        self.specs = specs
        self.rng = rng
        self.batched = batched


def _backend(ctx) -> str:
    return ctx.cell.config["backend"] if ctx.on_chip else "interpret"


def setup(ctx):
    from repro.core.characterize import characterize_specs
    from repro.core.coordinator import CoreCoordinator

    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    coord = CoreCoordinator(backend=_backend(ctx))
    sizes = tuple(int(b) for b in traffic["buffer_bytes"])
    specs, refused = characterize_specs(
        coord, pools=[cfg["pool"]], buffer_bytes=max(sizes),
        obs_strategies=tuple(traffic["observers"]),
        stress_strategies=(cfg["stressor"],), iters=int(cfg["iters"]))
    refused.update({f"{cfg['pool']}:{s}@{b}": why
                    for s in traffic["observers"] for b in sizes
                    for why in [coord.refusal(s, cfg["pool"], b)] if why})
    if refused:
        raise BenchError(f"the backend refuses {refused}")
    # one observer per strategy, swept over the working-set ladder (a
    # single size is a one-rung ladder): the Fig. 5 buffer sweep
    specs = [dataclasses.replace(s, observer=dataclasses.replace(
        s.observer, buffers=sizes)) for s in specs]
    state = State(coord, specs, np.random.default_rng(ctx.seed),
                  bool(cfg["batched"]))
    _sweep(state)                     # compile and warm every program
    return state


def _sweep(state):
    from repro.core.characterize import curvedb_from_result

    coord = state.coord
    order = state.rng.permutation(len(state.specs))
    with jax.profiler.TraceAnnotation("bench.sweep"):
        result = coord.run_matrix([state.specs[i] for i in order],
                                  batched=state.batched)
        curvedb_from_result(result, coord.platform.name,
                            backend=coord.backend)
    return result


def window(state, ctx, seconds: float, max_units: int = 0) -> Window:
    results, ends = [], []
    t0 = time.perf_counter()
    while True:
        results.append(_sweep(state))
        ends.append(time.perf_counter() - t0)
        wall = ends[-1]
        if wall >= seconds or (max_units and len(results) >= max_units):
            break
    took = [b - a for a, b in zip([0.0] + ends, ends)]
    print(f"bench: {len(took)} sweeps, median {statistics.median(took)!r} s, "
          f"max {max(took)!r} s", file=sys.stderr, flush=True)
    curves = sum(len(r.runs) for r in results)
    points = [_point(state.coord, run) for r in results for run in r.runs]
    read = [p for p in points if p["strategy"] == "r"
            and p["bytes"] > probes.VMEM_KERNEL_BYTES]
    return Window(
        seconds=wall, units=curves, attempted=curves, failed=0,
        end_to_end={"curves_per_s": curves / wall},
        data={"sweeps": len(results), "points": points,
              "stats": [r.stats for r in results],
              "read_bytes": sum(p["bytes_moved"] for p in read),
              "read_ns": sum(p["elapsed_ns"] for p in read)})


def _point(coord, run) -> dict:
    res = run.scenarios[0].main
    pool = coord.pools.pool(res.pool)
    return {"strategy": res.strategy, "pool": res.pool,
            "pool_kind": pool.node.kind, "bytes": res.buffer_bytes,
            "bytes_moved": res.bytes_moved, "elapsed_ns": res.elapsed_ns,
            "bandwidth_gbps": res.bandwidth_gbps,
            "transactions": res.transactions, "checksum": res.checksum,
            "chain_seed": res.chain_seed, "memory_kind": res.memory_kind,
            "want_kind": pool.effective_memory_kind() or "device",
            "peak_gbps": coord.platform.memories[res.pool].peak_bw_gbps}


def release(state) -> None:
    state.coord = None


def check(ctx) -> list:
    data = ctx.window.data
    rel_err = 0.0
    index_miss = kind_miss = over_peak = 0
    for p in data["points"]:
        ref = probes.checksum(
            p["strategy"], p["bytes"],
            probes.uses_vmem_kernel(p["bytes"], p["pool_kind"]),
            int(p["chain_seed"] or 0))
        got = p["checksum"]
        if p["strategy"] in ("l", "m", "t"):
            # a chase's checksum is a line index: exact or wrong
            index_miss += int(got is None or got != ref)
        else:
            err = (float("inf") if got is None
                   else abs(got - ref) / max(1.0, abs(ref)))
            rel_err = max(rel_err, err)
        kind_miss += int(p["strategy"] != "i"
                         and p["memory_kind"] != p["want_kind"])
        over_peak += int(p["bandwidth_gbps"] > p["peak_gbps"])
    fell = sum(s.degraded_ladders + s.modeled_floor_ladders
               for s in data["stats"])
    return [Check("checksum_rel_err", rel_err, CHECKSUM_REL_LIMIT),
            Check("chase_index_mismatches", index_miss, 0),
            Check("memory_kind_mismatches", kind_miss, 0),
            Check("degraded_ladders", fell, 0),
            Check("above_peak_points", over_peak, 0)]
