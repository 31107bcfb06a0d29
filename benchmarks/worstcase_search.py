"""Adversarial worst-case search vs. an equal-budget fixed grid.

Runs :func:`repro.core.search.worst_case_search` on the spmd backend
and pits it against the obvious alternative — a fixed characterization
grid of the SAME probe budget, measured through the SAME
``measure_candidates`` batched-dispatch path (identical per-probe cost;
the search can only win by *steering*).  The claim under test: the
model-seeded acquisition finds a strictly worse contention corner than
the best point of the equal-budget grid, because the grid must spend
its budget uniformly while the search follows the queueing prior into
the posted-write / locality-defeating corners the grid's single mixed
arm never plays.

Writes ``BENCH_worstcase.json`` (the CI artifact): the search envelope
keys, the worst corner each method found, the improvement margin and
the structural dispatch counts (exactly one host sync per search
iteration and per baseline batch — asserted).

The spmd backend needs a multi-device mesh.  Standalone this module
forces host devices before touching jax:

    PYTHONPATH=src python -m benchmarks.worstcase_search [--smoke] \
        [--fail-if-not-worse] [--out BENCH_worstcase.json]

Under ``benchmarks.run`` (whose process must keep seeing ONE device) it
re-executes itself in a subprocess with the devices forced.
``--fail-if-not-worse`` turns the search-beats-grid claim into a hard
exit code (the 8-device CI leg gates on it).
"""
import argparse
import itertools
import json
import os
import subprocess
import sys

_FORCE = "--xla_force_host_platform_device_count"
_N_DEV = max(2, int(os.environ.get("REPRO_SPMD_DEVICES", "8")))

if __name__ == "__main__":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_FORCE}={_N_DEV}".strip()

import jax  # noqa: E402  (after the device forcing above)

from benchmarks.common import (harness_setup,  # noqa: E402
                               print_table)

BUF = 256 << 10
ITERS = 20


def _budget(smoke: bool):
    """(iterations, batch): both methods probe iterations*batch
    coordinates, each under both observer strategies."""
    return (3, 4) if smoke else (6, 6)


def _grid_coords(budget: int, max_n: int):
    """The equal-budget fixed grid: uniform (n, rw, ir) coverage, the
    way ``characterize_surface`` would spend the same probes."""
    ns = list(range(1, max_n + 1))
    rws = (0.0, 0.5, 1.0)
    irs = (0.5, 1.0)
    cells = list(itertools.product(ns, rws, irs))
    # truncate/cycle deterministically to exactly the probe budget
    return [cells[i % len(cells)] for i in range(budget)] \
        if len(cells) < budget else cells[:budget]


def _run(smoke: bool, out: str, fail_if_not_worse: bool) -> dict:
    from repro.core.coordinator import CoreCoordinator
    from repro.core.exec.dispatch import DispatchStats
    from repro.core.search import (SearchArm, SearchSpec, _badness,
                                   _modeled_edge, measure_candidates,
                                   worst_case_search)

    iterations, batch = _budget(smoke)
    coord = CoreCoordinator(backend="spmd", faults=False,
                            quality="off")
    max_n = min(3, len(jax.devices()) - 1)
    spec = SearchSpec(pool="hbm", iterations=iterations, batch=batch,
                      max_stressors=max_n, buffer_bytes=BUF,
                      iters=ITERS, seed=0)

    # -- the search -------------------------------------------------------
    res = worst_case_search(coord, spec, execute=True)
    assert res.executed and res.fenced
    assert res.stats.host_sync_dispatches == iterations, \
        (res.stats.host_sync_dispatches, iterations)

    # -- the equal-budget fixed grid (same measurement path) --------------
    edges = _modeled_edge(coord.platform, spec.pool)
    grid = _grid_coords(iterations * batch, max_n)
    grid_stats = DispatchStats()
    grid_pts = []
    arm = SearchArm("b")        # the grid's single mixed-stream arm
    for i in range(0, len(grid), batch):
        chunk = grid[i:i + batch]
        results, fenced = measure_candidates(coord, spec, arm, chunk,
                                             it=i // batch,
                                             stats=grid_stats)
        assert fenced
        for ci, (n, rw, ir) in enumerate(chunk):
            for o in spec.obs_strategies:
                bw, lat = results[(ci, o)]
                grid_pts.append({
                    "n_stressors": n, "rw_ratio": rw, "inject_rate": ir,
                    "obs_strat": o, "bandwidth_gbps": bw,
                    "latency_ns": lat,
                    "badness": _badness(o, bw, lat, edges)})
    n_batches = -(-len(grid) // batch)
    assert grid_stats.host_sync_dispatches == n_batches, \
        (grid_stats.host_sync_dispatches, n_batches)

    # -- compare worst corners, per observer and overall ------------------
    rows, per_obs = [], {}
    for o in spec.obs_strategies:
        sw = res.worst(o)
        gw = max((p for p in grid_pts if p["obs_strat"] == o),
                 key=lambda p: p["badness"])
        margin = 100.0 * (sw.measured_badness / gw["badness"] - 1.0)
        per_obs[o] = {
            "search": sw.to_dict(),
            "grid": gw,
            "margin_pct": round(margin, 2),
        }
        rows.append({
            "obs": o,
            "search_worst": round(sw.measured_badness, 3),
            "search_corner": (f"{sw.arm} n{sw.n_stressors} "
                              f"rw{sw.rw_ratio} ir{sw.inject_rate}"),
            "grid_worst": round(gw["badness"], 3),
            "grid_corner": (f"b n{gw['n_stressors']} "
                            f"rw{gw['rw_ratio']} ir{gw['inject_rate']}"),
            "margin_pct": round(margin, 1),
        })
    print_table(
        f"worst corner found, {iterations * batch}-probe budget each "
        f"({len(jax.devices())} host engines; badness: ~1 uncontended, "
        f"larger = worse)", rows)

    best_margin = max(v["margin_pct"] for v in per_obs.values())
    print(f"worstcase search: {iterations} iterations x {batch} probes "
          f"= {res.stats.host_sync_dispatches} host-sync dispatches "
          f"(one per iteration); grid: {n_batches} batches -> "
          f"{grid_stats.host_sync_dispatches} dispatches; "
          f"best margin {best_margin:+.1f}%")

    report = {
        "devices": len(jax.devices()),
        "smoke": smoke,
        "budget": {"iterations": iterations, "batch": batch,
                   "coords": iterations * batch},
        "search": {
            "host_sync_dispatches": res.stats.host_sync_dispatches,
            "fenced": res.fenced,
            "envelope_keys": [k.to_string() for k in
                              sorted(res.envelope)],
            "arms_played": sorted({p.arm for p in res.points}),
        },
        "grid": {"host_sync_dispatches":
                 grid_stats.host_sync_dispatches},
        "per_observer": per_obs,
        "search_beats_grid": bool(best_margin > 0.0),
    }
    if fail_if_not_worse:
        assert best_margin > 0.0, \
            (f"search found no worse corner than the equal-budget grid "
             f"(best margin {best_margin:+.2f}%)")

    with open(out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {out}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small budget (CI)")
    ap.add_argument("--fail-if-not-worse", action="store_true",
                    help="hard-fail unless the search beats the grid")
    ap.add_argument("--out", default="BENCH_worstcase.json")
    # under benchmarks.run main() is called with no argv: parse
    # defaults, not the harness's own filter arguments
    args = ap.parse_args(argv if argv is not None else [])

    if not harness_setup("worst-case search"):
        _run(args.smoke, args.out, args.fail_if_not_worse)
        return 0
    # single-device harness process: re-exec with forced host devices
    # (same contract as benchmarks.surface_sweep)
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        raise RuntimeError(
            f"worst-case search needs >= 2 devices but XLA_FLAGS "
            f"already pins the host device count ({flags!r}); raise it "
            f"to >= 2 or unset the flag")
    env["XLA_FLAGS"] = f"{flags} {_FORCE}={_N_DEV}".strip()
    cmd = [sys.executable, "-m", "benchmarks.worstcase_search",
           "--out", args.out]
    if args.smoke:
        cmd.append("--smoke")
    if args.fail_if_not_worse:
        cmd.append("--fail-if-not-worse")
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                       env=env)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        raise RuntimeError(f"worstcase_search subprocess failed:\n"
                           f"{r.stderr[-2000:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
