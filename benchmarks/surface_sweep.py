"""Executable bandwidth–latency surface sweep (CurveDB v3).

Characterizes a small rf x dc x stressor-count surface on the spmd
backend — every grid cell is a contention ladder whose rungs execute as
fused shard_map dispatches — and writes the resulting schema-3 surface
database (the CI artifact next to ``BENCH_spmd.json``).

The sweep is the tentpole's structural proof: the grid varies ONLY the
stressor ``TrafficShape``, the coordinator's sweep-batched dispatch
stacks every same-signature ladder into one host-synchronous dispatch,
and this module asserts ``host_sync_dispatches == distinct
signatures`` on the executed result.

The spmd backend needs a multi-device mesh.  Standalone this module
forces host devices before touching jax:

    PYTHONPATH=src python -m benchmarks.surface_sweep [--smoke] \
        [--out SURFACE_spmd.json]

Under ``benchmarks.run`` (whose process must keep seeing ONE device) it
re-executes itself in a subprocess with the devices forced.
"""
import argparse
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


def _grids(smoke: bool):
    if smoke:
        return (0.0, 1.0), (0.5, 1.0)
    return (0.0, 0.5, 1.0), (0.25, 0.5, 1.0)


def _run(smoke: bool, out: str) -> dict:
    from repro.core.characterize import AXIS_N, CurveDB, characterize_surface
    from repro.core.coordinator import CoreCoordinator
    from repro.core.scenarios import surface_matrix

    rws, irs = _grids(smoke)
    coord = CoreCoordinator(backend="spmd", faults=False, quality="off")
    max_stressors = min(3, len(jax.devices()) - 1)
    db = characterize_surface(coord, pools=["hbm"], stress_pools=["hbm"],
                              buffer_bytes=BUF, rw_ratios=rws,
                              inject_rates=irs, iters=ITERS,
                              max_stressors=max_stressors)

    # the structural claim: ONE host-synchronous dispatch per distinct
    # role-program signature across the whole grid (each (rf, dc,
    # observer) cell is a distinct ladder signature here)
    specs = surface_matrix(pools=["hbm"], stress_pools=["hbm"],
                           buffer_bytes=BUF, rw_ratios=rws,
                           inject_rates=irs, iters=ITERS,
                           max_stressors=max_stressors)
    n_sig = len({coord._spmd_group_key(spec, obs, b)
                 for spec in specs for obs in spec.observers
                 for b in obs.buffers})
    st = db.meta
    print(f"surface sweep: {st['n_ladders']} ladders "
          f"({len(rws)}rf x {len(irs)}dc x "
          f"{max_stressors + 1} rungs x 2 observers) -> "
          f"{st['host_sync_dispatches']} host-sync dispatches, "
          f"{n_sig} distinct signatures, "
          f"{st['programs_built']} programs built "
          f"({st['aot_compiles']} AOT)")
    assert st["host_sync_dispatches"] == n_sig, \
        (st["host_sync_dispatches"], n_sig)

    rows = []
    for key, surf in sorted(db.surfaces.items()):
        for n in surf.axis(AXIS_N).values:
            for rw in (rws[0], rws[-1]):
                q = db.query(key.obs_pool, n, obs_strat=key.obs_strat,
                             stress_pool=key.stress_pool,
                             stress_strat=key.stress_strat, rw_ratio=rw)
                rows.append({
                    "surface": key.to_string(),
                    "k": int(n), "rw": rw,
                    "bw_GBps": round(q.bandwidth_gbps, 4),
                    "lat_ns": round(q.latency_ns, 1),
                })
    print_table(f"executed surface grid ({len(jax.devices())} host "
                f"engines), rw-axis edges", rows)

    db.save(out)
    print(f"wrote {out} (schema {CurveDB.load(out).schema}, "
          f"{len(db.surfaces)} surfaces, shape "
          f"{next(iter(db.surfaces.values())).shape})")
    return st


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="2x2 grid (CI)")
    ap.add_argument("--out", default="SURFACE_spmd.json")
    # under benchmarks.run main() is called with no argv: parse
    # defaults, not the harness's own filter arguments
    args = ap.parse_args(argv if argv is not None else [])

    if not harness_setup("surface sweep"):
        _run(args.smoke, args.out)
        return 0
    # single-device harness process: re-exec with forced host devices
    # (same contract as benchmarks.spmd_ladder)
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        raise RuntimeError(
            f"surface sweep needs >= 2 devices but XLA_FLAGS already "
            f"pins the host device count ({flags!r}); raise it to >= 2 "
            f"or unset the flag")
    env["XLA_FLAGS"] = f"{flags} {_FORCE}={_N_DEV}".strip()
    cmd = [sys.executable, "-m", "benchmarks.surface_sweep",
           "--out", args.out]
    if args.smoke:
        cmd.append("--smoke")
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                       env=env)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        raise RuntimeError(f"surface_sweep subprocess failed:\n"
                           f"{r.stderr[-2000:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
