"""Executable multi-engine contention: the spmd backend's ladder.

Runs a k=0..3 stressor ladder where every rung is ONE fused shard_map
dispatch over an 8-engine mesh — engine 0 measures, engines 1..k stress,
the rest idle, all sandwiched between the two psum barriers — and prints
the executed curve next to the queueing model's prediction.

The spmd backend needs a multi-device mesh.  Standalone this module
forces 8 host devices before touching jax:

    PYTHONPATH=src python -m benchmarks.spmd_ladder

Under ``benchmarks.run`` (whose process must keep seeing ONE device) it
re-executes itself in a subprocess with the devices forced.
"""
import os
import subprocess
import sys

_FORCE = "--xla_force_host_platform_device_count=8"

if __name__ == "__main__":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_FORCE}".strip()

import jax  # noqa: E402  (after the device forcing above)

from benchmarks.common import (harness_setup,  # noqa: E402
                               print_table)

BUF = 256 << 10


def _run() -> list:
    from repro import compat
    from repro.core.characterize import curvedb_from_result
    from repro.core.coordinator import CoreCoordinator
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)

    spec = ScenarioSpec(
        "spmd-ladder",
        (ObserverSpec("r", "hbm", (BUF,)),      # bandwidth observer
         ObserverSpec("l", "hbm", (BUF,))),     # latency observer
        (StressorSpec("w", "hbm", BUF),),
        iters=20, max_stressors=3)

    spmd = CoreCoordinator(backend="spmd", faults=False, quality="off")
    res = spmd.run_matrix([spec])
    st = res.stats
    print(f"spmd ladder: {st.spmd_rungs} rungs "
          f"({st.n_ladders} observers x {st.spmd_rungs // st.n_ladders} "
          f"rungs) -> {st.measure_dispatches} fused whole-ladder "
          f"dispatches ({st.host_sync_dispatches} host syncs total), "
          f"{st.model_evals} model evals for comparison")
    # the sweep-batched default blocks the host once per distinct
    # role-program signature (here the two observers differ, so two
    # groups), its rungs timed by in-dispatch callback clocks
    timing_source = res.runs[0].execution["timing_source"]
    assert timing_source == compat.CLOCK_SOURCE, timing_source
    assert st.measure_dispatches == st.spmd_groups
    assert st.host_sync_dispatches == st.spmd_groups
    assert st.host_sync_dispatches <= st.n_ladders

    rows = []
    for run in res.runs:
        assert run.execution["fenced"]
        assert run.execution["timing_source"] == timing_source
        for s in run.scenarios:
            rows.append({
                "curve": run.key,
                "k": s.n_stressors,
                "source": s.source,
                "bw_GBps": round(s.main.bandwidth_gbps, 4),
                "lat_ns": round(s.main.latency_ns, 1),
                "model_bw": round(s.modeled_bw_gbps, 1),
                "model_lat": round(s.modeled_lat_ns, 1),
            })
    print_table("executed SPMD contention ladder (8 host engines)", rows)

    # persist the ladder we already executed (no re-run)
    db = curvedb_from_result(res, spmd.platform.name, backend="spmd")
    key = "hbm:r|hbm:w"
    ex = db.provenance[key]["execution"]
    print(f"CurveDB provenance for {key!r}: backend={ex['backend']} "
          f"activity={ex['activity']} coupled={ex['coupled']} "
          f"executed_rungs={ex['executed_rungs']} fenced={ex['fenced']} "
          f"timing_source={ex['timing_source']} "
          f"dispatches={ex['dispatches']}")
    return rows


def main() -> list:
    if not harness_setup("spmd ladder"):
        return _run()
    # single-device harness process: re-exec with forced host devices.
    # Respect a pre-set device-count flag (like examples/
    # spmd_contention.py): appending a second
    # --xla_force_host_platform_device_count would either clobber the
    # user's choice or trip XLA's duplicate-flag parsing.  If the
    # pre-set flag is what pinned us below 2 devices, re-execing would
    # recurse forever — fail with the actionable message instead.
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        raise RuntimeError(
            f"spmd ladder needs >= 2 devices but XLA_FLAGS already pins "
            f"the host device count ({flags!r}); raise it to >= 2 or "
            f"unset the flag")
    env["XLA_FLAGS"] = f"{flags} {_FORCE}".strip()
    r = subprocess.run([sys.executable, "-m", "benchmarks.spmd_ladder"],
                       capture_output=True, text=True, timeout=600,
                       env=env)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        raise RuntimeError(f"spmd_ladder subprocess failed:\n"
                           f"{r.stderr[-2000:]}")
    return []


if __name__ == "__main__":
    main()
