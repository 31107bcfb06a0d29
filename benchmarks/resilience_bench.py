"""Resilience overhead + chaos completeness: the PR 9 benchmark.

Two legs, one committed ``BENCH_resilience.json``:

**zero-fault overhead** — the resilient executor
(:func:`repro.core.exec.journal.execute_plan`: retry wrapper, timing
validation, quality gate) versus the raw dispatch loop it replaced
(``Dispatcher.run_planned`` + fold, no resilience seam) over the SAME
warm DispatchPlan on the SAME dispatcher.  Both contenders hand
identical work to ``run_planned``, so each pass's MACHINERY cost is
its wall time minus the time spent inside ``run_planned`` (measured
by a timing proxy around the dispatcher) — the kernels' multi-percent
run-to-run jitter cancels out of the comparison instead of drowning
it.  The gate: with no faults injected the resilient machinery adds
**under 3%** of the warm sweep's wall time — resilience must be free
until the day it is needed.  (Whole-pass wall medians are reported
too, informationally.)

**chaos completeness** (``--chaos``) — the full 64-scenario sweep
(16 with ``--smoke``) under ~25% mixed fault injection: every curve
must still come back (retried, degraded or modeled — never dropped),
with the survived faults/retries/degradations recorded in the JSON.
The chaos coordinator resolves ``REPRO_FAULT_SPEC`` from the
environment when set (the CI chaos leg scopes it to this step), else
defaults to ``mixed=0.25,seed=7``.

The spmd backend needs a multi-device mesh.  Standalone this module
forces host devices before touching jax (``REPRO_SPMD_DEVICES``, CI's
matrix knob, picks the count); under ``benchmarks.run`` (whose process
must keep seeing ONE device) it re-executes itself in a subprocess:

    PYTHONPATH=src python -m benchmarks.resilience_bench \
        [--smoke] [--chaos] [--out BENCH_resilience.json] \
        [--fail-if-slower]
"""
import argparse
import json
import os
import subprocess
import sys
import time

N_DEV = max(2, int(os.environ.get("REPRO_SPMD_DEVICES", "8")))
_FORCE = f"--xla_force_host_platform_device_count={N_DEV}"

if __name__ == "__main__":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_FORCE}".strip()

OVERHEAD_BAND = 0.03
GATE_CRITERION = ("zero-fault resilient machinery (pass wall minus "
                  "time inside Dispatcher.run_planned — both "
                  "contenders hand run_planned identical work on the "
                  f"same warm plan) adds <= {OVERHEAD_BAND:.0%} of "
                  "the warm sweep wall; the gated contender runs the "
                  "full retry/validation/gate-evaluation machinery "
                  "with re-measurement pinned off — a quality-gate "
                  "RE-MEASUREMENT is an extra measurement dispatch "
                  "taken in response to actually-noisy data, reported "
                  "separately, not overhead")
WARM_ROUNDS = 7
DEFAULT_CHAOS = "mixed=0.25,seed=7"


def _specs(smoke: bool):
    # the perf harness's committed sweep: 64 scenarios (16 smoke)
    from benchmarks.perf_harness import _sweep_specs
    return _sweep_specs(smoke)


def _build_warm(coord, specs):
    """The sweep's packed DispatchPlan + a dispatcher whose program
    cache already holds every plan program (one cold run_matrix)."""
    from repro.core.exec import plan as exec_plan
    coord.run_matrix(specs)                   # cold: trace + compile
    triples = [(spec, obs, b) for spec in specs
               for obs in spec.observers for b in obs.buffers]
    plan = exec_plan.build_plan(triples, coord._spmd_engines(),
                                coord.pools, coord.platform.n_engines)
    return exec_plan.pack_engine_subsets(plan)


def _direct_pass(disp, plan, n_eng, activity):
    """The pre-resilience executor shape: run each planned dispatch
    raw and fold — no retry wrapper, no validation, no gate."""
    from repro.core.exec.assemble import observer_result
    from repro.core.exec.dispatch import DispatchStats
    stats = DispatchStats()
    executed = {}
    for planned in plan.dispatches:
        med, _spread, _fenced, _aot = disp.run_planned(
            planned, n_eng, activity, "batched", stats)
        for g, e in enumerate(planned.entries):
            for k in range(planned.n_scen):
                executed[(e.index, k)] = observer_result(
                    e.observer, e.buffer_bytes, e.spec.iters,
                    float(max(med[g][k], 1.0)))
    return executed, stats


def _resilient_pass(disp, plan, n_eng, activity, policy, gate):
    from repro.core.exec import journal as exec_journal
    from repro.core.exec.dispatch import DispatchStats
    stats = DispatchStats()
    executed, _fenced, _timing = exec_journal.execute_plan(
        disp, plan, n_eng=n_eng, activity=activity, mode="batched",
        stats=stats, policy=policy, gate=gate)
    return executed, stats


class _TimedDispatcher:
    """Proxy accumulating wall time spent inside ``run_planned``.
    Pass wall minus this is the executor's own machinery cost; both
    contenders hand ``run_planned`` identical work, so the kernels'
    run-to-run jitter never enters the overhead comparison."""

    def __init__(self, disp):
        self._disp = disp
        self.dispatch_s = 0.0

    def __getattr__(self, name):
        return getattr(self._disp, name)

    def run_planned(self, *a, **kw):
        t0 = time.perf_counter()
        try:
            return self._disp.run_planned(*a, **kw)
        finally:
            self.dispatch_s += time.perf_counter() - t0


def _overhead_leg(smoke: bool) -> dict:
    from repro.core.coordinator import CoreCoordinator
    from repro.core.exec.resilience import QualityGate, RetryPolicy

    specs = _specs(smoke)
    # hermetic: the measured coordinator must not see a stray
    # REPRO_FAULT_SPEC (the CI chaos step's env) in its dispatcher
    coord = CoreCoordinator(backend="spmd", faults=False, quality="off")
    plan = _build_warm(coord, specs)
    n_eng = coord._spmd_engines()
    disp = coord._dispatcher
    activity = coord.spmd_activity
    policy = RetryPolicy()
    # the GATED contender: full machinery — retry wrapper, timing
    # validation, per-cell noisy evaluation — with re-measurement
    # pinned off.  A re-measurement is an extra measurement dispatch
    # triggered by data that really was noisy: feature work, timed
    # separately below, not machinery overhead.
    eval_gate = QualityGate(remeasure=0)
    ship_gate = QualityGate()                 # the shipped default

    # one unmeasured pass per contender: all run on fully-warm caches
    base, _ = _direct_pass(disp, plan, n_eng, activity)
    resi, rstats = _resilient_pass(disp, plan, n_eng, activity, policy,
                                   eval_gate)
    assert set(base) == set(resi), "resilient path lost curve points"
    assert not (rstats.faults_injected or rstats.retried_dispatches
                or rstats.degraded_ladders), \
        "zero-fault leg saw resilience activity"

    def timed(fn, *fa):
        proxy = _TimedDispatcher(disp)
        t0 = time.perf_counter()
        out = fn(proxy, plan, n_eng, activity, *fa)
        wall = time.perf_counter() - t0
        return wall, wall - proxy.dispatch_s, out

    direct_s, resilient_s, shipped_s = [], [], []
    mach_d, mach_r, remeasures = [], [], 0
    for _ in range(WARM_ROUNDS):              # interleaved: shared
        wall, mach, _ = timed(_direct_pass)   # machine drift hits all
        direct_s.append(wall)
        mach_d.append(mach)
        wall, mach, _ = timed(_resilient_pass, policy, eval_gate)
        resilient_s.append(wall)
        mach_r.append(mach)
        wall, _mach, (_, sst) = timed(_resilient_pass, policy,
                                      ship_gate)
        shipped_s.append(wall)
        remeasures += sst.noisy_remeasures
    med = lambda xs: sorted(xs)[len(xs) // 2]
    d_wall, r_wall, s_wall = med(direct_s), med(resilient_s), \
        med(shipped_s)
    # the gated quantity: machinery time (wall minus run_planned) —
    # stable Python time, free of the kernels' wall-clock jitter
    overhead = (med(mach_r) - med(mach_d)) / d_wall
    return {
        "n_scenarios": len(specs),
        "n_dispatches": len(plan.dispatches),
        "rounds": WARM_ROUNDS,
        "direct_warm_s": round(d_wall, 4),
        "resilient_warm_s": round(r_wall, 4),
        "machinery_direct_s": round(med(mach_d), 4),
        "machinery_resilient_s": round(med(mach_r), 4),
        "overhead_frac": round(overhead, 4),
        # informational: the shipped config (re-measurement on) —
        # slower only when the machine really was noisy, and then by
        # exactly the extra measurement dispatches it chose to take
        "shipped_gate_warm_s": round(s_wall, 4),
        "shipped_gate_remeasures": remeasures,
        "gate": GATE_CRITERION,
        "pass": bool(overhead <= OVERHEAD_BAND),
    }


def _chaos_leg(smoke: bool) -> dict:
    from repro.core.coordinator import CoreCoordinator
    from repro.core.exec.resilience import FaultSpec

    spec_text = (os.environ.get("REPRO_FAULT_SPEC", "").strip()
                 or DEFAULT_CHAOS)
    fspec = FaultSpec.parse(spec_text)
    specs = _specs(smoke)
    n_curves = sum(len(o.buffers) for s in specs for o in s.observers)
    coord = CoreCoordinator(backend="spmd", faults=fspec)
    t0 = time.perf_counter()
    res = coord.run_matrix(specs)
    wall = time.perf_counter() - t0
    st = res.stats
    assert len(res.runs) == n_curves, \
        (f"chaos sweep dropped curves: {len(res.runs)} of {n_curves} "
         f"came back")
    for run in res.runs:                      # every rung has a value
        assert all(s.modeled_bw_gbps > 0 for s in run.scenarios), \
            f"curve {run.key} lost rung values under chaos"
        assert run.execution["attempts"] >= 1
    degraded = [run.key for run in res.runs
                if run.execution.get("degraded_from")]
    return {
        "fault_spec": spec_text,
        "n_scenarios": len(specs),
        "n_curves": len(res.runs),
        "wall_s": round(wall, 3),
        "faults_injected": st.faults_injected,
        "retried_dispatches": st.retried_dispatches,
        "degraded_ladders": st.degraded_ladders,
        "modeled_floor_ladders": st.modeled_floor_ladders,
        "noisy_remeasures": st.noisy_remeasures,
        "degraded_curves": degraded,
        "pass": True,                         # completing IS the gate
    }


def _reexec(argv) -> int:
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        raise RuntimeError(
            f"resilience bench needs >= 2 devices but XLA_FLAGS "
            f"already pins the host device count ({flags!r})")
    env["XLA_FLAGS"] = f"{flags} {_FORCE}".strip()
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.resilience_bench"] + argv,
        capture_output=True, text=True, timeout=900, env=env)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        raise RuntimeError(f"resilience_bench subprocess failed:\n"
                           f"{r.stderr[-2000:]}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--chaos", action="store_true")
    ap.add_argument("--out", default="BENCH_resilience.json")
    ap.add_argument("--fail-if-slower", action="store_true")
    # under benchmarks.run main() is called with no argv: parse
    # defaults, not the harness's own filter arguments
    argv = argv if argv is not None else []
    args = ap.parse_args(argv)

    import jax
    from benchmarks.common import harness_setup
    if harness_setup("resilience bench"):
        return _reexec(argv)

    out = {
        "schema": 1,
        "bench": "resilience",
        "n_devices": len(jax.devices()),
        "smoke": args.smoke,
        "zero_fault": _overhead_leg(args.smoke),
    }
    zf = out["zero_fault"]
    print(f"zero-fault machinery: resilient "
          f"{zf['machinery_resilient_s']}s vs direct "
          f"{zf['machinery_direct_s']}s over {zf['n_dispatches']} "
          f"dispatches of a {zf['direct_warm_s']}s warm sweep "
          f"({zf['overhead_frac'] * 100:+.2f}% of wall, band "
          f"{OVERHEAD_BAND * 100:.0f}%) -> "
          f"{'PASS' if zf['pass'] else 'FAIL'}")
    if args.chaos:
        ch = out["chaos"] = _chaos_leg(args.smoke)
        print(f"chaos sweep [{ch['fault_spec']}]: {ch['n_curves']} "
              f"curves all present in {ch['wall_s']}s — "
              f"{ch['faults_injected']} faults, "
              f"{ch['retried_dispatches']} retries, "
              f"{ch['degraded_ladders']} degraded, "
              f"{ch['modeled_floor_ladders']} modeled "
              f"({len(ch['degraded_curves'])} curves degraded)")
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out}")
    if args.fail_if_slower and not zf["pass"]:
        print(f"PERF GATE FAILED: {GATE_CRITERION}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
