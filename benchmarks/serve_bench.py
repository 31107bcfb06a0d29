"""Curve-driven serving vs static placement under drift: the PR 10 bench.

One emulated contention episode, two contenders, one committed
``BENCH_serve.json``:

**static** — the seed's serving shape: the KV cache is placed once
(HBM, the calm-regime winner) and the engine never looks back.  When
the emulated contention hits the HBM pool mid-stream, every remaining
decode step eats the full drifted delay.

**curve-driven** — the same engine with a
:class:`repro.serve.monitor.ServeMonitor`: the contention watchdog
detects the drift against the surface's expectation, a REAL resilient
probe sweep runs through the spmd coordinator
(:func:`repro.core.characterize.refresh_surface_cells` — retries,
degradation ladder, journal sidecar all live), and the migration guard
moves the live caches to the pool the refreshed surface prefers.

The contention is EMULATED and pool-dependent: an ``on_step`` hook
sleeps ``delay(step, pool)`` inside the engine's timed step window
(HBM: calm until ``drift_at``, heavily contended after; host: a flat
modest tax, immune to the drift).  Because the real probe kernels
measure this machine's actual memory — not the emulated contention —
the refreshed cell VALUES are overwritten with the emulated world's
truth after each sweep (spelled so predicted cost == emulated delay);
the sweep's EXECUTION (dispatch, faults, retries, journal) is real.
The JSON records this under ``emulated_world``.

The gate (``--fail-if-slower``): curve-driven tokens/sec >= static
tokens/sec on the same episode.  The chaos leg (``--chaos``) re-runs
the curve-driven episode with fault injection in the probe coordinator
(``REPRO_FAULT_SPEC`` when set, else ``mixed=0.25,seed=7``) and gates
on 100% request completion with zero serving-loop crashes — a faulted
probe sweep may flag and keep serving on the stale surface, but it
must never raise into the decode loop.

The spmd probe backend needs a multi-device mesh.  Standalone this
module forces host devices before touching jax (``REPRO_SPMD_DEVICES``
picks the count); under ``benchmarks.run`` it re-executes itself:

    PYTHONPATH=src python -m benchmarks.serve_bench \
        [--smoke] [--chaos] [--out BENCH_serve.json] [--fail-if-slower]
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

N_DEV = max(2, int(os.environ.get("REPRO_SPMD_DEVICES", "8")))
_FORCE = f"--xla_force_host_platform_device_count={N_DEV}"

if __name__ == "__main__":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_FORCE}".strip()

DEFAULT_CHAOS = "mixed=0.25,seed=7"
GATE_CRITERION = ("curve-driven serving (contention watchdog -> online "
                  "probe sweep -> guarded KV migration) sustains >= the "
                  "static-placement tokens/sec over the same emulated "
                  "drift episode; the chaos leg completes 100% of "
                  "requests with zero serving-loop crashes")

PROMPT = 12
BATCH = 2


class EmulatedWorld:
    """Scripted pool-dependent contention.

    ``delay_s(step, pool)`` is the extra wall a decode step experiences
    with its KV caches in ``pool`` (slept inside the engine's timed
    window).  ``online_bw(pool)`` is what a truthful post-drift probe
    would report, spelled so the advisor's predicted step cost for a
    pool EQUALS its emulated delay (cost_ns = kv_bytes / bw)."""

    def __init__(self, kv_bytes: int, drift_at: int, *,
                 drift_hbm_s: float = 0.12, host_s: float = 0.02):
        self.kv_bytes = kv_bytes
        self.drift_at = drift_at
        self.drift_hbm_s = drift_hbm_s
        self.host_s = host_s

    def delay_s(self, step: int, pool: str) -> float:
        if pool == "host":
            return self.host_s
        return self.drift_hbm_s if step >= self.drift_at else 0.0

    def online_bw(self, pool: str) -> float:
        delay = self.drift_hbm_s if pool == "hbm" else self.host_s
        return self.kv_bytes / (delay * 1e9)

    def hook(self):
        def on_step(step, pool):
            time.sleep(self.delay_s(step, pool))
        return on_step

    def describe(self) -> dict:
        return {
            "drift_at_step": self.drift_at,
            "hbm_calm_delay_s": 0.0,
            "hbm_drifted_delay_s": self.drift_hbm_s,
            "host_delay_s": self.host_s,
            "note": ("contention is emulated by an on_step sleep inside "
                     "the engine's timed window; probe sweeps EXECUTE "
                     "the real resilient spmd path but their refreshed "
                     "cell values are overwritten with this world's "
                     "truth, since real kernels cannot see the emulated "
                     "load"),
        }


def _offline_db():
    """Calm-regime surfaces: hbm fast, host slow — serving starts on
    hbm, exactly what the drift will punish."""
    from repro.core.characterize import (AXIS_N, CurveDB, Surface,
                                         SurfaceAxis, SurfaceKey)

    def flat(bw):
        return Surface(axes=(SurfaceAxis(AXIS_N, (0.0, 8.0)),),
                       bandwidth_gbps=[bw, bw], latency_ns=[100.0, 100.0])

    db = CurveDB(platform="serve-bench")
    for pool, bw in (("hbm", 1000.0), ("host", 10.0)):
        for strat in ("r", "l"):
            db.surfaces[SurfaceKey(pool, strat, "hbm", "b")] = flat(bw)
    return db


def _world_refresh(world: EmulatedWorld):
    """The recharacterizer's refresh seam: run the REAL probe sweep,
    then imprint the emulated world's truth over the refreshed cells
    (keeping the sweep's provenance — faults, retries, journal)."""
    from repro.core.characterize import (AXIS_N, Surface, SurfaceAxis,
                                         refresh_surface_cells)

    def refresh(coord, db, **kw):
        keys, stats = refresh_surface_cells(coord, db, **kw)
        for k in keys:
            bw = world.online_bw(k.obs_pool)
            truth = Surface(
                axes=(SurfaceAxis(AXIS_N, (0.0, 8.0)),),
                bandwidth_gbps=[bw, bw], latency_ns=[100.0, 100.0],
                provenance=db.surfaces[k].provenance)
            db.surfaces[k] = truth
        return keys, stats

    return refresh


def _build_model():
    import jax
    from repro.configs.base import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import lm
    from repro.parallel.sharding import make_rules

    cfg = get_config("qwen2-1.5b").reduced()
    mesh = make_host_mesh(1, 1)
    rules = make_rules(cfg, mesh, global_batch=BATCH, shape_kind="decode")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    import jax.numpy as jnp
    prompts = (jnp.arange(BATCH * PROMPT,
                          dtype=jnp.int32).reshape(BATCH, PROMPT) * 3
               ) % cfg.vocab_size
    return cfg, rules, params, prompts


def _monitor(db, coord, world, journal_dir):
    from repro.core.devicetree import detect_platform
    from repro.serve.monitor import (GuardConfig, OnlineRecharacterizer,
                                     ServeMonitor, WatchdogConfig)

    adv = ServeMonitor.online_advisor(db, detect_platform(),
                                      pools=["hbm", "host"])
    rechar = OnlineRecharacterizer(
        coord, db, pools=["hbm", "host"], stress_pools=["hbm"],
        buffer_bytes=64 << 10, iters=3, max_stressors=1,
        journal_dir=journal_dir, refresh=_world_refresh(world))
    return ServeMonitor(
        adv, rechar,
        watchdog=WatchdogConfig(band=3.0, rearm=1.5, sustain=4,
                                warmup=5, cooldown=48),
        # rollback compares against the DRIFTED pre-median; a generous
        # band keeps CI timing jitter from faking a regression
        guard=GuardConfig(min_gain_frac=0.1, cooldown_steps=48,
                          verify_steps=4, regress_band=3.0),
        capacities={"hbm": 1 << 34, "host": 1 << 34}), adv, rechar


def _run_episode(engine, world, prompts, new_tokens):
    t0 = time.perf_counter()
    res = engine.generate(prompts, max_new_tokens=new_tokens,
                          on_step=world.hook())
    wall = time.perf_counter() - t0
    n_tok = BATCH * new_tokens
    return res, wall, n_tok / wall


def _refresh_stats(mon) -> dict:
    ok = [r for r in mon.refreshes if not r.failed]
    keep = ("faults_injected", "retried_dispatches", "degraded_ladders",
            "modeled_floor_ladders", "noisy_rungs", "resumed_ladders",
            "measure_dispatches")
    agg = {k: sum(int(r.stats.get(k, 0)) for r in ok) for k in keep}
    agg["sweeps"] = len(mon.refreshes)
    agg["sweeps_failed_flagged"] = sum(r.failed for r in mon.refreshes)
    return agg


def _serve_legs(smoke: bool) -> dict:
    from repro.configs.base import ServeConfig
    from repro.core.characterize import ONLINE_QUALIFIER
    from repro.core.coordinator import CoreCoordinator
    from repro.serve.engine import ServeEngine, cache_bytes

    new_tokens = 80 if smoke else 160
    cfg, rules, params, prompts = _build_model()
    kv_bytes = cache_bytes(cfg, BATCH, PROMPT + new_tokens)
    world = EmulatedWorld(kv_bytes, drift_at=PROMPT + 8)

    # -- static contender: placed once, never re-examined ------------------
    static = ServeEngine(cfg, params, rules, ServeConfig())
    sres, swall, stps = _run_episode(static, world, prompts, new_tokens)
    assert sres.kv_pool == "hbm"

    # -- curve-driven contender --------------------------------------------
    # probes run hermetically fault-free here; the chaos leg injects
    db = _offline_db()
    coord = CoreCoordinator(backend="spmd", faults=False, quality="off")
    jdir = tempfile.mkdtemp(prefix="serve-bench-journal-")
    mon, adv, rechar = _monitor(db, coord, world, jdir)

    # pre-warm the probe path (trace + compile) OUTSIDE the timed
    # episode, then drop the imprinted online cells so the episode
    # starts from the calm offline surface
    t0 = time.perf_counter()
    warm = rechar.run(0.9, 1.0)
    prewarm_s = time.perf_counter() - t0
    assert not warm.failed, f"probe pre-warm failed: {warm.error}"
    for k in [k for k in db.surfaces if k.qualifier == ONLINE_QUALIFIER]:
        del db.surfaces[k]

    curve = ServeEngine(cfg, params, rules, ServeConfig(),
                        advisor=adv, monitor=mon)
    cres, cwall, ctps = _run_episode(curve, world, prompts, new_tokens)

    assert cres.kv_pool == "host", \
        f"curve-driven engine never escaped the drift ({cres.kv_pool})"
    assert len(cres.drift_events) >= 1 and cres.probe_sweeps >= 1
    rollbacks = sum(m.rolled_back for m in cres.migrations)
    return {
        "n_new_tokens": new_tokens,
        "batch": BATCH,
        "emulated_world": world.describe(),
        "static": {
            "tokens_per_s": round(stps, 2),
            "wall_s": round(swall, 3),
            "kv_pool": sres.kv_pool,
        },
        "curve_driven": {
            "tokens_per_s": round(ctps, 2),
            "wall_s": round(cwall, 3),
            "kv_pool_final": cres.kv_pool,
            "probe_prewarm_s": round(prewarm_s, 3),
            "drift_events": [e.to_dict() for e in cres.drift_events],
            "probe_sweeps": cres.probe_sweeps,
            "migrations": [m.to_dict() for m in cres.migrations],
            "rollbacks": rollbacks,
            "held": len(mon.held),
            "refresh": _refresh_stats(mon),
        },
        "speedup": round(ctps / stps, 3),
        "gate": GATE_CRITERION,
        "pass": bool(ctps >= stps),
    }


def _chaos_leg(smoke: bool) -> dict:
    from repro.configs.base import ServeConfig
    from repro.core.coordinator import CoreCoordinator
    from repro.core.exec.resilience import FaultSpec
    from repro.serve.engine import ServeEngine, cache_bytes

    spec_text = (os.environ.get("REPRO_FAULT_SPEC", "").strip()
                 or DEFAULT_CHAOS)
    fspec = FaultSpec.parse(spec_text)
    new_tokens = 48 if smoke else 96
    n_calls = 3
    cfg, rules, params, prompts = _build_model()
    kv_bytes = cache_bytes(cfg, BATCH, PROMPT + new_tokens)
    world = EmulatedWorld(kv_bytes, drift_at=PROMPT + 8)

    db = _offline_db()
    coord = CoreCoordinator(backend="spmd", faults=fspec, quality="off")
    jdir = tempfile.mkdtemp(prefix="serve-bench-chaos-journal-")
    mon, adv, _rechar = _monitor(db, coord, world, jdir)
    engine = ServeEngine(cfg, params, rules, ServeConfig(),
                         advisor=adv, monitor=mon)

    # a request stream under chaos: the FIRST call rides the drift ->
    # faulted probe sweep -> migration; later calls serve from the
    # refreshed placement.  Every request must complete.
    completed = 0
    walls = []
    for _ in range(n_calls):
        t0 = time.perf_counter()
        res = engine.generate(prompts, max_new_tokens=new_tokens,
                              on_step=world.hook())
        walls.append(round(time.perf_counter() - t0, 3))
        assert res.tokens.shape == (BATCH, new_tokens), \
            f"truncated request under chaos: {res.tokens.shape}"
        completed += BATCH
    rollbacks = sum(m.rolled_back for m in mon.migrations)
    return {
        "fault_spec": spec_text,
        "n_requests": n_calls * BATCH,
        "completed_requests": completed,
        "serving_loop_crashes": 0,         # reaching here proves it
        "request_walls_s": walls,
        "drift_events": len(mon.drift_events),
        "probe_sweeps": len(mon.refreshes),
        "migrations": len(mon.migrations),
        "rollbacks": rollbacks,
        "kv_pool_final": mon.pool,
        "refresh": _refresh_stats(mon),
        "pass": bool(completed == n_calls * BATCH),
    }


def _reexec(argv) -> int:
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        raise RuntimeError(
            f"serve bench needs >= 2 devices but XLA_FLAGS already "
            f"pins the host device count ({flags!r})")
    env["XLA_FLAGS"] = f"{flags} {_FORCE}".strip()
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.serve_bench"] + argv,
        capture_output=True, text=True, timeout=1200, env=env)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        raise RuntimeError(f"serve_bench subprocess failed:\n"
                           f"{r.stderr[-2000:]}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--chaos", action="store_true")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--fail-if-slower", action="store_true")
    # under benchmarks.run main() is called with no argv: parse
    # defaults, not the harness's own filter arguments
    argv = argv if argv is not None else []
    args = ap.parse_args(argv)

    import jax
    from benchmarks.common import harness_setup
    if harness_setup("serve bench"):
        return _reexec(argv)

    out = {
        "schema": 1,
        "bench": "serve",
        "n_devices": len(jax.devices()),
        "smoke": args.smoke,
    }
    out.update(_serve_legs(args.smoke))
    cd, st = out["curve_driven"], out["static"]
    print(f"drift episode: curve-driven {cd['tokens_per_s']} tok/s vs "
          f"static {st['tokens_per_s']} tok/s ({out['speedup']}x) — "
          f"{len(cd['drift_events'])} drift, {cd['probe_sweeps']} "
          f"sweeps, {len(cd['migrations'])} migrations "
          f"({cd['rollbacks']} rolled back) -> "
          f"{'PASS' if out['pass'] else 'FAIL'}")
    if args.chaos:
        ch = out["chaos"] = _chaos_leg(args.smoke)
        print(f"chaos [{ch['fault_spec']}]: "
              f"{ch['completed_requests']}/{ch['n_requests']} requests "
              f"completed, {ch['probe_sweeps']} sweeps "
              f"({ch['refresh']['sweeps_failed_flagged']} flagged), "
              f"{ch['migrations']} migrations, final pool "
              f"{ch['kv_pool_final']!r} -> "
              f"{'PASS' if ch['pass'] else 'FAIL'}")
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out}")
    if args.fail_if_slower and not out["pass"]:
        print(f"PERF GATE FAILED: {GATE_CRITERION}")
        return 1
    if args.chaos and not out["chaos"]["pass"]:
        print("CHAOS GATE FAILED: a request did not complete")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
