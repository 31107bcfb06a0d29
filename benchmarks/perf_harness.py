"""Perf harness: packed vs batched vs fused-per-ladder vs per-rung.

Times ``CoreCoordinator(backend="spmd")`` in four contender configs —
``packed`` (sweep-level megabatching + engine-subset width-packing:
narrow same-signature ladders run SIDE BY SIDE on disjoint engine
subsets of each stacked dispatch, the default), ``batched``
(megabatching with packing pinned off: one scan wave per stacked
ladder), ``fused`` (one dispatch per ladder, scanned psum sandwiches,
in-dispatch ``compat.device_clock`` rung timing) and ``per_rung`` (the
legacy 4-host-round-trips-per-rung path) — over a 64-scenario sweep
(16 with ``--smoke``) on 2- and 8-device meshes, plus a dedicated
WIDTH-PACKING section per leg (a sweep of 2-engine ladders, where
packing is at its strongest), and writes ``BENCH_spmd.json``
(schema 3): the committed perf trajectory for the spmd hot path.

    PYTHONPATH=src python -m benchmarks.perf_harness \
        [--smoke] [--out BENCH_spmd.json] [--fail-if-slower] \
        [--compile-cache-dir DIR]

Each mesh leg runs in a fresh subprocess (jax fixes the device count at
first init).  Per mode the sweep runs TWICE on one coordinator: the
cold pass pays tracing + fence verification + AOT compilation (ONE
program per distinct signature on the batched path, one per ladder
signature fused, K per signature per-rung), the warm pass is the
steady-state re-dispatch cost on cached programs.  Each mode reports
its distinct-program and AOT-compile counts next to its dispatch
counts, so the dispatch-vs-compile attribution is explicit rather than
inferred.  ``--compile-cache-dir`` opts into JAX's persistent
compilation cache (CI persists it across workflow runs via
actions/cache; host-callback-bearing programs are excluded by XLA —
see compat.persistent_cache).

``--smoke`` sizes the leg by ``REPRO_SPMD_DEVICES`` (the CI matrix
knob); ``--fail-if-slower`` exits non-zero when any measured leg fails
its perf gate (``GATE_CRITERION`` below: beat per-rung outright, stay
within a documented noise band of fused — whose dispatch-count
advantage is asserted structurally) — the gate verdict is recorded in
``BENCH_spmd.json`` either way.
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

BUF = 256 << 10
ITERS = 40
# the smoke sweep is 4x smaller, so its per-ladder work must be larger
# for the warm-path gate to measure dispatch structure rather than
# scheduler noise: with tiny rungs the per-rung path's many cheap
# dispatches sit within noise of the batched path's few larger ones
SMOKE_ITERS = 120
MAX_STRESSORS = 3
CACHE_CAP = 128

# (name, spmd_dispatch, spmd_pack): packed is the shipped default
# config; batched pins packing off so the pair isolates what width-
# packing alone buys on the SAME grouped dispatch structure
MODES = (("packed", "batched", "auto"), ("batched", "batched", "off"),
         ("fused", "ladder", "off"), ("per_rung", "rung", "off"))
# The gate (both CI legs): the batched sweep must beat the per-rung
# path outright on the warm (steady-state) sweep, and must not lose to
# the fused-per-ladder path beyond a 10% noise band.  Batched and
# fused share identical in-dispatch work and differ only in dispatch
# count, so on smoke-sized sweeps their true wall-clock gap is a few
# milliseconds — smaller than shared-runner scheduler noise; the
# dispatch-count advantage itself is asserted STRUCTURALLY
# (host_sync_dispatches == distinct signatures, unconditionally), so a
# broken grouping fails the leg regardless of wall clock.  The
# committed full-sweep BENCH numbers show batched beating both paths
# outright on both legs.  The width-packing section adds its own gate:
# on a mesh wide enough to pack the 2-engine sweep (>= 2 subsets),
# packed must beat packing-off on the warm pass outright — packing
# strictly removes scan waves and idle-engine work from the dispatch.
FUSED_NOISE_BAND = 1.10
GATE_CRITERION = ("batched warm sweep < per_rung warm sweep AND "
                  "batched warm sweep <= fused warm sweep x "
                  f"{FUSED_NOISE_BAND} (noise band; dispatch advantage "
                  "asserted structurally) AND, where the mesh packs "
                  "the 2-engine sweep, packed warm < packing-off warm")


def _sweep_specs(smoke: bool):
    from repro.core.scenarios import TrafficShape, scenario_matrix
    shapes = [("w", TrafficShape.steady()),
              ("r", TrafficShape.mixed(1, 1)),
              ("c", TrafficShape.steady()),
              ("w", TrafficShape.burst(0.5)),
              ("y", TrafficShape.steady()),
              ("r", TrafficShape.mixed(2, 1)),
              ("m", TrafficShape.strided(8)),
              ("w", TrafficShape.burst(0.25))]
    if smoke:
        # 2 pools x 2 observers x 2 stress pools x 2 shapes = 16
        # scenarios — the pool axes repeat each role-program signature
        # (hbm/host share one effective memory kind here), so even the
        # smoke sweep exercises real >1-ladder stacking
        return scenario_matrix(pools=("hbm", "host"), buffer_bytes=BUF,
                               obs_strategies=("r", "w"),
                               stress_shapes=shapes[:2],
                               iters=SMOKE_ITERS,
                               max_stressors=MAX_STRESSORS)
    # 2 pools x 2 observers x 2 stress pools x 8 shapes = 64 scenarios
    return scenario_matrix(pools=("hbm", "host"), buffer_bytes=BUF,
                           obs_strategies=("r", "w"),
                           stress_shapes=shapes, iters=ITERS,
                           max_stressors=MAX_STRESSORS)


def _count_signatures(specs) -> int:
    """Distinct role-program signatures in the sweep (mode-independent:
    what the batched path stacks under, and the honest denominator for
    every mode's compiles-per-signature number)."""
    from repro.core.coordinator import CoreCoordinator
    coord = CoreCoordinator(backend="spmd")
    return len({coord._spmd_group_key(spec, obs, b)
                for spec in specs for obs in spec.observers
                for b in obs.buffers})


# 5 interleaved rounds, median per mode: on a shared 1-core runner
# single-run drift is a few percent — comparable to the true batched
# vs per-rung gap on the cheap 2-device full sweep — and a 3-sample
# median still let one slow outlier decide the gate
WARM_ROUNDS = 5


def _time_modes(specs, n_sig: int) -> dict:
    """Cold + warm timings for all four contenders.

    The cold pass runs once per mode; the warm (steady-state) passes
    are INTERLEAVED round-robin across the modes and reported as the
    per-mode median — the gate rides on the warm numbers, and on a
    shared runner the machine drifts (frequency, thread placement)
    on second timescales, so back-to-back blocks per mode would hand
    whichever mode ran during a fast phase a spurious win."""
    from repro.core.coordinator import CoreCoordinator
    # a cache cap that holds EVERY mode's full program set (per-rung
    # needs K programs per signature, fused/batched one): the
    # comparison must measure dispatch mechanics, not LRU evictions.
    # The default cap (32) is a memory bound; the batched and fused
    # paths fit it on this sweep, the per-rung path does not — which
    # is itself a consequence of fusing, recorded via the program
    # counts below.
    # absorb one-time PROCESS costs (backend init, compat probes, XLA
    # thread pools) before any timed pass: they belong to the process,
    # not to whichever contender happens to be timed first.  One
    # single-spec matrix on a throwaway coordinator; its program cache
    # dies with it, so no contender inherits compiled sweep programs.
    CoreCoordinator(backend="spmd").run_matrix(specs[:1])
    coords, colds, cold_stats = {}, {}, {}
    for name, dispatch, pack in MODES:
        # hermetic timing: faults pinned off (immune to a stray
        # REPRO_FAULT_SPEC in the environment) and the quality gate
        # off so no re-measure perturbs the dispatch accounting
        coord = CoreCoordinator(backend="spmd", spmd_dispatch=dispatch,
                                spmd_pack=pack,
                                spmd_cache_cap=CACHE_CAP,
                                faults=False, quality="off")
        t0 = time.perf_counter()
        cold_res = coord.run_matrix(specs)
        colds[name] = time.perf_counter() - t0
        cold_stats[name] = cold_res.stats
        coords[name] = coord
    warm_samples = {name: [] for name, _d, _p in MODES}
    warm_res = {}
    for _ in range(WARM_ROUNDS):
        for name, _dispatch, _pack in MODES:
            t0 = time.perf_counter()
            res = coords[name].run_matrix(specs)
            warm_samples[name].append(time.perf_counter() - t0)
            warm_res[name] = res
    modes = {}
    for name, dispatch, pack in MODES:
        st = warm_res[name].stats
        cst = cold_stats[name]
        warm = sorted(warm_samples[name])[WARM_ROUNDS // 2]
        # every executed rung of every curve must be the verified
        # sandwich
        assert all(run.execution["fenced"]
                   for run in warm_res[name].runs), \
            "unfenced executed ladder in the perf sweep"
        assert all(s.main.elapsed_ns > 0 for run in warm_res[name].runs
                   for s in run.scenarios if s.source == "executed")
        if dispatch == "batched":
            # the sweep-level claim: host-synchronous dispatches
            # collapse to the number of distinct program signatures —
            # width-packing reshapes dispatches, it never adds any
            assert st.host_sync_dispatches == st.spmd_groups == n_sig, \
                (st.host_sync_dispatches, st.spmd_groups, n_sig)
            assert all(run.execution["batched"]
                       for run in warm_res[name].runs)
        if pack == "off":
            assert st.packed_ladders == 0, (name, st.packed_ladders)
        modes[name] = {
            "wall_s_cold": round(colds[name], 3),
            "wall_s_warm": round(warm, 3),
            "wall_s_warm_samples": [round(w, 3)
                                    for w in warm_samples[name]],
            "wall_s_total": round(colds[name] + warm, 3),
            "n_ladders": st.n_ladders,
            "rungs_per_ladder": st.spmd_rungs // max(1, st.n_ladders),
            "measure_dispatches": st.measure_dispatches,
            "host_sync_dispatches": st.host_sync_dispatches,
            "host_sync_per_ladder": round(
                st.host_sync_dispatches / max(1, st.n_ladders), 3),
            "program_cache_hits": st.program_cache_hits,
            # compile attribution (cold pass): programs actually
            # built, how many AOT lower().compile()-ed, and the
            # per-signature compile count this mode pays
            "distinct_programs": cst.programs_built,
            "aot_compiles": cst.aot_compiles,
            "compiles_per_signature": round(
                cst.programs_built / max(1, n_sig), 3),
            "timing_source":
                warm_res[name].runs[0].execution["timing_source"],
            # width-packing accounting (0 unless this contender packs
            # and the mesh is wide enough for the sweep's ladders)
            "packed_ladders": st.packed_ladders,
            "subset_width": st.subset_width,
        }
    return modes


def _packing_section(n_dev: int) -> dict:
    """The width-packing showcase: a sweep of 2-engine ladders
    (observer + ONE stressor), where a wide mesh packs
    ``n_dev // 2`` ladders side by side per dispatch.  Times the
    default (packed) against the same grouped dispatch with packing
    pinned off; the structural claims (ladders per host sync, subset
    accounting) are asserted unconditionally, the wall-clock gate only
    where the mesh actually packs."""
    from repro.core.scenarios import TrafficShape, scenario_matrix
    from repro.core.coordinator import CoreCoordinator
    shapes = [("w", TrafficShape.steady()),
              ("r", TrafficShape.mixed(1, 1))]
    # 2 pools x 2 observers x 2 stress pools x 2 shapes = 16 narrow
    # ladders; the pool axes repeat each signature, so every group
    # stacks >= 2 ladders and a >= 4-engine mesh packs them
    specs = scenario_matrix(pools=("hbm", "host"), buffer_bytes=BUF,
                            obs_strategies=("r", "w"),
                            stress_shapes=shapes, iters=SMOKE_ITERS,
                            max_stressors=1)
    width = min(2, n_dev)
    n_subsets = n_dev // width if n_dev >= 2 * width else 1
    coords, section = {}, {}
    for name, pack in (("packed", "auto"), ("packing_off", "off")):
        coords[name] = CoreCoordinator(backend="spmd",
                                       spmd_pack=pack,
                                       spmd_cache_cap=CACHE_CAP,
                                              faults=False, quality="off")
        t0 = time.perf_counter()
        coords[name].run_matrix(specs)
        section[name] = {"wall_s_cold":
                         round(time.perf_counter() - t0, 3)}
    warm_samples = {name: [] for name in coords}
    warm_res = {}
    for _ in range(WARM_ROUNDS):
        for name, coord in coords.items():
            t0 = time.perf_counter()
            warm_res[name] = coord.run_matrix(specs)
            warm_samples[name].append(time.perf_counter() - t0)
    for name, res in warm_res.items():
        st = res.stats
        assert all(run.execution["fenced"] for run in res.runs)
        section[name].update({
            "wall_s_warm": sorted(warm_samples[name])[WARM_ROUNDS // 2],
            "wall_s_warm_samples": [round(w, 3)
                                    for w in warm_samples[name]],
            "host_sync_dispatches": st.host_sync_dispatches,
            "ladders_per_dispatch": round(
                st.n_ladders / max(1, st.host_sync_dispatches), 2),
            "packed_ladders": st.packed_ladders,
            "subset_width": st.subset_width,
        })
    packed, off = section["packed"], section["packing_off"]
    # packing reshapes the stacked dispatches, it never adds any: both
    # configs sync once per signature, with every ladder on board
    assert packed["host_sync_dispatches"] == off["host_sync_dispatches"]
    assert off["packed_ladders"] == 0
    if n_subsets > 1:
        # every narrow ladder really ran in a width-`width` subset...
        assert packed["packed_ladders"] == len(specs), packed
        assert packed["subset_width"] == width, packed
        # ...and a wide mesh runs >= 4 ladders per host sync (the
        # stacked groups guarantee >= 2 even unpacked)
        if n_dev >= 4 * width:
            assert packed["ladders_per_dispatch"] >= 4, packed
    else:
        assert packed["packed_ladders"] == 0, packed
    gate_pass = (n_subsets == 1
                 or packed["wall_s_warm"] < off["wall_s_warm"])
    section.update({
        "n_scenarios": len(specs),
        "iters": SMOKE_ITERS,
        "ladder_width": width,
        "n_subsets": n_subsets,
        "speedup_packed_warm": round(
            off["wall_s_warm"] / max(packed["wall_s_warm"], 1e-9), 3),
        "gate": {"active": n_subsets > 1, "pass": gate_pass,
                 "packed_warm_s": round(packed["wall_s_warm"], 3),
                 "packing_off_warm_s": round(off["wall_s_warm"], 3)},
    })
    for name in coords:
        section[name]["wall_s_warm"] = round(
            section[name]["wall_s_warm"], 3)
    return section


def _run_leg(smoke: bool, cache_dir=None) -> dict:
    import jax

    from repro import compat
    if cache_dir:
        compat.persistent_cache(cache_dir)
    n_dev = len(jax.devices())
    assert n_dev >= 2, "perf harness leg needs a multi-device mesh"
    specs = _sweep_specs(smoke)
    n_sig = _count_signatures(specs)
    cache_prewarmed = bool(cache_dir and os.path.isdir(cache_dir)
                           and os.listdir(cache_dir))
    modes = _time_modes(specs, n_sig)
    packed, batched, fused, per_rung = (modes["packed"],
                                        modes["batched"],
                                        modes["fused"],
                                        modes["per_rung"])
    assert packed["timing_source"] == "callback", packed
    assert batched["timing_source"] == "callback", batched
    assert fused["timing_source"] == "callback", fused
    assert per_rung["timing_source"] == "host", per_rung
    k = fused["rungs_per_ladder"]

    def _ratios(a, b):
        return {kk: round(b[f"wall_s_{kk}"] / a[f"wall_s_{kk}"], 3)
                for kk in ("cold", "warm", "total")}

    packing = _packing_section(n_dev)
    gate_pass = (batched["wall_s_warm"] < per_rung["wall_s_warm"]
                 and batched["wall_s_warm"]
                 <= fused["wall_s_warm"] * FUSED_NOISE_BAND
                 and packing["gate"]["pass"])
    leg = {
        "devices": n_dev,
        "n_scenarios": len(specs),
        "ladder_rungs": k,
        "distinct_signatures": n_sig,
        "persistent_cache": bool(cache_dir),
        "cache_prewarmed": cache_prewarmed,
        "packed": packed,
        "batched": batched,
        "fused": fused,
        "per_rung": per_rung,
        # the dedicated 2-engine-ladder sweep: width-packing's best
        # case, with its own warm-pass gate where the mesh packs it
        "width_packing": packing,
        # the sweep cost a characterization run actually pays: tracing
        # + fence verification + AOT compile + dispatch (cold) and the
        # steady-state re-dispatch on cached programs (warm).  The
        # batched path compiles ONE program per distinct signature and
        # blocks the host once per signature per sweep, where fused
        # blocks once per ladder and per-rung 4K times per ladder.
        "speedup_batched_vs_fused": _ratios(batched, fused),
        "speedup_batched_vs_per_rung": _ratios(batched, per_rung),
        "speedup_fused_vs_per_rung": _ratios(fused, per_rung),
        "speedup_packed_vs_batched": _ratios(packed, batched),
        "dispatch_reduction_vs_fused": round(
            fused["host_sync_dispatches"]
            / batched["host_sync_dispatches"], 2),
        "dispatch_reduction_vs_per_rung": round(
            per_rung["host_sync_dispatches"]
            / batched["host_sync_dispatches"], 2),
        # the perf gate verdict (CI fails the leg on it with
        # --fail-if-slower): steady-state sweep, batched vs both
        "gate": {
            "criterion": GATE_CRITERION,
            "pass": gate_pass,
            "batched_warm_s": batched["wall_s_warm"],
            "fused_warm_s": fused["wall_s_warm"],
            "per_rung_warm_s": per_rung["wall_s_warm"],
            "packing_gate": packing["gate"],
        },
    }
    # the structural claims hold regardless of machine noise: the
    # batched sweep syncs once per SIGNATURE (packed or not), fused
    # once per LADDER, per-rung 4 times per RUNG
    assert packed["host_sync_dispatches"] == n_sig, leg
    assert batched["host_sync_dispatches"] == n_sig, leg
    assert fused["host_sync_per_ladder"] <= 2, leg
    assert per_rung["host_sync_per_ladder"] == 4 * k, leg
    assert leg["dispatch_reduction_vs_per_rung"] >= 3, leg
    # and the batched path compiles exactly one program per signature
    assert batched["distinct_programs"] <= n_sig, leg
    # the main sweep's ladders occupy k engines; the mesh packs them
    # exactly when a second k-engine subset fits
    assert (packed["packed_ladders"] > 0) == (n_dev >= 2 * k), leg
    return leg


_FORCE = "--xla_force_host_platform_device_count"


def _spawn_leg(n_dev: int, smoke: bool, cache_dir=None) -> dict:
    """One mesh size = one fresh interpreter (the harness process never
    initialises jax, so every leg gets its own device count).  Legs are
    CPU-only: forced host devices, never a chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        raise RuntimeError(
            f"XLA_FLAGS already pins the host device count ({flags!r}); "
            f"unset it — the perf harness forces its own mesh per leg")
    env["XLA_FLAGS"] = f"{flags} {_FORCE}={n_dev}".strip()
    with tempfile.TemporaryDirectory() as d:
        frag = os.path.join(d, "leg.json")
        cmd = [sys.executable, "-m", "benchmarks.perf_harness",
               "--_leg", str(n_dev), "--_fragment", frag]
        if smoke:
            cmd.append("--smoke")
        if cache_dir:
            cmd += ["--compile-cache-dir", cache_dir]
        r = subprocess.run(cmd, env=env, timeout=1800)
        if r.returncode != 0:
            raise RuntimeError(f"perf harness {n_dev}-device leg failed")
        with open(frag) as f:
            return json.load(f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small sweep, single leg (CI)")
    ap.add_argument("--out", default="BENCH_spmd.json")
    ap.add_argument("--fail-if-slower", action="store_true",
                    help="exit 1 if any measured leg fails its perf "
                         "gate (batched must beat per-rung warm and "
                         "stay within the fused noise band)")
    ap.add_argument("--compile-cache-dir", default=None,
                    help="enable JAX's persistent compilation cache "
                         "at this directory (CI persists it across "
                         "runs)")
    ap.add_argument("--_leg", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--_fragment", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args._leg is not None:            # subprocess mode: one mesh leg
        leg = _run_leg(args.smoke, args.compile_cache_dir)
        with open(args._fragment, "w") as f:
            json.dump(leg, f)
        return 0

    if args.smoke:
        legs = [max(2, int(os.environ.get("REPRO_SPMD_DEVICES", "8")))]
    else:
        legs = [2, 8]
    out = {
        "schema": 3,
        "bench": "spmd_packed_vs_batched_vs_fused_vs_per_rung",
        "generated_by": "benchmarks/perf_harness.py"
                        + (" --smoke" if args.smoke else ""),
        "n_scenarios": 16 if args.smoke else 64,
        "iters": SMOKE_ITERS if args.smoke else ITERS,
        "buffer_bytes": BUF,
        "spmd_cache_cap": CACHE_CAP,
        "gate_criterion": GATE_CRITERION,
        "legs": {},
    }

    def _write():
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")

    for n_dev in legs:
        print(f"== perf harness: {n_dev}-device leg "
              f"({out['n_scenarios']} scenarios) ==")
        leg = _spawn_leg(n_dev, args.smoke, args.compile_cache_dir)
        out["legs"][str(n_dev)] = leg
        for mode, _dispatch, _pack in MODES:
            m = leg[mode]
            print(f"   {mode:8s} cold {m['wall_s_cold']:7.3f}s  warm "
                  f"{m['wall_s_warm']:7.3f}s  "
                  f"{m['host_sync_dispatches']} syncs/sweep  "
                  f"{m['distinct_programs']} programs "
                  f"({m['aot_compiles']} AOT)  [{m['timing_source']}]")
        print(f"   {leg['distinct_signatures']} distinct signatures; "
              f"batched warm speedup: "
              f"{leg['speedup_batched_vs_fused']['warm']}x vs fused, "
              f"{leg['speedup_batched_vs_per_rung']['warm']}x vs "
              f"per-rung; gate "
              f"{'PASS' if leg['gate']['pass'] else 'FAIL'}")
        wp = leg["width_packing"]
        print(f"   width-packing ({wp['n_scenarios']} x "
              f"{wp['ladder_width']}-engine ladders, "
              f"{wp['n_subsets']} subsets): packed warm "
              f"{wp['packed']['wall_s_warm']:.3f}s vs off "
              f"{wp['packing_off']['wall_s_warm']:.3f}s "
              f"({wp['speedup_packed_warm']}x), "
              f"{wp['packed']['ladders_per_dispatch']} ladders/sync")
    _write()
    print(f"wrote {args.out}")

    if args.fail_if_slower:
        for n_dev in legs:
            leg = out["legs"][str(n_dev)]
            if not leg["gate"]["pass"]:
                # the structural claims (sync-per-signature, program
                # counts) are asserted unconditionally inside every
                # leg; the wall-clock sign additionally rides on a
                # noisy shared runner, so re-measure once before
                # declaring a regression
                print(f"{n_dev}-device gate failed "
                      f"({leg['gate']}); re-measuring once to "
                      f"separate regression from noise")
                retry = _spawn_leg(n_dev, args.smoke,
                                   args.compile_cache_dir)
                if retry["gate"]["pass"]:
                    out["legs"][str(n_dev)] = retry
                    _write()
            if not out["legs"][str(n_dev)]["gate"]["pass"]:
                print(f"FAIL: perf gate on the {n_dev}-device leg: "
                      f"{out['legs'][str(n_dev)]['gate']}",
                      file=sys.stderr)
                return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
