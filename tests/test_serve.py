"""Serving correctness: prefill+decode must equal the teacher-forced
forward pass — the strongest end-to-end invariant the KV-cache/ring-
buffer/SSM-state machinery has.  Covered for a full-attention arch, a
sliding-window arch (ring caches), an SSM arch and the hybrid.

The second half covers the ONLINE loop: the contention watchdog, the
resilient background probe sweep (flag-never-raise, journal resume),
and the guarded KV migration with hysteresis + rollback.
"""
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig, get_config
from repro.core.characterize import (AXIS_N, ONLINE_QUALIFIER, CurveDB,
                                     Surface, SurfaceAxis, SurfaceKey)
from repro.core.devicetree import detect_platform
from repro.core.placement import ContentionSpec, kv_cache_object
from repro.core.pools import PoolManager
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.parallel.sharding import make_rules
from repro.serve import engine as eng
from repro.serve import monitor as smon

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
N_DEV = max(2, int(os.environ.get("REPRO_SPMD_DEVICES", "8")))

PROMPT, NEW = 12, 4
ARCHS = ["qwen2-1.5b", "gemma3-1b", "mamba2-370m", "jamba-v0.1-52b",
         "jamba2-3b"]


def _logits_all(cfg, params, tokens):
    h, _, _ = lm.forward(params, tokens, cfg=cfg, mode="train")
    return lm.unembed_logits(params, h, cfg)


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    mesh = make_host_mesh(1, 1)
    rules = make_rules(cfg, mesh, global_batch=2, shape_kind="decode")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))

    total = PROMPT + NEW
    tokens = (jnp.arange(2 * total, dtype=jnp.int32).reshape(2, total) * 7
              ) % cfg.vocab_size

    # oracle: teacher-forced full forward
    ref_logits = np.asarray(_logits_all(cfg, params, tokens))

    # prefill on the prompt, then decode the remaining positions
    prefill = eng.make_prefill_step(cfg, rules, max_len=total)
    decode = eng.make_decode_step(cfg, rules)
    caches, logits_p = prefill(params, tokens[:, :PROMPT], None)
    np.testing.assert_allclose(np.asarray(logits_p),
                               ref_logits[:, PROMPT - 1], atol=3e-3)
    for i in range(NEW - 1):
        pos = PROMPT + i
        caches, logits_d = decode(params, caches, tokens[:, pos:pos + 1],
                                  jnp.int32(pos), None)
        np.testing.assert_allclose(np.asarray(logits_d), ref_logits[:, pos],
                                   atol=3e-3, err_msg=f"{arch} pos={pos}")


def test_ring_cache_window_semantics():
    """Sliding-window ring cache: decoding far past the window must match
    a fresh forward over the same context."""
    cfg = get_config("gemma3-1b").reduced()       # window = 8
    assert cfg.sliding_window == 8
    mesh = make_host_mesh(1, 1)
    rules = make_rules(cfg, mesh, global_batch=1, shape_kind="decode")
    params = lm.init_params(cfg, jax.random.PRNGKey(2))

    total = 24                                    # 3x the window
    tokens = (jnp.arange(total, dtype=jnp.int32)[None] * 5) % cfg.vocab_size
    ref_logits = np.asarray(_logits_all(cfg, params, tokens))

    prefill = eng.make_prefill_step(cfg, rules, max_len=total)
    decode = eng.make_decode_step(cfg, rules)
    caches, _ = prefill(params, tokens[:, :PROMPT], None)
    for pos in range(PROMPT, total - 1):
        caches, logits_d = decode(params, caches, tokens[:, pos:pos + 1],
                                  jnp.int32(pos), None)
        np.testing.assert_allclose(np.asarray(logits_d), ref_logits[:, pos],
                                   atol=3e-3, err_msg=f"pos={pos}")


def test_engine_generate_greedy():
    """engine.generate: shapes, vocabulary range, determinism, and the
    first greedy token agrees with the teacher-forced oracle."""
    cfg = get_config("qwen2-1.5b").reduced()
    mesh = make_host_mesh(1, 1)
    rules = make_rules(cfg, mesh, global_batch=2, shape_kind="decode")
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    engine = eng.ServeEngine(cfg, params, rules, ServeConfig())
    prompts = (jnp.arange(2 * PROMPT, dtype=jnp.int32).reshape(2, PROMPT) * 3
               ) % cfg.vocab_size
    out = engine.generate(prompts, max_new_tokens=NEW, temperature=0.0)
    assert out.tokens.shape == (2, NEW)
    assert out.kv_pool == "hbm"
    toks = np.asarray(out.tokens)
    assert ((0 <= toks) & (toks < cfg.padded_vocab)).all()

    # deterministic under greedy decoding
    out2 = engine.generate(prompts, max_new_tokens=NEW, temperature=0.0)
    np.testing.assert_array_equal(toks, np.asarray(out2.tokens))

    # first token: compare against the oracle where argmax is unambiguous
    ref_logits = np.asarray(_logits_all(cfg, params, prompts))[:, -1]
    top2 = np.sort(ref_logits, -1)[:, -2:]
    margin_ok = (top2[:, 1] - top2[:, 0]) > 1e-3
    expect = ref_logits.argmax(-1)
    for b in range(2):
        if margin_ok[b]:
            assert toks[b, 0] == expect[b]


def test_cache_bytes_and_pool_choice():
    cfg = get_config("qwen2-1.5b").reduced()
    nbytes = eng.cache_bytes(cfg, batch=4, max_len=64)
    # 2 layers x k+v x (4, 64, kv, hd) bf16
    from repro.models.lm import cache_struct
    struct = cache_struct(cfg, 4, 64)
    manual = sum(np.prod(s.shape) * 2 for s in jax.tree.leaves(struct))
    assert nbytes == manual
    assert eng.choose_kv_pool(cfg, 4, 64) == "hbm"   # no advisor -> default
    assert eng.choose_kv_pool(
        cfg, 4, 64, scfg=ServeConfig(kv_placement="host")) == "host"


# ---------------------------------------------------------------------------
# Online loop plumbing: jit caching + capacity derivation
# ---------------------------------------------------------------------------


class _SpyAdvisor:
    """Records every advise() call; always answers "hbm"."""

    def __init__(self, pools):
        self.pools = list(pools)
        self.platform = detect_platform()
        self.calls = []

    def advise(self, objects, contention, capacities=None):
        from repro.core.placement import PlacementDecision, PlacementPlan
        self.calls.append((list(objects), contention, capacities))
        plan = PlacementPlan()
        for o in objects:
            plan.decisions[o.name] = PlacementDecision("hbm", 1.0, {})
        return plan


def test_prefill_trace_cached_across_generate_calls(monkeypatch):
    """The seed re-jitted prefill on EVERY generate call; the engine
    must build one prefill per max_len and reuse it."""
    cfg = get_config("qwen2-1.5b").reduced()
    mesh = make_host_mesh(1, 1)
    rules = make_rules(cfg, mesh, global_batch=2, shape_kind="decode")
    params = lm.init_params(cfg, jax.random.PRNGKey(1))

    builds = {"n": 0}
    real = eng.make_prefill_step

    def counting(cfg_, rules_, **kw):
        builds["n"] += 1
        return real(cfg_, rules_, **kw)

    monkeypatch.setattr(eng, "make_prefill_step", counting)
    engine = eng.ServeEngine(cfg, params, rules, ServeConfig())
    prompts = (jnp.arange(2 * PROMPT, dtype=jnp.int32).reshape(2, PROMPT)
               * 3) % cfg.vocab_size

    out1 = engine.generate(prompts, max_new_tokens=NEW)
    out2 = engine.generate(prompts, max_new_tokens=NEW)
    assert builds["n"] == 1, "prefill was re-jitted on a repeated shape"
    assert len(engine._prefill_cache) == 1
    assert engine._prefill(PROMPT + NEW) is engine._prefill(PROMPT + NEW)
    np.testing.assert_array_equal(np.asarray(out1.tokens),
                                  np.asarray(out2.tokens))

    engine.generate(prompts, max_new_tokens=NEW + 2)   # new max_len
    assert builds["n"] == 2

    # the engine feeds its observed decode duty cycle back into the
    # placement solve as the inject_rate coordinate
    spy = _SpyAdvisor(["hbm", "host"])
    engine.advisor = spy
    engine._duty = 0.37
    engine.generate(prompts, max_new_tokens=NEW)
    _objs, cont, caps = spy.calls[-1]
    assert cont.inject_rate == 0.37
    assert cont.rw_ratio == pytest.approx(
        eng.decode_rw_mix(2, PROMPT + NEW))
    assert caps is None                  # no manager, no free-bytes hint


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "jamba2-3b"])
def test_decode_loop_compiles_once_across_generate_calls(arch):
    """The decode loop is one jitted program per (steps, temperature),
    kept across calls: a repeated call compiles nothing and loads
    nothing from the compile cache (an eager ``lax.scan`` of the steps
    was traced again and fetched from the cache on every call), and the
    program keeps the module name ``jit_scan``."""
    cfg = get_config(arch).reduced()
    rules = make_rules(cfg, make_host_mesh(1, 1), global_batch=2,
                       shape_kind="decode")
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    engine = eng.ServeEngine(cfg, params, rules, ServeConfig())
    prompts = (jnp.arange(2 * PROMPT, dtype=jnp.int32).reshape(2, PROMPT)
               * 3) % cfg.vocab_size
    out1 = engine.generate(prompts, max_new_tokens=NEW)
    misses = []

    def on_duration(event, *_a, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            misses.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        out2 = engine.generate(prompts, max_new_tokens=NEW)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert misses == []
    np.testing.assert_array_equal(np.asarray(out1.tokens),
                                  np.asarray(out2.tokens))
    assert list(engine._loop_cache) == [(NEW - 1, 0.0)]
    loop = engine._decode_loop(NEW - 1, 0.0)
    caches, logits = engine._prefill(PROMPT + NEW)(params, prompts)
    text = loop.lower(params, caches, prompts[:, :1], logits,
                      jax.random.PRNGKey(0), jnp.int32(PROMPT)).as_text()
    assert text.startswith("module @jit_scan")


def test_choose_kv_pool_derives_capacities():
    """Capacities come from live pool accounting (or the platform),
    never from an invented constant (the seed hard-coded host=256GiB)."""
    cfg = get_config("qwen2-1.5b").reduced()
    pm = PoolManager()

    spy = _SpyAdvisor(["hbm", "host"])
    assert eng.choose_kv_pool(cfg, 4, 64, advisor=spy, pool_mgr=pm,
                              inject_rate=0.7) == "hbm"
    _objs, cont, caps = spy.calls[-1]
    assert cont.inject_rate == 0.7
    assert cont.rw_ratio == pytest.approx(eng.decode_rw_mix(4, 64))
    assert set(caps) == {"hbm", "host"}
    for p, c in caps.items():
        assert c == pm.pool(p).available

    # without a manager: platform nameplate capacities, with the hbm
    # entry overridden by the caller's live free-bytes figure
    spy2 = _SpyAdvisor(["hbm", "host"])
    eng.choose_kv_pool(cfg, 4, 64, advisor=spy2, hbm_free_bytes=123 << 20)
    caps2 = spy2.calls[-1][2]
    assert caps2["hbm"] == 123 << 20
    assert caps2["host"] == detect_platform().memories["host"].size_bytes


# ---------------------------------------------------------------------------
# Watchdog -> probe sweep -> guarded migration (synthetic surfaces)
# ---------------------------------------------------------------------------


def _flat_surface(bw, lat=100.0):
    return Surface(axes=(SurfaceAxis(AXIS_N, (0.0, 8.0)),),
                   bandwidth_gbps=[bw, bw], latency_ns=[lat, lat])


def _synth_db(hbm_bw=1000.0, host_bw=100.0):
    """Offline surfaces: hbm fast, host slow — serving starts on hbm."""
    db = CurveDB(platform="synthetic")
    for pool, bw in (("hbm", hbm_bw), ("host", host_bw)):
        for strat in ("r", "l"):
            db.surfaces[SurfaceKey(pool, strat, "hbm", "b")] = \
                _flat_surface(bw)
    return db


def _imprint_online(db, hbm_bw, host_bw):
    """What a probe sweep would store: online-qualified cells."""
    keys = []
    for pool, bw in (("hbm", hbm_bw), ("host", host_bw)):
        for strat in ("r", "l"):
            k = SurfaceKey(pool, strat, "hbm", "b",
                           qualifier=ONLINE_QUALIFIER)
            db.surfaces[k] = _flat_surface(bw)
            keys.append(k)
    return keys


class _StubCoord:
    backend = "simulate"


def _drift_monitor(db, refresh, *, cooldown=24, cooldown_steps=10):
    adv = smon.ServeMonitor.online_advisor(db, detect_platform(),
                                           pools=["hbm", "host"])
    rechar = smon.OnlineRecharacterizer(_StubCoord(), db,
                                        pools=["hbm", "host"],
                                        refresh=refresh)
    mon = smon.ServeMonitor(
        adv, rechar,
        watchdog=smon.WatchdogConfig(band=1.5, rearm=1.2, sustain=3,
                                     warmup=4, cooldown=cooldown),
        guard=smon.GuardConfig(min_gain_frac=0.1,
                               cooldown_steps=cooldown_steps,
                               verify_steps=4, regress_band=1.1),
        capacities={"hbm": 1 << 30, "host": 1 << 30})
    mon.bind(kv_bytes=1 << 20, rw_mix=0.9, pool="hbm", inject_rate=1.0)
    return mon


CALM_NS, DRIFT_NS = 1.0e6, 3.0e6


def test_drift_triggers_exactly_one_probe_sweep():
    """Sustained deviation fires ONE drift event and ONE probe sweep at
    the live coordinates; the refreshed surface flips the advisor and
    the guarded migration verifies clean."""
    db = _synth_db()
    calls = []

    def refresh(coord, db_, **kw):
        calls.append(kw)
        return _imprint_online(db_, 50.0, 100.0), {"stub": True}

    mon = _drift_monitor(db, refresh)
    for _ in range(7):                       # warmup + calm
        assert mon.on_step(CALM_NS) is None
    acts = [mon.on_step(DRIFT_NS) for _ in range(14)]

    kinds = [a.kind for a in acts if a is not None]
    assert kinds == ["migrate"], "expected exactly one clean migration"
    assert len(mon.drift_events) == 1
    assert len(calls) == 1, "drift must trigger exactly one probe sweep"
    assert mon.pool == "host"

    mig = mon.migrations[0]
    assert (mig.from_pool, mig.to_pool) == ("hbm", "host")
    assert not mig.rolled_back
    assert mig.reason.startswith("verified")

    # the sweep ran at the LIVE coordinates, carrying drift evidence
    kw = calls[0]
    assert kw["rw_ratio"] == 0.9 and kw["inject_rate"] == 1.0
    assert kw["drift"]["pool"] == "hbm"
    assert kw["drift"]["ratio"] > 1.5

    # the refreshed cell resolves under the online qualifier (offline
    # surface untouched underneath)
    q = db.query("hbm", 0, stress_strat="w", rw_ratio=0.9,
                 qualifier=ONLINE_QUALIFIER)
    assert q.bandwidth_gbps == 50.0
    assert db.query("hbm", 0, stress_strat="w").bandwidth_gbps == 1000.0


def test_faulted_probe_sweep_flags_instead_of_raising():
    """A probe sweep that dies (e.g. injected faults exhausting the
    degradation ladder) must flag and leave serving on the stale
    surface — never raise into the decode loop."""
    from repro.core.exec.resilience import GroupExecutionError
    db = _synth_db()

    def refresh(coord, db_, **kw):
        raise GroupExecutionError("probe group online.hbm",
                                  RuntimeError("injected"))

    mon = _drift_monitor(db, refresh)
    for _ in range(7):
        mon.on_step(CALM_NS)
    for _ in range(10):
        assert mon.on_step(DRIFT_NS) is None   # no action ever escapes

    assert len(mon.drift_events) == 1          # cooldown: no event storm
    assert len(mon.refreshes) == 1
    assert mon.refreshes[0].failed
    assert "GroupExecutionError" in mon.refreshes[0].error
    assert mon.pool == "hbm" and not mon.migrations


def test_migration_hysteresis_holds_marginal_gain():
    """A refreshed surface that flips the decision by a hair stays put:
    the predicted gain must clear the hysteresis floor."""
    db = _synth_db()

    def refresh(coord, db_, **kw):
        # online: hbm only 5% worse than host — below the 10% floor
        return _imprint_online(db_, 95.0, 100.0), {}

    mon = _drift_monitor(db, refresh)
    for _ in range(7):
        mon.on_step(CALM_NS)
    for _ in range(10):
        assert mon.on_step(DRIFT_NS) is None

    assert len(mon.refreshes) == 1 and not mon.refreshes[0].failed
    assert not mon.migrations and mon.pool == "hbm"
    assert mon.held and "hysteresis floor" in mon.held[0][1]


def test_migration_rolls_back_on_regression():
    """A migration whose verification window regresses beyond the band
    is rolled back — caches return to the source pool."""
    db = _synth_db()

    def refresh(coord, db_, **kw):
        return _imprint_online(db_, 50.0, 100.0), {}

    mon = _drift_monitor(db, refresh)
    for _ in range(7):
        mon.on_step(CALM_NS)

    acts = []
    wall = DRIFT_NS
    for _ in range(10):
        a = mon.on_step(wall)
        acts.append(a)
        if a is not None and a.kind == "migrate":
            wall = 5.0e6        # post-migration steps WORSE than drift

    kinds = [a.kind for a in acts if a is not None]
    assert kinds == ["migrate", "rollback"]
    assert mon.pool == "hbm"
    mig = mon.migrations[0]
    assert mig.rolled_back and "regressed" in mig.reason


def test_readvise_hysteresis_and_forced_moves():
    """The advisor-level re-advise arithmetic under the online
    qualifier: clean flip, no-op, held, and forced (capacity-lost)
    moves."""
    db = _synth_db()
    _imprint_online(db, 50.0, 100.0)
    adv = smon.ServeMonitor.online_advisor(db, detect_platform(),
                                           pools=["hbm", "host"])
    obj = kv_cache_object("kv", 1 << 20,
                          bytes_read_per_token=float(1 << 20))
    spec = ContentionSpec(0, rw_ratio=0.9, inject_rate=1.0)
    caps = {"hbm": 1 << 30, "host": 1 << 30}

    dec = adv.readvise([obj], spec, {"kv": "hbm"}, capacities=caps)
    assert dec.moves == {"kv": ("hbm", "host")}
    assert dec.predicted_gain_frac == pytest.approx(0.5)

    # already on the winning pool: nothing to move, nothing held
    dec2 = adv.readvise([obj], spec, {"kv": "host"}, capacities=caps)
    assert not dec2.moves and not dec2.held

    # a floor above the predicted gain holds the flip
    dec3 = adv.readvise([obj], spec, {"kv": "hbm"}, capacities=caps,
                        min_gain_frac=0.6)
    assert not dec3.moves and "kv" in dec3.held

    # current pool no longer a candidate: forced move, no hysteresis
    dec4 = adv.readvise([obj], spec, {"kv": "peer"}, capacities=caps)
    assert dec4.moves == {"kv": ("peer", "host")}


# ---------------------------------------------------------------------------
# The monitored engine loop
# ---------------------------------------------------------------------------


def test_monitored_loop_matches_scan_tokens():
    """The python (monitored) decode loop is token-identical to the
    fused lax.scan path — same split order, same emission bookkeeping —
    including under sampling."""
    cfg = get_config("qwen2-1.5b").reduced()
    mesh = make_host_mesh(1, 1)
    rules = make_rules(cfg, mesh, global_batch=2, shape_kind="decode")
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    engine = eng.ServeEngine(cfg, params, rules, ServeConfig())
    prompts = (jnp.arange(2 * PROMPT, dtype=jnp.int32).reshape(2, PROMPT)
               * 3) % cfg.vocab_size

    for temp in (0.0, 0.7):
        ref = engine.generate(prompts, max_new_tokens=NEW,
                              temperature=temp, seed=3)
        steps = []
        out = engine.generate(
            prompts, max_new_tokens=NEW, temperature=temp, seed=3,
            on_step=lambda step, pool: steps.append((step, pool)))
        np.testing.assert_array_equal(np.asarray(ref.tokens),
                                      np.asarray(out.tokens))
        assert steps == [(PROMPT + i, "hbm") for i in range(NEW - 1)]
        assert out.probe_sweeps == 0 and not out.drift_events


def test_engine_monitored_drift_migrates_end_to_end():
    """Full loop through the REAL engine: pool-dependent contention
    (injected inside the timed step window) drifts the watchdog, the
    probe sweep flips the online surface, and the engine migrates the
    live caches to the pool where the contention vanishes — with the
    provenance trail landing in GenerateResult and tokens unchanged."""
    cfg = get_config("qwen2-1.5b").reduced()
    mesh = make_host_mesh(1, 1)
    rules = make_rules(cfg, mesh, global_batch=2, shape_kind="decode")
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    total_new = 17

    db = _synth_db()                 # offline: hbm wins -> start there

    def refresh(coord, db_, **kw):
        # the probe sweep "measures" hbm contended, host clean
        return _imprint_online(db_, 2.0, 1000.0), {"stub": True}

    adv = smon.ServeMonitor.online_advisor(db, detect_platform(),
                                           pools=["hbm", "host"])
    rechar = smon.OnlineRecharacterizer(_StubCoord(), db,
                                        pools=["hbm", "host"],
                                        refresh=refresh)
    mon = smon.ServeMonitor(
        adv, rechar,
        watchdog=smon.WatchdogConfig(band=2.5, rearm=1.5, sustain=3,
                                     warmup=4, cooldown=64),
        # generous regress_band: post-migration steps are compared to
        # the DRIFTED pre-median, and jit timing jitters on CI
        guard=smon.GuardConfig(min_gain_frac=0.1, cooldown_steps=64,
                               verify_steps=3, regress_band=3.0),
        capacities={"hbm": 1 << 34, "host": 1 << 34})
    engine = eng.ServeEngine(cfg, params, rules, ServeConfig(),
                             advisor=adv, monitor=mon)
    prompts = (jnp.arange(2 * PROMPT, dtype=jnp.int32).reshape(2, PROMPT)
               * 3) % cfg.vocab_size

    def contention(step, pool):
        # external load hits hbm-resident caches from decode step 8 on;
        # migrating to host escapes it
        if pool == "hbm" and step - PROMPT >= 8:
            time.sleep(0.3)

    res = engine.generate(prompts, max_new_tokens=total_new,
                          on_step=contention)

    assert res.kv_pool == "host"
    assert len(res.drift_events) == 1
    assert res.probe_sweeps == 1
    assert len(res.migrations) == 1
    assert not res.migrations[0].rolled_back
    assert (res.migrations[0].from_pool,
            res.migrations[0].to_pool) == ("hbm", "host")

    # the migration must not corrupt decoding: greedy tokens match a
    # plain unmonitored engine
    ref = eng.ServeEngine(cfg, params, rules, ServeConfig()).generate(
        prompts, max_new_tokens=total_new)
    np.testing.assert_array_equal(np.asarray(res.tokens),
                                  np.asarray(ref.tokens))


# ---------------------------------------------------------------------------
# Probe sweeps on the real mesh: resilience + journal resume
# ---------------------------------------------------------------------------


def run_forced(body: str, n_devices: int = N_DEV, timeout: int = 480,
               extra_env=None) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n_devices}"
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("SUBPROC_OK")
    """)
    env = dict(os.environ, PYTHONPATH=SRC, **(extra_env or {}))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    assert "SUBPROC_OK" in r.stdout
    return r.stdout


def test_probe_sweep_journal_resume_value_identical():
    """Real-mesh probe sweeps: a journaled sweep restores
    value-identically; a sweep KILLED mid-flight resumes from its
    sidecar through the recharacterizer (which consumes the sidecar on
    success); and a chaos-faulted sweep completes flagged, with every
    refreshed cell present."""
    run_forced("""
    import json, os, tempfile
    from repro.core.characterize import CurveDB, refresh_surface_cells
    from repro.core.coordinator import CoreCoordinator
    from repro.core.exec import journal as exec_journal
    from repro.serve.monitor import OnlineRecharacterizer

    coord = CoreCoordinator(backend="spmd", faults=False, quality="off")
    tmp = tempfile.mkdtemp()
    jpath = os.path.join(tmp, "probe.journal")
    kw = dict(pools=["hbm", "host"], stress_pools=["hbm"], rw_ratio=0.7,
              inject_rate=0.9, buffer_bytes=64 << 10, iters=3,
              max_stressors=1)
    # a pool no rung kernel can take (host memory, pinned_host here as
    # on a chip) is refused and reported, never measured in HBM
    n_keys = 2 * sum(coord.refusal("r", p, 64 << 10) is None
                     for p in kw["pools"])

    # 1. a complete journaled probe sweep ...
    db1 = CurveDB(platform=coord.platform.name)
    keys1, st1 = refresh_surface_cells(coord, db1, journal=jpath, **kw)
    assert len(keys1) == n_keys and st1["resumed_ladders"] == 0
    assert set(st1["refused"]) == {
        f"{p}:{o}" for p in kw["pools"] for o in ("r", "l")
        if coord.refusal(o, p, 64 << 10) is not None}

    # ... restores value-identically on the next run, executing nothing
    db2 = CurveDB(platform=coord.platform.name)
    keys2, st2 = refresh_surface_cells(coord, db2, journal=jpath, **kw)
    assert st2["measure_dispatches"] == 0
    assert st2["resumed_ladders"] > 0

    def doc(db):
        return json.dumps(
            {k.to_string(): [s.to_dict()["axes"],
                             s.to_dict()["bandwidth_gbps"],
                             s.to_dict()["latency_ns"]]
             for k, s in db.surfaces.items()}, sort_keys=True)
    assert doc(db1) == doc(db2), "journal resume was not value-identical"

    # 2. the serving path: a probe sweep killed mid-flight leaves its
    # sidecar; the restarted recharacterizer RESUMES it at the same
    # coordinates and deletes the sidecar after the merge
    jdir = os.path.join(tmp, "sidecars")
    db3 = CurveDB(platform=coord.platform.name)
    rc = OnlineRecharacterizer(coord, db3, pools=["hbm", "host"],
                               stress_pools=["hbm"],
                               buffer_bytes=64 << 10, iters=3,
                               max_stressors=1, journal_dir=jdir)
    real_record = exec_journal.SweepJournal.record
    calls = {"n": 0}
    def dying_record(self, planned, outcomes):
        real_record(self, planned, outcomes)
        calls["n"] += 1
        if calls["n"] >= 1:
            raise KeyboardInterrupt("simulated engine death")
    exec_journal.SweepJournal.record = dying_record
    try:
        rc.run(0.7, 0.9)
        raise SystemExit("probe sweep should have died mid-flight")
    except KeyboardInterrupt:
        pass
    finally:
        exec_journal.SweepJournal.record = real_record
    sidecar = rc._journal_path(0.7, 0.9)
    assert os.path.exists(sidecar), "dead sweep left no sidecar"
    assert not db3.surfaces, "a dead sweep must merge nothing"

    res = rc.run(0.7, 0.9)
    assert not res.failed
    assert res.stats["resumed_ladders"] > 0, "resume re-measured all"
    assert len(res.keys) == n_keys and len(db3.surfaces) == n_keys
    assert not os.path.exists(sidecar), "sidecar must be consumed"

    # 3. chaos faults: every dispatch attempt faults (rate 1.0 — the
    # tiny probe sweep has too few dispatch sites for a probabilistic
    # rate to draw reliably), so the sweep must ride the retry /
    # degradation ladder and STILL deliver every refreshed cell
    coordf = CoreCoordinator(backend="spmd", faults="runtime=1.0,seed=3",
                             quality="off")
    dbf = CurveDB(platform=coordf.platform.name)
    rcf = OnlineRecharacterizer(coordf, dbf, pools=["hbm", "host"],
                                stress_pools=["hbm"],
                                buffer_bytes=64 << 10, iters=3,
                                max_stressors=1)
    resf = rcf.run(0.7, 0.9)
    assert not resf.failed, resf.error
    assert len(resf.keys) == n_keys
    assert resf.stats["faults_injected"] > 0, "chaos seed injected nothing"
    """)


def test_make_host_mesh_refuses_more_devices_than_exist():
    n = len(jax.devices())
    assert make_host_mesh(1, 1).devices.size == 1
    with pytest.raises(ValueError, match="needs"):
        make_host_mesh(n + 1, 1)
    with pytest.raises(ValueError, match="needs"):
        make_host_mesh(1, n + 1)


def test_place_caches_raises_for_a_pool_the_platform_lacks():
    """The engine never keeps caches where they are because a pool is
    not backed: an unknown pool raises."""
    from repro.core.pools import PoolError
    cfg = get_config("qwen2-1.5b").reduced()
    mesh = make_host_mesh(1, 1)
    rules = make_rules(cfg, mesh, global_batch=2, shape_kind="decode")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    engine = eng.ServeEngine(cfg, params, rules, ServeConfig(),
                             pool_mgr=PoolManager())
    caches = {"k": jnp.zeros((2, 8))}
    assert engine._place_caches(caches, "hbm")["k"].shape == (2, 8)
    with pytest.raises(PoolError):
        engine._place_caches(caches, "no-such-pool")
