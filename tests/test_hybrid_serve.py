"""Jamba's Mamba-1 mixer, its selective-scan kernel, and the hybrid cache
on the serving path, at ``jamba2-3b``'s reduced size on the CPU.

* the mixer (``models/mamba.py``) against the published equations
  written out as a per-position python recurrence;
* the Pallas selective scan (interpret mode) against the ``lax.scan``
  recurrence, across chunk boundaries and from a non-zero state, and a
  prompt scanned in two calls against one;
* the engine's cache advice: a hybrid model's KV cache and recurrent
  state go to the advisor as two objects, each at its own read share,
  and are placed where the plan puts each; a model with one kind of
  cache is advised as before, as one ``kv`` object;
* the ``memscope.place`` span around a call's advice and placement."""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig, get_config
from repro.core import spans
from repro.core.devicetree import detect_platform
from repro.core.placement import (ContentionSpec, PlacementAdvisor,
                                  PlacementDecision, PlacementPlan,
                                  recurrent_state_object)
from repro.core.pools import PoolManager
from repro.kernels import ops
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.models import mamba
from repro.parallel.sharding import make_rules
from repro.serve import engine as eng

CFG = get_config("jamba2-3b").reduced()


def _silu(v):
    return v / (1.0 + np.exp(-v))


def _rms(v, w, eps):
    return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + eps) * (1.0 + w)


def _mixer_by_hand(p, x, cfg):
    """The published Mamba-1 equations, one position at a time, f64."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    s_cfg = cfg.ssm
    di, n, r, k = (s_cfg.d_inner(cfg.d_model), s_cfg.d_state, s_cfg.dt_rank,
                   s_cfg.d_conv)
    b, t, _ = x.shape
    xz = np.einsum("btd,dke->btke", x, p["w_xz"])
    xs, z = xz[:, :, 0], xz[:, :, 1]
    a = -np.exp(p["A_log"])
    out = np.zeros((b, t, cfg.d_model))
    for bi in range(b):
        state = np.zeros((di, n))
        for ti in range(t):
            conv = p["conv_b"].copy()
            for j in range(k):
                src = ti - (k - 1) + j
                if src >= 0:
                    conv += p["conv_w"][j] * xs[bi, src]
            u = _silu(conv)
            dbc = u @ p["w_dbc"]
            dt = _rms(dbc[:r], p["dt_norm"], cfg.norm_eps)
            bm = _rms(dbc[r:r + n], p["b_norm"], cfg.norm_eps)
            cm = _rms(dbc[r + n:], p["c_norm"], cfg.norm_eps)
            delta = np.log1p(np.exp(dt @ p["w_dt"] + p["dt_bias"]))
            state = np.exp(delta[:, None] * a) * state + \
                (delta * u)[:, None] * bm[None, :]
            y = state @ cm + p["ssm_D"] * u
            out[bi, ti] = (y * _silu(z[bi, ti])) @ p["w_ssm_out"]
    return out


@pytest.mark.parametrize("mode", ["train", "prefill"])
def test_mamba1_mixer_follows_the_published_recurrence(mode):
    """Both whole-prompt paths (the ``lax.scan`` of training, the Pallas
    kernel of prefill) against the equations by hand.  The program runs
    in f32 here (the reduced config), so the only differences are f32
    rounding and summation order: 1e-4 of the output's scale."""
    params = mamba.mamba1_init(jax.random.PRNGKey(3), CFG, jnp.float32)
    # non-zero norm offsets and conv bias, so that each is exercised
    rng = np.random.default_rng(4)
    for name in ("dt_norm", "b_norm", "c_norm", "conv_b"):
        params[name] = jnp.asarray(
            rng.normal(0, 0.1, params[name].shape), jnp.float32)
    x = rng.standard_normal((2, 19, CFG.d_model)).astype(np.float32)
    got, cache = mamba.mamba_layer(params, jnp.asarray(x), cfg=CFG,
                                   mode=mode)
    want = _mixer_by_hand(params, x.astype(np.float64), CFG)
    assert (cache is not None) == (mode == "prefill")
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=1e-4 * np.abs(want).max())


def _scan_inputs(seed, b, t, d, n=16):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    dt = jnp.asarray(rng.uniform(1e-3, 0.3, (b, t, d)), jnp.float32)
    a = -jnp.asarray(np.tile(np.arange(1, n + 1), (d, 1)), jnp.float32)
    return f(b, t, d), dt, a, f(b, t, n), f(b, t, n), f(b, d, n)


@pytest.mark.parametrize("t,d,block_t,block_d", [
    (37, 256, 16, 128),    # 3 chunks, the last padded; 2 channel blocks
    (64, 128, 8, 128),     # 8 whole chunks
    (5, 384, 256, 512),    # one short chunk; one block of 3 lane tiles
])
def test_selective_scan_kernel_matches_the_recurrence(t, d, block_t,
                                                      block_d):
    """The kernel against ``lax.scan``, from a non-zero state: the same
    f32 operations in the same order of time, so within 1e-5."""
    x, dt, a, bm, cm, s0 = _scan_inputs(t, 2, t, d)
    y, s_t = ops.selective_scan(x, dt, a, bm, cm, s0)
    y_ref, s_ref = mamba.selective_scan_seq(x, dt, a, bm, cm, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_t), np.asarray(s_ref),
                               atol=1e-5)
    from repro.kernels import selective_scan as ss
    y2, s2 = ss.selective_scan(x, dt, a, bm, cm, s0, block_t=block_t,
                               block_d=block_d, interpret=True)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_ref), atol=1e-5)


def test_selective_scan_in_two_calls_equals_one():
    """A prompt scanned in two calls, the first call's final state the
    second's initial state, gives the one call's outputs and state."""
    x, dt, a, bm, cm, s0 = _scan_inputs(9, 1, 40, 128)
    y, s_t = ops.selective_scan(x, dt, a, bm, cm, s0)
    y1, s1 = ops.selective_scan(x[:, :24], dt[:, :24], a, bm[:, :24],
                                cm[:, :24], s0)
    y2, s2 = ops.selective_scan(x[:, 24:], dt[:, 24:], a, bm[:, 24:],
                                cm[:, 24:], s1)
    np.testing.assert_allclose(np.concatenate([y1, y2], 1), y, atol=1e-5)
    np.testing.assert_allclose(s2, s_t, atol=1e-5)


# ---------------------------------------------------------------------------
# The hybrid cache on the serving path
# ---------------------------------------------------------------------------


class _SpyAdvisor:
    """Records every advise() call; answers with ``pools`` by object."""

    def __init__(self, pools):
        self.answer = dict(pools)
        self.pools = sorted(set(pools.values()))
        self.platform = detect_platform()
        self.calls = []

    def advise(self, objects, contention, capacities=None):
        self.calls.append((list(objects), contention))
        plan = PlacementPlan()
        for o in objects:
            plan.decisions[o.name] = PlacementDecision(
                self.answer.get(o.name, "hbm"), 1.0, {})
        return plan


def test_hybrid_cache_is_advised_as_kv_and_state():
    spy = _SpyAdvisor({"kv": "hbm", "state": "host"})
    pools = eng.choose_cache_pools(CFG, 2, 64, advisor=spy)
    assert pools == {"kv": "hbm", "state": "host"}
    (objs, contention), = spy.calls
    kv, state = objs
    assert (kv.name, state.name) == ("kv", "state")
    assert kv.size_bytes == eng.cache_bytes(CFG, 2, 64, kind="kv") > 0
    assert state.size_bytes == eng.cache_bytes(CFG, 2, 64, kind="state") > 0
    assert kv.size_bytes + state.size_bytes == eng.cache_bytes(CFG, 2, 64)
    # each at its own read share: the KV read mix, and the state read
    # whole and written back every step
    assert kv.rw_ratio == eng.decode_rw_mix(2, 64) == contention.rw_ratio
    assert state.rw_ratio == 0.5
    assert kv.bytes_per_step == kv.size_bytes
    assert state.bytes_per_step == 2 * state.size_bytes
    # 2 attention layers of (2, 64, kv 2, 16) k and v in bf16; 2 Mamba
    # layers of a (2, 128, 16) f32 state and a (2, 3, 128) bf16 conv tail
    assert kv.size_bytes == 2 * 2 * (2 * 64 * 2 * 16) * 2
    assert state.size_bytes == 2 * (2 * 128 * 16 * 4 + 2 * 3 * 128 * 2)


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mamba2-370m"])
def test_one_kind_of_cache_is_advised_as_before(arch):
    """A model with one kind of cache: one ``kv`` object of the whole
    cache, at the contention's read mix, as ``choose_kv_pool`` asks."""
    cfg = get_config(arch).reduced()
    spy = _SpyAdvisor({"kv": "hbm"})
    pools = eng.choose_cache_pools(cfg, 4, 64, advisor=spy)
    (objs, contention), = spy.calls
    assert [o.name for o in objs] == ["kv"]
    assert objs[0].rw_ratio is None
    assert objs[0].size_bytes == eng.cache_bytes(cfg, 4, 64)
    assert contention.rw_ratio == eng.decode_rw_mix(4, 64)
    spy2 = _SpyAdvisor({"kv": "hbm"})
    assert eng.choose_kv_pool(cfg, 4, 64, advisor=spy2) == "hbm"
    assert spy2.calls[0][0] == objs and spy2.calls[0][1] == contention
    kind = "kv" if arch == "qwen2-1.5b" else "state"
    assert pools == {kind: "hbm"}


class _RecordingDB:
    """A CurveDB stand-in that records the rw_ratio of every query."""

    def __init__(self):
        self.rw = []

    def query(self, pool, n, *, obs_strat, rw_ratio=None, **_kw):
        self.rw.append(rw_ratio)
        return type("Q", (), {"bandwidth_gbps": 100.0, "latency_ns": 100.0,
                              "extrapolated": False})()


def test_an_object_is_costed_at_its_own_read_share():
    db = _RecordingDB()
    adv = PlacementAdvisor(db, detect_platform(), pools=["hbm"])
    adv.advise([recurrent_state_object("state", 1 << 10)],
               ContentionSpec(0, rw_ratio=0.99))
    assert db.rw == [0.5, 0.5]          # bandwidth and latency queries
    db.rw.clear()
    adv.advise([dataclasses.replace(recurrent_state_object("s", 1 << 10),
                                    rw_ratio=None)],
               ContentionSpec(0, rw_ratio=0.99))
    assert db.rw == [0.99, 0.99]


def _engine(advisor=None, pool_mgr=None):
    rules = make_rules(CFG, make_host_mesh(1, 1), global_batch=2,
                       shape_kind="decode")
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    return eng.ServeEngine(CFG, params, rules, ServeConfig(),
                           advisor=advisor, pool_mgr=pool_mgr)


def test_each_kind_of_cache_is_placed_in_its_pool():
    pm = PoolManager()
    engine = _engine(pool_mgr=pm)
    caches = lm.init_cache(CFG, 2, 16)
    kinds = {(top, name): eng.cache_kind(g)
             for top, name, g in eng._groups(caches)}
    assert sorted(set(kinds.values())) == ["kv", "state"]
    placed = engine._place_by_kind(caches, {"kv": "hbm", "state": "host"})
    default = jax.devices()[0].default_memory().kind
    want = {"kv": pm.pool("hbm").effective_memory_kind() or default,
            "state": pm.pool("host").effective_memory_kind() or default}
    assert want["kv"] != want["state"]
    for (top, name), kind in kinds.items():
        for leaf in jax.tree.leaves(placed[top][name]):
            assert leaf.sharding.memory_kind == want[kind]
    assert jax.tree.structure(placed) == jax.tree.structure(caches)


def test_generate_opens_the_place_span_with_its_arguments(tmp_path):
    """One ``memscope.place`` span a call, carrying both kinds' bytes
    and the pool the advice gave each."""
    from jax.profiler import ProfileData
    engine = _engine(advisor=_SpyAdvisor({"kv": "hbm", "state": "hbm"}))
    prompts = jnp.arange(2 * 12, dtype=jnp.int32).reshape(2, 12) % 200
    engine.generate(prompts, max_new_tokens=3)      # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = engine.generate(prompts, max_new_tokens=3)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    found = [dict(e.stats) for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == spans.PREFIX + "place"]
    assert len(found) == 1
    args = {k: str(v) for k, v in found[0].items()}
    assert args["kv_bytes"] == str(eng.cache_bytes(CFG, 2, 15, kind="kv"))
    assert args["state_bytes"] == str(eng.cache_bytes(CFG, 2, 15,
                                                      kind="state"))
    assert (args["kv_pool"], args["state_pool"]) == ("hbm", "hbm")
    assert out.kv_pool == "hbm"
