"""Per-architecture smoke tests (assignment requirement):

Every assigned arch instantiates a REDUCED same-family config and runs
one forward + one train step on CPU, asserting output shapes and no NaNs.
Also covers the period decomposition and analytic parameter counts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS
from repro.configs.base import ShapeSpec, TrainConfig, get_config
from repro.models import blocks, lm
from repro.parallel.sharding import make_rules
from repro.train import step as step_mod

B, S = 2, 32


def _frontend(cfg, b, s, seed=0):
    rng = np.random.default_rng(seed)
    if cfg.frontend == "audio":
        return {"frame_embeds": jnp.asarray(
            rng.standard_normal((b, s, cfg.d_model), np.float32) * 0.02)}
    if cfg.frontend == "vlm":
        return {"prefix_embeds": jnp.asarray(
            rng.standard_normal((b, cfg.n_prefix_embeds, cfg.d_model),
                                np.float32) * 0.02)}
    return None


@pytest.fixture(scope="module")
def host_mesh():
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(1, 1)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_forward_smoke(arch):
    cfg = get_config(arch).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.arange(B * S, dtype=jnp.int32).reshape(B, S) % cfg.vocab_size
    h, caches, aux = lm.forward(params, tokens, cfg=cfg, mode="train",
                                frontend=_frontend(cfg, B, S))
    assert h.shape == (B, S, cfg.d_model)
    assert caches is None
    assert not np.isnan(np.asarray(h, np.float32)).any(), arch
    logits = lm.unembed_logits(params, h, cfg)
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert not np.isnan(np.asarray(logits)).any(), arch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_train_step_smoke(arch, host_mesh):
    cfg = get_config(arch).reduced()
    tcfg = TrainConfig(total_steps=4, warmup_steps=1, microbatches=1,
                       remat="layer", loss_chunk=16)
    rules = make_rules(cfg, host_mesh, global_batch=B, shape_kind="train")
    state = step_mod.init_state(cfg, tcfg, jax.random.PRNGKey(0))
    step = jax.jit(step_mod.make_train_step(cfg, rules, tcfg))
    tokens = jnp.arange(B * S, dtype=jnp.int32).reshape(B, S) % cfg.vocab_size
    labels = jnp.roll(tokens, -1, axis=1)
    new_state, metrics = step(state, tokens, labels, _frontend(cfg, B, S))
    loss = float(metrics["loss"])
    assert np.isfinite(loss), arch
    assert float(metrics["grad_norm"]) > 0.0, arch
    # params actually moved
    delta = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32) -
                                      b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(new_state["params"]),
                                jax.tree.leaves(state["params"])))
    assert delta > 0.0, arch


# ---------------------------------------------------------------------------
# Period decomposition
# ---------------------------------------------------------------------------


def test_period_gemma3():
    cfg = get_config("gemma3-4b")
    plan = blocks.make_plan(cfg)
    assert plan.period == 6              # 5 local : 1 global
    assert plan.n_layers == cfg.n_layers
    # layer 5, 11, ... are global
    assert cfg.layer_is_global_attn(5)
    assert not cfg.layer_is_global_attn(0)


def test_period_jamba():
    cfg = get_config("jamba-v0.1-52b")
    plan = blocks.make_plan(cfg)
    assert plan.period == 8              # attn at idx 4 of each 8 block
    assert cfg.layer_kind(4) == "attn"
    assert cfg.layer_kind(0) == "ssm"
    assert cfg.layer_is_moe(1) and not cfg.layer_is_moe(0)


def test_period_dense():
    for arch in ("qwen2-1.5b", "glm4-9b", "mamba2-370m"):
        assert blocks.make_plan(get_config(arch)).period == 1


def test_scan_equals_unrolled():
    """The period-scanned forward must equal a layer-by-layer unroll."""
    cfg = get_config("gemma3-1b").reduced()   # period 2 reduced
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    tokens = jnp.arange(B * S, dtype=jnp.int32).reshape(B, S) % cfg.vocab_size
    h_scan, _, _ = lm.forward(params, tokens, cfg=cfg, mode="train")

    # manual unroll using the same per-layer apply
    from repro.models.common import rmsnorm
    plan = blocks.make_plan(cfg)
    x = lm.embed_tokens(params, tokens, cfg)
    for r in range(plan.n_full):
        for p in range(plan.period):
            lp = jax.tree.map(lambda a: a[r], params["scan"][f"p{p}"])
            x, _, _ = blocks.layer_apply(lp, x, cfg=cfg, layer_idx=p,
                                         mode="train")
    for j in range(plan.n_tail):
        x, _, _ = blocks.layer_apply(
            params["tail"][f"t{j}"], x, cfg=cfg,
            layer_idx=plan.tail_layer_idx(j), mode="train")
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    np.testing.assert_allclose(np.asarray(h_scan), np.asarray(x),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# Analytic parameter counts vs actual pytrees
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_count_matches_pytree(arch):
    cfg = get_config(arch).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    analytic = cfg.n_params()
    assert abs(actual - analytic) / max(actual, 1) < 0.02, \
        (arch, actual, analytic)


def test_full_config_param_counts_plausible():
    """Sanity: full configs land near their published sizes."""
    expect = {"qwen2-1.5b": (1.2e9, 2.1e9),
              "glm4-9b": (8.0e9, 10.5e9),
              "gemma3-4b": (3.0e9, 4.8e9),
              "olmoe-1b-7b": (6.0e9, 7.8e9),
              "mamba2-370m": (3.3e8, 4.6e8),
              "jamba-v0.1-52b": (4.6e10, 5.6e10),
              "phi3.5-moe-42b-a6.6b": (3.9e10, 4.5e10)}
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).n_params()
        assert lo <= n <= hi, (arch, n)


def test_active_params_moe():
    cfg = get_config("olmoe-1b-7b")
    assert cfg.n_active_params() < 0.35 * cfg.n_params()


def test_long500k_applicability():
    runnable = {a for a in ALL_ARCHS if get_config(a).sub_quadratic}
    assert runnable == {"gemma3-4b", "gemma3-1b", "mamba2-370m",
                        "jamba-v0.1-52b", "jamba2-3b"}
