"""Distribution tests that need a multi-device mesh.

jax fixes the device count at first init, and the main pytest process
must keep seeing ONE device (assignment requirement), so each test here
spawns a fresh interpreter with ``xla_force_host_platform_device_count``
set — the same mechanism launch/dryrun.py uses.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_forced(body: str, n_devices: int = 8, timeout: int = 480) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n_devices}"
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("SUBPROC_OK")
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    assert "SUBPROC_OK" in r.stdout
    return r.stdout


def test_main_process_sees_one_device():
    import jax
    assert len(jax.devices()) == 1


def test_production_mesh_shapes():
    run_forced("""
    import jax
    from repro.launch.mesh import make_production_mesh
    m = make_production_mesh()
    assert m.devices.shape == (16, 16) and m.axis_names == ("data", "model")
    m2 = make_production_mesh(multi_pod=True)
    assert m2.devices.shape == (2, 16, 16)
    assert m2.axis_names == ("pod", "data", "model")
    """, n_devices=512)


def test_dp_tp_train_step_matches_single_device():
    """The same reduced train step on a (2,2) mesh and on 1 device must
    produce identical losses and parameter updates — the sharding rules
    change placement, never math."""
    out = run_forced("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import TrainConfig, get_config
    from repro.models import lm
    from repro.parallel.sharding import make_rules
    from repro.train import step as step_mod

    cfg = get_config("qwen2-1.5b").reduced()
    tcfg = TrainConfig(total_steps=5, warmup_steps=1, loss_chunk=16)
    B, S = 4, 32
    tokens = (jnp.arange(B * S, dtype=jnp.int32).reshape(B, S) * 13) % cfg.vocab_size
    labels = jnp.roll(tokens, -1, axis=1)

    results = {}
    for shape in ((1, 1), (2, 2), (4, 2)):
        from repro import compat
        mesh = compat.make_mesh(shape, ("data", "model"))
        rules = make_rules(cfg, mesh, global_batch=B, shape_kind="train")
        state = step_mod.init_state(cfg, tcfg, jax.random.PRNGKey(0))
        specs = step_mod.state_specs(cfg, rules, tcfg, state["params"])
        sh = jax.tree.map(lambda s, sp: NamedSharding(mesh, sp), state, specs)
        state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
        bsh = NamedSharding(mesh, P(rules.batch if rules.batch else None, None))
        tk = jax.device_put(tokens, bsh)
        lb = jax.device_put(labels, bsh)
        step = jax.jit(step_mod.make_train_step(cfg, rules, tcfg))
        new_state, metrics = step(state, tk, lb, None)
        results[shape] = (float(metrics["loss"]),
                          np.asarray(jax.device_get(
                              jax.tree.leaves(new_state["params"])[0]),
                              np.float32))
    base_loss, base_p = results[(1, 1)]
    for shape in ((2, 2), (4, 2)):
        loss, p = results[shape]
        assert abs(loss - base_loss) < 3e-4, (shape, loss, base_loss)
        np.testing.assert_allclose(p, base_p, atol=3e-4)
    print("losses", {k: v[0] for k, v in results.items()})
    """, n_devices=8)
    assert "losses" in out


def test_decode_step_matches_single_device():
    run_forced("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import get_config
    from repro.models import lm
    from repro.parallel.sharding import make_rules
    from repro.serve import engine as eng

    cfg = get_config("gemma3-1b").reduced()
    B, PROMPT = 2, 12
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = (jnp.arange(B * PROMPT, dtype=jnp.int32).reshape(B, PROMPT) * 7) % cfg.vocab_size

    outs = {}
    for shape in ((1, 1), (2, 4)):
        from repro import compat
        mesh = compat.make_mesh(shape, ("data", "model"))
        rules = make_rules(cfg, mesh, global_batch=B, shape_kind="decode")
        prefill = jax.jit(eng.make_prefill_step(cfg, rules, max_len=PROMPT + 4))
        decode = jax.jit(eng.make_decode_step(cfg, rules))
        caches, logits = prefill(params, tokens, None)
        caches, logits2 = decode(params, caches, tokens[:, -1:],
                                 jnp.int32(PROMPT), None)
        outs[shape] = np.asarray(logits2)
    np.testing.assert_allclose(outs[(2, 4)], outs[(1, 1)], atol=3e-4)
    """, n_devices=8)


def test_gpipe_matches_sequential():
    run_forced("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.parallel.pipeline import make_gpipe, reference_pipeline
    from repro import compat
    mesh = compat.make_mesh((4,), ("stage",))
    def apply_stage(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 8, 8)) * 0.5,
              "b": jnp.zeros((4, 1, 8))}
    x = jax.random.normal(jax.random.PRNGKey(1), (7, 2, 8))
    run = jax.jit(make_gpipe(mesh, apply_stage, n_micro=7, x_spec=P()))
    y = run(params, x)
    yref = reference_pipeline(apply_stage, params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref), atol=1e-5)
    """, n_devices=4)


def test_compressed_psum_matches_f32_psum():
    run_forced("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.parallel import compression
    from repro import compat
    mesh = compat.make_mesh((8,), ("data",))

    def f(g):
        err = jax.tree.map(lambda x: jnp.zeros_like(x), g)
        mean, _ = compression.compressed_psum(g, err, "data")
        exact = jax.tree.map(lambda x: jax.lax.pmean(x, "data"), g)
        return mean, exact

    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 64))}
    fm = jax.shard_map(f, mesh=mesh, in_specs=({"w": P("data")},),
                       out_specs=({"w": P("data")}, {"w": P("data")}))
    mean, exact = fm(g)
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    np.testing.assert_allclose(np.asarray(mean["w"]),
                               np.asarray(exact["w"]), atol=scale)
    """, n_devices=8)


def test_dryrun_cell_on_8_devices():
    """The full dry-run path (lower+compile+analyze) on a small mesh."""
    out = run_forced("""
    import jax
    # reuse the dryrun cell machinery on a (2,4) mesh via monkeypatching
    import repro.launch.dryrun as dr
    import repro.launch.mesh as mesh_mod
    def small_mesh(*, multi_pod=False):
        shape = (2, 2, 2) if multi_pod else (2, 4)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        from repro import compat
        return compat.make_mesh(shape, axes)
    dr.make_production_mesh = small_mesh
    from repro.configs.base import get_config, SHAPES
    import dataclasses
    # shrink the shape so CPU lowering is fast
    SHAPES["train_4k"] = dataclasses.replace(
        SHAPES["train_4k"], seq_len=128, global_batch=8)
    cfg = get_config("qwen2-1.5b")
    object.__setattr__(cfg, "n_layers", 2)
    lowered, compiled, meta = dr.lower_cell("qwen2-1.5b", "train_4k")
    rec = dr.analyze_cell(compiled, meta, cfg, SHAPES["train_4k"])
    assert rec["hlo_flops"] > 0
    assert rec["bytes_per_device"] > 0
    assert rec["bottleneck"] in ("compute", "memory", "collective")
    print("bottleneck", rec["bottleneck"])
    """, n_devices=8)
    assert "bottleneck" in out


def test_spmd_backend_executes_fenced_ladder_on_8_devices():
    """ISSUE-2/4 acceptance: on an 8-virtual-device CPU mesh the spmd
    backend executes a k=0..3 ladder as ONE fused whole-ladder dispatch
    (DispatchStats proves it: one host-synchronous dispatch per ladder,
    per-rung elapsed from in-dispatch device clocks), the barrier
    dependency holds structurally on every scanned rung, and a
    multi-observer spec measuring two pools yields per-observer CurveDB
    curves whose every point was executed."""
    run_forced("""
    import jax
    from repro.core.characterize import characterize_matrix
    from repro.core.coordinator import (CoreCoordinator,
                                        build_rung_program,
                                        measured_region_is_fenced)
    from repro.core.exec.program import spmd_branch_fn as _spmd_branch_fn
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)
    import numpy as np
    assert len(jax.devices()) == 8

    BUF = 64 << 10
    spec = ScenarioSpec(
        "spmd-multi",
        (ObserverSpec("r", "hbm", (BUF,)),      # bandwidth observer
         ObserverSpec("l", "hbm", (BUF,))),     # latency observer
        (StressorSpec("w", "hbm", BUF),),
        iters=3, max_stressors=3)

    c = CoreCoordinator(backend="spmd")
    res = c.run_matrix([spec])
    # 2 observers x 4 rungs (k=0..3), ONE fused dispatch per LADDER
    assert res.stats.n_scenarios == 1
    assert res.stats.n_ladders == 2
    assert res.stats.spmd_rungs == 8
    assert res.stats.measure_dispatches == 2
    assert res.stats.host_sync_dispatches == \
        2 + res.stats.noisy_remeasures
    for run in res.runs:
        assert run.execution["backend"] == "spmd"
        assert run.execution["executed_rungs"] == [0, 1, 2, 3]
        assert run.execution["modeled_rungs"] == []
        assert run.execution["n_engines"] == 8
        assert run.execution["timing_source"] == "callback"
        assert run.execution["dispatches"] == \
            1 + run.execution["remeasures"]
        assert len(run.execution["rung_time_spread_ns"]) == 4
        for s in run.scenarios:
            assert s.source == "executed"
            assert s.main.elapsed_ns > 0

    # the executed program really carries the barrier dependency edge
    fns = [_spmd_branch_fn("r", None, 128, 3),
           _spmd_branch_fn("w", None, 128, 3),
           _spmd_branch_fn("i", None, 1, 3)]
    _mesh, f = build_rung_program(8, fns, [0, 1, 1, 1, 2, 2, 2, 2])
    xf = np.ones((8, 128, 128), np.float32)
    xi = np.zeros((8, 128, 128), np.int32)
    assert measured_region_is_fenced(f, xf, xi)

    # per-observer curves, executed provenance, in CurveDB
    db = characterize_matrix(c, [spec])
    assert set(db.curves) == {"hbm:r|hbm:w", "hbm:l|hbm:w"}
    for key in db.curves:
        assert len(db.curves[key]) == 4
        ex = db.provenance[key]["execution"]
        assert ex["backend"] == "spmd" and ex["fenced"]
        assert ex["executed_rungs"] == [0, 1, 2, 3]
    assert all(p.bandwidth_gbps > 0 for p in db.curves["hbm:r|hbm:w"])
    assert all(p.latency_ns > 0 for p in db.curves["hbm:l|hbm:w"])
    print("spmd ladder OK")
    """, n_devices=8)
