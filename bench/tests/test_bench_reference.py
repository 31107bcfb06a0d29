"""The plain references against the program, on the CPU at small sizes:
the probe checksums' arithmetic, and the float32 qwen2 forward against
the program's forward on the same seeded weights.  Also the control: the
reference in float8 in the program's place fails the serving check."""
import numpy as np
import pytest

from bench.drivers import serve
from bench.reference import probes
from bench.reference import qwen2 as ref
from bench.tests import small


def test_probe_arithmetic_matches_the_program():
    from repro.core import workloads as wl
    from repro.kernels import chase
    for nbytes in (512, 64 << 10, 256 << 10, 3 << 20, 256 << 20):
        assert probes.rows_for(nbytes) == wl.rows_for(nbytes)
        assert probes.chase_steps(probes.rows_for(nbytes)) == \
            wl.chase_steps(wl.rows_for(nbytes))
    for n, seed in ((2, 0), (17, 3), (512, 1)):
        assert np.array_equal(probes.sattolo(n, seed),
                              chase.make_chain(n, seed))
        assert np.array_equal(probes.strided_cycle(n, 8),
                              chase.make_strided_chain(n, 8))
    assert probes.VMEM_KERNEL_BYTES == wl.VMEM_KERNEL_BYTES


def test_chase_reference_visits_every_line():
    nxt = probes.sattolo(1000, 7)
    seen = {0}
    idx = 0
    for _ in range(999):
        idx = int(nxt[idx])
        seen.add(idx)
    assert len(seen) == 1000 and int(nxt[idx]) == 0


def test_seed_keys_past_32_bits():
    import jax
    a = jax.random.key_data(ref.base_key(5))
    b = jax.random.key_data(ref.base_key(5 + (1 << 32)))
    assert not np.array_equal(a, b)
    ref.base_key((1 << 62) + 3)
    with pytest.raises(ValueError):
        ref.base_key(-1)


TINY = dict(small.SERVE_SIZES, hidden_size=64, intermediate_size=128,
            num_attention_heads=4, head_dim=16, vocab_size=512,
            rope_theta=1e6, rms_norm_eps=1e-6, name="qwen2-1.5b")


def test_program_layout_holds_the_reference_weights():
    from bench import qwen2_program as qp
    mc = small.program_config(TINY)
    seed = 3_000_000_123
    params = qp.make_params(seed, TINY, mc)
    for layer in range(TINY["num_hidden_layers"]):
        w = ref.layer_weights(ref.base_key(seed), layer, TINY)
        p = params["scan"]["p0"]
        np.testing.assert_array_equal(
            np.asarray(w["q_w"]).reshape(64, 4, 16),
            np.asarray(p["attn"]["wq"][layer]))
        np.testing.assert_array_equal(np.asarray(w["down_w"]),
                                      np.asarray(p["mlp"]["w_out"][layer]))
    assert params["embed"].shape[0] == mc.padded_vocab


def test_reference_forward_matches_the_program_forward():
    import jax.numpy as jnp
    from bench import qwen2_program as qp
    from repro.models import lm
    mc = small.program_config(TINY)
    seed = 11
    params = qp.make_params(seed, TINY, mc)
    toks = np.random.default_rng(0).integers(0, 512, (2, 40), np.int32)
    h, _c, _a = lm.forward(params, jnp.asarray(toks), cfg=mc, mode="train")
    got = np.asarray(lm.unembed_logits(params, h, mc), np.float32)
    want = np.asarray(ref.logits_at(seed, TINY, toks, list(range(40)),
                                    q_block=8))
    assert got.shape == want.shape
    # the program computes in bf16, the reference in f32
    assert np.abs(got - want).max() <= 0.03 * np.abs(want).max()
    # query blocks change nothing but the order of work
    whole = np.asarray(ref.logits_at(seed, TINY, toks, [5, 39]))
    np.testing.assert_allclose(whole, want[:, [5, 39]], atol=1e-5)


def test_fp8_control_fails_where_the_program_passes(monkeypatch):
    """Served tokens of the program come out correct by the driver's
    check; with the control in the program's place (the tokens that a
    float8 reference puts first) the same check comes out not correct."""
    import jax.numpy as jnp
    from bench import harness
    from bench import qwen2_program as qp
    from repro.configs.base import ServeConfig
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.sharding import make_rules
    from repro.serve.engine import ServeEngine
    cell = small.serve_cell()
    cfg = cell.config
    mc = small.program_config(cfg)
    seed = 2_000_000_017
    params = qp.make_params(seed, cfg, mc)
    rules = make_rules(mc, make_host_mesh(1, 1), global_batch=4,
                       shape_kind="decode")
    eng = ServeEngine(mc, params, rules, ServeConfig(kv_placement="hbm"))
    prompts = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (4, 64), np.int32)
    tokens = np.asarray(eng.generate(jnp.asarray(prompts),
                                     max_new_tokens=16).tokens)
    ctx = harness.RunContext(cell, seed, False, "cpu", 1, window=harness.Window(
        1.0, 1, 1, 0, {}, data={"calls": [(prompts, tokens)]}))
    [sound] = serve.check(ctx)
    monkeypatch.setattr(serve, "CONTROL_QUANT", "fp8")
    [control] = serve.check(ctx)
    assert sound.ok and sound.value <= serve.LOGIT_GAP_LIMIT
    assert not control.ok and control.value > serve.LOGIT_GAP_LIMIT
