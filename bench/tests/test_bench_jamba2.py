"""The ``serve.jamba2-longdoc`` cell on the CPU at a small size: the
seeded Jamba weights in the program's layout, the plain float32 Jamba
reference against the program's prefill and decode through its caches,
the cell's correctness check on a sound run and on each fault it must catch, the
fp8 control, the Jamba counts, and the cell's new trace readers on
recorded events."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts_jamba2 as cj
from bench import harness
from bench import jamba2_program as jp
from bench import trace as tr
from bench.reference import jamba2 as ref

# jamba2-3b's reduced() widths (hidden 64, 4 heads of 16, 2 KV heads,
# MLP 128, d_state 16, dt_rank 8) and its period-2 pattern over 4 layers
# (attention at 1 and 3), with a vocabulary of 512
SIZES = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 512, "attn_layer_period": 2, "attn_layer_offset": 1,
         "mamba_dt_rank": 8}
# the correctness check at the cell's limit needs logits of the full model's
# scale (the tied head's std grows with the square root of the hidden
# size): hidden 1024, as bench/tests/small.py sizes qwen2's
CHECK_SIZES = dict(SIZES, hidden_size=1024, intermediate_size=2048,
                   head_dim=256, vocab_size=8192, mamba_dt_rank=64)
SEED = 3_000_000_419


def small_cell(sizes=SIZES) -> harness.Cell:
    cell = harness.load_cell("serve.jamba2-longdoc")
    cell.config = dict(cell.config, **sizes, advisor=dict(
        cell.config["advisor"], buffer_bytes=256 << 10, iters=2))
    cell.traffic = dict(cell.traffic, deck=[[32, 2], [48, 1]], batch=2,
                        new_tokens=8)
    return cell


def program_config(cfg: dict):
    """The program's jamba2-3b config at the sizes of ``cfg``."""
    from repro.configs.base import get_config
    mc = get_config("jamba2-3b")
    return dataclasses.replace(
        mc, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        attn_every=cfg["attn_layer_period"],
        attn_offset=cfg["attn_layer_offset"],
        ssm=dataclasses.replace(mc.ssm, dt_rank=cfg["mamba_dt_rank"]))


def test_the_cell_files_agree_with_the_program():
    """At full size the bench file and the registered config agree, and
    the counts give the size of the program's parameter pytree."""
    from repro.models import lm
    cell = harness.load_cell("serve.jamba2-longdoc")
    mc = jp.program_config(cell.config)
    shapes = jax.eval_shape(lambda k: lm.init_params(mc, k),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert cj.weights(cell.config) == n == 3_029_337_472
    assert (cj.n_attention(cell.config), cj.n_mamba(cell.config)) == (2, 26)
    assert [i for i in range(28) if mc.layer_kind(i) == "attn"] == [7, 21]
    assert cell.traffic["deck"] == [[16384, 1]] and cell.traffic["batch"] == 1
    assert cell.config["reduced"] == []


def test_program_layout_holds_the_reference_weights():
    cfg = small_cell().config
    params = jp.make_params(SEED, cfg, program_config(cfg))
    key = ref.base_key(SEED)
    for layer in range(4):
        w = ref.layer_weights(key, layer, cfg, ref.is_attention(cfg, layer))
        p = jax.tree.map(lambda a: a[layer // 2],
                         params["scan"][f"p{layer % 2}"])
        np.testing.assert_array_equal(np.asarray(w["down_w"]),
                                      np.asarray(p["mlp"]["w_out"]))
        if ref.is_attention(cfg, layer):
            np.testing.assert_array_equal(
                np.asarray(w["q_w"]).reshape(64, 4, 16),
                np.asarray(p["attn"]["wq"]))
        else:
            np.testing.assert_array_equal(
                np.asarray(w["in_w"][:, 128:]),
                np.asarray(p["ssm"]["w_xz"][:, 1]))
            np.testing.assert_array_equal(np.asarray(w["A_log"]),
                                          np.asarray(p["ssm"]["A_log"]))


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_prefill_then_decode_matches_the_reference(precision):
    """The program's prefill (the Pallas scan, interpret mode) and then
    its decode through the KV cache and the recurrent state, against
    the reference's full forward over the same tokens.

    With the weights in f32 the two compute the same equations in the
    same type: within 2e-4 of the logits' scale (f32 rounding and
    summation order).  In bf16, as served, the mixer keeps x, dt, B, C
    and y in bf16 between its matmuls, as Jamba's bf16 path does: within
    8% of the scale of these small logits (this seed reads 5.9%, and
    2.0% with the mixer alone in f32).  The serving path against the
    program's own bf16 forward of the whole sequence, whose roundings it
    shares but for the decode attention and the scan's order: within 3%
    (this seed reads 1.4%)."""
    from repro.serve import engine as eng
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.sharding import make_rules
    cfg = small_cell().config
    mc = program_config(cfg)
    params = jp.make_params(SEED, cfg, mc)
    if precision == "f32":
        mc = dataclasses.replace(mc, param_dtype="float32",
                                 compute_dtype="float32")
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    prompt, total = 40, 46
    toks = np.random.default_rng(1).integers(0, 512, (2, total), np.int32)
    want = np.asarray(ref.logits_at(SEED, cfg, toks,
                                    list(range(prompt - 1, total)),
                                    q_block=16))
    rules = make_rules(mc, make_host_mesh(1, 1), global_batch=2,
                       shape_kind="decode")
    caches, logits = eng.make_prefill_step(mc, rules, max_len=total)(
        params, jnp.asarray(toks[:, :prompt]))
    got = [np.asarray(logits, np.float32)]
    decode = eng.make_decode_step(mc, rules)
    for pos in range(prompt, total):
        caches, logits = decode(params, caches,
                                jnp.asarray(toks[:, pos:pos + 1]),
                                jnp.int32(pos))
        got.append(np.asarray(logits, np.float32))
    got = np.stack(got, 1)[..., :512]
    tol = {"f32": 2e-4, "bf16": 0.08}[precision]
    assert np.abs(got - want).max() <= tol * np.abs(want).max()
    if precision == "bf16":
        from repro.models import lm
        h, _c, _a = lm.forward(params, jnp.asarray(toks), cfg=mc,
                               mode="train")
        own = np.asarray(lm.unembed_logits(params, h, mc),
                         np.float32)[:, prompt - 1:, :512]
        assert np.abs(got - own).max() <= 0.03 * np.abs(own).max()


def _run(cell, monkeypatch, hook=None, seconds=0.5):
    monkeypatch.setattr(jp, "program_config", program_config)
    return harness.run_cell(cell, seed=SEED, seconds=seconds, trace=False,
                            on_chip=False, driver_hook=hook)


def _state_unchanged():
    """Decode returns the SSM state (and conv tail) it was given, while
    the KV cache is updated."""
    from repro.serve import engine
    orig = engine.make_decode_step

    def make(cfg, rules):
        step = orig(cfg, rules)

        def decode(params, caches, token, write_pos, frontend=None):
            new, logits = step(params, caches, token, write_pos, frontend)
            keep = {top: {name: (g if engine.cache_kind(g) == "state"
                                 else new[top][name])
                          for name, g in grp.items()}
                    for top, grp in caches.items()}
            return keep, logits
        return decode
    return engine, "make_decode_step", make


def _token_altered():
    """Every sampled token is the one after the argmax."""
    from repro.serve import engine
    orig = engine.sample_token

    def sample(logits, key, temperature=0.0):
        return (orig(logits, key, temperature) + 1) % logits.shape[-1]
    return engine, "sample_token", sample


def test_serve_jamba2_sound(monkeypatch):
    out = _run(small_cell(CHECK_SIZES), monkeypatch)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"gen_tok_s", "call_p90_ms", "setup_s"}
    assert out["correct"]


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered])
def test_serve_jamba2_faults(monkeypatch, fault):
    out = _run(small_cell(CHECK_SIZES), monkeypatch,
               hook=lambda _d: monkeypatch.setattr(*fault()))
    assert not out["correct"]
    gap = out["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_fp8_control_fails_where_the_program_passes(monkeypatch):
    """The cell's correctness check of one window's calls: the served tokens come
    out correct; with the control in the program's place (the tokens a
    float8 reference puts first) the same check comes out not
    correct."""
    drv = harness.load_module("drivers", "serve_jamba2")
    for name in ("_call", "_prompt_len", "CONTROL_QUANT", "check"):
        assert hasattr(drv, name)
    cell = small_cell(CHECK_SIZES)
    monkeypatch.setattr(jp, "program_config", program_config)
    ctx = harness.RunContext(cell, SEED, False, "cpu", 1)
    state = drv.setup(ctx)
    calls = [drv._call(state, 48)[:2] for _ in range(2)]
    drv.release(state)
    ctx.window = harness.Window(1.0, 2, 2, 0, {}, data={"calls": calls})
    [sound] = drv.check(ctx)
    monkeypatch.setattr(drv, "CONTROL_QUANT", "fp8")
    [control] = drv.check(ctx)
    assert sound.ok and not control.ok, (sound, control)


# ---------------------------------------------------------------------------
# Counts and trace readers
# ---------------------------------------------------------------------------


def test_jamba_counts_by_hand():
    cfg = dict(small_cell().config)
    # a mixer: in 64*256 + conv 4*128 + bias 128 + x_proj 128*40 + norms
    # 40 + dt_proj 8*128 + bias 128 + A 128*16 + D 128 + out 128*64
    assert cj.mamba_params(cfg) == (16384 + 512 + 128 + 5120 + 40 + 1024
                                    + 128 + 2048 + 128 + 8192)
    # q 64*64, k and v 64*32 each, o 64*64
    assert cj.attention_params(cfg) == 4096 + 4096 + 4096
    # 2 mixers, 2 attention layers, 4 MLPs of 3*64*128 and 2 norms of 64,
    # the tied embedding and the final norm
    assert cj.weights(cfg) == (2 * cj.mamba_params(cfg) + 2 * 12288
                               + 4 * (24576 + 128) + 512 * 64 + 64)
    per_token = 2 * (2 * (16384 + 5120 + 1024 + 8192) + 2 * 12288
                     + 4 * 24576)
    assert cj.decode_flops(cfg, 1, 10) == (per_token + 2 * 4 * 4 * 16 * 10
                                           + 2 * 64 * 512)
    assert cj.prefill_flops(cfg, 2, 3) == 2 * (3 * per_token
                                               + 2 * 4 * 4 * 16 * 6
                                               + 2 * 64 * 512)
    # the f32 state (128 x 16) and bf16 conv tail (3 x 128) of 2 mixers,
    # read and written; KV of 2 layers over 10 positions, 2 heads of 16
    assert cj.decode_bytes(cfg, 1, 10) == (
        2 * cj.weights(cfg) + 2 * 2 * 10 * 2 * 2 * 16
        + 2 * 2 * (4 * 128 * 16 + 2 * 3 * 128))
    full = harness.load_cell("serve.jamba2-longdoc").config
    # 5.72 GFLOP a token: twice the 2.86 B parameters outside the embedding
    assert cj._linear_flops_per_token(full) == 2 * (
        cj.weights(full) - 65536 * 2560 - 2560 - 28 * 2 * 2560
        - 26 * (4 * 5120 + 5120 + 192 + 5120 + 5120 * 16 + 5120))


def _event(name, start, end):
    return tr.Event(name, start, end)


SCAN_INLINE = ("%selective_scan.3 = (f32[1,64,256]{2,1,0:T(8,128)}, "
               "f32[1,16,256]{2,1,0:T(8,128)}) custom-call(bf16[1,64,256]"
               "{2,1,0:T(8,128)} %x, f32[1,64,256]{2,1,0} %dt, f32[16,256]"
               "{1,0} %a, f32[1,64,16]{2,1,0} %b, f32[1,64,16]{2,1,0} %c, "
               "f32[1,16,256]{2,1,0} %s), custom_call_target=\"tpu_custom_"
               "call\", operand_layout_constraints={bf16[1,64,256]{2,1,0}, "
               "f32[1,64,256]{2,1,0}, f32[16,256]{1,0}, f32[1,64,16]{2,1,0},"
               " f32[1,64,16]{2,1,0}, f32[1,16,256]{2,1,0}}, "
               "frontend_attributes={kernel_metadata={}}")
SCAN_BY_NAME = ("%selective_scan.1 = (f32[1,64,256]{2,1,0:T(8,128)}, "
                "f32[1,16,256]{2,1,0:T(8,128)}) custom-call(%x, %dt, %a, %b,"
                " %c, %s), custom_call_target=\"tpu_custom_call\", "
                "operand_layout_constraints={bf16[1,64,256]{2,1,0}, "
                "f32[1,64,256]{2,1,0}, f32[16,256]{1,0}, f32[1,64,16]{2,1,0}"
                ", f32[1,64,16]{2,1,0}, f32[1,16,256]{2,1,0}}, "
                "frontend_attributes={kernel_metadata={}}")
# y and the final state out; x (bf16), dt, A, B, C and the state in
SCAN_BYTES = (4 * 64 * 256 + 4 * 16 * 256 + 2 * 64 * 256 + 4 * 64 * 256
              + 4 * 16 * 256 + 2 * 4 * 64 * 16 + 4 * 16 * 256)


@pytest.mark.parametrize("name", [SCAN_INLINE, SCAN_BY_NAME])
def test_scan_bytes_from_the_instruction(name):
    roof = harness.load_module("metrics", "selective_scan_roofline")
    assert cj.array_bytes(roof.call_shapes(name)) == SCAN_BYTES


def _run_ctx(trace, units=2, prompt_lens=(32, 32)):
    cell = small_cell()
    window = harness.Window(1.0, units, units, 0, {}, data={
        "batch": 2, "new_tokens": 8, "prompt_lens": list(prompt_lens)})
    return harness.RunContext(cell, SEED, True, "TPU v5 lite", 1,
                              window=window, trace=trace)


def test_readers_on_recorded_events():
    """A window of 10 ms: two scan calls of 1 ms, two ``jit_scan``
    programs of 2 ms, and a 1 ms ``memscope.place`` span over idle
    device time."""
    ms = 1e6
    ops = [_event(SCAN_INLINE, 1 * ms, 2 * ms),
           _event(SCAN_BY_NAME, 3 * ms, 4 * ms),
           _event("%while.38 = (..) while(..), body=%region_1", 5 * ms,
                  9 * ms)]
    modules = [_event("jit_scan(123)", 5 * ms, 7 * ms),
               _event("jit_scan(123)", 7 * ms, 9 * ms)]
    host = [_event("bench.window", 0, 10 * ms),
            _event("memscope.place", 9 * ms, 10 * ms)]
    trace = tr.Trace([tr.Device("/device:TPU:0", ops, modules)], host,
                     (0, 10 * ms))
    run = _run_ctx(trace)
    read = {m: harness.load_module("metrics", m).read(run) for m in (
        "selective_scan_ms", "selective_scan_roofline",
        "decode_hbm_roofline.jamba2", "serve_mfu.jamba2",
        "idle_place.serve")}
    assert read["selective_scan_ms"] == pytest.approx(1.0)
    assert read["selective_scan_roofline"] == pytest.approx(
        100 * 2 * SCAN_BYTES / 2e-3 / 819e9)
    steps = sum(cj.decode_bytes(run.cell.config, 2, 32 + i + 1)
                for i in range(7))
    assert read["decode_hbm_roofline.jamba2"] == pytest.approx(
        100 * 2 * steps / 4e-3 / 819e9)
    assert read["serve_mfu.jamba2"] == pytest.approx(
        100 * 2 * cj.generate_flops(run.cell.config, 2, 32, 8)
        / (10e-3 * 197e12))
    # the device is idle from 9 to 10 ms, all of it under the span
    assert read["idle_place.serve"] == pytest.approx(10.0)


def test_readers_find_nothing_without_their_events():
    """A program without the scan kernel or the place span (the parent
    of this cell's program) reads nothing, and raises nothing."""
    ms = 1e6
    trace = tr.Trace([tr.Device("/device:TPU:0",
                                [_event("%fusion.1 = f32[8]", 0, ms)], [])],
                     [_event("bench.window", 0, 10 * ms)], (0, 10 * ms))
    run = _run_ctx(trace)
    for m in ("selective_scan_ms", "selective_scan_roofline",
              "decode_hbm_roofline.jamba2", "idle_place.serve"):
        assert harness.load_module("metrics", m).read(run) is None, m
