"""The trace reduction: interval arithmetic on hand-made events, and the
reading of a small trace recorded with the harness's profiler settings."""
import os

import pytest

from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def _ev(name, s, e, **stats):
    return tr.Event(name, float(s), float(e), stats)


def _trace():
    ops = [_ev("fusion.1", 100, 200), _ev("fusion.1", 150, 250),
           _ev("read_hbm", 400, 700, long_name="f32[1,8,128]"),
           _ev("fusion.2", 900, 1000), _ev("outside", 1200, 1300)]
    host = [_ev(tr.WINDOW_SPAN, 0, 1100), _ev("bench.sweep", 50, 1050),
            _ev("bench.generate", 720, 890), _ev("PjitFunction(f)", 260, 390),
            _ev("compile", 720, 880)]
    return tr.Trace([tr.Device("/device:TPU:0", ops, [])], host, (0, 1100))


def test_merge_unions_and_clips():
    assert tr.merge([(5, 8), (0, 3), (2, 4), (7, 12)], 1, 10) == \
        [(1, 4), (5, 10)]
    assert tr.merge([(0, 1)], 2, 3) == []


def test_busy_idle_and_kernel_time():
    t = _trace()
    # busy: [100, 250] + [400, 700] + [900, 1000]; "outside" is not in
    # the window
    assert t.window_s == pytest.approx(1100e-9)
    assert tr.busy_s(t) == pytest.approx(550e-9)
    assert tr.idle_share(t) == pytest.approx(1 - 550 / 1100)
    assert tr.seconds(tr.ops_matching(t, r"^fusion")) == \
        pytest.approx(300e-9)
    assert [e.name for e in tr.ops_matching(t, r"f32\[1,8")] == ["read_hbm"]


def test_gaps_are_labelled_by_the_host():
    gaps = tr.gaps(_trace(), n=2)
    # idle: [0, 100], [250, 400], [700, 900], [1000, 1100]
    assert gaps == [["bench.generate/compile", pytest.approx(200e-9)],
                    ["bench.sweep/PjitFunction(f)", pytest.approx(150e-9)]]
    bd = tr.breakdown(_trace())
    assert bd["device_ops"][:2] == [["read_hbm", pytest.approx(300e-9)],
                                    ["fusion.1", pytest.approx(200e-9)]]
    assert len(bd["idle_gaps"]) == 4


def test_recorded_cpu_trace():
    """Three ``bench.generate`` spans inside ``bench.window``, recorded on
    the CPU: the window and the spans are read; the CPU backend writes
    no device plane, so nothing is busy and no gap is reported."""
    t = tr.load(os.path.join(DATA, "cpu_small.xplane.pb"))
    spans = [h for h in t.host if h.name == "bench.generate"]
    assert len(spans) == 3
    assert all(t.window[0] <= h.start_ns < h.end_ns <= t.window[1]
               for h in spans)
    assert t.devices == [] and tr.busy_s(t) == 0.0
    assert tr.breakdown(t) == {"device_ops": [], "idle_gaps": []}


def test_traced_run_reports_per_layer_metrics(monkeypatch):
    """A ``--trace 1`` run on the CPU: the counter is read; the device
    readers find no device plane and ``read_gbps`` no read above 32 MiB
    in a 256 KiB sweep, so they are left out; the line carries the
    traced window and a breakdown."""
    from bench.tests import small
    out = small.run(small.char_cell("hbm-stream"), monkeypatch, trace=True)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"compiles_per_curve"}
    assert out["metrics"]["compiles_per_curve"]["unit"] == "compiles/curve"
    assert out["device"]["window_s"] > 0 and out["device"]["busy_s"] == 0
    assert out["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_a_listed_metric_with_nothing_to_read_fails_on_the_chip():
    """On the chip every per-layer metric the cell lists must be read: a
    trace with no device operation leaves the kernel readers nothing,
    which fails the run there and only leaves the metrics out on the
    CPU."""
    from bench import harness
    cell = harness.load_cell("char.hbm-stream")
    host = [_ev(tr.WINDOW_SPAN, 0, 1100)]
    ctx = harness.RunContext(cell, 1, True, "TPU v5 lite", 1,
                             window=harness.Window(1.0, 7, 7, 0, {}),
                             trace=tr.Trace([], host, (0, 1100)),
                             counters={"jit_misses": 0})
    with pytest.raises(harness.BenchError, match="stream_read_roofline"):
        harness.read_per_layer(ctx, strict=True)
    assert set(harness.read_per_layer(ctx, strict=False)) == \
        {"compiles_per_curve"}


def test_readers_match_the_names_compiled_for_v5e():
    """The names the program's kernels and programs take when compiled
    for a v5e: the vmapped read kernel is an HLO custom call named
    ``vmap_jit_stream_read__.<n>``; the engine's prefill and decode loop
    are the modules ``jit_prefill`` and ``jit_scan``."""
    from bench import harness
    long_name = ("%vmap_jit_stream_read__.2 = f32[7,1,1]{2,1,0} "
                 "custom-call(f32[7,524288,128]{2,1,0} %a.1), "
                 'custom_call_target="tpu_custom_call"')
    ops = [_ev("vmap_jit_stream_read__.2", 100, 3_000_100,
               long_name=long_name,
               tf_op="jit(<lambda>)/vmap(jit(stream_read))/pallas_call"),
           _ev("vmap_jit_stream_copy__.2", 3_000_100, 4_000_000,
               long_name="f32[7,524288,128]")]
    mods = [_ev("jit_prefill(17)", 0, 2_000), _ev("jit_scan(18)", 2_000,
                                                   9_000),
            _ev("jit_scan_helper(19)", 9_000, 9_500)]
    host = [_ev(tr.WINDOW_SPAN, 0, 5_000_000)]
    t = tr.Trace([tr.Device("/device:TPU:0", ops, mods)], host,
                 (0, 5_000_000))
    ctx = harness.RunContext(harness.load_cell("char.hbm-stream"), 1, True,
                             "TPU v5 lite", 1, trace=t)
    roof = harness.load_module("metrics", "stream_read_roofline").read(ctx)
    # 7 x 524288 rows of 512 bytes and the 4-byte sum, in 3 ms, over
    # 819 GB/s
    assert roof == pytest.approx(100 * (7 * 524288 * 512 + 4) / 3e-3
                                 / 819e9)
    for reader, want in (("prefill_ms", "jit_prefill(17)"),
                         ("decode_hbm_roofline", "jit_scan(18)")):
        pattern = harness.load_module("metrics", reader).PROGRAM
        assert [e.name for e in tr.modules_matching(t, pattern)] == [want]


def test_recorded_tpu_trace():
    """A ``--trace 1`` run of ``char.hbm-stream`` recorded on a TPU v5e
    (three sweeps): the TPU profiler names each operation by its whole
    HLO instruction, from which the read kernel's buffer is parsed; the
    device readers all find something, and no share passes 100%."""
    from bench import harness
    t = tr.load(os.path.join(DATA, "tpu_char_hbm_stream.xplane.pb"))
    assert [d.name for d in t.devices] == ["/device:TPU:0"]
    reads = tr.ops_matching(t, r"stream_read")
    assert len(reads) == 366
    assert {tr.largest_operand(e) for e in reads} == {(524288, 128)}
    assert 0 < tr.busy_s(t) < t.window_s
    bd = tr.breakdown(t)
    assert bd["device_ops"][0][0] == "vmap_jit_stream_read__.2"
    assert len(bd["idle_gaps"]) == 10
    assert all(g[0].startswith("bench.sweep") for g in bd["idle_gaps"])
    ctx = harness.RunContext(harness.load_cell("char.hbm-stream"), 1, True,
                             "TPU v5 lite", 1, trace=t)
    for name in ("stream_read_roofline", "device_idle.curves"):
        value = harness.load_module("metrics", name).read(ctx)
        assert value is not None and 0 < value < 100, (name, value)


def test_idle_in_call_lies_within_the_device_idle_share():
    """``idle_in_call.serve`` counts the device's idle time inside the
    ``bench.generate`` spans only, so it is at most ``device_idle.serve``,
    which also counts the wait for the next call to be due."""
    from bench import harness
    ops = [_ev("fusion.1", 100, 300), _ev("fusion.2", 500, 600)]
    host = [_ev(tr.WINDOW_SPAN, 0, 1000), _ev("bench.generate", 50, 450),
            _ev("bench.generate", 480, 700), _ev("bench.wait", 700, 1000),
            _ev("PjitFunction(scan)", 300, 450)]
    t = tr.Trace([tr.Device("/device:TPU:0", ops, [])], host, (0, 1000))
    ctx = harness.RunContext(harness.load_cell("serve.qwen2-deck"), 1,
                             True, "TPU v5 lite", 1, trace=t)
    total = harness.load_module("metrics", "device_idle.serve").read(ctx)
    in_call = harness.load_module("metrics", "idle_in_call.serve").read(ctx)
    # idle [0, 100], [300, 500], [600, 1000]; inside the calls [50, 100],
    # [300, 450], [480, 500], [600, 700]
    assert total == pytest.approx(70.0)
    assert in_call == pytest.approx(32.0)
    assert in_call <= total
    # the recorded CPU trace has calls but no device plane; a trace with
    # a device and no call has nothing to read either
    ctx.trace = tr.load(os.path.join(DATA, "cpu_small.xplane.pb"))
    for name in ("device_idle.serve", "idle_in_call.serve"):
        assert harness.load_module("metrics", name).read(ctx) is None
    ctx.trace = tr.Trace(t.devices, host[:1], (0, 1000))
    assert harness.load_module("metrics", "idle_in_call.serve").read(
        ctx) is None


def test_recorded_tpu_serving_trace():
    """A ``--trace 1`` run of ``serve.qwen2-deck`` recorded on a TPU v5e
    (3 calls, seed 3160000013).  To keep the file small, each operation
    nested inside another (the decode steps inside the scan's ``while``),
    the operations' own stats, the async line and the HLO metadata plane
    were left out; the busy union is unchanged by that.  The prompt
    lengths are dealt again by the driver from the seed.  Every reader
    of the cell finds something and reads what the run printed."""
    import numpy as np
    from bench import harness
    from bench.drivers import serve
    t = tr.load(os.path.join(DATA, "tpu_serve_qwen2_deck.xplane.pb"))
    assert [d.name for d in t.devices] == ["/device:TPU:0"]
    assert len([h for h in t.host if h.name == "bench.generate"]) == 3
    cell = harness.load_cell("serve.qwen2-deck")
    state = serve.State(None, cell.config, cell.traffic,
                        np.random.default_rng(3160000013))
    for length in (256, 512, 1024, 2048):      # set-up's warm-up calls
        serve._prompts(state, length)
    lens = []
    for _ in range(3):
        lens.append(serve._prompt_len(state))
        serve._prompts(state, lens[-1])
    assert lens == [256, 512, 512]
    ctx = harness.RunContext(
        cell, 3160000013, True, "TPU v5 lite", 1, trace=t,
        counters={"jit_misses": 3}, window=harness.Window(
            2.1295031350000215, 3, 3, 0, {}, data={
                "prompt_lens": lens, "batch": 8, "new_tokens": 64}))
    got = {k: v["value"] for k, v in
           harness.read_per_layer(ctx, strict=True).items()}
    assert got == pytest.approx({
        "prefill_ms": 53.073074333333345,
        "decode_hbm_roofline": 87.81236596262058,
        "serve_mfu": 7.6341714217349965, "compiles_per_call": 1.0,
        "device_idle.serve": 53.13849511913487,
        "idle_in_call.serve": 26.11864681546947})
    assert got["idle_in_call.serve"] <= got["device_idle.serve"]
    bd = tr.breakdown(t)
    assert bd["device_ops"][0][0] == "while.38"
    assert bd["idle_gaps"][0][0].startswith("bench.wait")
