"""The idle share put down to the program's ``memscope.*`` spans: the
interval arithmetic on a hand-made trace, the four readers, their
partition of ``device_idle.curves``, and the readings of recorded
traces from a TPU v5e."""
import collections
import os

import pytest

from bench import harness
from bench import spans
from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
READERS = ("idle_build.curves", "idle_timed.curves", "idle_coord.curves",
           "idle_unattributed.curves")


def _ev(name, s, e):
    return tr.Event(name, float(s), float(e), {})


def _trace(devices=True):
    """Window [0, 1000].  Device busy [100, 300], [500, 600], [800, 850]
    (idle 650).  Host spans: plan [0, 50]; inputs [50, 120]; build
    [120, 400]; timed [400, 700]; readback [700, 720]; assemble
    [750, 900]; curvedb [900, 950]; a benchmark span and a JAX event
    over everything, which attribute nothing."""
    ops = [_ev("fusion.1", 100, 300), _ev("fusion.2", 500, 600),
           _ev("fusion.3", 800, 850)]
    host = [_ev(tr.WINDOW_SPAN, 0, 1000), _ev("bench.sweep", 0, 990),
            _ev("PjitFunction(<lambda>)", 0, 1000),
            _ev("memscope.plan", 0, 50), _ev("memscope.inputs", 50, 120),
            _ev("memscope.build", 120, 400), _ev("memscope.timed", 400, 700),
            _ev("memscope.readback", 700, 720),
            _ev("memscope.assemble", 750, 900),
            _ev("memscope.curvedb", 900, 950)]
    devs = [tr.Device("/device:TPU:0", ops, [])] if devices else []
    return tr.Trace(devs, host, (0, 1000))


def _ctx(trace):
    return harness.RunContext(harness.load_cell("char.hbm-stream"), 1, True,
                              "TPU v5 lite", 1, trace=trace)


def _read(trace):
    ctx = _ctx(trace)
    return {name: harness.load_module("metrics", name).read(ctx)
            for name in READERS + ("device_idle.curves",)}


def test_each_reader_takes_its_spans_idle_time():
    got = _read(_trace())
    # idle: [0,100] [300,500] [600,800] [850,1000]
    # build [120,400] -> idle [300,400] = 100
    # timed [400,700] -> idle [400,500] + [600,700] = 200
    # coord plan [0,50] + assemble [750,900] + curvedb [900,950]
    #   -> 50 + [750,800] 50 + [850,900] 50 + [900,950] 50 = 200
    # inputs [50,120] -> idle [50,100] = 50; readback [700,720] = 20
    # none: [720,750] + [950,1000] = 80
    assert got == {"idle_build.curves": pytest.approx(10.0),
                   "idle_timed.curves": pytest.approx(20.0),
                   "idle_coord.curves": pytest.approx(20.0),
                   "idle_unattributed.curves": pytest.approx(8.0),
                   "device_idle.curves": pytest.approx(65.0)}
    assert 100 * spans.idle_share(_trace(), ("inputs", "readback")) == \
        pytest.approx(7.0)


def test_the_readers_partition_the_idle_share():
    t = _trace()
    got = _read(t)
    rest = 100 * spans.idle_share(t, ("inputs", "readback"))
    assert sum(got[n] for n in READERS) + rest == \
        pytest.approx(got["device_idle.curves"])


def test_without_spans_all_idle_time_is_unattributed():
    t = _trace()
    t.host = [h for h in t.host if not h.name.startswith(spans.PREFIX)]
    got = _read(t)
    assert got["idle_build.curves"] == got["idle_timed.curves"] == \
        got["idle_coord.curves"] == 0.0
    assert got["idle_unattributed.curves"] == \
        pytest.approx(got["device_idle.curves"])


def test_no_device_plane_reads_nothing():
    assert all(v is None for v in _read(_trace(devices=False)).values())
    ctx = _ctx(None)
    for name in READERS:
        assert harness.load_module("metrics", name).read(ctx) is None


def test_shares_average_over_devices():
    t = _trace()
    # a second device busy over the whole window is never idle
    t.devices.append(tr.Device("/device:TPU:1", [_ev("f", 0, 1000)], []))
    got = _read(t)
    assert got["idle_build.curves"] == pytest.approx(5.0)
    assert got["device_idle.curves"] == pytest.approx(32.5)


def test_recorded_tpu_trace_without_spans():
    """The trace recorded before the program had spans: every idle
    millisecond is unattributed."""
    t = tr.load(os.path.join(DATA, "tpu_char_hbm_stream.xplane.pb"))
    got = _read(t)
    assert got["idle_build.curves"] == got["idle_timed.curves"] == \
        got["idle_coord.curves"] == 0.0
    assert 0 < got["idle_unattributed.curves"] < 100
    assert got["idle_unattributed.curves"] == \
        pytest.approx(got["device_idle.curves"])


def test_recorded_tpu_trace_with_spans():
    """A ``--trace 1`` run of ``char.hbm-stream`` recorded on a TPU v5e
    with the program's spans (three sweeps of 7 measurements, 5 of them
    built afresh): every span is there, the four readers read, none
    passes 100%, and with inputs and readback they add up to
    ``device_idle.curves`` within a point."""
    t = tr.load(os.path.join(DATA, "tpu_char_hbm_stream_spans.xplane.pb"))
    counts = collections.Counter(h.name[len(spans.PREFIX):] for h in t.host
                                 if h.name.startswith(spans.PREFIX))
    assert counts == {"plan": 3, "inputs": 27, "build": 21, "timed": 21,
                      "readback": 21, "assemble": 3, "curvedb": 3}
    got = _read(t)
    assert all(v is not None and 0 <= v <= 100 for v in got.values()), got
    rest = 100 * spans.idle_share(t, ("inputs", "readback"))
    assert sum(got[n] for n in READERS) + rest == \
        pytest.approx(got["device_idle.curves"], abs=1.0)
    # the program's build is where most of the idle time lies
    assert got["idle_build.curves"] == max(got[n] for n in READERS)
