"""The serving driver's open loop, on a stand-in engine: calls are due
at the traffic file's rate whatever the engine does, a call's latency
runs from when it was due, and the window is whole calls."""
import time
import types

import numpy as np
import pytest

from bench import harness
from bench.drivers import serve


class Engine:
    """Returns zero tokens at once; ``service_s`` makes each call take
    that long."""

    def __init__(self, service_s=0.0):
        self.service_s = service_s
        self.calls = 0

    def generate(self, tokens, *, max_new_tokens):
        self.calls += 1
        if self.service_s:
            time.sleep(self.service_s)
        return types.SimpleNamespace(
            tokens=np.zeros((tokens.shape[0], max_new_tokens), np.int32))


def _state(rate_per_s, engine):
    traffic = dict(harness.load_json("traffic", "qwen2-deck"),
                   rate_per_s=rate_per_s, deck=[[8, 2], [16, 1]], batch=2,
                   new_tokens=4)
    return serve.State(engine, {"vocab_size": 100}, traffic,
                       np.random.default_rng(3_000_000_007))


def test_arrivals_are_due_while_the_window_is_open():
    assert serve.arrivals(1.25, 50) == 63      # due at 0, 0.8, ..., 49.6 s
    assert serve.arrivals(1.2, 50) == 60       # 49.1(6) s is the last
    assert serve.arrivals(20, 0.25) == 5
    assert serve.arrivals(1.25, 50, max_units=20) == 20
    assert serve.arrivals(0.5, 1) == 1


def test_latency_counts_the_wait_behind_a_slow_engine():
    """Calls due every 100 ms: an engine that answers at once leaves no
    call waiting; slowed by a hook to 160 ms a call, each call waits
    60 ms longer than the one before, and its latency, from when it was
    due, grows by that wait."""
    fast = serve.window(_state(10.0, Engine()), None, 0.5)
    assert fast.units == 5
    assert max(fast.data["wait_ms"]) < 30
    assert max(fast.data["latency_ms"]) < 100

    engine = Engine()
    orig = engine.generate

    def slowed(tokens, **kw):
        time.sleep(0.16)
        return orig(tokens, **kw)
    engine.generate = slowed
    w = serve.window(_state(10.0, engine), None, 0.5)
    lat, wait = w.data["latency_ms"], w.data["wait_ms"]
    assert w.units == 5 and engine.calls == 5
    assert wait[0] < 30 and lat[0] >= 160
    for k in range(1, 5):
        assert wait[k] - wait[k - 1] == pytest.approx(60, abs=30)
        assert lat[k] - lat[k - 1] == pytest.approx(60, abs=30)
        assert lat[k] == pytest.approx(wait[k] + 160, abs=30)
    assert lat[4] > lat[0] + 180
    assert w.end_to_end["call_p90_ms"] == pytest.approx(
        float(np.percentile(lat, 90)))


def test_the_window_is_whole_calls_and_ends_on_the_last():
    """Calls due every 50 ms for 0.25 s: five arrive (the last at 0.2 s);
    each is served whole, and the window closes when the last one has
    its tokens, so its length is when that call was due plus its
    latency."""
    engine = Engine(service_s=0.03)
    w = serve.window(_state(20.0, engine), None, 0.25)
    assert w.units == w.attempted == len(w.data["calls"]) == 5
    assert engine.calls == 5 and w.failed == 0
    assert all(t.shape == (2, 4) for _p, t in w.data["calls"])
    assert w.seconds == pytest.approx(0.2 + w.data["latency_ms"][-1] / 1e3,
                                      abs=5e-3)
    assert w.end_to_end["gen_tok_s"] == pytest.approx(5 * 2 * 4 / w.seconds)


def test_the_rate_is_read_from_the_traffic_file():
    cell = harness.load_cell("serve.qwen2-deck")
    rate = cell.traffic["rate_per_s"]
    assert rate > 0 and round(rate / 0.05, 6) == round(rate / 0.05)
    for k in ("loop", "clients"):
        assert k not in cell.traffic
    state = _state(rate, Engine())
    state.traffic = dict(cell.traffic, deck=[[8, 2]], batch=2, new_tokens=4)
    seconds = 2.5 / rate                       # due at 0, 1/rate, 2/rate
    w = serve.window(state, None, seconds)
    assert w.units == serve.arrivals(rate, seconds) == 3
    assert w.seconds >= 2 / rate
