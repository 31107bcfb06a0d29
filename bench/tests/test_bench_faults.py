"""Each cell's check on the CPU: a sound run comes out correct, and a run
with the timed path broken underneath comes out not correct, once for
each fault the cell can have.  The harness's look for a chip is skipped;
everything else of a run (set-up, window, release, reference) runs."""
import jax.numpy as jnp
import pytest

from bench import control
from bench.tests import small


def _altered(orig, fn):
    def kernel(*a, **kw):
        return fn(orig(*a, **kw))
    return kernel


def _patch_ops(monkeypatch, name, make):
    from repro.kernels import ops
    monkeypatch.setattr(ops, name, make(getattr(ops, name)))


def test_char_stream_sound(monkeypatch):
    out = small.run(small.char_cell("hbm-stream"), monkeypatch)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 7 and out["metrics"]["curves_per_s"]["value"]


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_char_stream_faults(monkeypatch, fault):
    make = {"half_batch": control.half_read,
            "answer_altered": lambda f: _altered(f, lambda r: r * 1.01)
            }[fault]
    out = small.run(small.char_cell("hbm-stream"), monkeypatch,
                    hook=lambda _d: _patch_ops(monkeypatch, "stream_read",
                                               make))
    assert not out["correct"]
    assert out["checks"]["checksum_rel_err"]["value"] > \
        out["checks"]["checksum_rel_err"]["limit"]


def test_char_wss_sound_and_chase_altered(monkeypatch):
    sizes = (64 << 10, 256 << 10)
    out = small.run(small.char_cell("wss-latency", sizes), monkeypatch)
    assert out["correct"], out["checks"]
    for kernel in ("chase_vmem", "chase_hbm"):
        _patch_ops(monkeypatch, kernel,
                   lambda f: _altered(f, lambda r: r + 1))
    out = small.run(small.char_cell("wss-latency", sizes), monkeypatch)
    assert not out["correct"]
    assert out["checks"]["chase_index_mismatches"]["value"] > 0


def _stale_state(driver):
    """Decode returns its caches unchanged."""
    from repro.serve import engine
    orig = engine.make_decode_step

    def make(cfg, rules):
        step = orig(cfg, rules)

        def decode(params, caches, token, write_pos, frontend=None):
            return caches, step(params, caches, token, write_pos,
                                frontend)[1]
        return decode
    return engine, "make_decode_step", make


def _token_altered(driver):
    """Every sampled token is the one after the argmax."""
    from repro.serve import engine
    orig = engine.sample_token

    def sample(logits, key, temperature=0.0):
        return (orig(logits, key, temperature) + 1) % logits.shape[-1]
    return engine, "sample_token", sample


def _half_batch(driver):
    """Only the first half of the batch is generated; its rows stand in
    for the rest."""
    from repro.serve import engine
    orig = engine.ServeEngine.generate

    def generate(self, tokens, **kw):
        half = tokens.shape[0] // 2
        out = orig(self, tokens[:half], **kw)
        out.tokens = jnp.concatenate([out.tokens, out.tokens], axis=0)
        return out
    return engine.ServeEngine, "generate", generate


def test_serve_sound(monkeypatch):
    out = small.run(small.serve_cell(), monkeypatch, seconds=0.5)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"gen_tok_s", "call_p90_ms", "setup_s"}


@pytest.mark.parametrize("fault", [_stale_state, _token_altered,
                                   _half_batch])
def test_serve_faults(monkeypatch, fault):
    def hook(driver):
        monkeypatch.setattr(*fault(driver))
    out = small.run(small.serve_cell(), monkeypatch, seconds=0.5,
                    hook=hook)
    assert not out["correct"]
    gap = out["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
