"""Driver: greedy ``generate`` calls through the program's serving engine.

Set-up characterizes the configuration's advisor pool (``hbm`` ``r`` and
``l``) through the toolkit and builds a ``PlacementAdvisor`` from it, as
a deployment does before it serves; makes the weights on the device from
the seed; builds a ``ServeEngine`` under that advisor; and makes one call
at every prompt length of the traffic mix, which compiles and warms each
shape.

The window is an open loop: one call arrives every ``1 / rate_per_s``
seconds, due whether or not the previous call has finished, and waits
its turn while the engine is busy.  Arrivals are due while less than
``--seconds`` have passed; the window ends when the last call that
arrived has its tokens on the host, so every call is whole.  A call's
latency runs from when it was due to its tokens on the host, and so
counts the wait that a slow call imposes on the calls behind it.  Each
call's prompt length is dealt from a deck of lengths reshuffled from
the seed, its prompt ids drawn uniformly over the vocabulary.

The check: after the window, with the engine freed, a sample of the
window's calls drawn from the seed (the longest prompt always among
them) goes through the plain float32 reference, teacher-forced on the
prompt and the served tokens.  The number compared is the widest gap by
which a served token's reference logit lies below the reference's best
at that position.
"""
from __future__ import annotations

import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import Check, Window
from bench.reference import qwen2 as ref

# The limit, set on a TPU v5e at the cell's own size and traffic: sound
# runs read 0.0252-0.0524 on 26 seeds, the fp8 control 0.598-0.910 on
# 12 of them (PERF.md, "How correct is decided").  0.2 lies 3.8 times
# above the largest sound reading and 3.0 times below the smallest
# control reading: more room above, since fresh seeds read higher.
LOGIT_GAP_LIMIT = 0.2
# The control (``bench/control.py``) sets a lower precision here: the
# check then judges, in place of the served tokens, the tokens that the
# reference in that precision puts first at the same positions.
CONTROL_QUANT = "f32"


class State:
    def __init__(self, engine, cfg, traffic, rng):
        self.engine = engine
        self.cfg = cfg
        self.traffic = traffic
        self.rng = rng
        self.deck = []


def _advisor(ctx, coord):
    from repro.core.characterize import (characterize_specs,
                                         curvedb_from_result)
    from repro.core.placement import PlacementAdvisor

    adv = ctx.cell.config["advisor"]
    specs, refused = characterize_specs(
        coord, pools=[adv["pool"]], buffer_bytes=int(adv["buffer_bytes"]),
        obs_strategies=("r", "l"), stress_strategies=(adv["stressor"],),
        iters=int(adv["iters"]))
    if refused:
        raise RuntimeError(f"the advisor's characterization is refused: "
                           f"{refused}")
    with jax.profiler.TraceAnnotation("bench.characterize"):
        db = curvedb_from_result(coord.run_matrix(specs),
                                 coord.platform.name, backend=coord.backend)
    return PlacementAdvisor(db, coord.platform, pools=[adv["pool"]])


def build_engine(ctx, cfg, traffic, mc=None):
    """The engine the window drives; ``mc`` overrides the program's
    config (tests run a small one on the CPU)."""
    from bench import qwen2_program as qp
    from repro.configs.base import ServeConfig
    from repro.core.coordinator import CoreCoordinator
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.sharding import make_rules
    from repro.serve.engine import ServeEngine

    mc = mc or qp.program_config(cfg)
    coord = CoreCoordinator(
        backend=ctx.cell.config["advisor"]["backend"]
        if ctx.on_chip else "interpret")
    advisor = _advisor(ctx, coord)
    params = qp.make_params(ctx.seed, cfg, mc)
    rules = make_rules(mc, make_host_mesh(1, 1),
                       global_batch=int(traffic["batch"]),
                       shape_kind="decode")
    return ServeEngine(mc, params, rules, ServeConfig(), advisor=advisor,
                       pool_mgr=coord.pools)


def setup(ctx, mc=None):
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    engine = build_engine(ctx, cfg, traffic, mc)
    state = State(engine, cfg, traffic, np.random.default_rng(ctx.seed))
    for length in sorted({int(n) for n, _ in traffic["deck"]}):
        _call(state, length)          # compile and warm this shape
    return state


def _prompt_len(state) -> int:
    if not state.deck:
        deck = [int(n) for n, k in state.traffic["deck"]
                for _ in range(int(k))]
        state.deck = list(state.rng.permutation(deck))
    return int(state.deck.pop())


def _prompts(state, length: int) -> np.ndarray:
    b = int(state.traffic["batch"])
    vocab = int(state.cfg["vocab_size"])
    return state.rng.integers(0, vocab, (b, length), dtype=np.int32)


def _generate(state, prompts: np.ndarray) -> np.ndarray:
    """One call, its tokens on the host."""
    with jax.profiler.TraceAnnotation("bench.generate"):
        out = state.engine.generate(
            jnp.asarray(prompts),
            max_new_tokens=int(state.traffic["new_tokens"]))
        return np.asarray(out.tokens)


def _call(state, length: int):
    prompts = _prompts(state, length)
    t0 = time.perf_counter()
    tokens = _generate(state, prompts)
    return prompts, tokens, time.perf_counter() - t0


def arrivals(rate_per_s: float, seconds: float, max_units: int = 0) -> int:
    """How many calls are due in a window of ``seconds``: one at 0 and
    one every ``1 / rate_per_s`` seconds while less than ``seconds``
    have passed (at most ``max_units``, where that is set)."""
    n = max(1, math.ceil(seconds * rate_per_s - 1e-9))
    return min(n, max_units) if max_units else n


def window(state, ctx, seconds: float, max_units: int = 0) -> Window:
    rate = float(state.traffic["rate_per_s"])
    period = 1.0 / rate
    n = arrivals(rate, seconds, max_units)
    calls, lat_s, wait_s = [], [], []
    t0 = time.perf_counter()
    for k in range(n):
        prompts = _prompts(state, _prompt_len(state))
        due = t0 + k * period
        early = due - time.perf_counter()
        if early > 0:
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(early)
        start = time.perf_counter()
        tokens = _generate(state, prompts)
        done = time.perf_counter()
        calls.append((prompts, tokens))
        lat_s.append(done - due)
        wait_s.append(start - due)
    wall = done - t0
    lat_ms = np.asarray(lat_s) * 1e3
    wait_ms = np.asarray(wait_s) * 1e3
    print(f"bench: {n} calls due every {period * 1e3!r} ms; latency median "
          f"{float(np.median(lat_ms))!r} ms, p90 "
          f"{float(np.percentile(lat_ms, 90))!r} ms; wait for the engine "
          f"median {float(np.median(wait_ms))!r} ms, longest "
          f"{float(wait_ms.max())!r} ms, last call's {float(wait_ms[-1])!r} "
          f"ms", file=sys.stderr, flush=True)
    tokens = sum(t.size for _p, t in calls)
    return Window(
        seconds=wall, units=n, attempted=n, failed=0,
        end_to_end={"gen_tok_s": tokens / wall,
                    "call_p90_ms": float(np.percentile(lat_ms, 90))},
        data={"calls": calls,
              "prompt_lens": [p.shape[1] for p, _t in calls],
              "latency_ms": lat_ms.tolist(), "wait_ms": wait_ms.tolist(),
              "batch": int(state.traffic["batch"]),
              "new_tokens": int(state.traffic["new_tokens"])})


def release(state) -> None:
    state.engine = None


def sample_calls(calls, n: int, seed: int):
    """``n`` calls drawn from ``seed``: one of the longest prompts, and
    the rest uniformly from the others."""
    rng = np.random.default_rng([seed, 1])
    lens = np.asarray([p.shape[1] for p, _ in calls])
    longest = np.flatnonzero(lens == lens.max())
    first = int(rng.choice(longest))
    rest = [i for i in range(len(calls)) if i != first]
    more = rng.choice(rest, size=min(n - 1, len(rest)), replace=False)
    return [first] + sorted(int(i) for i in more)


def token_gaps(seed, cfg, prompts, tokens, quant="f32"):
    """Per served token, how far its reference logit lies below the
    reference's best at the position that produced it; and, for
    ``quant`` other than f32, the same gap of the token that the
    ``quant`` reference puts first."""
    s = prompts.shape[1]
    n = tokens.shape[1]
    seq = np.concatenate([prompts, tokens[:, :-1]], axis=1)
    pos = list(range(s - 1, s - 1 + n))
    logits = np.asarray(ref.logits_at(seed, cfg, seq, pos), np.float32)
    vocab = logits.shape[-1]
    best = logits.max(-1)
    if quant != "f32":
        low = np.asarray(ref.logits_at(seed, cfg, seq, pos, quant=quant))
        tokens = low.argmax(-1)
    inside = tokens < vocab
    got = np.take_along_axis(logits, np.minimum(tokens, vocab - 1)[..., None],
                             -1)[..., 0]
    return np.where(inside, best - got, np.inf)


def _worst_gap(ctx, quant: str) -> float:
    calls = ctx.window.data["calls"]
    picked = sample_calls(calls, int(ctx.cell.traffic["check_calls"]),
                          ctx.seed)
    worst = 0.0
    for i in picked:
        prompts, tokens = calls[i]
        worst = max(worst, float(token_gaps(ctx.seed, ctx.cell.config,
                                            prompts, tokens, quant).max()))
    return worst


def check(ctx) -> list:
    return [Check("served_logit_gap", _worst_gap(ctx, CONTROL_QUANT),
                  LOGIT_GAP_LIMIT)]
