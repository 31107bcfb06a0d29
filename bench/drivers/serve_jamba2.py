"""Driver: greedy ``generate`` calls of a Jamba model through the
program's serving engine.

Set-up, the open-loop window and the draw of the checked calls are
those of ``bench/drivers/serve.py``: the advisor's characterization,
the weights from the seed, an engine under that advisor, one warm-up
call at every prompt length, and calls due every ``1 / rate_per_s``
seconds.  What differs is the model: the weights are the Jamba
generator's (``bench/reference/jamba2.py``), laid out by
``bench/jamba2_program.py``, and the check runs the plain float32
Jamba reference, teacher-forced on each sampled call's prompt and served
tokens: the number compared is the widest gap by which a served token's
reference logit lies below the reference's best at that position.
"""
from __future__ import annotations

import numpy as np

from bench.drivers import serve
from bench.drivers.serve import (State, _call, _prompt_len, arrivals,  # noqa: F401
                                 release, sample_calls, window)
from bench.harness import Check
from bench.reference import jamba2 as ref

# The limit, set on a TPU v5e at the cell's own size and traffic: sound
# runs read 0.0914-0.2646 on 15 seeds, the fp8 control 2.298-3.258 on 6
# of them (PERF.md, "How correct is decided").  0.8 lies 3.0 times above
# the largest sound reading and 2.9 times below the smallest control
# reading.  The sound readings are five times qwen2's: the mixer keeps x,
# dt, B, C and y in bf16 between its matmuls, as Jamba's bf16 path does.
LOGIT_GAP_LIMIT = 0.8
# The control (``bench/control.py``) sets a lower precision here: the
# check then judges, in place of the served tokens, the tokens that the
# reference in that precision puts first at the same positions.
CONTROL_QUANT = "f32"


def build_engine(ctx, cfg, traffic, mc=None):
    """The engine the window drives; ``mc`` overrides the program's
    config (tests run a small one on the CPU)."""
    from bench import jamba2_program as jp
    from repro.configs.base import ServeConfig
    from repro.core.coordinator import CoreCoordinator
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.sharding import make_rules
    from repro.serve.engine import ServeEngine

    mc = mc or jp.program_config(cfg)
    coord = CoreCoordinator(
        backend=ctx.cell.config["advisor"]["backend"]
        if ctx.on_chip else "interpret")
    advisor = serve._advisor(ctx, coord)
    params = jp.make_params(ctx.seed, cfg, mc)
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


def check(ctx) -> list:
    calls = ctx.window.data["calls"]
    worst = 0.0
    for i in sample_calls(calls, int(ctx.cell.traffic["check_calls"]),
                          ctx.seed):
        prompts, tokens = calls[i]
        worst = max(worst, float(token_gaps(ctx.seed, ctx.cell.config,
                                            prompts, tokens,
                                            CONTROL_QUANT).max()))
    return [Check("served_logit_gap", worst, LOGIT_GAP_LIMIT)]
