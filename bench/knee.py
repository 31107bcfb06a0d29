"""Find the highest call rate a serving cell sustains, on the chip, at
the cell's own size.

    python bench/knee.py --workload serve.qwen2-deck --seeds 11,12,13 \\
        --seconds 50 --fractions 0.7,0.8,0.9,1.0

Set-up once, as the cell's driver makes it (the first seed's weights).
Then, for every seed, a closed loop: each call issued as soon as the
previous one returns, for ``--seconds``, the deck and prompts dealt from
that seed; its calls completed per second, and the median call time at
each prompt length.  The highest sustained rate is the median of those
rates.  Then the cell's own open loop at each fraction of it, on the
first seed: the latency tail and the wait for the engine, whose growth
to the window's end shows a rate the engine does not sustain.

Prints one JSON line per loop and a last one with the highest rate and
four fifths of it rounded to 0.05 calls/s, the ``rate_per_s`` that a
traffic file at that load states.  A measuring tool for ``PERF.md``; the
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def closed_loop(driver, state, seconds: float) -> dict:
    by_len = {}
    n = 0
    t0 = time.perf_counter()
    while True:
        prompts, _tokens, dt = driver._call(state, driver._prompt_len(state))
        by_len.setdefault(prompts.shape[1], []).append(dt * 1e3)
        n += 1
        wall = time.perf_counter() - t0
        if wall >= seconds:
            break
    return {"calls": n, "wall_s": wall, "calls_per_s": n / wall,
            "median_ms_by_prompt": {str(k): statistics.median(v)
                                    for k, v in sorted(by_len.items())}}


def main(argv=None) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".jax_compile_cache")
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--fractions", default="0.8,0.9,1.0")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from bench import harness
    from repro import compat

    if jax.devices()[0].platform != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 3
    compat.use_compile_cache()
    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    devs = jax.devices()
    ctx = harness.RunContext(cell, seeds[0], True, devs[0].device_kind,
                             len(devs))
    driver = harness.load_module("drivers", cell.traffic["driver"])
    t0 = time.perf_counter()
    state = driver.setup(ctx)
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)

    rates = []
    for seed in seeds:
        state.rng, state.deck = np.random.default_rng(seed), []
        line = dict(closed_loop(driver, state, args.seconds), seed=seed,
                    loop="closed")
        rates.append(line["calls_per_s"])
        print(json.dumps(line), flush=True)
    highest = statistics.median(rates)

    traffic = state.traffic
    for f in (float(x) for x in args.fractions.split(",")):
        state.rng, state.deck = np.random.default_rng(seeds[0]), []
        state.traffic = dict(traffic, rate_per_s=f * highest)
        w = driver.window(state, ctx, args.seconds)
        wait = w.data["wait_ms"]
        print(json.dumps({
            "loop": "open", "fraction": f, "rate_per_s": f * highest,
            "calls": w.units, "window_s": w.seconds,
            **w.end_to_end,
            "latency_median_ms": statistics.median(w.data["latency_ms"]),
            "wait_median_ms": statistics.median(wait),
            "wait_last_ms": wait[-1], "wait_max_ms": max(wait)}),
            flush=True)
    state.traffic = traffic
    driver.release(state)
    print(json.dumps({"highest_calls_per_s": highest, "rates": rates,
                      "rate_per_s_at_0.8": round(0.8 * highest / 0.05)
                      * 0.05}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
