"""Drive characterize -> place -> serve once on one TPU chip and check
every result against a plain reference.

    python chip_smoke.py               # one chip: phases A, B, C
    python chip_smoke.py --four-chips  # the spmd ladder on a 4-chip host

Phase A characterizes the chip's pools through ``CoreCoordinator`` on
the ``tpu`` backend: every probe letter on ``hbm`` at 256 MiB (the
pointer chases walk 256 MiB chains), ``host``, and the VMEM-resident
kernels on ``vmem``.  Each point prints its achieved GB/s or ns, the
memory kind its operands lived in, and its checksum against a numpy
reference (a sum for streams, a chain walk for chases).  Pairs the
backend refuses print their reason.  Phase B builds a
``PlacementAdvisor`` from that chip-measured database.  Phase C serves
qwen2-1.5b at its published widths (random weights from a seed) through
``ServeEngine``: batch 8, 512-token prompts, 64 new tokens, three
``generate`` calls under the advisor's KV placement, each checked
against a teacher-forced ``lm.forward(mode="train")`` over the same
sequence, then one call with the KV cache in host memory.

``--four-chips`` runs only the executed-contention ``spmd`` ladder on
the 4-chip mesh for pools ``hbm`` and ``host``, beside the 1-engine
``tpu``-backend measurement of the same observer on device 0.

Everything runs in this one process, which holds the chip.  The last
line of standard output is one JSON object naming the device; it is
printed only when every check passed.  Without a TPU, or without the
repository's ``src/`` beside this file, the script exits non-zero.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

MiB = 1 << 20
STREAM_BYTES = 256 * MiB          # hbm streams and chase chains
VMEM_BYTES = 16 * MiB             # the vmem pool's resident kernels
PROBES = tuple("rswxycblmti")     # every probe letter of the registry
CHAR_ITERS = 20                   # passes per timing sample (chases: /10)

ARCH = "qwen2-1.5b"
BATCH, PROMPT, NEW_TOKENS, CALLS = 8, 512, 64, 3
# bf16 weights and activations over 28 layers: the engine's KV-cached
# decode and the reference's full-sequence forward round differently.
# Last-position logits must agree to within this share of the
# reference's largest |logit|; a greedy token may differ from the
# reference's argmax only where the two candidates' reference logits
# lie within the same margin of each other (a bf16 near-tie)
LOGIT_TOL = 2e-2

SPMD_BYTES = 64 * MiB
SPMD_ITERS = 20
SPMD_STRESSORS = 3


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def check_below_peak(platform, res) -> None:
    """A bandwidth above the memory's peak means the timing missed
    work the result was credited with."""
    peak = platform.memories[res.pool].peak_bw_gbps
    check(res.bandwidth_gbps <= peak,
          f"{res.pool}:{res.strategy} reads {res.bandwidth_gbps!r} GB/s, "
          f"above the {peak} GB/s peak of its memory")


# ---------------------------------------------------------------------------
# Plain references for the probe kernels' checksums
# ---------------------------------------------------------------------------


def chain_walk(nxt, steps: int) -> int:
    """Follow ``nxt`` from line 0 for ``steps`` dependent loads."""
    idx = 0
    for _ in range(steps):
        idx = int(nxt[idx])
    return idx


def reference_checksum(res, pool_kind: str) -> float:
    """What the probe kernel behind ``res`` must have returned, from
    numpy alone: the sum of the sequential-integer buffer for streams
    (plus what the kernel wrote), the final index for chases."""
    import numpy as np

    from repro.core import workloads as wl
    from repro.kernels import chase

    rows = wl.rows_for(res.buffer_bytes)
    n = rows * wl.LANE
    x = np.arange(n, dtype=np.float32).astype(np.float64)
    vmem = res.buffer_bytes <= wl.VMEM_KERNEL_BYTES or pool_kind == "vmem"
    s = res.strategy
    if s in ("r", "s", "c"):
        return float(x.sum())
    if s == "x":                      # read, add one, write back
        return float(x.sum() + n)
    if s in ("w", "y"):               # the last value stored per element
        return float(n * (7.0 if (s == "w" and vmem) else 1.0))
    if s == "b":                      # half the blocks read, half written
        blk = min(512, rows)
        if rows // blk < 8:           # the kernel keeps >= 8 blocks
            blk = max(b for b in range(1, rows // 8 + 1) if rows % b == 0)
        nb = rows // blk
        n_r = max(1, min(nb - 1, int(round(nb * 0.5))))
        return float(x[:n_r * blk * wl.LANE].sum()
                     + (nb - n_r) * blk * wl.LANE)
    if s in ("l", "m"):
        nxt = chase.make_chain(rows, res.chain_seed)
        return float(chain_walk(nxt, wl.chase_steps(rows)))
    if s == "t":
        nxt = chase.make_strided_chain(rows, 8)
        return float(chain_walk(nxt, wl.chase_steps(rows)))
    if s == "i":                      # powers of the identity: its trace
        return 128.0
    raise SmokeFailure(f"no reference for strategy {s!r}")


# ---------------------------------------------------------------------------
# Phase A: characterize
# ---------------------------------------------------------------------------


def phase_characterize(coord, *, stream_bytes=STREAM_BYTES,
                       vmem_bytes=VMEM_BYTES, iters=CHAR_ITERS):
    from repro.core.characterize import (characterize_specs,
                                         curvedb_from_result)
    from repro.kernels import ops

    log(f"== phase A: characterize (backend={coord.backend}, "
        f"interpret={ops._interp(None)})")
    specs, refused = characterize_specs(
        coord, pools=["hbm", "host"], buffer_bytes=stream_bytes,
        obs_strategies=PROBES, stress_strategies=("w",), iters=iters)
    vspecs, vrefused = characterize_specs(
        coord, pools=["vmem"], buffer_bytes=vmem_bytes,
        obs_strategies=PROBES, stress_strategies=("w",), iters=iters)
    refused.update(vrefused)
    for pair, why in sorted(refused.items()):
        log(f"A refused {pair}: {why}")
    t0 = time.perf_counter()
    result = coord.run_matrix(specs + vspecs)
    wall = time.perf_counter() - t0
    st = result.stats
    log(f"A measured {len(result.runs)} ladders in {wall!r} s "
        f"(compilation included): {st.measure_dispatches} measured "
        f"passes, {st.degraded_ladders} degraded, "
        f"{st.modeled_floor_ladders} at the modeled floor")
    check(st.degraded_ladders == 0 and st.modeled_floor_ladders == 0,
          "a characterization ladder degraded or fell to the model")

    for run in result.runs:
        res = run.scenarios[0].main
        kind = coord.pools.pool(res.pool).node.kind
        ref = reference_checksum(res, kind)
        got = res.checksum
        ok = got is not None and abs(got - ref) <= 1e-4 * max(1.0, abs(ref))
        rate = (f"{res.latency_ns!r} ns/load" if res.transactions
                else f"{res.elapsed_ns!r} ns busy" if res.strategy == "i"
                else f"{res.bandwidth_gbps!r} GB/s")
        log(f"A point {run.key} strategy={res.strategy} "
            f"bytes={res.buffer_bytes} {rate} "
            f"memory_kind={res.memory_kind or 'none (touches no memory)'} "
            f"checksum={got!r} reference={ref!r} "
            f"{'OK' if ok else 'MISMATCH'}")
        check(ok, f"checksum mismatch: {res.pool}:{res.strategy}")
        check_below_peak(coord.platform, res)
        if res.strategy != "i":
            want = coord.pools.pool(res.pool).effective_memory_kind()
            check(res.memory_kind == (want or "device"),
                  f"{res.pool}:{res.strategy} operands lived in "
                  f"{res.memory_kind}, not the pool's memory")
    db = curvedb_from_result(result, coord.platform.name,
                             backend=coord.backend)
    db.meta["refused"] = refused
    return db


# ---------------------------------------------------------------------------
# Phase B: place
# ---------------------------------------------------------------------------


def phase_place(db, coord):
    from repro.core.placement import PlacementAdvisor

    # the advisor weighs a pool by its bandwidth ("r") and latency
    # ("l") surfaces: only pools the chip measured both for take part
    pools = [p for p in ("hbm", "host")
             if {"r", "l"} <= {k.obs_strat for k in db.surfaces
                               if k.obs_pool == p}]
    log(f"== phase B: place (advisor pools {pools}; chip-measured "
        f"surfaces {len(db.surfaces)})")
    check(bool(pools), "no pool has chip-measured r and l surfaces")
    return PlacementAdvisor(db, coord.platform, pools=pools)


# ---------------------------------------------------------------------------
# Phase C: serve
# ---------------------------------------------------------------------------


def make_reference(cfg):
    """Teacher-forced logits of the plain training forward at the
    positions that predicted each generated token."""
    import functools

    import jax

    from repro.models import lm

    @functools.partial(jax.jit, static_argnames="prompt_len")
    def ref_logits(params, seq, prompt_len):
        hidden, _c, _a = lm.forward(params, seq[:, :-1], cfg=cfg,
                                    mode="train")
        return lm.unembed_logits(params, hidden[:, prompt_len - 1:], cfg)

    return ref_logits


def compare_to_reference(ref_logits, params, prompts, out, label: str):
    import jax.numpy as jnp
    import numpy as np

    seq = jnp.concatenate([prompts, out.tokens], axis=1)
    ref = np.asarray(ref_logits(params, seq, prompt_len=prompts.shape[1]),
                     np.float32)                      # (B, T, V)
    toks = np.asarray(out.tokens)
    scale = float(np.abs(ref).max())
    margin = LOGIT_TOL * scale
    ref_tok = ref.argmax(-1)
    mism = ref_tok != toks
    gap = (np.take_along_axis(ref, ref_tok[..., None], -1)[..., 0]
           - np.take_along_axis(ref, toks[..., None], -1)[..., 0])
    last = np.asarray(out.last_logits, np.float32)
    dlast = float(np.abs(last - ref[:, -1]).max())
    log(f"C {label}: {toks.size} tokens, {int(mism.sum())} differ from "
        f"the reference argmax (largest reference-logit gap "
        f"{float(gap[mism].max()) if mism.any() else 0.0!r}, allowed "
        f"{margin!r}); last-position logits max|diff|={dlast!r} vs "
        f"max|ref|={scale!r} (allowed {LOGIT_TOL} of it)")
    check(not mism.any() or float(gap[mism].max()) <= margin,
          f"{label}: greedy tokens differ from the reference forward")
    check(dlast <= margin, f"{label}: last-position logits disagree")


def phase_serve(advisor, coord, cfg=None, *, batch=BATCH, prompt=PROMPT,
                new_tokens=NEW_TOKENS, calls=CALLS):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import ServeConfig, get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import lm
    from repro.parallel.sharding import make_rules
    from repro.serve.engine import ServeEngine, cache_bytes

    cfg = cfg or get_config(ARCH)
    mesh = make_host_mesh(1, 1)
    rules = make_rules(cfg, mesh, global_batch=batch, shape_kind="decode")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    log(f"== phase C: serve {cfg.name} (d_model={cfg.d_model}, "
        f"layers={cfg.n_layers}, vocab={cfg.vocab_size}, "
        f"{n_params} params {cfg.param_dtype}); batch={batch} "
        f"prompt={prompt} new_tokens={new_tokens}; kv cache "
        f"{cache_bytes(cfg, batch, prompt + new_tokens)} B")
    engine = ServeEngine(cfg, params, rules, ServeConfig(),
                         advisor=advisor, pool_mgr=coord.pools)
    ref_logits = make_reference(cfg)
    rng = np.random.default_rng(0)
    first = None
    for call in range(calls):
        prompts = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                           (batch, prompt), np.int32))
        t0 = time.perf_counter()
        out = engine.generate(prompts, max_new_tokens=new_tokens)
        jax.block_until_ready(out.tokens)
        wall = time.perf_counter() - t0
        kind = coord.pools.pool(out.kv_pool).effective_memory_kind()
        log(f"C call {call}: kv_pool={out.kv_pool} "
            f"(memory kind {kind or 'device'}) tokens "
            f"{tuple(out.tokens.shape)} in {wall!r} s (wall, "
            f"compilation included on call 0)")
        compare_to_reference(ref_logits, params, prompts, out,
                             f"call {call}")
        if first is None:
            first = (prompts, out)

    prompts, out = first
    host = ServeEngine(cfg, params, rules, ServeConfig(kv_placement="host"),
                       advisor=advisor, pool_mgr=coord.pools)
    try:
        hout = host.generate(prompts, max_new_tokens=new_tokens)
        jax.block_until_ready(hout.tokens)
    except Exception as exc:          # what the chip does with host KV
        first_line = str(exc).strip().splitlines()[0][:300]
        log(f"C host KV: refused ({type(exc).__name__}: {first_line})")
        return
    same = bool(np.array_equal(np.asarray(hout.tokens),
                               np.asarray(out.tokens)))
    log(f"C host KV: ran with kv_pool={hout.kv_pool}; tokens "
        f"{'match' if same else 'DIFFER from'} the hbm run")
    check(same, "host-KV decode returned other tokens than the hbm run")


# ---------------------------------------------------------------------------
# --four-chips: the executed-contention spmd ladder
# ---------------------------------------------------------------------------


def phase_four_chips(*, nbytes=SPMD_BYTES, iters=SPMD_ITERS,
                     stressors=SPMD_STRESSORS):
    import jax

    from repro.core.coordinator import CoreCoordinator
    from repro.core.scenarios import (ObserverSpec, ScenarioSpec,
                                      StressorSpec)

    n_dev = len(jax.devices())
    log(f"== four chips: spmd ladder over {n_dev} devices")
    check(n_dev >= 2, "the spmd backend needs >= 2 devices")
    spmd = CoreCoordinator(backend="spmd")
    tpu = CoreCoordinator(backend="tpu")
    for pool in ("hbm", "host"):
        spec = ScenarioSpec(f"four.{pool}", ObserverSpec("r", pool, (nbytes,)),
                            (StressorSpec("w", pool, nbytes),),
                            iters=iters, max_stressors=stressors)
        why = spmd.refusal("r", pool, nbytes)
        if why is not None:
            log(f"4 {pool}: refused on the spmd backend: {why}")
            continue
        res = spmd.run_matrix([spec])
        st = res.stats
        run = res.runs[0]
        ex = run.execution
        one = tpu.run_matrix([spec]).runs[0].scenarios[0].main
        rungs = " ".join(f"k{s.n_stressors}={s.main.bandwidth_gbps!r}"
                         for s in run.scenarios)
        log(f"4 {pool}: rung0 spmd={run.scenarios[0].main.bandwidth_gbps!r}"
            f" GB/s vs tpu 1-engine={one.bandwidth_gbps!r} GB/s on "
            f"device 0; rungs [{rungs}] GB/s; fenced={ex['fenced']} "
            f"activity={ex['activity']} "
            f"timing_source={ex['timing_source']} "
            f"executed_rungs={ex['executed_rungs']} "
            f"operand_memory_kinds={ex.get('operand_memory_kinds')} "
            f"degraded={st.degraded_ladders} "
            f"modeled_floor={st.modeled_floor_ladders}")
        for res in [x.main for x in run.scenarios] + [one]:
            check_below_peak(spmd.platform, res)
        check(ex["fenced"], f"{pool}: the spmd ladder is not fenced")
        check(ex["activity"] == "pallas", f"{pool}: rungs ran no Pallas")
        check(st.degraded_ladders == 0 and st.modeled_floor_ladders == 0,
              f"{pool}: the spmd ladder degraded or fell to the model")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the spmd ladder on a 4-chip host")
    args = ap.parse_args(argv)
    # a fault inside the runtime prints the Python stack that led to it
    faulthandler.enable()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run the script "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind}) and no accelerator", file=sys.stderr)
        return 2
    from repro import compat
    from repro.core.coordinator import CoreCoordinator

    cache = compat.use_compile_cache()
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            phase_four_chips()
        else:
            coord = CoreCoordinator()
            check(coord.backend == "tpu",
                  f"coordinator resolved backend {coord.backend!r}")
            log(f"platform tree {coord.platform.name} for "
                f"{dev.device_kind}")
            db = phase_characterize(coord)
            advisor = phase_place(db, coord)
            phase_serve(advisor, coord)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
