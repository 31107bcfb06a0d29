"""Serving driver: batched prefill+decode with MEMSCOPE-placed KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --reduced --batch 4 --prompt-len 32 --new-tokens 32

The pools are characterized on the backend the platform resolves to:
compiled probe kernels on a TPU, the queueing model elsewhere.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.configs.base import ServeConfig, get_config
from repro.core.characterize import characterize
from repro.core.coordinator import CoreCoordinator
from repro.core.placement import PlacementAdvisor
from repro.launch.mesh import describe, make_host_mesh
from repro.models import lm
from repro.parallel.sharding import make_rules
from repro.serve.engine import ServeEngine, cache_bytes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.launch.serve")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kv-placement", default="auto",
                    choices=["auto", "hbm", "host"])
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    compat.use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh(args.data, args.model)
    rules = make_rules(cfg, mesh, global_batch=args.batch,
                       shape_kind="decode")

    # MEMSCOPE: characterize, then let the advisor place the KV cache
    coord = CoreCoordinator()
    db = characterize(coord, pools=["hbm", "host"],
                      obs_strategies=("r", "l"), stress_strategies=("w",),
                      iters=10)
    if db.meta["refused"]:
        print(f"[serve] refused on the {coord.backend} backend: "
              f"{db.meta['refused']}")
    advisor = PlacementAdvisor(db, coord.platform)

    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    engine = ServeEngine(cfg, params, rules,
                         ServeConfig(kv_placement=args.kv_placement),
                         advisor=advisor, pool_mgr=coord.pools)

    max_len = args.prompt_len + args.new_tokens
    kv_bytes = cache_bytes(cfg, args.batch, max_len)
    print(f"[serve] arch={cfg.name} mesh={describe(mesh)} "
          f"kv_cache={kv_bytes / 2**20:.2f} MiB")

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len), dtype=np.int32))
    frontend = None
    if cfg.frontend == "vlm":
        frontend = {"prefix_embeds": jnp.asarray(rng.standard_normal(
            (args.batch, cfg.n_prefix_embeds, cfg.d_model),
            dtype=np.float32) * 0.02)}
    elif cfg.frontend == "audio":
        frontend = {"frame_embeds": jnp.asarray(rng.standard_normal(
            (args.batch, args.prompt_len, cfg.d_model),
            dtype=np.float32) * 0.02)}

    t0 = time.time()
    out = engine.generate(prompts, max_new_tokens=args.new_tokens,
                          temperature=args.temperature, seed=args.seed,
                          frontend=frontend)
    jax.block_until_ready(out.tokens)
    wall = time.time() - t0
    total_new = args.batch * args.new_tokens
    print(f"[serve] kv_pool={out.kv_pool} "
          f"{total_new} tokens in {wall:.2f}s "
          f"({total_new / wall:.1f} tok/s incl. compile)")
    print(f"[serve] sample: {np.asarray(out.tokens[0, :16]).tolist()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
