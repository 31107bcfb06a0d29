import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# The lines above MUST run before any other import (jax locks the
# platform and device count on first init).  The dry-run is CPU-only:
# it never claims a chip, so the per-cell child processes of ``--all``
# never compete for one.  This module is the multi-pod dry-run:
# it AOT-lowers + compiles every (architecture x input shape) cell on the
# production meshes — 16x16 (one pod) and 2x16x16 (two pods) — proving
# that every sharding in the system is coherent at 256/512 chips, and it
# extracts the roofline inputs (FLOPs / bytes / collective bytes) from
# the compiled artifact.  No array is ever allocated: inputs are
# ShapeDtypeStructs.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-4b \
#       --shape train_4k --mesh pod
#   PYTHONPATH=src python -m repro.launch.dryrun --all   (every cell)
import argparse
import gc
import json
import subprocess
import sys
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import hlo as hlo_mod
from repro.analysis import roofline
from repro.configs.base import (SHAPES, MeshConfig, ModelConfig, ShapeSpec,
                                TrainConfig, default_microbatches, get_config)
from repro.configs import ALL_ARCHS
from repro.launch.mesh import describe, make_production_mesh
from repro.models import lm
from repro.parallel.sharding import make_rules, mesh_axis_size
from repro.serve import engine as serve_engine
from repro.train import step as train_step_mod

DEFAULT_OUT = "experiments/dryrun"


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------


def frontend_specs(cfg: ModelConfig, batch: int, seq: int,
                   kind: str) -> Optional[Dict[str, jax.ShapeDtypeStruct]]:
    """Stub modality frontends: precomputed frame/patch embeddings."""
    if cfg.frontend == "audio":
        s = 1 if kind == "decode" else seq
        return {"frame_embeds": jax.ShapeDtypeStruct(
            (batch, s, cfg.d_model), jnp.float32)}
    if cfg.frontend == "vlm" and kind != "decode":
        return {"prefix_embeds": jax.ShapeDtypeStruct(
            (batch, cfg.n_prefix_embeds, cfg.d_model), jnp.float32)}
    return None


def input_specs(arch: str, shape_name: str) -> Dict[str, Any]:
    """Every model input for one cell, as ShapeDtypeStructs."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    b, s = shape.global_batch, shape.seq_len
    toks = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if shape.kind == "train":
        return {"tokens": toks, "labels": toks,
                "frontend": frontend_specs(cfg, b, s, "train")}
    if shape.kind == "prefill":
        return {"tokens": toks,
                "frontend": frontend_specs(cfg, b, s, "prefill")}
    # decode: one new token against a seq_len KV cache
    return {"token": jax.ShapeDtypeStruct((b, 1), jnp.int32),
            "caches": lm.cache_struct(cfg, b, s),
            "write_pos": jax.ShapeDtypeStruct((), jnp.int32),
            "frontend": frontend_specs(cfg, b, 1, "decode")}


# ---------------------------------------------------------------------------
# Per-cell training configuration (activation-residency knobs)
# ---------------------------------------------------------------------------


def pick_loss_chunk(cfg: ModelConfig, shape: ShapeSpec, mesh) -> int:
    """Bound the per-device logits chunk to ~256 MiB f32."""
    dp = 1
    for a in ("pod", "data"):
        dp *= mesh_axis_size(mesh, a)
    tp = mesh_axis_size(mesh, "model")
    b_dev = max(1, shape.global_batch // dp)
    v_dev = cfg.padded_vocab // tp if cfg.padded_vocab % tp == 0 \
        else cfg.padded_vocab
    budget = 256 << 20
    chunk = budget // max(1, b_dev * v_dev * 4)
    chunk = max(128, min(1024, (chunk // 128) * 128 or 128))
    return min(chunk, shape.seq_len)


def cell_train_config(cfg: ModelConfig, shape: ShapeSpec, mesh,
                      mesh_cfg: MeshConfig, *,
                      overrides: Optional[dict] = None) -> TrainConfig:
    tc = TrainConfig(
        microbatches=default_microbatches(cfg, shape, mesh_cfg),
        loss_chunk=pick_loss_chunk(cfg, shape, mesh),
        remat="layer", zero1=True)
    if overrides:
        import dataclasses
        tc = dataclasses.replace(tc, **overrides)
    return tc


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


def _shardings_for(mesh, struct, specs):
    return jax.tree.map(lambda s, sp: NamedSharding(mesh, sp), struct, specs)


def _batch_sharding(mesh, rules, struct):
    b = rules.batch if rules.batch else None
    if struct is None:
        return None
    def spec_of(s):
        return NamedSharding(mesh, P(b, *([None] * (len(s.shape) - 1))))
    return jax.tree.map(spec_of, struct)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               train_overrides: Optional[dict] = None,
               q_chunk: int = 256):
    """Build + lower + compile one cell. Returns (lowered, compiled, meta)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        raise SystemExit(
            f"{arch} is pure full-attention: long_500k is skipped by "
            f"design (DESIGN.md §Arch-applicability)")
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_cfg = MeshConfig(pods=2 if multi_pod else 1)
    rules = make_rules(cfg, mesh, global_batch=shape.global_batch,
                       shape_kind=shape.kind)
    specs = input_specs(arch, shape_name)
    t0 = time.time()

    with mesh:
        if shape.kind == "train":
            tcfg = cell_train_config(cfg, shape, mesh, mesh_cfg,
                                     overrides=train_overrides)
            state_struct = train_step_mod.state_struct(cfg, tcfg)
            state_specs = train_step_mod.state_specs(
                cfg, rules, tcfg, state_struct["params"])
            state_sh = _shardings_for(mesh, state_struct, state_specs)
            tok_sh = _batch_sharding(mesh, rules, specs["tokens"])
            fe_sh = _batch_sharding(mesh, rules, specs["frontend"])
            step = train_step_mod.make_train_step(
                cfg, rules, tcfg, microbatches=tcfg.microbatches)
            jitted = jax.jit(step, in_shardings=(
                state_sh, tok_sh, tok_sh, fe_sh), donate_argnums=(0,))
            lowered = jitted.lower(state_struct, specs["tokens"],
                                   specs["labels"], specs["frontend"])
            meta_extra = {"microbatches": tcfg.microbatches,
                          "loss_chunk": tcfg.loss_chunk,
                          "remat": tcfg.remat, "zero1": tcfg.zero1}
        else:
            params_struct = jax.eval_shape(
                lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
            pspecs = lm.param_specs(rules, params_struct)
            params_sh = _shardings_for(mesh, params_struct, pspecs)
            fe_sh = _batch_sharding(mesh, rules, specs["frontend"])
            if shape.kind == "prefill":
                prefill = serve_engine.make_prefill_step(
                    cfg, rules, max_len=shape.seq_len, q_chunk=q_chunk)
                tok_sh = _batch_sharding(mesh, rules, specs["tokens"])
                jitted = jax.jit(prefill, in_shardings=(
                    params_sh, tok_sh, fe_sh))
                lowered = jitted.lower(params_struct, specs["tokens"],
                                       specs["frontend"])
            else:  # decode
                decode = serve_engine.make_decode_step(cfg, rules)
                cache_specs = lm.cache_specs(rules, specs["caches"])
                cache_sh = _shardings_for(mesh, specs["caches"],
                                          cache_specs)
                tok_sh = _batch_sharding(mesh, rules, specs["token"])
                pos_sh = NamedSharding(mesh, P())
                jitted = jax.jit(decode, in_shardings=(
                    params_sh, cache_sh, tok_sh, pos_sh, fe_sh),
                    donate_argnums=(1,))
                lowered = jitted.lower(params_struct, specs["caches"],
                                       specs["token"], specs["write_pos"],
                                       specs["frontend"])
            meta_extra = {"q_chunk": q_chunk}

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    meta = {"arch": arch, "shape": shape_name,
            "mesh": describe(mesh), "multi_pod": multi_pod,
            "n_devices": mesh.size,
            "t_lower_s": round(t_lower, 1),
            "t_compile_s": round(t_compile, 1), **meta_extra}
    return lowered, compiled, meta


def analyze_cell(compiled, meta, cfg: ModelConfig,
                 shape: ShapeSpec) -> Dict[str, Any]:
    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            mem[k] = int(getattr(ma, k, 0))
    bytes_per_device = (mem.get("argument_size_in_bytes", 0)
                        + mem.get("temp_size_in_bytes", 0)
                        + mem.get("output_size_in_bytes", 0)
                        - mem.get("alias_size_in_bytes", 0))
    xla_cost = compiled.cost_analysis() or {}
    cost = hlo_mod.analyze(compiled.as_text())
    terms = roofline.compute_terms(
        cost, cfg=cfg, shape=shape, mesh_desc=meta["mesh"],
        n_devices=meta["n_devices"], bytes_per_device=bytes_per_device)
    rec = dict(meta)
    rec.update(
        memory_analysis=mem,
        bytes_per_device=bytes_per_device,
        xla_cost={k: float(v) for k, v in xla_cost.items()
                  if isinstance(v, (int, float))},
        hlo_flops=cost.flops,
        hlo_bytes=cost.bytes,
        movement_bytes=cost.movement_bytes,
        collective_bytes=cost.collective_bytes,
        collective_by_kind=cost.collective_summary(),
        while_trips=cost.while_trips,
        unknown_trip_whiles=cost.unknown_trip_whiles,
        t_compute=terms.t_compute,
        t_memory=terms.t_memory,
        t_collective=terms.t_collective,
        bottleneck=terms.bottleneck,
        model_flops=terms.model_flops,
        useful_ratio=terms.useful_ratio,
        roofline_fraction=terms.roofline_fraction,
        t_bound=terms.t_bound,
    )
    return rec


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str, verbose: bool = True,
             train_overrides: Optional[dict] = None) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    lowered, compiled, meta = lower_cell(
        arch, shape_name, multi_pod=multi_pod,
        train_overrides=train_overrides)
    rec = analyze_cell(compiled, meta, cfg, shape)
    os.makedirs(out_dir, exist_ok=True)
    tag = "multipod" if multi_pod else "pod"
    path = os.path.join(out_dir,
                        f"{arch}_{shape_name}_{tag}.json".replace("/", "-"))
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    if verbose:
        ma = rec["memory_analysis"]
        print(f"[dryrun] {arch} {shape_name} {rec['mesh']}: "
              f"compile {rec['t_compile_s']}s  "
              f"mem/dev={rec['bytes_per_device'] / 2**30:.2f} GiB "
              f"(args {ma.get('argument_size_in_bytes', 0) / 2**30:.2f} "
              f"temp {ma.get('temp_size_in_bytes', 0) / 2**30:.2f})")
        print(f"  flops/dev={rec['hlo_flops']:.3e} "
              f"bytes/dev={rec['hlo_bytes']:.3e} "
              f"coll/dev={rec['collective_bytes']:.3e} "
              f"{rec['collective_by_kind']}")
        print(f"  C={rec['t_compute'] * 1e3:.2f}ms M={rec['t_memory'] * 1e3:.2f}ms "
              f"X={rec['t_collective'] * 1e3:.2f}ms -> {rec['bottleneck']} "
              f"useful={rec['useful_ratio']:.3f} "
              f"roofline={rec['roofline_fraction']:.3f}")
    del lowered, compiled
    gc.collect()
    return rec


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------


def all_cells():
    for arch in ALL_ARCHS:
        cfg = get_config(arch)
        for shape_name in cfg.shapes():
            yield arch, shape_name


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.dryrun",
        description="multi-pod AOT dry-run (lower+compile, no allocation)")
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--all", action="store_true",
                    help="run every cell (CPU only, subprocess per cell)")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    if args.list:
        for arch, shape in all_cells():
            print(arch, shape)
        return 0

    if args.all:
        failures = []
        for arch, shape in all_cells():
            for mesh_kind in ("pod", "multipod"):
                tag = f"{arch}_{shape}_{mesh_kind}".replace("/", "-")
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip] {tag}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--mesh", mesh_kind, "--out", args.out]
                print(f"[run ] {tag}", flush=True)
                r = subprocess.run(cmd)
                if r.returncode != 0:
                    failures.append(tag)
        if failures:
            print("FAILED cells:", failures)
            return 1
        print("all cells OK")
        return 0

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all / --list)")
    run_cell(args.arch, args.shape, multi_pod=(args.mesh == "multipod"),
             out_dir=args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
