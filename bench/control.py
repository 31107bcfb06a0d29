"""Read the controls that set each limit's upper end, on the chip, at the
cell's own size.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 20

For every seed, one run of the cell in this process, with:

* serving cells (``--control fp8``, the default): the program as it is,
  and, on the same window, the driver's check run a second time with
  the control in the program's place: the reference in float8, whose
  first token at each position of the same prompts and served tokens is
  judged as if it had been served.  Both readings, and whether each
  comes out correct, go on the line;
* characterization cells: the timed path broken underneath as the
  control (``--control half_read``: every stream read leaves out half
  its buffer; ``--control chase_plus_one``: every chase answer is
  altered by one line).

Prints one JSON line per seed with the compared numbers (``checks``) and
the control's (``control_checks``, ``control_correct``).  This is a
measuring tool for the limits in ``PERF.md``; the benchmark's own runs
never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def half_read(orig):
    def read(x, *, block_rows, **kw):
        rows = x.shape[-2] // 2
        return orig(x[..., :rows, :], block_rows=min(block_rows, rows), **kw)
    return read


def plus_one(orig):
    def kernel(*a, **kw):
        return orig(*a, **kw) + 1
    return kernel


CHAR_CONTROLS = {"half_read": ("stream_read", half_read),
                 "chase_plus_one": ("chase_hbm", plus_one)}


def main(argv=None) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".jax_compile_cache")
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", default="fp8",
                    choices=("fp8",) + tuple(CHAR_CONTROLS))
    args = ap.parse_args(argv)

    import jax
    from bench import harness
    from repro import compat
    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    compat.use_compile_cache()
    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        control = {}

        def hook(driver):
            if args.control in CHAR_CONTROLS:
                name, make = CHAR_CONTROLS[args.control]
                setattr(ops, name, make(getattr(ops, name)))
                return
            check = driver.check

            def check_and_control(ctx):
                out = check(ctx)
                driver.CONTROL_QUANT = args.control
                try:
                    control["checks"] = check(ctx)
                finally:
                    driver.CONTROL_QUANT = "f32"
                return out
            driver.check = check_and_control

        saved = {k: getattr(ops, k) for k in ("stream_read", "chase_hbm")}
        t0 = time.perf_counter()
        try:
            res = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                                   trace=False, driver_hook=hook)
        finally:
            for k, v in saved.items():
                setattr(ops, k, v)
        line = {"seed": seed, "control": args.control,
                "correct": res["correct"], "checks": res["checks"]}
        if "checks" in control:
            line["control_checks"] = {c.name: {"value": c.value,
                                               "limit": c.limit}
                                      for c in control["checks"]}
            line["control_correct"] = all(c.ok for c in control["checks"])
        line.update(metrics=res["metrics"],
                    wall_s=time.perf_counter() - t0)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
