"""Run one benchmark cell once on the accelerator this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics read from a profiler trace
of the window, with ``busy_s``/``window_s`` and a ``breakdown``.  The
process exits non-zero, and prints no result, when JAX finds no TPU or
fewer chips than the cell asks for, when the program under test
(``src/repro``) is not beside this directory, or when a per-layer
metric the cell lists finds nothing to read in its trace.

JAX's persistent compilation cache lives at ``<checkout>/.jax_compile_cache``
whatever the environment says, so that only a cell's first run in a
checkout compiles and two checkouts share nothing.
"""
from __future__ import annotations

import os
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")


def main(argv=None) -> int:
    sys.path[:0] = [ROOT, SRC]
    from bench import harness

    args = harness.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
    except harness.BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: the program under test is not here: no {SRC}/repro",
              file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".jax_compile_cache")

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 3
    from repro import compat
    cache = compat.use_compile_cache()
    if cache != os.environ["JAX_COMPILATION_CACHE_DIR"]:
        print(f"bench: the compile cache is at {cache!r}, not in the "
              f"checkout", file=sys.stderr)
        return 2
    try:
        result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                                  trace=bool(args.trace), t_start=T_START)
    except harness.BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
