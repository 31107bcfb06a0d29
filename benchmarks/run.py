"""Benchmark harness entry point: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig4 tab2  # substring filter
"""
import sys
import time
import traceback

from benchmarks import (fig4_homogeneous_bw, fig5_homogeneous_lat,
                        fig6_7_heterogeneous, fig8_9_scratchpad,
                        fig10_validation, fig11_13_partition,
                        fig14_applications, resilience_bench, roofline,
                        scenario_matrix, serve_bench, spmd_ladder,
                        surface_sweep, tab2_3_mlp, worstcase_search)

SUITES = [
    ("fig4_homogeneous_bw", fig4_homogeneous_bw.main),
    ("fig5_homogeneous_lat", fig5_homogeneous_lat.main),
    ("tab2_3_mlp", tab2_3_mlp.main),
    ("fig6_7_heterogeneous", fig6_7_heterogeneous.main),
    ("fig8_9_scratchpad", fig8_9_scratchpad.main),
    ("fig10_validation", fig10_validation.main),
    ("fig11_13_partition", fig11_13_partition.main),
    ("fig14_applications", fig14_applications.main),
    ("scenario_matrix", scenario_matrix.main),
    ("spmd_ladder", spmd_ladder.main),
    ("surface_sweep", surface_sweep.main),
    ("worstcase_search", worstcase_search.main),
    ("resilience_bench", resilience_bench.main),
    ("serve_bench", serve_bench.main),
    ("roofline", roofline.main),
]


def main() -> int:
    from repro import compat
    compat.use_compile_cache()
    filters = sys.argv[1:]
    failures = []
    for name, fn in SUITES:
        if filters and not any(f in name for f in filters):
            continue
        t0 = time.time()
        print(f"\n{'=' * 70}\n=== {name}\n{'=' * 70}")
        try:
            fn()
            print(f"--- {name} OK ({time.time() - t0:.1f}s)")
        except Exception:
            traceback.print_exc()
            failures.append(name)
            print(f"--- {name} FAILED")
    if failures:
        print(f"\nFAILED: {failures}")
        return 1
    print("\nall benchmarks OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
