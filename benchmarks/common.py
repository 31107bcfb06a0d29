"""Shared helpers for the paper-figure benchmarks.

Every benchmark prints a small CSV table and returns the rows, so
``benchmarks.run`` can aggregate and EXPERIMENTS.md can quote them.
Backends: the contention ladders use the queueing model (the `simulate`
backend — this container has one CPU device); fig10 additionally
*executes* the Pallas kernels (interpret mode) to cross-validate.
"""
from __future__ import annotations

import sys
from typing import Dict, Iterable, List

from repro.core.coordinator import (ActivitySpec, CoreCoordinator,
                                    ExperimentConfig)
from repro.core.devicetree import detect_platform
from repro.core.pools import PoolManager


def harness_setup(name: str, min_devices: int = 2) -> bool:
    """What a multi-device harness calls first.  Places the persistent
    compile cache (``compat.use_compile_cache``), then says whether the
    harness must re-run itself in a child process with forced host
    devices to get ``min_devices``.  Only a CPU process does that: on a
    chip this process already holds the devices, and a child that needs
    them would fail or hang, so too few chips is an error, not a
    re-exec."""
    import jax

    from repro import compat
    compat.use_compile_cache()
    n = len(jax.devices())
    if n >= min_devices:
        return False
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{name} needs >= {min_devices} devices and this "
            f"{jax.devices()[0].device_kind} process has {n}; on a chip "
            f"it runs in-process on the devices present")
    return True


def coordinator(platform: str = None, backend: str = "simulate"):
    plat = detect_platform(platform)
    return CoreCoordinator(PoolManager(plat), plat, backend=backend)


def ladder_rows(coord, main: ActivitySpec, stress: ActivitySpec,
                label: str, iters: int = 500) -> List[Dict]:
    res = coord.run(ExperimentConfig(main=main, stress=stress, iters=iters))
    rows = []
    for s in res.scenarios:
        rows.append({
            "case": label,
            "stressors": s.n_stressors,
            "bw_GBps": round(s.modeled_bw_gbps, 3),
            "lat_ns": round(s.modeled_lat_ns, 2),
            "stress_bw_GBps": round(s.stress_bw_gbps, 3),
        })
    return rows


def print_table(title: str, rows: Iterable[Dict]) -> List[Dict]:
    rows = list(rows)
    print(f"\n## {title}")
    if not rows:
        print("(no rows)")
        return rows
    cols = []
    for r in rows:
        for c in r:
            if c not in cols:
                cols.append(c)
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r.get(c, "")) for c in cols))
    sys.stdout.flush()
    return rows
