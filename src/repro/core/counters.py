"""Performance-counter analog — AOT program analysis.

MEMSCOPE samples ARMv8 PMU events around the measured region.  A TPU
exposes no user PMU, but an AOT-compiled XLA program is *fully analysable
before it runs*: ``cost_analysis()`` gives exact FLOPs and bytes touched,
``memory_analysis()`` gives the allocation picture, and the lowered HLO
names every collective.  Together with wall-clock sandwich timing these
cover the paper's Table-IV methodology (cycles, mem accesses, cache
refills -> flops, HBM bytes, per-access cycles).

Six "counters" per activity, mirroring the 6-counter/core ARM PMU limit.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax


MAX_COUNTERS = 6   # ARM PMU exposes 6 programmable counters per core

#: available events (the pmevtyper analog)
EVENTS = (
    "WALL_NS",          # measured region wall time
    "HLO_FLOPS",        # cost_analysis flops
    "HLO_BYTES",        # cost_analysis bytes accessed
    "TRANSACTIONS",     # bytes / line_bytes
    "NS_PER_TX",        # wall / transactions
    "PEAK_MEMORY",      # memory_analysis temp+arg bytes
)


def select_events(names: Tuple[str, ...]) -> Tuple[str, ...]:
    bad = [n for n in names if n not in EVENTS]
    if bad:
        raise KeyError(f"unknown events {bad}; available {EVENTS}")
    if len(names) > MAX_COUNTERS:
        raise ValueError(
            f"at most {MAX_COUNTERS} counters per core (got {len(names)})")
    return names


def cost_of(fn: Callable, *args, **kw) -> Dict[str, float]:
    """AOT cost analysis of fn(*args) without executing it."""
    lowered = jax.jit(fn).lower(*args, **kw)
    compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    mem = compiled.memory_analysis()
    peak = 0.0
    if mem is not None:
        peak = float(
            getattr(mem, "temp_size_in_bytes", 0) +
            getattr(mem, "argument_size_in_bytes", 0) +
            getattr(mem, "output_size_in_bytes", 0))
    return {"HLO_FLOPS": flops, "HLO_BYTES": byts, "PEAK_MEMORY": peak}
