"""MEMSCOPE core — the paper's contribution as a composable subsystem.

devicetree   platform description + auto-detect (DTB analog)
pools        Memory Pool Manager (genpool analog) + upool export
workloads    Workload Library (Table-I access strategies)
coordinator  Core Coordinator: scenario ladders + barrier sandwich
counters     perf-counter analog (AOT cost analysis, event names)
spans        program spans on the profiler's host trace
simulate     closed queueing-network model (contention at v5e scale)
characterize performance curves + Little's-law MLP (CurveDB)
placement    characterization-driven Placement Advisor (upool payoff)
interface    debugfs-entry analog (config strings, results, CLI)
"""
from repro.core.coordinator import (  # noqa: F401
    ActivitySpec, CoreCoordinator, ExperimentConfig, ExperimentResult,
)
from repro.core.devicetree import Platform, detect_platform  # noqa: F401
from repro.core.pools import PoolManager  # noqa: F401
