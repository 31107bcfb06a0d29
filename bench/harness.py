"""Run one benchmark cell once and print its result line.

A cell is found by name: ``bench/workloads/<cell>.json`` names its
configuration (``bench/configs/<config>.json``) and its traffic mix
(``bench/traffic/<traffic>.json``), and the traffic mix names the driver
(``bench/drivers/<driver>.py``) that generates it from its parameters.
Each per-layer metric is a reader of its own, ``bench/metrics/<name>.py``.
Which metrics a cell reports, and their units, ``BENCHMARK.json`` says.
A new cell, configuration, traffic mix or metric is a new file and new
entries there; none needs an edit to a file that exists.

A run: set-up (the driver builds the system under test from ``--seed``
and warms every shape its traffic uses), the measured window (whole
units of work until ``--seconds`` have passed; with ``--trace 1`` under
the profiler), the device's peak memory, the driver's release of the
program's state, then the comparison with the plain reference that
decides ``correct``.  The last line of standard output is one JSON
object; the numbers compared, each beside its limit, close both it and
standard error.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import re
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
JIT_MISS_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """A cell that cannot run as specified (exit code 2)."""


def load_json(kind: str, name: str, root: str = BENCH) -> dict:
    """``bench/<kind>/<name>.json``."""
    return _json(root, kind, name + ".json")


def _json(root: str, *parts: str) -> dict:
    path = os.path.join(root, *parts)
    if not os.path.isfile(path):
        raise BenchError(f"no {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = BENCH):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    if not NAME_RE.match(name):
        raise BenchError(f"{kind} name {name!r} is not a valid name")
    path = os.path.join(root, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict         # bench/workloads/<name>.json
    config: dict       # bench/configs/<config>.json
    traffic: dict      # bench/traffic/<traffic>.json
    end_to_end: Dict[str, str]   # metric -> unit, from BENCHMARK.json
    per_layer: Dict[str, str]

    @property
    def chips(self) -> int:
        return int(self.spec["chips"])


def cell_metrics(benchmark: dict, name: str):
    """The end-to-end and per-layer metrics ``BENCHMARK.json`` gives the
    cell ``name``, each with its unit: an end-to-end metric that lists
    its ``workloads`` belongs to those, one that lists none to every
    cell; a per-layer metric likewise, where without a list it belongs
    to every cell that reports the end-to-end metric it ``moves``."""
    e2e = {m["name"]: m["unit"] for m in benchmark["end_to_end"]
           if name in m.get("workloads", [name])}
    per_layer = {m["name"]: m["unit"] for m in benchmark["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in e2e
                                  else [])}
    return e2e, per_layer


def load_cell(name: str, root: str = BENCH) -> Cell:
    """The cell ``name``, the configuration and traffic mix it names,
    from the files under ``root``, and its metrics from the
    ``BENCHMARK.json`` beside ``root``."""
    if not NAME_RE.match(name):
        raise BenchError(f"workload name {name!r} is not a valid name")
    spec = load_json("workloads", name, root)
    if spec.get("name") != name:
        raise BenchError(f"bench/workloads/{name}.json names "
                         f"{spec.get('name')!r}")
    e2e, per_layer = cell_metrics(
        _json(os.path.dirname(root), "BENCHMARK.json"), name)
    if not e2e:
        raise BenchError(f"BENCHMARK.json gives {name} no end-to-end metric")
    return Cell(name, spec, load_json("configs", spec["config"], root),
                load_json("traffic", spec["traffic"], root), e2e, per_layer)


@dataclasses.dataclass
class Check:
    """One number compared with the plain reference, and its limit: the
    run is correct only if ``value <= limit`` for every check."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Window:
    """What a driver's measured window did: ``units`` whole units of
    work (curves, calls) in ``seconds`` of host wall time, and what the
    metrics and the check need of it."""
    seconds: float
    units: int
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class RunContext:
    """Handed to the driver and to every per-layer metric reader."""
    cell: Cell
    seed: int
    on_chip: bool
    device_kind: str
    n_devices: int
    window: Optional[Window] = None
    trace: Any = None               # bench.trace.Trace, --trace 1 only
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)


class EventCounter:
    """Counts ``jax.monitoring`` events while entered."""

    def __init__(self):
        self.counts: Dict[str, int] = {}

    def _event(self, name, *_a, **_kw):
        self.counts[name] = self.counts.get(name, 0) + 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._event)
        return False


def _device(n_devices_used: int, trace_info: Optional[dict]) -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs[:n_devices_used]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if trace_info is not None:
        out.update(trace_info)
    return out


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             on_chip: bool = True, t_start: Optional[float] = None,
             driver_hook: Optional[Callable[[Any], None]] = None) -> dict:
    """Run ``cell`` once; return the result object (not yet printed).
    ``driver_hook`` (tests only) is applied to the driver module before
    set-up, to break the timed path underneath."""
    import jax

    t_cell = time.perf_counter()
    t_start = t_cell if t_start is None else t_start
    devs = jax.devices()
    ctx = RunContext(cell, seed, on_chip, devs[0].device_kind, len(devs))
    driver = load_module("drivers", cell.traffic["driver"])
    if driver_hook is not None:
        driver_hook(driver)
    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    print(f"bench: set-up {setup_s!r} s: {t_cell - t_start!r} s of imports "
          f"and device start, the rest in the driver's set-up",
          file=sys.stderr, flush=True)

    max_units = int(cell.traffic.get("trace_max_units", 0)) if trace else 0
    trace_info = None
    with EventCounter() as ev:
        if trace:
            from bench import trace as tr
            log_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
                    window = driver.window(state, ctx, seconds, max_units)
            finally:
                jax.profiler.stop_trace()
        else:
            window = driver.window(state, ctx, seconds, max_units)
    ctx.window = window
    ctx.counters = {"jit_misses": ev.counts.get(JIT_MISS_EVENT, 0)}
    print(f"bench: window {window.seconds!r} s, {window.units} units, "
          f"{ctx.counters['jit_misses']} jit cache misses", file=sys.stderr,
          flush=True)
    breakdown = None
    if trace:
        ctx.trace = tr.load(log_dir)
        trace_info = {"busy_s": tr.busy_s(ctx.trace),
                      "window_s": ctx.trace.window_s}
        breakdown = tr.breakdown(ctx.trace)
        _rmtree(log_dir)
    device = _device(cell.chips, trace_info)

    if trace:
        metrics = read_per_layer(ctx, strict=on_chip)
    else:
        metrics = {}
        values = dict(window.end_to_end, setup_s=setup_s)
        for name, unit in cell.end_to_end.items():
            metrics[name] = {"value": float(values[name]), "unit": unit}

    driver.release(state)
    del state
    gc.collect()
    checks: List[Check] = driver.check(ctx)
    correct = window.failed == 0 and all(c.ok for c in checks)
    out = {"correct": bool(correct), "attempted": int(window.attempted),
           "failed": int(window.failed), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def read_per_layer(ctx: RunContext, strict: bool) -> Dict[str, dict]:
    """Every per-layer metric ``BENCHMARK.json`` gives the cell, from its
    reader.  A reader that finds nothing to read returns None and its
    metric is left out; with ``strict`` (on the chip, where every listed
    metric has something to read) that is an error, so that a kernel or
    program the readers no longer find fails the run and does not just
    drop its metric."""
    metrics: Dict[str, dict] = {}
    missing = []
    for name, unit in ctx.cell.per_layer.items():
        value = load_module("metrics", name).read(ctx)
        if value is None:
            missing.append(name)
        else:
            metrics[name] = {"value": float(value), "unit": unit}
    if missing and strict:
        raise BenchError(f"the readers of {', '.join(missing)} found nothing "
                         f"to read in this run of {ctx.cell.name}")
    return metrics


def _rmtree(path: str) -> None:
    import shutil
    shutil.rmtree(path, ignore_errors=True)


def emit(result: dict) -> None:
    """Print the result line last on standard output, and each number
    compared beside its limit last on standard error."""
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True, type=int)
    ap.add_argument("--seconds", required=True, type=float)
    ap.add_argument("--trace", required=True, type=int, choices=(0, 1))
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")
    if not 0 <= args.seed < 1 << 63:
        ap.error("--seed must be a whole number in [0, 2**63)")
    return args
