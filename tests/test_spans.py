"""Program spans and compile counters on the characterize path.

A small sweep runs on the ``interpret`` backend under the profiler; its
``memscope.*`` spans are read back from the recorded trace: each span
of the table appears in order with its arguments, and no span encloses
another.  The counters ``run_matrix`` keeps (``programs_built``,
``xla_compiles``, ``cache_loads``) are checked against what the sweep
built and against an independent count of JAX's compile events; in a
fresh interpreter whose persistent compile cache the environment
places, a second sweep loads every program it builds from the cache."""
import glob
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import compat
from repro.core import spans
from repro.core.characterize import characterize_specs, curvedb_from_result
from repro.core.coordinator import CoreCoordinator
from repro.core.scenarios import (ObserverSpec, ScenarioSpec, StressorSpec,
                                  TrafficShape)
from repro.core.workloads import VMEM_KERNEL_BYTES

OBSERVERS = ("r", "s", "w", "l")
# observers whose group is measured by a jit(vmap(...)) program that a
# fresh coordinator builds (at 64 KiB the read and the chase are
# VMEM-resident, so the three programs differ); "w" shares one
# unvmapped measurement, whose kernel is jitted once per process
VMAPPED = ("r", "s", "l")
MEASUREMENT_ARGS = {"strategy", "bytes", "members", "group"}
SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _sweep(log_dir, batched):
    """One sweep of ``OBSERVERS`` under the profiler; the matrix
    result, its CurveDB, the ``memscope.*`` host events in time order,
    and JAX's backend-compile events counted around ``run_matrix``."""
    coord = CoreCoordinator(backend="interpret")
    specs, refused = characterize_specs(
        coord, pools=["hbm"], buffer_bytes=64 << 10,
        obs_strategies=OBSERVERS, stress_strategies=("w",), iters=2)
    assert not refused
    compiles = []

    def on_duration(event, *_args, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        try:
            result = coord.run_matrix(specs, batched=batched)
        finally:
            jax.monitoring.unregister_event_duration_listener(on_duration)
        db = curvedb_from_result(result, coord.platform.name,
                                 backend=coord.backend)
    finally:
        jax.profiler.stop_trace()
    return result, db, _memscope_events(log_dir), len(compiles)


def _memscope_events(log_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    events = [(e.start_ns, e.start_ns + e.duration_ns,
               e.name[len(spans.PREFIX):], dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith(spans.PREFIX)]
    return sorted(events, key=lambda ev: ev[0])


def _measurements(events):
    """The measurement spans grouped by their ``group`` argument, in
    the order the groups ran."""
    groups = {}
    for _s, _e, name, args in events:
        if "group" in args:
            groups.setdefault(args["group"], []).append((name, args))
    return [groups[g] for g in sorted(groups)]


@pytest.mark.parametrize("batched", [True, False])
def test_spans_cover_the_sweep_in_order_and_flat(tmp_path, batched):
    _result, _db, events, _n = _sweep(tmp_path, batched)
    names = [name for _s, _e, name, _a in events]
    assert set(names) <= set(spans.NAMES)
    # plan first (batched only), assemble then curvedb last
    assert names[-2:] == ["assemble", "curvedb"]
    assert (names[0] == "plan") == batched
    assert names.count("plan") == int(batched)
    # flat: each span ends before the next one starts
    for (_s0, e0, n0, _a0), (s1, _e1, n1, _a1) in zip(events, events[1:]):
        assert e0 <= s1, (n0, n1)
    measured = _measurements(events)
    assert len(measured) == len(OBSERVERS)
    assert sorted(args["strategy"] for m in measured
                  for _n, args in m[:1]) == sorted(OBSERVERS)
    for m in measured:
        for name, args in m:
            assert set(args) == MEASUREMENT_ARGS, (name, args)
            assert args["bytes"] == 64 << 10 and args["members"] == 1
        want = ["inputs", "build", "timed", "readback"]
        if args["strategy"] == "w":
            want.remove("inputs")           # the write makes its output
        assert [name for name, _a in m] == want, args
    # the spans between plan and assemble are all measurement spans
    body = events[int(batched):-2]
    assert all("group" in args for _s, _e, _n, args in body)


@pytest.mark.parametrize("batched", [True, False])
def test_compile_counters(tmp_path, batched):
    result, db, _events, n_compile_events = _sweep(tmp_path, batched)
    st = result.stats
    assert st.programs_built == len(VMAPPED)
    assert st.xla_compiles + st.cache_loads == n_compile_events
    # a program the coordinator builds is compiled or loaded
    assert n_compile_events >= st.programs_built
    for key in ("programs_built", "xla_compiles", "cache_loads"):
        assert db.meta[key] == getattr(st, key), key


# two sweeps in one fresh interpreter whose persistent compile cache is
# placed by the environment; prints each sweep's counters and JAX's
# backend-compile events counted around it
_CACHED_SWEEPS = """
import json
from repro import compat
compat.use_compile_cache()
import jax
from repro.core.characterize import characterize_specs, curvedb_from_result
from repro.core.coordinator import CoreCoordinator

events = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, *_a, **_k: events.append(event))
for _ in range(2):
    coord = CoreCoordinator(backend="interpret")
    specs, _refused = characterize_specs(
        coord, pools=["hbm"], buffer_bytes=64 << 10,
        obs_strategies=%r, stress_strategies=("w",), iters=2)
    del events[:]
    result = coord.run_matrix(specs, batched=True)
    meta = curvedb_from_result(result, coord.platform.name,
                               backend=coord.backend).meta
    print(json.dumps({
        "events": events.count("/jax/core/compile/backend_compile_duration"),
        **{k: meta[k] for k in ("programs_built", "xla_compiles",
                                "cache_loads")}}))
"""


def test_cache_loads_are_split_from_compiles(tmp_path):
    """With the persistent compile cache on, the first sweep writes its
    programs to it and a second sweep on a fresh coordinator, which
    builds its programs again, loads each of them: no XLA compile, one
    load a program."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env[compat.CACHE_ENV] = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", _CACHED_SWEEPS % (OBSERVERS,)],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    first, second = [json.loads(line) for line in r.stdout.splitlines()]
    assert first["xla_compiles"] >= first["programs_built"] == len(VMAPPED)
    assert first["xla_compiles"] + first["cache_loads"] == first["events"]
    assert second["programs_built"] == len(VMAPPED)
    assert second["xla_compiles"] == 0
    assert second["cache_loads"] == second["programs_built"] == \
        second["events"]


def _specs(coord, observers, buffer_bytes=64 << 10):
    specs, refused = characterize_specs(
        coord, pools=["hbm"], buffer_bytes=buffer_bytes,
        obs_strategies=observers, stress_strategies=("w",), iters=2)
    assert not refused
    return specs


def _measured(result):
    """Each curve's key with the checksum and operand memory kind of
    every point."""
    return [(run.key, [(sc.main.checksum, sc.main.memory_kind)
                       for sc in run.scenarios]) for run in result.runs]


def test_second_sweep_reuses_the_measured_programs():
    """Back-to-back batched sweeps on one coordinator: the second builds
    no program, traces, compiles and loads nothing, and measures the
    same checksums in the same memory as the first."""
    coord = CoreCoordinator(backend="interpret")
    specs = _specs(coord, OBSERVERS)
    first = coord.run_matrix(specs, batched=True)
    second = coord.run_matrix(specs, batched=True)
    assert first.stats.programs_built == len(VMAPPED)
    st = second.stats
    assert st.programs_built == 0
    assert st.program_cache_hits == first.stats.programs_built
    assert st.xla_compiles + st.cache_loads == 0
    assert _measured(second) == _measured(first)
    meta = curvedb_from_result(second, coord.platform.name,
                               backend=coord.backend).meta
    assert meta["programs_built"] == 0
    assert meta["program_cache_hits"] == len(VMAPPED)


def _mixed_checksum(buffer_bytes, read_fraction):
    """The mixed stream's checksum from numpy: the sum of the blocks it
    reads of the sequential-integer buffer, plus one for every element
    of the blocks it writes (the kernel keeps >= 8 blocks, and one of
    each kind)."""
    rows = buffer_bytes // 512
    blk = max(b for b in range(1, rows // 8 + 1) if rows % b == 0)
    nb = rows // blk
    n_r = max(1, min(nb - 1, int(round(nb * read_fraction))))
    x = np.arange(rows * 128, dtype=np.float64)
    return float(x[:n_r * blk * 128].sum() + (nb - n_r) * blk * 128)


def _spec(name, observer):
    return ScenarioSpec(name, observer, (StressorSpec("w", "hbm", 64 << 10),),
                        iters=2)


def _key_read_fractions(coord):
    """Two read fractions of the mixed stream: two programs, each
    checked against its own fraction's reference."""
    buf = 64 << 10
    fractions = (0.5, 0.75)
    specs = [_spec(f"b{rf}", ObserverSpec(
        "b", "hbm", (buf,), TrafficShape(kind="mixed", read_fraction=rf)))
        for rf in fractions]
    result = coord.run_matrix(specs, batched=True)
    for run, rf in zip(result.runs, fractions):
        assert run.scenarios[0].main.checksum == \
            pytest.approx(_mixed_checksum(buf, rf), rel=1e-6)
    return result, 2, 0


def _key_ladder_sizes(coord):
    """One observer swept over two buffer sizes: two programs."""
    spec = _spec("s", ObserverSpec("s", "hbm", (64 << 10, 128 << 10)))
    return coord.run_matrix([spec], batched=True), 2, 0


def _key_stream_reads_share(coord):
    """Above the VMEM-kernel size "r" and "s" both run the HBM stream
    read at the same block: one program for both groups."""
    specs = _specs(coord, ("r", "s"), buffer_bytes=VMEM_KERNEL_BYTES + 512)
    return coord.run_matrix(specs, batched=True), 1, 1


def _key_fresh_coordinator(coord):
    """Another coordinator holds none of this one's programs."""
    specs = _specs(coord, OBSERVERS)
    CoreCoordinator(backend="interpret").run_matrix(specs, batched=True)
    return coord.run_matrix(specs, batched=True), len(VMAPPED), 0


@pytest.mark.parametrize("case", [_key_read_fractions, _key_ladder_sizes,
                                  _key_stream_reads_share,
                                  _key_fresh_coordinator],
                         ids=lambda f: f.__name__[len("_key_"):])
def test_measured_program_key(case):
    """What the key of the coordinator's measured-pass programs keeps
    apart (static arguments, operand shapes, coordinators) and what it
    shares (one kernel at the same arguments, whatever the strategy)."""
    result, built, hits = case(CoreCoordinator(backend="interpret"))
    assert result.stats.programs_built == built
    assert result.stats.program_cache_hits == hits


def test_span_names_are_the_programs_own():
    with pytest.raises(KeyError):
        spans.span("sweep")
    with spans.measurement(group=3, strategy="r"):
        with spans.span("timed"):
            pass
