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
import pytest

from repro import compat
from repro.core import spans
from repro.core.characterize import characterize_specs, curvedb_from_result
from repro.core.coordinator import CoreCoordinator

OBSERVERS = ("r", "s", "w", "l")
# observers whose group is measured by a fresh jit(vmap(...)) program
# (at 64 KiB the read and the chase are VMEM-resident); "w" runs the
# registry workload, whose kernel is jitted once per process
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
        if args["strategy"] == "w" or not batched:
            want.append("inputs")           # the registry frees its buffer
        assert [name for name, _a in m] == want, args
    # the spans between plan and assemble are all measurement spans
    body = events[int(batched):-2]
    assert all("group" in args for _s, _e, _n, args in body)


@pytest.mark.parametrize("batched", [True, False])
def test_compile_counters(tmp_path, batched):
    result, db, _events, n_compile_events = _sweep(tmp_path, batched)
    st = result.stats
    assert st.programs_built == (len(VMAPPED) if batched else 0)
    assert st.xla_compiles + st.cache_loads == n_compile_events
    # a fresh jit(vmap(...)) program is compiled or loaded when built
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
    fresh programs to it and a second sweep, whose programs are fresh
    again, loads each of them: no XLA compile, one load a program."""
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


def test_span_names_are_the_programs_own():
    with pytest.raises(KeyError):
        spans.span("sweep")
    with spans.measurement(group=3, strategy="r"):
        with spans.span("timed"):
            pass
