"""The SPMD sandwich, enforced structurally.

The paper's invariant (1) — measurement starts only after every engine
passed the start barrier — used to be advisory in
``build_scenario_program``: the ``ready`` psum had no dataflow edge into
the measured activity, so JAX folded it away at trace time and XLA was
free to begin the observed work before the stressors were running.
These tests pin the fix down by inspecting the traced jaxpr for the
dependency edge (they run on the single-device main process; the mesh
size does not change the program structure).  The multi-device
*execution* of the spmd backend is covered in test_distribution.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.coordinator import (build_ladder_program,
                                    build_rung_program,
                                    build_scenario_program,
                                    measured_region_is_fenced)
from repro.core.exec.plan import effective_duty as _effective_duty
from repro.core.exec.program import spmd_branch_fn as _spmd_branch_fn

ROWS = 16


def _operands(n_eng: int):
    xf = np.ones((n_eng, ROWS, 128), np.float32)
    xi = np.zeros((n_eng, ROWS, 128), np.int32)
    return xf, xi


# ---------------------------------------------------------------------------
# The checker itself: it must reject an unfenced program
# ---------------------------------------------------------------------------


def test_checker_rejects_advisory_barrier():
    """A psum nothing depends on (the historical bug) is NOT a fence."""
    mesh = compat.make_mesh_from_devices(jax.devices()[:1], ("engine",))

    def buggy(x):
        x = x[0]
        ready = jax.lax.psum(x[0], "engine")   # no edge into `out`
        out = x * 2.0
        return out[None], ready

    f = jax.shard_map(buggy, mesh=mesh, in_specs=(P("engine"),),
                      out_specs=(P("engine"), P()))
    assert not measured_region_is_fenced(f, np.ones((1, 8), np.float32))


def test_checker_requires_a_shard_map():
    assert not measured_region_is_fenced(lambda x: x * 2,
                                         jnp.ones((4,)))


# ---------------------------------------------------------------------------
# The fixed programs carry the dependency edge
# ---------------------------------------------------------------------------


def test_rung_program_measured_region_is_fenced():
    fns = [_spmd_branch_fn("r", None, ROWS, 2),
           _spmd_branch_fn("w", None, ROWS, 2)]
    _mesh, f = build_rung_program(1, fns, [0])
    assert measured_region_is_fenced(f, *_operands(1))


def test_scenario_program_measured_region_is_fenced():
    """Regression for the build_scenario_program barrier-ordering bug:
    `out` must have a data dependency on the start-barrier psum."""
    _mesh, f = build_scenario_program(
        1, 0,
        main_fn=lambda m: jnp.sum(m, axis=-1, keepdims=True),
        stress_fn=lambda s: jnp.sum(s * 2, axis=-1, keepdims=True),
        idle_fn=lambda s: jnp.sum(s * 0, axis=-1, keepdims=True))
    assert measured_region_is_fenced(f, np.ones((1, 8), np.float32),
                                     np.ones((1, 8), np.float32))


def test_scenario_program_executes():
    """The fixed program still runs and produces per-engine outputs
    (single-device mesh: engine 0 = observed, no stressors)."""
    _mesh, f = build_scenario_program(
        1, 0,
        main_fn=lambda m: m * 3.0,
        stress_fn=lambda s: s * 2.0,
        idle_fn=lambda s: s * 0.0)
    x = np.ones((1, 8), np.float32)
    out, barrier = f(x, x)
    np.testing.assert_allclose(np.asarray(out), 3.0 * x)
    assert np.asarray(barrier).shape == ()


# ---------------------------------------------------------------------------
# Every spmd branch traces and runs (single engine, every strategy kind)
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# The fused whole-ladder program: scanned sandwiches + in-dispatch clocks
# ---------------------------------------------------------------------------


def test_ladder_program_measured_region_is_fenced():
    """Every scanned rung of the fused ladder carries its own verified
    psum sandwich — the checker recurses into the scan body and
    requires the step carry to consume the stop barrier."""
    fns = [_spmd_branch_fn("r", None, ROWS, 2),
           _spmd_branch_fn("w", None, ROWS, 2)]
    _mesh, f = build_ladder_program(1, fns, [[0], [1]], samples=2)
    assert measured_region_is_fenced(f, *_operands(1))


def test_ladder_program_executes_with_monotone_clock():
    """The fused ladder runs end to end and its in-dispatch stamp pairs
    bracket every sample: stop strictly after start (the value-threaded
    device_clock fills must serialize), and consecutive samples must
    not overlap."""
    fns = [_spmd_branch_fn("r", None, ROWS, 4),
           _spmd_branch_fn("w", None, ROWS, 4)]
    K, S = 3, 2
    _mesh, f = build_ladder_program(1, fns, [[0], [1], [0]], samples=S)
    xf, xi = _operands(1)
    outs, t0s, t1s, xf2, xi2 = f(xf, xi)
    assert np.isfinite(np.asarray(outs)).all()
    # operands pass through unchanged (the cache rebinds them)
    np.testing.assert_array_equal(np.asarray(xf2), xf)
    np.testing.assert_array_equal(np.asarray(xi2), xi)
    t0 = np.asarray(t0s[0]).astype(np.int64)
    t1 = np.asarray(t1s[0]).astype(np.int64)
    start = t0[:, 0] * 10**9 + t0[:, 1]
    stop = t1[:, 0] * 10**9 + t1[:, 1]
    assert t0.shape == (K * S, 2)
    assert (stop > start).all()                 # every sample bracketed
    assert (start[1:] >= stop[:-1]).all()       # samples serialized


def test_stacked_ladder_program_is_fenced_and_times_per_ladder():
    """The sweep-batched STACKED program — the fused ladder's scan
    table tiled with a leading scenario axis (G ladders x K rungs) —
    still verifies structurally (one scanned body serves every rung of
    every stacked ladder), and its stamp pairs decode per (ladder,
    rung, sample) with every sample bracketed and the whole stack
    serialized (ladder g+1 cannot open before ladder g retired:
    invariant 4 across the group)."""
    G, K, S = 3, 2, 2
    # rung 0 is cheap, rung 1 deliberately orders of magnitude
    # heavier: the (G, K, S) decode is only correct if the flat scan
    # order really is ladder-major, which the cost asymmetry makes
    # observable above clock/dispatch noise
    fns = [_spmd_branch_fn("r", None, ROWS, 2),
           _spmd_branch_fn("r", None, ROWS, 50_000)]
    table = np.tile(np.asarray([[0], [1]], np.int32), (G, 1))
    _mesh, f = build_ladder_program(1, fns, table, samples=S)
    assert measured_region_is_fenced(f, *_operands(1))
    xf, xi = _operands(1)
    outs, t0s, t1s, xf2, xi2 = f(xf, xi)
    assert np.isfinite(np.asarray(outs)).all()
    t0 = np.asarray(t0s)[0].astype(np.int64)
    t1 = np.asarray(t1s)[0].astype(np.int64)
    assert t0.shape == (G * K * S, 2)
    start = t0[:, 0] * 10**9 + t0[:, 1]
    stop = t1[:, 0] * 10**9 + t1[:, 1]
    assert (stop > start).all()                 # every sample bracketed
    assert (start[1:] >= stop[:-1]).all()       # stack fully serialized
    # ladder-major order, for real: decoded as (G, K, S) like the
    # coordinator does, EVERY stacked ladder must show its heavy rung
    # heavier than its cheap rung (a rung-major flat order — e.g.
    # np.repeat instead of np.tile in the builder — interleaves the
    # costs and breaks this for G != K)
    d = (stop - start).reshape(G, K, S)
    med = np.median(d, axis=2)                  # (G, K)
    assert (med[:, 1] > med[:, 0]).all(), med


def test_stacked_checker_rejects_unfenced_stacked_scan():
    """Negative: a stacked multi-ladder scan whose steps carry no psum
    sandwich (or only an advisory one) must NOT verify — batching
    ladders must not dilute the fence requirement."""
    mesh = compat.make_mesh_from_devices(jax.devices()[:1], ("engine",))
    G, K = 3, 2

    def advisory_stack(xf, xi):
        xf, xi = xf[0], xi[0]

        def step(carry, _):
            ready = jax.lax.psum(xf[0, 0], "engine")   # nothing uses it
            out = jnp.sum(xf) + carry
            return carry + 1.0, (out, ready)

        _c, (outs, _r) = jax.lax.scan(step, jnp.float32(0.0),
                                      jnp.arange(G * K))
        return outs[None]

    f = jax.shard_map(advisory_stack, mesh=mesh,
                      in_specs=(P("engine"), P("engine")),
                      out_specs=P("engine", None))
    assert not measured_region_is_fenced(f, *_operands(1))


def test_fence_check_accepts_pretraced_jaxpr():
    """The single-trace AOT pipeline hands the checker an existing
    ClosedJaxpr (``jit(...).trace``) instead of paying a second
    make_jaxpr trace; both spellings must agree."""
    fns = [_spmd_branch_fn("r", None, ROWS, 2)]
    _mesh, f = build_rung_program(1, fns, [0])
    xf, xi = _operands(1)
    traced = f.trace(xf, xi)
    assert measured_region_is_fenced(f, jaxpr=traced.jaxpr)
    assert measured_region_is_fenced(f, xf, xi) \
        == measured_region_is_fenced(f, jaxpr=traced.jaxpr)


def test_ladder_checker_rejects_unfenced_scan():
    """A scanned ladder whose steps carry no psum sandwich (or only an
    advisory one nothing depends on) must NOT verify."""
    from repro.core.exec.fence import _shard_map_bodies

    mesh = compat.make_mesh_from_devices(jax.devices()[:1], ("engine",))

    def no_fence(xf, xi):
        xf, xi = xf[0], xi[0]

        def step(carry, _):
            out = jnp.sum(xf) + carry
            return carry + 1.0, out

        _c, outs = jax.lax.scan(step, jnp.float32(0.0), jnp.arange(3))
        return outs[None]

    f = jax.shard_map(no_fence, mesh=mesh,
                      in_specs=(P("engine"), P("engine")),
                      out_specs=P("engine", None))
    assert not measured_region_is_fenced(f, *_operands(1))

    def advisory(xf, xi):
        xf, xi = xf[0], xi[0]

        def step(carry, _):
            ready = jax.lax.psum(xf[0, 0], "engine")   # nothing uses it
            out = jnp.sum(xf) + carry
            return carry + 1.0, (out, ready)

        _c, (outs, _r) = jax.lax.scan(step, jnp.float32(0.0),
                                      jnp.arange(3))
        return outs[None]

    f2 = jax.shard_map(advisory, mesh=mesh,
                       in_specs=(P("engine"), P("engine")),
                       out_specs=P("engine", None))
    assert not measured_region_is_fenced(f2, *_operands(1))


def test_effective_duty_guard_unified():
    """All three work-balancing call sites and the n_active stamping
    share one duty helper: absent shapes and degenerate 0/None duties
    count as always-on, real duty cycles pass through."""
    from repro.core.scenarios import TrafficShape

    assert _effective_duty(None) == 1.0
    assert _effective_duty(TrafficShape.steady()) == 1.0
    assert _effective_duty(TrafficShape.burst(0.5)) == 0.5

    class DuckShape:        # a deserialized/foreign shape with 0 duty
        duty_cycle = 0.0

    assert _effective_duty(DuckShape()) == 1.0


@pytest.mark.parametrize("strategy", ["r", "w", "c", "b", "l", "t", "i"])
def test_spmd_branch_fns_execute(strategy):
    from repro.core.scenarios import TrafficShape
    shape = {"b": TrafficShape.mixed(1, 1),
             "t": TrafficShape.strided(4)}.get(strategy)
    fns = [_spmd_branch_fn(strategy, shape, ROWS, 2)]
    _mesh, f = build_rung_program(1, fns, [0])
    xf, xi = _operands(1)
    xi[0, :ROWS, 0] = np.roll(np.arange(ROWS), 1)   # a valid cycle
    out, barrier = f(xf, xi)
    assert np.isfinite(np.asarray(out)).all()
    assert measured_region_is_fenced(f, xf, xi)


# ---------------------------------------------------------------------------
# Width-packing: per-subset fence isolation (needs a >=4-engine mesh,
# so this one test runs in a forced-host-device subprocess)
# ---------------------------------------------------------------------------


def test_packed_fence_subset_isolation():
    """A packed program is fenced only if EVERY collective in the
    measured region respects the declared engine subsets: its own
    grouped-psum sandwich passes; a cross-subset psum group, a
    declaration that does not match the traced grouping, a global-psum
    program claimed as packed, and a post-barrier cross-subset
    ppermute leak must all be rejected."""
    import os
    import subprocess
    import sys
    import textwrap

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.core.exec.fence import measured_region_is_fenced
        from repro.core.exec.program import (build_ladder_program,
                                             spmd_branch_fn)

        fns = [spmd_branch_fn("r", None, 4, 2),
               spmd_branch_fn("i", None, 1, 2)]
        table = [[0, 1, 0, 1]]     # two width-2 ladders side by side
        subsets = ((0, 1), (2, 3))
        xf = np.ones((4, 4, 16), np.float32)
        xi = np.zeros((4, 4, 16), np.int32)

        _m, fn = build_ladder_program(4, fns, table, samples=1,
                                      subsets=subsets)
        # the packed program's sandwich isolates its own subsets...
        assert measured_region_is_fenced(fn, xf, xi, subsets=subsets)
        # ...but is NOT a fence for any other partition of the mesh
        assert not measured_region_is_fenced(
            fn, xf, xi, subsets=((0, 2), (1, 3)))
        assert not measured_region_is_fenced(
            fn, xf, xi, subsets=((0, 1, 2, 3),))

        # a GLOBAL-psum program claimed as packed must be rejected
        # (each subset's barrier would wait on the other's engines);
        # the same program is a perfectly good unpacked fence
        _m2, fn2 = build_ladder_program(4, fns, table, samples=1,
                                        subsets=None)
        assert not measured_region_is_fenced(fn2, xf, xi,
                                             subsets=subsets)
        assert measured_region_is_fenced(fn2, xf, xi)

        # correct sandwich + a cross-subset ppermute INSIDE the
        # measured region: data leaks between packed ladders
        def leaky():
            m = compat.make_mesh_from_devices(jax.devices()[:4],
                                              ("engine",))
            def per_engine(xf, xi):
                xf = xf[0]
                token = compat.psum_grouped(xf[0, 0], "engine",
                                            subsets)
                xf, _t = jax.lax.optimization_barrier(
                    (xf + token * 0, token))
                stolen = jax.lax.ppermute(
                    xf[0, 0], "engine",
                    perm=[(2, 0), (0, 2), (1, 3), (3, 1)])
                out = jnp.sum(xf) + stolen
                done = compat.psum_grouped(out, "engine", subsets)
                return (out + done * 0)[None]
            f = jax.shard_map(per_engine, mesh=m,
                              in_specs=(P("engine"), P("engine")),
                              out_specs=P("engine"),
                              check_vma=False)
            return jax.jit(f)
        assert not measured_region_is_fenced(leaky(), xf, xi,
                                             subsets=subsets)
        print("PACKED_FENCE_OK")
    """)
    env = dict(os.environ, PYTHONPATH=src)
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=480,
                       env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    assert "PACKED_FENCE_OK" in r.stdout
