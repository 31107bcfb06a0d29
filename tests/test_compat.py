"""repro.compat helpers on the installed JAX, plus the grep lint:
the spellings compat.py owns, and APIs the installed JAX removed, must
not appear anywhere else.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------------------
# Helpers on the installed JAX
# ---------------------------------------------------------------------------


def test_make_mesh_works_on_this_jax():
    m = compat.make_mesh((1, 1), ("data", "model"))
    assert m.axis_names == ("data", "model")
    assert m.devices.shape == (1, 1)


def test_make_mesh_from_devices():
    m = compat.make_mesh_from_devices(jax.devices()[:1], ("engine",))
    assert m.axis_names == ("engine",)


def test_shard_map_with_grouped_psum_traces():
    """The installed ``jax.shard_map`` (``check_vma=False``, as the
    ladder programs call it) traces and runs a compat grouped psum."""
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((1,), ("d",))
    f = jax.shard_map(lambda x: compat.psum_grouped(x * 2, "d"),
                      mesh=mesh, in_specs=(P(),), out_specs=P(),
                      check_vma=False)
    np.testing.assert_array_equal(
        np.asarray(f(jnp.ones((4,)))), 2 * np.ones((4,)))


def test_pool_placement_lands_in_the_pool_memory_kind():
    """Placement through a pool lands in the memory kind the pool
    reports, and an array placed in host memory says so: no pool
    quietly falls back to device memory under its own name."""
    from repro.core.devicetree import TPU_V5E
    from repro.core.pools import PoolManager
    mgr = PoolManager(TPU_V5E)
    for name in ("hbm", "host"):
        pool = mgr.pool(name)
        x = pool.place(jnp.ones((8, 128)))
        kind = pool.effective_memory_kind()
        assert x.sharding.memory_kind == (kind or jax.devices()[0]
                                          .default_memory().kind)
    assert mgr.pool("host").effective_memory_kind() in \
        compat.device_memory_kinds(jax.devices()[0])


def test_pool_with_unlisted_memory_kind_raises():
    """A pool whose declared memory kind the device does not list is an
    error, never a quiet placement in the default memory."""
    import dataclasses

    from repro.core.devicetree import TPU_V5E
    from repro.core.pools import PoolError, PoolManager
    node = dataclasses.replace(TPU_V5E.memories["host"],
                               memory_kind="no_such_memory")
    plat = dataclasses.replace(
        TPU_V5E, memories={**TPU_V5E.memories, "host": node})
    pool = PoolManager(plat).pool("host")
    with pytest.raises(PoolError, match="no_such_memory"):
        pool.effective_memory_kind()
    with pytest.raises(PoolError):
        pool.place(jnp.ones((8, 128)))


def test_cost_of_reads_the_compiled_cost_analysis():
    from repro.core.counters import cost_of
    cost = cost_of(lambda x: x @ x, jnp.ones((8, 8)))
    assert cost["HLO_FLOPS"] > 0
    assert cost["PEAK_MEMORY"] > 0


def test_device_clock_stamps():
    """The in-dispatch timestamp probe: a host callback (so the rung
    provenance says ``"callback"``), plausible monotonic [s, ns] parts
    under jit, and strict ordering when the stamp's VALUE is threaded
    into the dependent computation (the async-fill contract the fused
    spmd ladder relies on)."""
    assert compat.CLOCK_SOURCE == "callback"

    def f(x):
        t0 = compat.device_clock(x[0])
        # value-thread the stamp (exact zero at runtime) into the work
        y = jnp.sum(x + jnp.minimum(t0[0] + t0[1], 0).astype(x.dtype))
        t1 = compat.device_clock(y)
        return y, t0, t1

    y, t0, t1 = jax.jit(f)(jnp.ones((128,)))
    t0, t1 = np.asarray(t0).astype(np.int64), np.asarray(t1).astype(np.int64)
    assert t0.shape == (2,) and t0.dtype == np.int64
    assert 0 <= t0[1] < 1_000_000_000 and 0 <= t1[1] < 1_000_000_000
    assert t1[0] * 10**9 + t1[1] > t0[0] * 10**9 + t0[1]
    assert float(y) == 128.0                  # the zero really is exact


def test_donation_supported_probe():
    """The donation probe returns a stable bool and never raises."""
    assert compat.donation_supported() in (True, False)
    assert compat.donation_supported() == compat.donation_supported()


def _reset_cache_config(old_dir):
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_compilation_cache_dir", old_dir)
    compilation_cache.reset_cache()


def test_persistent_cache_writes_to_its_directory(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR unset, compat.persistent_cache
    turns JAX's on-disk compile cache on at the given directory: a
    freshly compiled callback-free program lands there."""
    monkeypatch.delenv(compat.CACHE_ENV, raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        assert compat.persistent_cache(str(tmp_path))
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        x = jnp.ones((32, 32))
        jax.block_until_ready(jax.jit(lambda v: v @ v + 1.75)(x))
        assert any(n.endswith("-cache") for n in os.listdir(tmp_path))
    finally:
        _reset_cache_config(old)


def test_persistent_cache_never_overrides_the_environment(tmp_path,
                                                          monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR places the cache, no repo code
    sets the directory."""
    monkeypatch.setenv(compat.CACHE_ENV, str(tmp_path / "from_env"))
    old = jax.config.jax_compilation_cache_dir
    try:
        compat.persistent_cache(str(tmp_path / "from_code"))
        compat.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == old
    finally:
        _reset_cache_config(old)


def test_default_compile_cache_is_a_fixed_ignored_path(monkeypatch):
    """Without the environment variable, the entry points cache at one
    fixed path inside the checkout, which git ignores."""
    path = compat.default_cache_dir()
    assert path == os.path.join(ROOT, ".jax_compile_cache")
    with open(os.path.join(ROOT, ".gitignore"), encoding="utf-8") as f:
        assert ".jax_compile_cache/" in f.read().split()
    seen = []
    monkeypatch.setattr(compat, "persistent_cache",
                        lambda d: seen.append(d) or True)
    compat.use_compile_cache()
    assert seen == [path]


def test_psum_grouped_lands_in_the_jaxpr():
    """compat.psum_grouped: a plain global all-reduce when no groups
    are given (executed here), and with groups the axis_index_groups
    partition must land in the traced program — trace-level is what
    matters, because the packed fence checker reads the grouping back
    out of the jaxpr params.  (Grouped psum only LOWERS on a real
    multi-engine mesh; the packed-execution tests cover that leg.)"""
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((1,), ("engine",))

    def body(groups):
        # check_vma=False: shard_map's replication check has no rule
        # for grouped psum; the ladder programs trace this way too
        return jax.shard_map(
            lambda x: compat.psum_grouped(x, "engine", groups),
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)

    x = jnp.arange(4.0)
    np.testing.assert_array_equal(np.asarray(body(None)(x)),
                                  np.asarray(x))
    jaxpr = jax.make_jaxpr(body(((0,),)))(x)
    found = [e.params.get("axis_index_groups")
             for sub in jax.core.subjaxprs(jaxpr.jaxpr)
             for e in sub.eqns if "psum" in e.primitive.name]
    assert found and tuple(map(tuple, found[0])) == ((0,),)


# ---------------------------------------------------------------------------
# Module-size lint: the exec pipeline must not regrow a monolith
# ---------------------------------------------------------------------------


def test_exec_pipeline_module_size_lint():
    """The coordinator split is enforced structurally: no module in
    ``src/repro/core/exec/`` may exceed 600 lines, and the coordinator
    facade must stay under 700 — a stage that outgrows its budget
    needs a new seam, not a bigger file."""
    exec_dir = os.path.join(ROOT, "src", "repro", "core", "exec")
    offenders = []
    for name in sorted(os.listdir(exec_dir)):
        if not name.endswith(".py"):
            continue
        path = os.path.join(exec_dir, name)
        with open(path, encoding="utf-8") as f:
            n = sum(1 for _ in f)
        if n > 600:
            offenders.append(f"core/exec/{name}: {n} lines (max 600)")
    coord = os.path.join(ROOT, "src", "repro", "core", "coordinator.py")
    with open(coord, encoding="utf-8") as f:
        n = sum(1 for _ in f)
    if n >= 700:
        offenders.append(f"core/coordinator.py: {n} lines (max 699)")
    assert not offenders, "monolith regrowth:\n" + "\n".join(offenders)


# ---------------------------------------------------------------------------
# Drift lint: grep the tree for version-sensitive symbols
# ---------------------------------------------------------------------------

# Spelled with [] splits so this file does not match itself.
_FORBIDDEN = [
    # compat.make_mesh owns the mesh axis-type choice (Auto axes)
    r"\bAxis" + r"Type\b",
    r"axis_" + r"types\s*=",
    # APIs the installed JAX removed or renamed
    r"\bTPUCompiler" + r"Params\b",
    r"jax\.experimental\s+import\s+shard" + r"_map",
    r"jax\.experimental\.shard" + r"_map",
    r"\bcheck_" + r"rep\s*=",
    # lax.switch's `operand=` kwarg is gone: operands are passed
    # positionally.  Two spellings: same-line, and a bare continuation
    # line (the historical bug had the kwarg on its own wrapped line)
    r"lax\.switch\(.*oper" + r"and\s*=",
    r"^\s*oper" + r"and\s*=",
    # the rung clock is one host callback, owned by compat.device_clock
    r"\bio_call" + r"back\b",
    # the persistent compilation cache is placed only by
    # compat.persistent_cache, which honours JAX_COMPILATION_CACHE_DIR
    r"jax_compilation_" + r"cache_dir",
    r"jax_persistent_" + r"cache_min",
    r"\bset_cache_" + r"dir\b",
    r"jax\.experimental\.compilation_" + r"cache",
    # grouped collectives: compat.psum_grouped is the one spelling the
    # packed fence checker reads back (reading the param OUT of a
    # traced jaxpr — params.get(...) — carries no "=" and stays legal)
    r"axis_index_" + r"groups\s*=",
]

_SCAN_DIRS = ("src", "tests", "benchmarks", "examples")
_EXEMPT = (os.path.join("src", "repro", "compat.py"),
           os.path.join("tests", "test_compat.py"))


def _py_files():
    for f in sorted(os.listdir(ROOT)):          # chip_smoke.py & co.
        if f.endswith(".py"):
            yield os.path.join(ROOT, f)
    for d in _SCAN_DIRS:
        for root, _dirs, files in os.walk(os.path.join(ROOT, d)):
            for f in files:
                if f.endswith(".py"):
                    yield os.path.join(root, f)


def test_no_compat_owned_or_removed_jax_symbols_outside_compat():
    pats = [re.compile(p) for p in _FORBIDDEN]
    offenders = []
    for path in _py_files():
        rel = os.path.relpath(path, ROOT)
        if rel in _EXEMPT:
            continue
        with open(path, encoding="utf-8") as f:
            for lineno, line in enumerate(f, 1):
                for pat in pats:
                    if pat.search(line):
                        offenders.append(
                            f"{rel}:{lineno}: {line.strip()}"
                            f"  [{pat.pattern}]")
    assert not offenders, (
        "compat-owned or removed JAX spellings outside repro/compat.py:"
        "\n" + "\n".join(offenders))
