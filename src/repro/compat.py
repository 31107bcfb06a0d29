"""The few JAX spellings this repo fixes in one place.

The repo is written for the installed JAX (0.9) and calls its API
directly.  What stays here is a helper that encodes a choice the rest
of the code must make the same way everywhere:

* meshes use ``Auto`` axes (the sharding rules rely on GSPMD
  propagation);
* rung timestamps inside a dispatch come from one host callback
  (:func:`device_clock`), the only ``io_callback`` in the tree;
* grouped all-reduces spell ``axis_index_groups`` once
  (:func:`psum_grouped`), so the fence checker can read it back;
* the persistent compile cache is placed by :func:`persistent_cache`,
  which never overrides ``JAX_COMPILATION_CACHE_DIR``.

The grep lint in ``tests/test_compat.py`` keeps those spellings, and
removed APIs, out of every other module.  Nothing here touches device
state at import time: the dry-run sets
``xla_force_host_platform_device_count`` first.
"""
from __future__ import annotations

import functools
import os
import time
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np

# Where rung timestamps come from: a host callback, not a device
# counter (the installed JAX exposes none).  Recorded as the
# ``timing_source`` of every fused spmd ladder.
CLOCK_SOURCE = "callback"


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence[Any]] = None):
    """``jax.make_mesh`` with every axis in Auto mode."""
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(shape), axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices)


def make_mesh_from_devices(devices: Sequence[Any], axes: Sequence[str]):
    """1-D (or reshaped) explicit-device mesh."""
    return jax.sharding.Mesh(np.array(devices), tuple(axes))


# ---------------------------------------------------------------------------
# In-dispatch timing probe (rung clocks)
# ---------------------------------------------------------------------------


def _clock_parts(_dep=None):
    """Monotonic wall clock split into x32-safe int32 parts."""
    t = time.perf_counter_ns()
    return np.asarray([t // 1_000_000_000, t % 1_000_000_000], np.int32)


def device_clock(dep):
    """A ``(2,)``-int32 ``[seconds, nanoseconds]`` host timestamp taken
    from INSIDE the dispatch, data-dependent on ``dep``.

    The fused spmd ladder brackets every scanned rung sample with two of
    these, so per-rung elapsed time comes from in-dispatch deltas
    instead of host ``perf_counter`` around ``block_until_ready``.  The
    stamp is a host callback (:data:`CLOCK_SOURCE`): each costs a
    device-to-host round trip, and XLA will not persist a program that
    holds one in the compile cache.

    Consumers MUST thread the returned stamp's *value* into the work
    being timed (see the coordinator's exact-zero ``min(stamp, 0)``
    trick): the callback fills its result buffer asynchronously, so a
    scheduling-only edge (``optimization_barrier``) does not make the
    measured work wait for the stamp."""
    import jax.numpy as jnp
    from jax.experimental import io_callback
    return io_callback(_clock_parts, jax.ShapeDtypeStruct((2,), jnp.int32),
                       dep, ordered=False)


# ---------------------------------------------------------------------------
# Persistent compile cache
# ---------------------------------------------------------------------------

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def persistent_cache(cache_dir: str) -> bool:
    """Enable JAX's persistent compilation cache at ``cache_dir`` unless
    ``JAX_COMPILATION_CACHE_DIR`` already places it, and return whether
    the cache is on.

    SCOPE: the cache is PROCESS-GLOBAL JAX configuration — once enabled
    it serves (and is written by) every compile in the process.  With
    the environment variable set, JAX reads it at start-up and this
    function changes nothing.  Every program is cached, however small:
    sweeps are dominated by many medium-sized programs that sit below
    the default write thresholds.  XLA refuses to persist programs that
    hold host callbacks (:func:`device_clock`), so fused ladder
    programs recompile in every process."""
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # the cache module memoizes a "disabled" verdict if anything
        # was compiled before the dir was set; reset it so the next
        # compilation re-initializes against the new directory
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc)
        _cc.reset_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return bool(jax.config.jax_compilation_cache_dir)


# the checkout this package runs from (src/repro/compat.py -> root)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_cache_dir() -> str:
    """The fixed in-checkout cache path, ``<checkout>/.jax_compile_cache``
    (listed in ``.gitignore``).  The path is part of a cache entry's
    key, so it is never made from a temporary name, a pid or the time."""
    return os.path.join(REPO_ROOT, ".jax_compile_cache")


def use_compile_cache() -> Optional[str]:
    """What every entry point (``chip_smoke.py``, ``launch/serve.py``,
    the bench harnesses) calls first: the persistent compile cache
    lives where ``JAX_COMPILATION_CACHE_DIR`` says, else at
    :func:`default_cache_dir`.  Returns the directory in use."""
    persistent_cache(default_cache_dir())
    return jax.config.jax_compilation_cache_dir or None


# ---------------------------------------------------------------------------
# Input buffer donation (per-backend availability)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def donation_supported() -> bool:
    """Does this process's backend implement input buffer donation?

    Probed by compiling a trivial donated program and checking that JAX
    did not warn the donation away (platforms without donation keep the
    program correct but ignore ``donate_argnums``).  The fused spmd
    ladder donates its cached rung operands so repeated dispatches
    alias buffers in place instead of copying."""
    import warnings
    import jax.numpy as jnp
    x = jnp.ones((8,), jnp.float32)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        jax.block_until_ready(jax.jit(lambda v: v + 1.0, donate_argnums=0)(x))
    return not any("donat" in str(m.message).lower() for m in w)


def psum_grouped(x, axis, groups=None):
    """``jax.lax.psum`` over disjoint index groups of one mesh axis —
    the grouped-collective spelling behind engine-subset width-packing
    (each packed ladder's psum sandwich reduces over ITS engine subset
    only).  ``groups`` is a tuple of index tuples that must partition
    the axis (e.g. ``((0, 1), (2, 3))`` on a 4-engine mesh); ``None``
    or empty means a plain global all-reduce.  The packed fence checker
    reads the grouping back out of the traced jaxpr."""
    if not groups:
        return jax.lax.psum(x, axis)
    return jax.lax.psum(x, axis,
                        axis_index_groups=tuple(tuple(g) for g in groups))


# ---------------------------------------------------------------------------
# Memory kinds
# ---------------------------------------------------------------------------


def device_memory_kinds(device) -> Tuple[str, ...]:
    return tuple(m.kind for m in device.addressable_memories())
