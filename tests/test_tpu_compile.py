"""Every probe kernel compiles for a TPU v5e, in the form the
coordinator calls it, at the largest size each path accepts.

Nothing runs: the TPU compiler installed beside JAX compiles for a
described v5e that is not attached, so a kernel the chip would refuse
(a block that breaks the (8, 128) tiling, a scalar stored to VMEM, more
VMEM than a kernel may use) fails here instead of on the chip.  The
kernels are called with ``interpret=False`` exactly as
``repro.kernels.ops`` calls them on a TPU process, bare and vmapped as
``workloads.measure_group`` stacks its members; kernels that take no
operand get their output pinned to the described chip.  Each compiled
program must hold a Mosaic kernel (``tpu_custom_call``).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.workloads import LANE, VMEM_KERNEL_BYTES, chase_steps, \
    rows_for
from repro.kernels import chase, compute_probe, stream

os.environ.setdefault("TPU_LOG_DIR", "disabled")

# the HBM streams take any size; this is the stacked-batch cap of
# measure_group, the most one measured pass ever holds
HBM_BYTES = 1 << 30
G = 4                                 # vmapped group members


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


def _f32(rows, sharding, g=None):
    shape = (rows, LANE) if g is None else (g, rows, LANE)
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _i32(rows, sharding, g=None):
    shape = (rows, LANE) if g is None else (g, rows, LANE)
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


HBM_ROWS = rows_for(HBM_BYTES)
HBM_ROWS_G = rows_for(HBM_BYTES // G)
VMEM_ROWS = rows_for(VMEM_KERNEL_BYTES)
BLK = 512


def _read(x):
    return stream.read_hbm(x, block_rows=BLK, interpret=False)


def _rmw(x):
    return stream.rmw_hbm(x, block_rows=BLK, interpret=False)


def _copy(x):
    return stream.copy_hbm(x, block_rows=BLK, interpret=False)


def _mixed(x):
    return stream.mixed_hbm(x, read_fraction=0.5, block_rows=BLK,
                            interpret=False)


def _read_vmem(x):
    return stream.read_vmem(x, repeats=8, interpret=False)


def _chase_vmem(b):
    return chase.chase_vmem(b, n_steps=chase_steps(b.shape[-2]),
                            interpret=False)


def _chase_hbm(b):
    return chase.chase_hbm(b, n_steps=chase_steps(b.shape[-2]),
                           interpret=False)


# name -> (function, operands(sharding)); operand-free kernels
# (None) are compiled with their output pinned to the described chip
KERNELS = {
    "read_hbm": (_read, lambda s: [_f32(HBM_ROWS, s)]),
    "read_hbm_vmapped": (jax.vmap(_read),
                         lambda s: [_f32(HBM_ROWS_G, s, G)]),
    "rmw_hbm": (_rmw, lambda s: [_f32(HBM_ROWS, s)]),
    "rmw_hbm_vmapped": (jax.vmap(_rmw),
                        lambda s: [_f32(HBM_ROWS_G, s, G)]),
    "copy_hbm": (_copy, lambda s: [_f32(HBM_ROWS, s)]),
    "copy_hbm_vmapped": (jax.vmap(_copy),
                         lambda s: [_f32(HBM_ROWS_G, s, G)]),
    "mixed_hbm": (_mixed, lambda s: [_f32(HBM_ROWS, s)]),
    "mixed_hbm_vmapped": (jax.vmap(_mixed),
                          lambda s: [_f32(HBM_ROWS_G, s, G)]),
    "write_hbm": (functools.partial(stream.write_hbm, HBM_ROWS,
                                    block_rows=BLK, interpret=False),
                  None),
    "write_hbm_seeded": (
        lambda seed: stream.write_hbm_seeded(seed, HBM_ROWS,
                                             block_rows=BLK,
                                             interpret=False),
        lambda s: [jax.ShapeDtypeStruct((1, 1), jnp.float32,
                                        sharding=s)]),
    "read_vmem": (_read_vmem, lambda s: [_f32(VMEM_ROWS, s)]),
    "read_vmem_vmapped": (jax.vmap(_read_vmem),
                          lambda s: [_f32(VMEM_ROWS, s, G)]),
    "write_vmem": (functools.partial(stream.write_vmem, VMEM_ROWS,
                                     repeats=8, interpret=False), None),
    "chase_vmem": (_chase_vmem, lambda s: [_i32(VMEM_ROWS, s)]),
    "chase_vmem_vmapped": (jax.vmap(_chase_vmem),
                           lambda s: [_i32(VMEM_ROWS, s, G)]),
    "chase_hbm": (_chase_hbm, lambda s: [_i32(HBM_ROWS, s)]),
    "chase_hbm_stacked": (_chase_hbm, lambda s: [_i32(HBM_ROWS_G, s, G)]),
    "mxu_probe": (lambda a: compute_probe.mxu_probe(a, iters=64,
                                                    interpret=False),
                  lambda s: [jax.ShapeDtypeStruct((128, 128), jnp.float32,
                                                  sharding=s)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_probe_kernel_compiles_for_v5e(name, one_chip):
    fn, operands = KERNELS[name]
    if operands is None:
        compiled = jax.jit(fn, out_shardings=one_chip).lower().compile()
    else:
        compiled = jax.jit(fn).lower(*operands(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_selective_scan_compiles_for_v5e_at_jamba2_widths(one_chip):
    """The Mamba-1 prefill scan as the mixer calls it on a chip, at
    jamba2-3b's widths (d_inner 5120, d_state 16) over a 16,384-token
    prompt; its instruction carries the kernel's name, which the
    benchmark's trace readers find it by."""
    from repro.kernels import selective_scan
    t, d, n = 16384, 5120, 16
    shapes = [((1, t, d), jnp.bfloat16), ((1, t, d), jnp.float32),
              ((d, n), jnp.float32), ((1, t, n), jnp.float32),
              ((1, t, n), jnp.float32), ((1, d, n), jnp.float32)]
    compiled = jax.jit(functools.partial(
        selective_scan.selective_scan, interpret=False)).lower(
        *[jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)
          for shp, dt in shapes]).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%selective_scan" in text


def test_vmem_kernels_are_not_handed_more_than_compiles(one_chip):
    """The residency cap the workloads hand VMEM kernels is what the
    compile above proved: one more block of rows is refused by the
    coordinator, never handed to a VMEM kernel."""
    from repro.core.devicetree import TPU_V5E
    from repro.core.pools import PoolManager
    from repro.core.workloads import refusal
    vmem = PoolManager(TPU_V5E).pool("vmem")
    assert refusal("r", vmem, VMEM_KERNEL_BYTES) is None
    assert refusal("r", vmem, VMEM_KERNEL_BYTES + (BLK << 9)) is not None
    assert VMEM_KERNEL_BYTES < stream.VMEM_LIMIT_BYTES


@pytest.mark.parametrize("strategy,mixed", [("r", False), ("b", True),
                                            ("m", False), ("l", False)])
def test_spmd_rung_passes_stay_inside_the_loop(strategy, mixed, one_chip,
                                               monkeypatch):
    """An spmd rung activity makes ``n`` passes over a loop-invariant
    buffer.  Compiled for the chip, its kernel must sit in the loop
    body: a kernel hoisted out of the loop runs once while the rung is
    credited with n passes (the rung then reads above the HBM peak)."""
    import re

    from repro.core.exec import program
    from repro.core.scenarios import TrafficShape
    from repro.kernels import ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # compiled kernels
    rows = 8192
    fn = program.spmd_branch_fn(
        strategy, TrafficShape.mixed(1, 1) if mixed else None, rows, 5,
        activity="pallas")
    text = jax.jit(fn).lower(_f32(rows, one_chip),
                             _i32(rows, one_chip)).compile().as_text()
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    holders = [c.split()[0].lstrip("%")
               for c in re.split(r"\n(?=\S)", text) if "tpu_custom_call" in c]
    assert holders and set(holders) <= bodies, (holders, bodies)
