"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
``xla_force_host_platform_device_count`` *before* any jax init, and smoke
tests must keep seeing 1 device.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro import compat
from repro.configs.base import MeshConfig


def _mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return compat.make_mesh(shape, axes)


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's target: one v5e pod (16x16 = 256 chips) or two
    pods (2x16x16 = 512 chips) with a leading "pod" data-parallel axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def mesh_from_config(mc: MeshConfig):
    return _mesh(mc.shape, mc.axis_names)


def make_host_mesh(data: int = 1, model: int = 1):
    """A (data, model) mesh over this process's first data*model
    devices; asking for more devices than exist raises."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"mesh data={data} x model={model} needs {data * model} "
            f"devices; this process has {n}")
    return _mesh((data, model), ("data", "model"))


def describe(mesh) -> str:
    return "x".join(f"{n}={s}" for n, s in
                    zip(mesh.axis_names, mesh.devices.shape))
