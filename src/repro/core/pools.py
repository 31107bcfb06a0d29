"""Memory Pool Manager — one allocator per detected memory module.

The genalloc/genpool analog: every :class:`MemoryNode` from the device
tree gets a :class:`MemoryPool` that (a) tracks allocations against the
module's capacity exactly like ``gen_pool_alloc/gen_pool_free``, and
(b) places JAX arrays on the right physical memory via sharding
``memory_kind`` (HBM = "device", host DRAM = "pinned_host").  VMEM is not
directly addressable from XLA programs, so its pool hands out *residency
descriptors* consumed by the Pallas workloads (BlockSpec decisions) —
the software-managed-scratchpad equivalent of an allocation.

``upool()`` exports a pool to applications — the ``/dev/upool<ID>`` mmap
analog: it returns a placement function usable by any framework object
(KV caches, optimizer state, ...).
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core.devicetree import MemoryNode, Platform, detect_platform


class PoolError(RuntimeError):
    pass


@dataclass
class Allocation:
    """A live allocation handle (the gen_pool_alloc return value)."""
    pool_id: int
    nbytes: int
    array: Optional[jax.Array] = None      # None for VMEM residency grants
    tag: str = ""


class MemoryPool:
    """Allocator over one memory module."""

    def __init__(self, pool_id: int, node: MemoryNode):
        self.id = pool_id
        self.node = node
        self.capacity = node.size_bytes
        self.allocated = 0
        self._handles: Dict[int, Allocation] = {}
        self._next = itertools.count()

    # -- genpool API ---------------------------------------------------
    def alloc(self, shape: Tuple[int, ...], dtype=jnp.float32, *,
              init: Optional[Callable[[Tuple[int, ...], Any], Any]] = None,
              tag: str = "") -> Allocation:
        nbytes = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
        if self.allocated + nbytes > self.capacity:
            raise PoolError(
                f"pool {self.node.name}#{self.id}: alloc {nbytes}B exceeds "
                f"capacity ({self.allocated}/{self.capacity}B used)")
        arr = None
        if self.node.memory_kind is not None:
            data = (init(shape, dtype) if init is not None
                    else jnp.zeros(shape, dtype))
            arr = self._place(data)
        a = Allocation(self.id, nbytes, arr, tag)
        a.handle = next(self._next)
        self._handles[a.handle] = a
        self.allocated += nbytes
        return a

    def free(self, a: Allocation) -> None:
        if self._handles.pop(getattr(a, "handle", -1), None) is None:
            raise PoolError(f"double free / foreign handle in pool {self.id}")
        self.allocated -= a.nbytes
        a.array = None

    def destroy(self) -> None:
        self._handles.clear()
        self.allocated = 0

    # -- placement -------------------------------------------------------
    def place(self, data: jax.Array) -> jax.Array:
        """Place an array on this pool's memory kind (public hook for
        transient measurement buffers that bypass alloc accounting)."""
        return self._place(data)

    def _place(self, data: jax.Array) -> jax.Array:
        dev = jax.devices()[0]
        kind = self.effective_memory_kind()
        if kind is None:
            return jax.device_put(data, dev)
        # a kind the device advertises but refuses raises here
        return jax.device_put(
            data, jax.sharding.SingleDeviceSharding(dev, memory_kind=kind))

    def effective_memory_kind(self) -> Optional[str]:
        """The memory kind :meth:`place` lands arrays in.

        ``None`` = the device's default memory: pools that declare no
        kind (VMEM residency, modeled peers) or ``"device"``.  A declared
        kind the device does not list is an error, never a quiet
        fallback to the default memory.  Two pools with equal effective
        kinds are *execution-equivalent* — the matrix runner uses this
        to decide which observers may share one stacked vmapped
        measurement batch."""
        kind = self.node.memory_kind
        if kind in (None, "device"):
            return None
        have = compat.device_memory_kinds(jax.devices()[0])
        if kind not in have:
            raise PoolError(
                f"pool {self.node.name}: memory kind {kind!r} is not "
                f"addressable on {jax.devices()[0].device_kind} "
                f"(device lists {list(have)})")
        return kind

    def sharding_for(self, mesh, spec) -> jax.sharding.NamedSharding:
        """NamedSharding carrying this pool's memory kind (upool export)."""
        return jax.sharding.NamedSharding(
            mesh, spec, memory_kind=self.effective_memory_kind())

    # -- status -----------------------------------------------------------
    @property
    def available(self) -> int:
        return self.capacity - self.allocated

    def status(self) -> str:
        n = self.node
        return (f"pool {self.id}: {n.name:8s} kind={n.kind:5s} "
                f"size={self.capacity >> 20} MiB "
                f"free={self.available >> 20} MiB "
                f"allocs={len(self._handles)}")


class PoolManager:
    """Auto-instantiates one pool per device-tree memory node."""

    def __init__(self, platform: Optional[Platform] = None):
        self.platform = platform or detect_platform()
        self._pools: Dict[str, MemoryPool] = {}
        for i, (name, node) in enumerate(
                sorted(self.platform.memories.items())):
            self._pools[name] = MemoryPool(i, node)

    def pool(self, name_or_id) -> MemoryPool:
        if isinstance(name_or_id, int):
            for p in self._pools.values():
                if p.id == name_or_id:
                    return p
            raise PoolError(f"no pool with id {name_or_id}")
        if name_or_id not in self._pools:
            raise PoolError(
                f"no pool {name_or_id!r}; have {sorted(self._pools)}")
        return self._pools[name_or_id]

    def pools(self) -> List[MemoryPool]:
        return sorted(self._pools.values(), key=lambda p: p.id)

    # the /dev/upool<ID> analog: applications get a placement handle
    def upool(self, name_or_id) -> "UserPool":
        return UserPool(self.pool(name_or_id))

    def status(self) -> str:
        return "\n".join(p.status() for p in self.pools())

    def destroy_all(self) -> None:
        for p in self.pools():
            p.destroy()


@dataclass
class UserPool:
    """User-space export of a pool (mmap-on-/dev/upool analog)."""
    pool: MemoryPool

    def place(self, tree, mesh=None, specs=None):
        """Place a pytree of arrays into this pool's memory and return
        once the copies have landed: no transfer into or out of host
        memory is left in flight when the caller drops the arrays (the
        TPU runtime faults on a pinned_host DMA that completes after its
        buffers went away)."""
        if mesh is None:
            placed = jax.tree.map(self.pool._place, tree)
        else:
            placed = jax.tree.map(
                lambda x, sp: jax.device_put(
                    x, self.pool.sharding_for(mesh, sp)), tree, specs)
        return jax.block_until_ready(placed)

    def sharding(self, mesh, spec):
        return self.pool.sharding_for(mesh, spec)

    @property
    def name(self) -> str:
        return self.pool.node.name
