"""Platform description + auto-detection — the device-tree analog.

MEMSCOPE discovers memory modules from the kernel device tree (DTB nodes
with ``compatible = "mempool"``).  Our platforms are described by the same
kind of declarative tree (a dict / JSON file with one node per memory
module), and ``detect_platform()`` auto-builds the description for the
runtime it finds — exactly the role the DTB plays for the kernel module.

Each node records the *modeled* temporal characteristics used by the
queueing simulator (``repro.core.simulate``) and by the roofline; on real
TPU hardware the same numbers are the published v5e specs.
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax

# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MemoryNode:
    """One memory module (a DTB ``mempool`` node)."""
    name: str                 # pool name, e.g. "hbm"
    kind: str                 # hbm | vmem | host | peer
    size_bytes: int
    peak_bw_gbps: float       # sustained sequential bandwidth, GB/s
    base_latency_ns: float    # unloaded round-trip latency
    port: str = "noc"         # shared interconnect this module hangs off
    max_mlp: int = 16         # per-engine outstanding-transaction limit
    memory_kind: Optional[str] = None   # jax memory kind ("device", ...)

    @property
    def reg(self) -> str:
        """DTS-style reg string (size only; PA base is virtualised)."""
        return f"<0x0 0x{self.size_bytes:x}>"


@dataclass(frozen=True)
class InterconnectNode:
    """A shared transaction port (the CCI analog)."""
    name: str
    bw_gbps: float
    queue_entries: int        # shared outstanding-transaction entries


@dataclass(frozen=True)
class Platform:
    name: str
    n_engines: int            # traffic-generating compute engines ("cores")
    line_bytes: int           # transaction granularity
    memories: Dict[str, MemoryNode]
    ports: Dict[str, InterconnectNode]
    peak_flops: float = 0.0   # per engine, FLOP/s (bf16)
    shared_port: str = "noc"  # the CCI analog every off-core Tx traverses
    # name of a *transparent shared cache* node (ZCU102: "l2").  None on
    # v5e: VMEM is a private software-managed scratchpad, so hit-path
    # bank contention structurally cannot arise there (DESIGN.md
    # §hardware-adaptation) — cacheable small buffers simply become
    # VMEM-resident with no cross-engine cache coupling.
    cache_node: Optional[str] = None

    def node(self, name: str) -> MemoryNode:
        if name not in self.memories:
            raise KeyError(
                f"no memory node {name!r}; available: "
                f"{sorted(self.memories)}")
        return self.memories[name]

    def to_json(self) -> str:
        return json.dumps({
            "name": self.name,
            "n_engines": self.n_engines,
            "line_bytes": self.line_bytes,
            "peak_flops": self.peak_flops,
            "shared_port": self.shared_port,
            "memories": {k: dataclasses.asdict(v)
                         for k, v in self.memories.items()},
            "ports": {k: dataclasses.asdict(v)
                      for k, v in self.ports.items()},
        }, indent=2)

    @staticmethod
    def from_json(text: str) -> "Platform":
        d = json.loads(text)
        return Platform(
            name=d["name"], n_engines=d["n_engines"],
            line_bytes=d["line_bytes"],
            peak_flops=d.get("peak_flops", 0.0),
            shared_port=d.get("shared_port", "noc"),
            memories={k: MemoryNode(**v) for k, v in d["memories"].items()},
            ports={k: InterconnectNode(**v)
                   for k, v in d["ports"].items()},
        )


# ---------------------------------------------------------------------------
# The modeled TPU v5e platform (DESIGN.md §2 mapping table).
#
# Numbers: HBM bw/size and bf16 FLOPs are published v5e specs; VMEM size is
# the documented 128 MiB; VMEM bandwidth/latency, host-PCIe and ICI figures
# are modeling estimates (marked in DESIGN.md).  The 512-byte line is the
# natural TPU transaction granularity (one (8,128)·f32 VREG tile row ≈ a
# DMA burst), the analog of the 64-byte ARM cache line.
# ---------------------------------------------------------------------------

TPU_V5E = Platform(
    name="tpu-v5e",
    # the modeled contention ladder's depth: up to 8 traffic engines
    # (paper: 4 cores).  A v5e chip has ONE TensorCore, so on the chip
    # only rung 0 is measured by one engine; the spmd backend executes
    # rungs on min(n_engines, devices) chips, one engine per chip.
    n_engines=8,
    line_bytes=512,
    peak_flops=197e12,
    # max_mlp calibration: TPU DMA queues pipeline deeply (hundreds of
    # outstanding 512B-line transactions), unlike a CPU core's ~6-entry
    # LSQ — this is WHY TPUs hide HBM latency, and it is the recorded
    # hardware-adaptation delta vs. the paper's ARM numbers.  Values are
    # set so a single stream reaches the plausible fraction of peak
    # (hbm: ~340 GB/s single DMA stream; host: ~8 GB/s PCIe stream) and
    # full 8-engine ladders saturate the module.
    memories={
        "hbm": MemoryNode("hbm", "hbm", 16 << 30, 819.0, 390.0,
                          port="noc", max_mlp=256, memory_kind="device"),
        "vmem": MemoryNode("vmem", "vmem", 128 << 20, 11_000.0, 35.0,
                           port="core", max_mlp=256, memory_kind=None),
        "host": MemoryNode("host", "host", 256 << 30, 28.0, 2_100.0,
                           port="pcie", max_mlp=32,
                           memory_kind="pinned_host"),
        "peer": MemoryNode("peer", "peer", 16 << 30, 45.0, 1_400.0,
                           port="ici", max_mlp=32, memory_kind=None),
    },
    ports={
        "noc": InterconnectNode("noc", 1_600.0, 64),
        "core": InterconnectNode("core", 22_000.0, 16),
        "pcie": InterconnectNode("pcie", 32.0, 32),
        "ici": InterconnectNode("ici", 50.0, 32),
    },
)

# The ZCU102 platform from the paper (used to sanity-check the simulator
# against the paper's published curves — Fig. 4/5, Tables II/III, and the
# cache experiments Fig. 10-13: the shared L2 appears as a "cache"-kind
# node whose single bank port every cacheable access traverses).
ZCU102 = Platform(
    name="zcu102",
    n_engines=4,              # quad Cortex-A53
    line_bytes=64,
    peak_flops=12e9,
    memories={
        "dram": MemoryNode("dram", "hbm", 256 << 20, 4.8, 150.0,
                           port="cci", max_mlp=6, memory_kind="device"),
        "pl-dram": MemoryNode("pl-dram", "host", 256 << 20, 1.6, 380.0,
                              port="cci", max_mlp=6, memory_kind=None),
        "ocm": MemoryNode("ocm", "vmem", 128 << 10, 3.2, 120.0,
                          port="cci", max_mlp=4, memory_kind=None),
        "bram": MemoryNode("bram", "vmem", 1 << 20, 1.2, 200.0,
                           port="cci", max_mlp=4, memory_kind=None),
        # the unified 16-way 1 MiB LLC; single-banked on this SoC —
        # calibrated so 1 core extracts ~21 GB/s hitting in L2 and 4
        # contending cores see the paper's ~3.2x cycles/access blow-up
        "l2": MemoryNode("l2", "cache", 1 << 20, 27.0, 30.0,
                         port="l2bank", max_mlp=12, memory_kind=None),
    },
    ports={"cci": InterconnectNode("cci", 9.6, 16),
           # 12 writeback-buffer entries: one y-stream engine (posted MLP
           # 12) fits exactly — reproducing the paper's Fig. 13 boundary
           # (identical at 1 stressor, collapse at >= 2)
           "l2bank": InterconnectNode("l2bank", 27.0, 12)},
    shared_port="cci",
    cache_node="l2",
)


def zcu102_partitioned() -> Platform:
    """The Minerva-Jailhouse page-coloring setup of §IV-D: 1/4 of the LLC
    (256 KiB) exported as the *private cache pool* (pvtpool); the shared
    part shrinks to 768 KiB.  pvtpool is just another heterogeneous
    memory module from MEMSCOPE's point of view."""
    mems = dict(ZCU102.memories)
    mems["l2"] = dataclasses.replace(mems["l2"], size_bytes=768 << 10)
    mems["pvtpool"] = MemoryNode("pvtpool", "cache", 256 << 10, 27.0, 30.0,
                                 port="l2bank", max_mlp=12,
                                 memory_kind=None)
    return dataclasses.replace(ZCU102, name="zcu102-partitioned",
                               memories=mems)


# device trees of the TPUs this repo describes, keyed by
# ``jax.Device.device_kind``
_TPU_TREES = {"TPU v5 lite": TPU_V5E, "TPU v5e": TPU_V5E}


def detect_platform(override: Optional[str] = None) -> Platform:
    """Auto-detect like MEMSCOPE reads the DTB at module load.

    On a TPU the tree is keyed by the device's ``device_kind``; a TPU
    kind with no tree is an error.  Off-TPU (the CPU, for tests and the
    simulate backend) returns the explicitly *modeled* v5e tree.
    """
    if override == "zcu102":
        return ZCU102
    if override == "tpu-v5e":
        return TPU_V5E
    if override is not None:
        raise KeyError(f"unknown platform {override!r}")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return TPU_V5E
    if dev.device_kind not in _TPU_TREES:
        raise KeyError(
            f"no device tree for TPU kind {dev.device_kind!r}; "
            f"described: {sorted(_TPU_TREES)}")
    return _TPU_TREES[dev.device_kind]
