"""Plain numpy references for the probe kernels' outputs.

What each probe kernel must have returned, from numpy alone: the sum of
the sequential-integer buffer (plus what the kernel wrote) for streams,
the final index of the chain walk for pointer chases.  The buffer-size
arithmetic and the Sattolo chain are written out here rather than taken
from the program, so that a change to the program cannot move the
reference with it.
"""
from __future__ import annotations

import functools
import math

import numpy as np

LANE = 128
LINE_BYTES = LANE * 4
# streams above this size are HBM-streaming kernels, below it the
# cacheable strategies r/w/l use VMEM-resident ones
VMEM_KERNEL_BYTES = 32 << 20


def rows_for(buffer_bytes: int) -> int:
    """Lines of one probe buffer: whole 512-line blocks where a buffer
    holds one or more of them."""
    rows = max(1, buffer_bytes // LINE_BYTES)
    block = 512 if rows >= 512 else rows
    return (rows // block) * block or rows


def chase_steps(rows: int) -> int:
    """One short of the cycle, so the walk ends at line 0's predecessor."""
    return max(1, rows - 1)


def sattolo(n_lines: int, seed: int) -> np.ndarray:
    """The single-cycle permutation Sattolo's algorithm draws from
    ``numpy.random.default_rng(seed)``."""
    rng = np.random.default_rng(seed)
    p = np.arange(n_lines)
    for i in range(n_lines - 1, 0, -1):
        j = rng.integers(0, i)
        p[i], p[j] = p[j], p[i]
    return p


def strided_cycle(n_lines: int, stride: int) -> np.ndarray:
    """next[i] = (i + s) mod n, with s the smallest value >= stride
    coprime to n."""
    if n_lines == 1:
        return np.zeros(1, np.int64)
    s = max(1, stride) % n_lines or 1
    while math.gcd(s, n_lines) != 1:
        s += 1
        if s >= n_lines:
            s = 1
            break
    return (np.arange(n_lines) + s) % n_lines


def walk(nxt: np.ndarray, steps: int) -> int:
    """Follow ``nxt`` from line 0 for ``steps`` dependent loads."""
    idx = 0
    for _ in range(steps):
        idx = int(nxt[idx])
    return idx


@functools.lru_cache(maxsize=64)
def checksum(strategy: str, buffer_bytes: int, vmem: bool,
             chain_seed: int = 0) -> float:
    """The checksum the probe ``strategy`` must report for a buffer of
    ``buffer_bytes`` (``vmem``: the VMEM-resident variant ran)."""
    rows = rows_for(buffer_bytes)
    n = rows * LANE
    x = np.arange(n, dtype=np.float32).astype(np.float64)
    s = strategy
    if s in ("r", "s", "c"):
        return float(x.sum())
    if s == "x":                      # read, add one, write back
        return float(x.sum() + n)
    if s in ("w", "y"):               # the last value stored per element
        return float(n * (7.0 if (s == "w" and vmem) else 1.0))
    if s == "b":                      # half the blocks read, half written
        blk = min(512, rows)
        if rows // blk < 8:           # the kernel keeps >= 8 blocks
            blk = max(b for b in range(1, rows // 8 + 1) if rows % b == 0)
        nb = rows // blk
        n_r = max(1, min(nb - 1, int(round(nb * 0.5))))
        return float(x[:n_r * blk * LANE].sum() + (nb - n_r) * blk * LANE)
    if s in ("l", "m"):
        return float(walk(sattolo(rows, chain_seed), chase_steps(rows)))
    if s == "t":
        return float(walk(strided_cycle(rows, 8), chase_steps(rows)))
    if s == "i":                      # powers of the identity: its trace
        return 128.0
    raise KeyError(f"no reference for strategy {s!r}")


def uses_vmem_kernel(buffer_bytes: int, pool_kind: str) -> bool:
    return buffer_bytes <= VMEM_KERNEL_BYTES or pool_kind == "vmem"
