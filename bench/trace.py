"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: the device's busy time (the union of the intervals in
which an operation ran), the time of each named kernel or executable,
and the longest idle gaps, each labelled by what the host was doing.

The traced window is the benchmark's own host span ``bench.window``;
the host spans the drivers open around each call into the program
(``bench.<what>``) label the gaps.  Everything here reads the file with
JAX's own ``ProfileData``; nothing depends on the program.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
BENCH_PREFIX = "bench."
# device lines that hold one event per executed operation, and one per
# executed program, as the TPU profiler names them
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event:
    """One trace event: a name and an interval in nanoseconds.  Its
    ``stats`` (HLO long name, op category, ...) are read from the
    profiler's event only when asked for: a serving window holds a
    million device operations, and few of them are ever looked at."""
    __slots__ = ("name", "start_ns", "end_ns", "_stats", "_raw")

    def __init__(self, name: str, start_ns: float, end_ns: float,
                 stats: Optional[Dict[str, str]] = None, raw=None):
        self.name = name
        self.start_ns = start_ns
        self.end_ns = end_ns
        self._stats = stats
        self._raw = raw

    @property
    def stats(self) -> Dict[str, str]:
        if self._stats is None:
            self._stats = ({k: str(v) for k, v in self._raw.stats}
                           if self._raw is not None else {})
        return self._stats

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclass
class Device:
    name: str
    ops: List[Event]
    modules: List[Event]


@dataclass
class Trace:
    devices: List[Device]
    host: List[Event]            # every host event, bench spans included
    window: Tuple[float, float]  # (start_ns, end_ns) of bench.window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _events(line) -> Iterable[Event]:
    for e in line.events:
        yield Event(e.name, e.start_ns, e.start_ns + e.duration_ns, raw=e)


def load(path: str) -> Trace:
    """Read one trace; ``path`` is the file or a profiler log dir."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    devices: List[Device] = []
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops: List[Event] = []
            modules: List[Event] = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(_events(line))
                elif line.name == MODULES_LINE:
                    modules.extend(_events(line))
            if ops or modules:
                devices.append(Device(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    window = (min(e.start_ns for e in spans), max(e.end_ns for e in spans))
    return Trace(devices, host, window)


# ---------------------------------------------------------------------------
# Intervals
# ---------------------------------------------------------------------------


def merge(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _busy_intervals(dev: Device, window) -> List[Tuple[float, float]]:
    events = dev.ops or dev.modules
    return merge(((e.start_ns, e.end_ns) for e in events), *window)


def busy_s(trace: Trace) -> float:
    """Seconds of the window in which an operation ran, averaged over
    the traced devices."""
    if not trace.devices:
        return 0.0
    tot = sum(sum(e - s for s, e in _busy_intervals(d, trace.window))
              for d in trace.devices)
    return tot * 1e-9 / len(trace.devices)


def idle_share(trace: Trace) -> float:
    """1 - busy / window, as a share (0..1)."""
    return 1.0 - busy_s(trace) / trace.window_s


# ---------------------------------------------------------------------------
# Named kernels and executables
# ---------------------------------------------------------------------------


def _in_window(e: Event, window) -> bool:
    return window[0] <= (e.start_ns + e.end_ns) / 2 <= window[1]


def _matches(e: Event, pattern: "re.Pattern") -> bool:
    return bool(pattern.search(e.name)) or any(
        pattern.search(e.stats.get(k, ""))
        for k in ("long_name", "hlo_op", "tf_op"))


def ops_matching(trace: Trace, regex: str) -> List[Event]:
    """The device operations in the window whose name, or whose HLO
    long name, matches ``regex`` (on every device)."""
    pat = re.compile(regex)
    return [e for d in trace.devices for e in d.ops
            if _in_window(e, trace.window) and _matches(e, pat)]


def modules_matching(trace: Trace, regex: str) -> List[Event]:
    """The executed programs in the window whose name matches."""
    pat = re.compile(regex)
    return [e for d in trace.devices for e in d.modules
            if _in_window(e, trace.window) and pat.search(e.name)]


_SHAPE = re.compile(r"\b(?:f32|s32|u32|bf16|f16|s8|u8)\[([0-9,]*)\]")


def largest_operand(e: Event) -> Tuple[int, ...]:
    """The dimensions of the largest array named in the operation's HLO
    text (the probe kernels' buffer), or () when it names none.  The TPU
    profiler names each operation by its whole HLO instruction,
    ``%name = f32[1,1]{..} custom-call(f32[524288,128]{..} %x), ...``;
    a ``long_name`` stat, where a trace has one, holds the same text."""
    best: Tuple[int, ...] = ()
    size = -1
    for m in _SHAPE.finditer(e.stats.get("long_name") or e.name):
        dims = tuple(int(x) for x in m.group(1).split(",") if x)
        n = 1
        for x in dims:
            n *= x
        if n > size:
            best, size = dims, n
    return best


def seconds(events: Sequence[Event]) -> float:
    return sum(e.dur_ns for e in events) * 1e-9


def short_name(name: str) -> str:
    """An operation's instruction name, ``%vmap_jit_stream_read__.2 =
    f32[1,1] custom-call(...)`` -> ``vmap_jit_stream_read__.2``."""
    return name.split(" = ", 1)[0].lstrip("%")


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The device operations that took most time in the window, summed
    by instruction name over all devices."""
    tot: Dict[str, float] = {}
    for d in trace.devices:
        for e in d.ops or d.modules:
            if _in_window(e, trace.window):
                k = short_name(e.name)
                tot[k] = tot.get(k, 0.0) + e.dur_ns * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda t: -t[1])[:n]]


# ---------------------------------------------------------------------------
# Idle gaps, labelled by the host
# ---------------------------------------------------------------------------


def _label(trace: Trace, s: float, e: float) -> str:
    """What the host was doing in [s, e]: the innermost benchmark span
    around the gap's middle, and the host event that overlaps the gap
    longest among the others."""
    mid = (s + e) / 2
    spans = [h for h in trace.host if h.name.startswith(BENCH_PREFIX)
             and h.name != WINDOW_SPAN and h.start_ns <= mid <= h.end_ns]
    bench = min(spans, key=lambda h: h.dur_ns).name if spans else WINDOW_SPAN
    best, best_ov = "", 0.0
    for h in trace.host:
        if h.name.startswith(BENCH_PREFIX):
            continue
        ov = min(e, h.end_ns) - max(s, h.start_ns)
        if ov > best_ov:
            best, best_ov = h.name, ov
    return f"{bench}/{best}" if best else bench


def gaps(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` longest idle intervals of the first device in the
    window, as [label, seconds]."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    busy = _busy_intervals(trace.devices[0], trace.window)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    idle.sort(key=lambda iv: iv[0] - iv[1])
    return [[_label(trace, s, e), (e - s) * 1e-9] for s, e in idle[:n]]


def breakdown(trace: Trace, n: int = 10) -> Dict[str, List[List]]:
    return {"device_ops": top_ops(trace, n), "idle_gaps": gaps(trace, n)}
