"""Put the device's idle time down to the program's own spans.

The program under test opens flat host spans named ``memscope.<name>``
(``jax.profiler.TraceAnnotation``, on the same clock as the device
planes) on its characterize path.  A share here is the part of the
traced window in which the device is idle, outside the busy union that
``device_idle.*`` reads, and the host is inside the named spans,
averaged over the traced devices.  The spans do not nest, so the
shares of disjoint sets of names, with the share under no such span,
add up to the idle share.

Nothing here imports the program: a program without the spans reads 0
under every name and all of its idle time under none.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

from bench import trace as tr

PREFIX = "memscope."

Intervals = List[Tuple[float, float]]


def _overlap(a: Intervals, b: Intervals) -> float:
    """The length of the intersection of two sorted disjoint interval
    lists."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _idle(dev: tr.Device, window) -> Intervals:
    """The window less the device's busy union."""
    lo, hi = window
    edges = [lo] + [x for iv in tr._busy_intervals(dev, window)
                    for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_share(trace: tr.Trace, names: Optional[Iterable[str]]
               ) -> Optional[float]:
    """The share (0..1) of the window in which the device is idle and
    the host is inside a ``memscope.<name>`` span for one of ``names``;
    with ``names`` None, inside none of the program's spans.  None when
    the trace has no device plane."""
    if not trace.devices:
        return None
    want = None if names is None else {PREFIX + n for n in names}
    spans = tr.merge(((h.start_ns, h.end_ns) for h in trace.host
                      if h.name.startswith(PREFIX)
                      and (want is None or h.name in want)), *trace.window)
    tot = 0.0
    for dev in trace.devices:
        idle = _idle(dev, trace.window)
        under = _overlap(idle, spans)
        tot += under if want is not None else (
            sum(e - s for s, e in idle) - under)
    lo, hi = trace.window
    return tot / len(trace.devices) / (hi - lo)
