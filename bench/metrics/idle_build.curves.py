"""idle_build.curves: the share of the traced window in which the device
is idle while the program builds a measurement's program
(``memscope.build``: the first call, which traces, lowers, compiles or
loads from the persistent cache, and runs once).  Layer: workloads
(``core/workloads.py``, ``_timed``)."""
from bench import spans


def read(run):
    share = None if run.trace is None else spans.idle_share(run.trace,
                                                             ("build",))
    return None if share is None else 100.0 * share
