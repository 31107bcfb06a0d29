"""idle_timed.curves: the share of the traced window in which the device
is idle inside the measured samples (``memscope.timed``: the timed
calls whose median the toolkit reports, so this idle time counts in
``read_gbps``).  Layer: workloads (``core/workloads.py``, ``_timed``)."""
from bench import spans


def read(run):
    share = None if run.trace is None else spans.idle_share(run.trace,
                                                             ("timed",))
    return None if share is None else 100.0 * share
