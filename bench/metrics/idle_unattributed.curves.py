"""idle_unattributed.curves: the share of the traced window in which the
device is idle and the host is inside none of the program's
``memscope.*`` spans.  A program without the spans reads its whole
idle share here.  Layer: device."""
from bench import spans


def read(run):
    share = None if run.trace is None else spans.idle_share(run.trace,
                                                             None)
    return None if share is None else 100.0 * share
