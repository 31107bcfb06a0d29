"""idle_coord.curves: the share of the traced window in which the device
is idle while the program plans the sweep, assembles its runs (the
queueing-model solves included) or builds its CurveDB
(``memscope.plan``, ``memscope.assemble``, ``memscope.curvedb``).
Layer: characterize / coordinator."""
from bench import spans


def read(run):
    share = None if run.trace is None else spans.idle_share(
        run.trace, ("plan", "assemble", "curvedb"))
    return None if share is None else 100.0 * share
