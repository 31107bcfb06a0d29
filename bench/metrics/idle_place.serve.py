"""idle_place.serve: the share of the traced window in which the device
is idle while the serving engine advises and places a call's caches
(the program's ``memscope.place`` span).  Nothing to read, where the
program opens no such span.  Layer: serve engine
(``serve/engine.py``, ``ServeEngine.generate``)."""
from bench import spans

SPAN = spans.PREFIX + "place"


def read(run):
    if run.trace is None or not any(h.name == SPAN for h in run.trace.host):
        return None
    share = spans.idle_share(run.trace, ("place",))
    return None if share is None else 100.0 * share
