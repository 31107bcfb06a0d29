"""idle_in_call.serve: the share of the traced window in which the
device is idle while the host is inside a ``generate`` call (the
benchmark's ``bench.generate`` span: the engine's placement, dispatch,
eager decode loop and readback of the tokens), averaged over the traced
devices.  At a fixed arrival rate ``device_idle.serve`` also counts the
wait for the next call to be due; this share leaves that wait out.
Layer: serve engine."""
from bench import spans
from bench import trace as tr

SPAN = "bench.generate"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    t = run.trace
    calls = tr.merge(((h.start_ns, h.end_ns) for h in t.host
                      if h.name == SPAN), *t.window)
    if not calls:
        return None
    idle = sum(spans._overlap(spans._idle(d, t.window), calls)
               for d in t.devices)
    return 100.0 * idle / len(t.devices) / (t.window[1] - t.window[0])
