"""prefill_ms: the device time of the engine's prefill executable
(``jax.jit`` of ``make_prefill_step``'s ``prefill``, so the module
``jit_prefill``) per ``generate`` call in the traced window.
Layer: serve engine."""
from bench import trace as tr

PROGRAM = r"^jit_prefill\b"


def read(run):
    if run.trace is None or not run.window.units:
        return None
    events = tr.modules_matching(run.trace, PROGRAM)
    if not events:
        return None
    return tr.seconds(events) * 1e3 / run.window.units
