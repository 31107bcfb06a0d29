"""compiles_per_call: jit cache misses in the window (XLA compiles plus
persistent-cache loads, counted from ``jax.monitoring``) per
``generate`` call.  Layer: serve engine."""


def read(run):
    if not run.window.units:
        return None
    return run.counters["jit_misses"] / run.window.units
