"""compiles_per_curve: jit cache misses in the window (XLA compiles plus
persistent-cache loads, counted from ``jax.monitoring``) per curve the
window completed.  Layer: characterize / coordinator."""


def read(run):
    if not run.window.units:
        return None
    return run.counters["jit_misses"] / run.window.units
