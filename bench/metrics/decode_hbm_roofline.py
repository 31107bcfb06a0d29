"""decode_hbm_roofline: the HBM bytes every decode step of the traced
window must move (weights, tied LM head and KV cache, counted from
shapes by ``bench.counts.decode_bytes``) over the device time of the
decode program, as a share of the chip's HBM bandwidth.
Layer: model step.

The decode program is the engine's eager ``lax.scan`` over its decode
steps, which JAX compiles as the module ``jit_scan``: one per call,
holding the call's ``new_tokens - 1`` steps."""
from bench import counts
from bench import trace as tr

PROGRAM = r"^jit_scan\b"


def read(run):
    if run.trace is None or not run.window.units:
        return None
    events = tr.modules_matching(run.trace, PROGRAM)
    t = tr.seconds(events)
    if t <= 0:
        return None
    d = run.window.data
    cfg = run.cell.config
    nbytes = sum(counts.decode_bytes(cfg, d["batch"], s + i + 1)
                 for s in d["prompt_lens"] for i in range(d["new_tokens"] - 1))
    peak = counts.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * nbytes / t / peak
