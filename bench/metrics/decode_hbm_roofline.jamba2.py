"""decode_hbm_roofline.jamba2: the HBM bytes every decode step of the
traced window must move (weights, tied LM head, the attention layers'
KV cache read plus written, every Mamba layer's state and conv tail read
and written, counted from shapes by ``bench.counts_jamba2.decode_bytes``)
over the device time of the decode program, as a share of the chip's
HBM bandwidth.  Layer: model step.

The decode program is the engine's ``lax.scan`` over its decode steps,
compiled as the module ``jit_scan``: one per call, holding the call's
``new_tokens - 1`` steps."""
from bench import counts
from bench import counts_jamba2
from bench import trace as tr

PROGRAM = r"^jit_scan\b"


def read(run):
    if run.trace is None or not run.window.units:
        return None
    t = tr.seconds(tr.modules_matching(run.trace, PROGRAM))
    if t <= 0:
        return None
    d = run.window.data
    cfg = run.cell.config
    nbytes = sum(counts_jamba2.decode_bytes(cfg, d["batch"], s + i + 1)
                 for s in d["prompt_lens"] for i in range(d["new_tokens"] - 1))
    peak = counts.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * nbytes / t / peak
