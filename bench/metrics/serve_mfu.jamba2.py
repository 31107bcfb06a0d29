"""serve_mfu.jamba2: the matmul and attention FLOPs of every call's
prefill and decode steps in the traced window, counted from shapes
(``bench.counts_jamba2``), over the window's length times the chip's
bf16 peak.  Layer: model step."""
from bench import counts
from bench import counts_jamba2


def read(run):
    if run.trace is None or not run.trace.devices or not run.window.units:
        return None
    d = run.window.data
    cfg = run.cell.config
    flops = sum(counts_jamba2.generate_flops(cfg, d["batch"], s,
                                             d["new_tokens"])
                for s in d["prompt_lens"])
    peak = counts.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (run.trace.window_s * peak * run.cell.chips)
