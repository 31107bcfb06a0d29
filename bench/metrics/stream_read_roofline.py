"""stream_read_roofline: the bytes the HBM read kernel must move
(``bench.counts.read_hbm_bytes`` of each call's buffer, read from the
operand shape the trace gives the call) over the kernel's device time,
as a share of the chip's HBM bandwidth.  Layer: kernels.

The kernel is the Pallas call inside the toolkit's jitted
``ops.stream_read``; compiled for a v5e, its HLO instruction is named
``vmap_jit_stream_read__.<n>`` and its op name ends in
``vmap(jit(stream_read))/pallas_call``."""
from bench import counts
from bench import trace as tr

KERNEL = r"stream_read"


def read(run):
    if run.trace is None:
        return None
    events = [e for e in tr.ops_matching(run.trace, KERNEL)
              if len(tr.largest_operand(e)) >= 2]
    t = tr.seconds(events)
    if not events or t <= 0:
        return None
    nbytes = 0
    for e in events:
        dims = tr.largest_operand(e)
        rows = 1
        for x in dims[:-1]:
            rows *= x
        nbytes += counts.read_hbm_bytes(rows)
    peak = counts.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * nbytes / t / peak
