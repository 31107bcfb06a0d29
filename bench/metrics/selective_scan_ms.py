"""selective_scan_ms: the device time of the Mamba-1 selective-scan
kernel (the Pallas call named ``selective_scan``, one a Mamba layer in
each prefill) per ``generate`` call in the traced window.
Layer: kernels (``kernels/selective_scan.py``)."""
from bench import trace as tr

KERNEL = r"^%?selective_scan\b"


def read(run):
    if run.trace is None or not run.window.units:
        return None
    events = tr.ops_matching(run.trace, KERNEL)
    if not events:
        return None
    return tr.seconds(events) * 1e3 / run.window.units
