"""selective_scan_roofline: the least HBM bytes of every call of the
Mamba-1 selective-scan kernel (x, dt, A, B, C and the initial state
read, y and the final state written, each once, counted from the
array shapes of the kernel's HLO instruction in the trace) over the
kernel's device time, as a share of the chip's HBM bandwidth.
Layer: kernels (``kernels/selective_scan.py``).

Compiled for a v5e the kernel's instruction reads ``%selective_scan.<n>
= (f32[..], f32[..]) custom-call(bf16[..] %x, f32[..] %dt, ...),
custom_call_target="tpu_custom_call", operand_layout_constraints={..}``:
its results, then its operands with their shapes."""
import re

from bench import counts
from bench import counts_jamba2
from bench import trace as tr

KERNEL = r"^%?selective_scan\b"
_SHAPE = re.compile(r"\b(f32|s32|u32|bf16|f16|s8|u8)\[([0-9,]*)\]")


def _shapes(text):
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in _SHAPE.finditer(text)]


def call_shapes(name: str):
    """(type, dims) of every result and operand of one kernel call."""
    head, _, rest = name.partition("custom_call_target")
    results, _, operands = head.partition("custom-call(")
    found = _shapes(results) + _shapes(operands)
    if not _shapes(operands):
        # operands printed by name alone: their shapes are in the layout
        # constraints
        layouts = rest.partition("operand_layout_constraints=")[2]
        found += _shapes(layouts.partition("frontend_attributes=")[0])
    return found


def read(run):
    if run.trace is None:
        return None
    events = tr.ops_matching(run.trace, KERNEL)
    t = tr.seconds(events)
    if not events or t <= 0:
        return None
    nbytes = sum(counts_jamba2.array_bytes(call_shapes(e.name))
                 for e in events)
    peak = counts.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * nbytes / t / peak
