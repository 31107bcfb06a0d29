"""chase_hbm_ns: the device time of the ``chase_hbm`` kernel per
dependent load, the loads of each call counted from the chain buffer's
shape that the trace gives it (one short of the cycle per chain).
Layer: kernels."""
from bench import counts
from bench import trace as tr

KERNEL = r"chase_hbm"


def read(run):
    if run.trace is None:
        return None
    events = [e for e in tr.ops_matching(run.trace, KERNEL)
              if len(tr.largest_operand(e)) >= 2]
    if not events:
        return None
    loads = 0
    for e in events:
        dims = tr.largest_operand(e)
        chains = 1
        for x in dims[:-2]:
            chains *= x
        loads += counts.chase_hbm_loads(max(1, dims[-2] - 1), chains)
    return tr.seconds(events) * 1e9 / loads
