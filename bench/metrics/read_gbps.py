"""read_gbps: the hbm read probe at rung 0 as the toolkit reports it to
its users: the bytes over the host-clocked elapsed time of that probe's
measured passes (``WorkloadResult``), summed over every sweep of the
window.  Layer: workloads (``core/workloads.py``)."""


def read(run):
    ns = run.window.data.get("read_ns", 0)
    if not ns:
        return None
    return run.window.data["read_bytes"] / ns
