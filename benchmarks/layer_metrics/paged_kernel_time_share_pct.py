"""Share of the device's busy time spent in the paged-decode kernel,
in a cell whose programs hold other Pallas kernels too (a routed
model's grouped matmuls): of the trace's ``pallas_kernel`` operations
only those with an output of three or more dims, ``(lanes, 1,
hidden)`` (``moe_work``'s rule).  Where the paged kernel is the only
one this reads what ``kernel_time_share_pct`` reads."""

from layer_metrics.moe_work import paged_kernel_seconds


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s") or not trace.get("ops"):
        return None
    calls, seconds = paged_kernel_seconds(trace)
    if not calls:
        return None
    return 100.0 * seconds / trace["busy_s"]
