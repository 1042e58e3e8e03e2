"""Share of the device's busy time spent in the held experts' grouped
matmuls, a prefill's and a decode step's alike: every ``pallas_kernel``
with a 2-D output (``mla_work``'s rule).  The router, the sort of the
assignments, the gathers of their rows and the shared expert are not in
it: none has a shape of its own."""

from layer_metrics.mla_work import grouped_matmul_seconds, share


def read(ctx):
    trace, config = ctx.get("trace"), ctx.get("config") or {}
    if not trace or not trace.get("busy_s") or not trace.get("ops"):
        return None
    if not share(config):
        return None
    seconds = grouped_matmul_seconds(trace)
    return 100.0 * seconds / trace["busy_s"] if seconds else None
