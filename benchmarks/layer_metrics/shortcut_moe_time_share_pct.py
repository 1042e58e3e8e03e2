"""Share of the device's busy time spent in a double layer's routed
shortcut: the held experts' grouped matmuls (a prefill's and a decode
step's), the router and the routing counters, found by the whole-shape
rule ``longcat_work`` states.  The sort, the gathers, the gated sum and
the identity experts' ``gate x h`` have no shape of their own and are
left out."""

from layer_metrics.longcat_work import double, seconds, shortcut_keys


def read(ctx):
    trace, config = ctx.get("trace"), ctx.get("config") or {}
    if not trace or not trace.get("busy_s") or not trace.get("ops") or not double(config):
        return None
    keys = shortcut_keys(trace, config)
    return 100.0 * seconds(trace, keys) / trace["busy_s"] if keys else None
