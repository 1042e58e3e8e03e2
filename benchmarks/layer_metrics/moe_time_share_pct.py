"""Share of the device's busy time spent in the routed expert layer's
operations: the grouped expert matmuls, the router and the routing
counters, found in the trace by the whole-shape rule ``moe_work``
states (the permutation between router and matmuls has no shape of its
own and is left out)."""

from layer_metrics.moe_work import expert_layer_seconds, routed


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s") or not trace.get("ops") \
            or not routed(ctx.get("config") or {}):
        return None
    return 100.0 * expert_layer_seconds(trace, ctx["config"]) / trace["busy_s"]
