"""Share of the device's busy time spent in a double layer's two dense
SwiGLU FFNs, as far as a trace shows them: the gate and up projections
and their product (``(rows, ffn_hidden_size)`` outputs,
``longcat_work``'s rule).  The down projections cannot be told from
other float32 ``(rows, hidden)`` outputs and are left out: a third of
the pair's weight bytes."""

from layer_metrics.longcat_work import dense_pair_keys, double, seconds


def read(ctx):
    trace, config = ctx.get("trace"), ctx.get("config") or {}
    if not trace or not trace.get("busy_s") or not trace.get("ops") or not double(config):
        return None
    keys = dense_pair_keys(trace, config)
    return 100.0 * seconds(trace, keys) / trace["busy_s"] if keys else None
