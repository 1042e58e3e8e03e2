"""The decode steps' grouped expert matmuls as a share of their
roofline.  A decode step is bound by memory: per layer it must read the
gate, up and down matrices of every expert a lane was routed to.  Needed
bytes = the experts actually hit, counted by the program
(``moe_active_expert_steps``: experts hit summed over the decode
(layer, step)s that ran) x one expert's three matrices; the least time
is that over the chip's HBM bytes/s; the share is that over the traced
seconds of the grouped-matmul kernels with a decode step's row count
(``max_slots x experts_per_tok``).

Counter and kernel seconds are both of the traced interval: the counter
as the difference of the two ``engine_stats()`` snapshots taken inside
it, so the bytes counted are never of more steps than the seconds."""

from harness.window import engine_delta
from layer_metrics.moe_work import expert_weight_bytes, grouped_matmul_seconds, routed


def read(ctx):
    trace, config = ctx.get("trace"), ctx.get("config") or {}
    shape = routed(config)
    if not trace or not trace.get("ops") or not shape or not ctx.get("peaks"):
        return None
    hit = engine_delta(ctx, "moe_active_expert_steps", span="trace")
    rows = config["engine"]["max_slots"] * shape[1]
    seconds = grouped_matmul_seconds(trace, rows=rows)
    if not hit or not seconds:
        return None
    return 100.0 * expert_weight_bytes(config, hit) / ctx["peaks"]["hbm_bytes_per_s"] / seconds
