"""A decode step's held experts and shared expert as a share of their
weight-streaming roofline.  Needed bytes = the three matrices (88.08
MB) of every held expert actually hit, counted by the program
(``moe_held_active_expert_steps``: held experts hit, summed over the
decode (routed layer, step)s that ran), plus the shared expert's gate
and up matrices once a layer-step (``moe_layer_steps``); the least time
is that over the chip's HBM bytes/s; the share is that over the traced
seconds of the decode step's grouped-matmul kernels and the shared
expert's gate and up projections (``mla_work``'s rule).  The shared down
projection has no shape of its own: its seconds cannot be found, so its
bytes are left out too.

Counters and seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.mla_work import decode_expert_seconds, matrix_bytes, pass_rows, share


def read(ctx):
    trace, config = ctx.get("trace"), ctx.get("config") or {}
    shape, rows = share(config), pass_rows(ctx)
    if not trace or not trace.get("ops") or not shape or not rows or not ctx.get("peaks"):
        return None
    hit = engine_delta(ctx, "moe_held_active_expert_steps", span="trace")
    steps = engine_delta(ctx, "moe_layer_steps", span="trace")
    seconds = decode_expert_seconds(trace, config, rows)
    if hit is None or not steps or not seconds:
        return None
    needed = (3 * hit + 2 * shape[5] * steps) * matrix_bytes(config)
    return 100.0 * needed / ctx["peaks"]["hbm_bytes_per_s"] / seconds
