"""A decode step's held ReLU-gated experts as a share of their
weight-streaming roofline.  Needed bytes = the three matrices (11.8 MB)
of every held expert actually hit, counted by the program
(``moe_held_active_expert_steps``: held experts hit, summed over the
decode (layer, step)s that ran); the least time is that over the chip's
HBM bytes/s; the share is that over the traced seconds of the decode
step's grouped matmuls (``smallthinker_work.decode_expert_seconds``: the
2-D Pallas kernels over ``moe_held_pass_rows`` rows).

Counter and seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.smallthinker_work import (
    context, decode_expert_seconds, expert_bytes, pass_rows)


def read(ctx):
    found, rows = context(ctx), pass_rows(ctx)
    if not found or not rows or not ctx.get("peaks"):
        return None
    trace, z = found
    hit = engine_delta(ctx, "moe_held_active_expert_steps", span="trace")
    seconds = decode_expert_seconds(trace, rows)
    if not hit or not seconds:
        return None
    return 100.0 * hit * expert_bytes(z) / ctx["peaks"]["hbm_bytes_per_s"] / seconds
