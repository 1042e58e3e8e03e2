"""The decode state update of the state-space layers as a share of its
roofline.  Needed work = the lane-steps x state-space layers the decode
steps of the traced interval ran, counted by the program
(``engine_stats()["ssm_lane_steps"]``) x one state read and written
(``ssm_work.step_bytes``: 2 x 327,680 B at 16 x 5,120 float32); the least
time is that over HBM bytes/s; the share is that over the traced seconds
of the operations shaped like the resting state (``ssm_work.is_step``: the
kernel ``ssm_state_step`` by its first output, XLA's fusions by theirs).
A lane that did not run and a second pass over the state are in the
seconds and not in the needed work: it under-reads, never over-reads.

Counters and seconds are both of the traced interval.  Nothing to read,
and no number, where the program has no such counter."""

from harness.window import engine_delta
from layer_metrics.ssm_work import context, is_step, seconds_of, step_least_seconds


def read(ctx):
    found = context(ctx)
    if not found or not ctx.get("peaks"):
        return None
    trace, z = found
    lane_steps = engine_delta(ctx, "ssm_lane_steps", span="trace")
    seconds = seconds_of(trace, z, is_step)
    if not lane_steps or not seconds:
        return None
    return 100.0 * step_least_seconds(z, lane_steps, ctx["peaks"]) / seconds
