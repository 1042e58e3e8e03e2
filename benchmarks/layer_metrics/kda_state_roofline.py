"""The decode state update of the KDA layers as a share of its roofline.
Needed work = the lane-steps x KDA layers the decode steps of the traced
interval ran, counted by the program
(``engine_stats()["delta_lane_steps"]``) x one state read and written
(``kda_work.step_bytes``: 4,194,304 B at 32 x 128 x 128 float32); the
least time is that over HBM bytes/s; the share is that over the traced
seconds of the state kernel and the operands made for it
(``kda_work.is_step``: four dims ``(slots, heads, ., .)``).  A lane that
did not run and a second pass over the state are in the seconds and not
in the needed work: it under-reads, never over-reads.

Counters and seconds are both of the traced interval.  Nothing to read,
and no number, where the program has no such counter."""

from harness.window import engine_delta
from layer_metrics.kda_work import context, is_step, seconds_of, step_least_seconds


def read(ctx):
    found = context(ctx)
    if not found or not ctx.get("peaks"):
        return None
    trace, z = found
    lane_steps = engine_delta(ctx, "delta_lane_steps", span="trace")
    seconds = seconds_of(trace, z, is_step)
    if not lane_steps or not seconds:
        return None
    return 100.0 * step_least_seconds(z, lane_steps, ctx["peaks"]) / seconds
