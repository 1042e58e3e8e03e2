"""The window layers' latent decode kernel as a share of its roofline.
Needed work = the rows the window layers' decode attention read in the
traced interval, counted by the program
(``engine_stats()["window_rows_read"]``: ``min(cached, window - 1)`` a
lane-step and layer) x one row's 2,176 B and 270,336 FLOP at 64 heads
(``dots3_work``); the least time is the larger of bytes over HBM bytes/s
and FLOPs over bf16 FLOP/s; the share is that over the traced seconds of
the ``pallas_kernel`` whose output is ``(lanes, 64, 1024)``.  The kernel
moves whole pages of 1,152 lanes for the 1,088 values it needs and up to
a page before the window's edge, so it cannot read over ~85 by bytes
alone.

Counter and seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.dots3_work import (
    context, is_window_kernel, least_seconds, seconds_of, window_row_bytes, window_row_flops)


def read(ctx):
    found = context(ctx)
    if not found or not ctx.get("peaks"):
        return None
    trace, z = found
    rows = engine_delta(ctx, "window_rows_read", span="trace")
    seconds = seconds_of(trace, z, is_window_kernel)
    if not rows or not seconds:
        return None
    return 100.0 * least_seconds(
        rows, window_row_bytes(z), window_row_flops(z), ctx["peaks"]) / seconds
