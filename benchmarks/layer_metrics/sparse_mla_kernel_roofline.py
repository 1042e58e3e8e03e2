"""The attention that reads chosen rows as a share of its roofline.
Needed work = the rows the full layers' decode attention read in the
traced interval, counted by the program
(``engine_stats()["sparse_rows_read"]``) x one row's 1,152 B and 278,528
FLOP at 128 heads (``dots3_work``); the least time is the larger of bytes
over HBM bytes/s and FLOPs over bf16 FLOP/s; the share is that over the
traced seconds of the operations that gather the chosen rows, score them,
weight them and merge the step's own row (``dots3_work``'s rule: XLA's
gather and einsums, or a Pallas kernel whose output is the full layers'
flash state ``(lanes, heads, rank)``: whichever does the work, the rows
needed are the chosen set's, not the rows a kernel moves).

Counter and seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.dots3_work import (
    context, full_row_bytes, full_row_flops, is_sparse_attention, least_seconds, seconds_of)


def read(ctx):
    found = context(ctx)
    if not found or not ctx.get("peaks"):
        return None
    trace, z = found
    rows = engine_delta(ctx, "sparse_rows_read", span="trace")
    seconds = seconds_of(trace, z, is_sparse_attention)
    if not rows or not seconds:
        return None
    return 100.0 * least_seconds(
        rows, full_row_bytes(z), full_row_flops(z), ctx["peaks"]) / seconds
