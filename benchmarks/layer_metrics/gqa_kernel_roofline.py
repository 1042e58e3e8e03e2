"""The grouped-query decode page loop as a share of its roofline.
Needed work = the cached K/V rows decode lane-steps read in the traced
interval, counted by the program (``engine_stats()["gqa_kv_rows_read"]``:
a full layer's every cached row, a window layer's live ones) x one row's
2,048 B of K and V and 14,336 FLOP at 28 heads (``smallthinker_work``: 7
FLOP a byte, so the bytes bind on a v5e; the larger of the two is
taken); the share is that least time over the traced seconds of the
operations that produce the step's attended values
(``smallthinker_work.is_decode_attention``: the page-loop kernel by what
it returns, or XLA's gather lane — whichever does the work).

Counter and seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.smallthinker_work import (
    context, is_decode_attention, pair_flops, row_bytes, seconds_of)


def read(ctx):
    found = context(ctx)
    if not found or not ctx.get("peaks"):
        return None
    trace, z = found
    rows = engine_delta(ctx, "gqa_kv_rows_read", span="trace")
    seconds = seconds_of(trace, z, is_decode_attention)
    if not rows or not seconds:
        return None
    least = max(rows * row_bytes(z) / ctx["peaks"]["hbm_bytes_per_s"],
                rows * pair_flops(z) / ctx["peaks"]["bf16_flops"])
    return 100.0 * least / seconds
