"""The prefill's chunked scan of the linear-attention layers as a share
of its roofline.  Needed work = the padded positions x linear layers the
prefill calls of the traced interval scanned, counted by the program
(``engine_stats()["delta_prefill_positions"]``: what the device
computed) x the larger of a position's 34,560 B (q, k, v in, the output
out) over HBM bytes/s and the recurrence's 3,870,720 FLOP over bf16
FLOP/s (``delta_work``); the share is that over the traced seconds of the
scan's operations (``delta_work.is_scan``: arrays laid ``(prompts, heads,
chunks, 64, ...)``).  It counts the recurrence, not the chunked form's
extra products: it under-reads and cannot over-read.

Counters and seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.delta_work import context, is_scan, scan_least_seconds, seconds_of


def read(ctx):
    found = context(ctx)
    if not found or not ctx.get("peaks"):
        return None
    trace, z = found
    positions = engine_delta(ctx, "delta_prefill_positions", span="trace")
    seconds = seconds_of(trace, z, is_scan)
    if not positions or not seconds:
        return None
    return 100.0 * scan_least_seconds(z, positions, ctx["peaks"]) / seconds
