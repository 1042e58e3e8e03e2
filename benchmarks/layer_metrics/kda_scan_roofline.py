"""The prefill's chunked scan of the KDA layers as a share of its
roofline.  Needed work = the padded positions x KDA layers the prefill
calls of the traced interval scanned, counted by the program
(``engine_stats()["delta_prefill_positions"]``: what the device computed)
x the larger of a position's 32,768 B (q, k, v in, the output out) over
HBM bytes/s and the recurrence's 3,670,016 FLOP over bf16 FLOP/s
(``kda_work``); the share is that over the traced seconds of the scan's
operations (``kda_work.is_scan``: arrays laid ``(prompts, heads, chunks,
64, ...)``).  It counts the recurrence, not the chunked form's extra
products (the block-wise decays among them): it under-reads and cannot
over-read.

Counters and seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.kda_work import context, is_scan, scan_least_seconds, seconds_of


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
