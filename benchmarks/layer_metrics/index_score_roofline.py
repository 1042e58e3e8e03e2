"""The indexer's scoring of cached keys as a share of its roofline.
Needed work = the keys the decode lane-steps of the traced interval
scored, over the full layers, counted by the program
(``engine_stats()["index_keys_scored"]``) x one key's 256 B and 16,512
FLOP (``dots3_work``); the least time is the larger of bytes over HBM
bytes/s and FLOPs over bf16 FLOP/s (the bytes, at 64 FLOP a byte); the
share is that over the traced seconds of the scoring operations (the
keys' gather, the heads' products, the weighted sum, or a Pallas kernel
whose output is the ``(lanes, span)`` scores: not the top-k).

Counter and seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.dots3_work import (
    context, index_key_bytes, index_key_flops, is_index_score, least_seconds, seconds_of)


def read(ctx):
    found = context(ctx)
    if not found or not ctx.get("peaks"):
        return None
    trace, z = found
    keys = engine_delta(ctx, "index_keys_scored", span="trace")
    seconds = seconds_of(trace, z, is_index_score)
    if not keys or not seconds:
        return None
    return 100.0 * least_seconds(
        keys, index_key_bytes(z), index_key_flops(z), ctx["peaks"]) / seconds
