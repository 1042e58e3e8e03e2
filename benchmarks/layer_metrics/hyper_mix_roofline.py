"""The mixing of a residual of several rows as a share of its roofline.
Needed work = the position-sub-layers the prefill calls and the decode
steps of the traced interval ran, counted by the program
(``engine_stats()["hyper_prefill_positions"]`` + ``["hyper_decode_positions"]``:
padded positions x mixed sub-layers, what the device computed) x one
position's 186,368 B (``hyper_work``: the rows read once for ``pre``,
read and written once for ``post``, float32); the least time is that over
HBM bytes/s (4.6 FLOP/B: the bytes bound it); the share is that over the
traced seconds of the mixing's operations.  ``phi`` (1.4 MB a call) and a
call's padding to its tile of 128 positions are not in the needed work:
it under-reads, never over-reads.

Counters and seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.hyper_work import least_seconds, mixing_seconds, streams


def read(ctx):
    trace, config = ctx.get("trace"), ctx.get("config") or {}
    if not trace or not trace.get("ops") or not streams(config) or not ctx.get("peaks"):
        return None
    prefill = engine_delta(ctx, "hyper_prefill_positions", span="trace")
    decode = engine_delta(ctx, "hyper_decode_positions", span="trace")
    _calls, seconds = mixing_seconds(trace, config)
    if prefill is None or decode is None or not (prefill + decode) or not seconds:
        return None
    return 100.0 * least_seconds(config, prefill + decode, ctx["peaks"]) / seconds
