"""Cached tokens a decode step attended, on average over the lanes and
steps that ran: the engine's ``decode_kv_tokens`` over
``decode_lane_steps``, as deltas over the window.  What
``decode_step_ms`` has to be read against."""

from harness.window import engine_delta


def read(ctx):
    tokens, steps = engine_delta(ctx, "decode_kv_tokens"), engine_delta(ctx, "decode_lane_steps")
    if tokens is None or not steps:
        return None
    return tokens / steps
