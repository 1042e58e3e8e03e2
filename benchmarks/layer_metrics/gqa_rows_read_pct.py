"""Cached K/V rows decode read as a share of what it would read with no
window: the engine's ``gqa_kv_rows_read`` (a full layer's every cached
row, a window layer's live ones, summed over layers and active
lane-steps) over ``gqa_kv_rows_cached`` (``decode_kv_tokens`` x layers),
as deltas over the window, in percent: what the windows spare a step.
100 while every lane is under the window; ``(full + window layers x
window / context) / layers`` once every lane is past it.  A program
without the counters reads nothing."""

from harness.window import engine_delta


def read(ctx):
    read_, cached = engine_delta(ctx, "gqa_kv_rows_read"), engine_delta(ctx, "gqa_kv_rows_cached")
    if read_ is None or not cached:
        return None
    return 100.0 * read_ / cached
