"""Rows the batcher put into a device call on average: its ``rows`` over
``batches`` counters, as deltas over the window."""

from harness.window import engine_delta


def read(ctx):
    rows, batches = engine_delta(ctx, "rows"), engine_delta(ctx, "batches")
    if not rows or not batches:
        return None
    return rows / batches
