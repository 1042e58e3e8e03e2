"""Mean wait in the engine's own queue, submit to the stream's first
prefill slice: the engine's ``queue_wait_s`` over ``queue_waits``, as
deltas over the window (every stream counts, traced or not)."""

from harness.window import engine_delta


def read(ctx):
    seconds, waits = engine_delta(ctx, "queue_wait_s"), engine_delta(ctx, "queue_waits")
    if seconds is None or not waits:
        return None
    return 1e3 * seconds / waits
