"""Mean wait between a streaming handler's entry and ``engine.submit``:
the engine's ``ingress_wait_s`` over ``ingress_waits``, as deltas over
the window.  The handler pulls every chunk through asyncio's default
executor and a stream waiting for tokens holds its thread, so with more
callers than threads a request waits here, in a queue the engine's own
cannot see."""

from harness.window import engine_delta


def read(ctx):
    seconds, waits = engine_delta(ctx, "ingress_wait_s"), engine_delta(ctx, "ingress_waits")
    if seconds is None or not waits:
        return None
    return 1e3 * seconds / waits
