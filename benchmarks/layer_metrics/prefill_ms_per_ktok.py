"""Device time of the prefill programs per thousand prompt tokens they
computed: traced program time over the engine's ``prefill_tokens``
delta across the traced interval (counter read over HTTP at the trace's
start and stop, so it can be off by one wave)."""

from harness.window import engine_delta, module_seconds


def read(ctx):
    got = module_seconds(ctx, "prefill")
    tokens = engine_delta(ctx, "prefill_tokens", "trace")
    if not got or not tokens:
        return None
    return 1e3 * got[1] / (tokens / 1000.0)
