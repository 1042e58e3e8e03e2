"""The part of ``ttft_device_idle_pct`` in which the engine had no
stream admitted or queued (``no_work``): from the harvest that emptied
it to the next wave that found a request — the answers' way out, the
callers' turn-around and the way back in, not the engine's own work."""

from layer_metrics.idle_work import idle_pct


def read(ctx):
    return idle_pct(ctx, "untraced", where=("no_work",))
