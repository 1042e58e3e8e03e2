"""Mean time of a token event from the harvest that queued it
(``_stream_push``'s stamp) to the return of the transport's write:
``deliver_lag_s`` over ``deliveries``, as deltas over the untraced
stretch of the traced run."""

from layer_metrics.untraced import ratio


def read(ctx):
    return ratio(ctx, "deliver_lag_s", "deliveries", 1e3)
