"""Share of the token events that were picked up with their stream's
next event already queued — the consumer was a whole wave behind:
``deliveries_behind`` over ``deliveries``, as deltas over the untraced
stretch of the traced run, in percent."""

from layer_metrics.untraced import ratio


def read(ctx):
    return ratio(ctx, "deliveries_behind", "deliveries", 100.0)
