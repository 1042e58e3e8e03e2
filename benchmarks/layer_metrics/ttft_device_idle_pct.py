"""``device_idle_pct`` in a cell whose end-to-end metric is a time to
first token: the device's idle share of the untraced stretch, on the
program's own clock."""

from layer_metrics.idle_work import idle_pct


def read(ctx):
    return idle_pct(ctx, "untraced")
