"""``device_idle_traced_pct`` in a cell whose end-to-end metric is a
time to first token: the same quotient over the traced interval, to hold
against the trace's own idle share of the same run."""

from layer_metrics.idle_work import idle_pct


def read(ctx):
    return idle_pct(ctx, "trace")
