"""``device_idle_pct``'s quotient over the traced interval's two
snapshots: what to hold against ``100 x (1 - busy_s / window_s)`` of the
same run's trace (the program's idle leaves out the bubbles between a
program's own operations), and against ``device_idle_pct`` of the same
run for what the profiler's python tracer adds."""

from layer_metrics.idle_work import idle_pct


def read(ctx):
    return idle_pct(ctx, "trace")
