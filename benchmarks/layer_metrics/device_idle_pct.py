"""The device's idle share of the untraced stretch of the traced run,
on the program's own clock: ``d(device_idle_s)`` over
``d(device_idle_s) + d(device_busy_s)`` in percent — time the device sat
between two programs of the wave loop, no profiler running.  What
``host_gap_pct`` (blind since the loop enqueues ahead) and
``host_busy_pct`` (a proxy from the host's side) stood in for."""

from layer_metrics.idle_work import idle_pct


def read(ctx):
    return idle_pct(ctx, "untraced")
