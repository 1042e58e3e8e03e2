"""Share of the engine thread's time in the wave loop that was the
host's work and not a wait for the device: ``host_work_s`` over
``host_work_s + host_wait_s`` (the seam's phases other than ``wait``,
and ``wait``: blocked in a wave's readback), as deltas over the untraced
stretch of the traced run, in percent.  While it is low the device sets
the pace and the host's work hides under a running chunk; at 100 the
host sets it."""

from layer_metrics.untraced import delta


def read(ctx):
    work, wait = delta(ctx, "host_work_s"), delta(ctx, "host_wait_s")
    if work is None or wait is None or work + wait <= 0:
        return None
    return 100.0 * work / (work + wait)
