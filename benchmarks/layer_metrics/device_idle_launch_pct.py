"""The part of ``device_idle_pct`` under the chunk's launch: idle
seconds booked to ``launch.plan`` (under the lock: retire, growth,
tables), ``launch.call`` (the argument puts and the chunk's dispatch) and
``launch.post`` (the screen, the async copies, the ``_Wave``), over the
same busy + idle of the untraced stretch."""

from layer_metrics.idle_work import LAUNCH, idle_pct


def read(ctx):
    return idle_pct(ctx, "untraced", where=LAUNCH)
