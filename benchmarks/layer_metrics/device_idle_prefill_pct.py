"""The part of ``device_idle_pct`` under admission and the prefill
calls: idle seconds booked to ``admit``, ``prefill.pack`` (the numpy
tables and their puts), ``prefill.call`` (the jitted call) and
``prefill.tail`` (the eager tail that installs the decode state), over
the same busy + idle of the untraced stretch."""

from layer_metrics.idle_work import PREFILL, idle_pct


def read(ctx):
    return idle_pct(ctx, "untraced", where=PREFILL)
