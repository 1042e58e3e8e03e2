"""Rows answered per second: replies by their client-side arrival stamp
inside the window (``window.burst_span``), each counting its rows."""

from harness.window import burst_span


def read(ctx):
    rows, seconds = burst_span(ctx)
    ctx["samples"]["rows_s"] = {"rows": rows, "seconds": seconds}
    return rows / seconds
