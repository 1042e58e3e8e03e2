"""Tokens delivered per second: token events by their client-side
arrival stamp, over the stretch of the window between the end of its
first burst of events and the end of its last (``window.burst_span``:
all the tokens of that stretch over all of its time, so a decode chunk
straddling an edge does not move the rate).  Streams that were open when
the stretch began or ended count by what arrived inside."""

from harness.window import burst_span


def read(ctx):
    tokens, seconds = burst_span(ctx)
    ctx["samples"]["out_tok_s"] = {"tokens": tokens, "seconds": seconds}
    return tokens / seconds
