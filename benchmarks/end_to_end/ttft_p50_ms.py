"""Median wait from sending a prompt to its first token event, over
the requests sent inside the window.  The first event carries the first
decode chunk, so this is prefill plus one chunk plus any wait for the
few other callers' prefills."""

from harness.stats import percentile
from harness.window import ttfts_ms


def read(ctx):
    values = ttfts_ms(ctx)
    ctx["samples"]["ttft_p50_ms"] = len(values)
    return percentile(values, 50)
