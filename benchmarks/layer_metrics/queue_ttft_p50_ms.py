"""Median send-to-first-token where a queue always stands: mostly the
wait for a slot, so it is recorded here and judged nowhere."""

from harness.stats import percentile
from harness.window import ttfts_ms


def read(ctx):
    values = ttfts_ms(ctx)
    ctx["samples"]["queue_ttft_p50_ms"] = len(values)
    return percentile(values, 50)
