"""Median over completed requests of the mean time per token after the
first event (client clock)."""

from harness.stats import percentile
from harness.window import token_gaps_ms


def read(ctx):
    values = token_gaps_ms(ctx)
    ctx["samples"]["tpot_p50_ms"] = len(values)
    return percentile(values, 50)
