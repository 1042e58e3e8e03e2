"""Tokens a second on the engine's own clock: ``tokens`` (read back at
the harvests) over ``clock_s``, as deltas over the untraced stretch of
the traced run — ``out_tok_s``'s twin, counted where the tokens are made
and not where a client sees them arrive."""

from layer_metrics.untraced import ratio


def read(ctx):
    return ratio(ctx, "tokens", "clock_s")
