"""Mean time from a stream's admission to the harvest that held its
first token — its own wave, prefill and chunk: ``first_token_s`` over
``first_tokens``, as deltas over the untraced stretch of the traced
run."""

from layer_metrics.untraced import ratio


def read(ctx):
    return ratio(ctx, "first_token_s", "first_tokens", 1e3)
