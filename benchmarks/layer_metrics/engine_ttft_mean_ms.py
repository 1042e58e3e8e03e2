"""Mean time from a request's ingress stamp to the harvest that held
its first token, on the engine's clock: ``ttft_s`` over ``ttfts``, as
deltas over the untraced stretch of the traced run.  With
``first_token_mean_ms`` it says how much of that a request waited for
other groups' waves."""

from layer_metrics.untraced import ratio


def read(ctx):
    return ratio(ctx, "ttft_s", "ttfts", 1e3)
