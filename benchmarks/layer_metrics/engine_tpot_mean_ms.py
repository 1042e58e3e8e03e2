"""Mean time per token after the first as a stream saw it on the
engine's clock: ``decode_stream_s`` (first-token harvest to finish) over
``decode_stream_tokens`` (the tokens after the first), both summed as
streams finish, as deltas over the untraced stretch of the traced run.
A first event holds a whole chunk's tokens, so it reads a little under a
wave over its steps."""

from layer_metrics.untraced import ratio


def read(ctx):
    return ratio(ctx, "decode_stream_s", "decode_stream_tokens", 1e3)
