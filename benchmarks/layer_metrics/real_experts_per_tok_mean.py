"""Real experts a token chose per layer, on average over the window:
(``moe_assignments`` - ``moe_zero_assignments``) over
``moe_routed_tokens`` (the (token, layer)s routed), all counted by the
programs.  12 picks of which a third fall on identity experts when
routing is even: 8.  The engine's ``moe_few_real_tokens`` /
``moe_many_real_tokens`` say how far single tokens lie from it."""

from harness.window import engine_delta
from layer_metrics.longcat_work import double


def read(ctx):
    if not double(ctx.get("config") or {}):
        return None
    zero = engine_delta(ctx, "moe_zero_assignments")
    every = engine_delta(ctx, "moe_assignments")
    tokens = engine_delta(ctx, "moe_routed_tokens")
    if zero is None or every is None or not tokens:
        return None
    return (every - zero) / tokens
