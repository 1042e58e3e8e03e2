"""Experts whose weights one decode step streams, per layer, on average
over the window: the engine's ``moe_active_expert_steps`` (experts hit,
summed over the decode (layer, step)s that ran) over ``moe_layer_steps``."""

from harness.window import engine_delta


def read(ctx):
    hit, steps = engine_delta(ctx, "moe_active_expert_steps"), engine_delta(ctx, "moe_layer_steps")
    if hit is None or not steps:
        return None
    return hit / steps
