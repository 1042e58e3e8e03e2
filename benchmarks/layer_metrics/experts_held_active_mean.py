"""Held experts whose weights one decode step streams, per routed
layer, on average over the window: the engine's
``moe_held_active_expert_steps`` (held experts hit, summed over the
decode (routed layer, step)s that ran) over ``moe_layer_steps``."""

from harness.window import engine_delta
from layer_metrics.mla_work import share


def read(ctx):
    if not share(ctx.get("config") or {}):
        return None
    hit = engine_delta(ctx, "moe_held_active_expert_steps")
    steps = engine_delta(ctx, "moe_layer_steps")
    if hit is None or not steps:
        return None
    return hit / steps
