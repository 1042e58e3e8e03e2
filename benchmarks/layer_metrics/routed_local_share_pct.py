"""Share of the window's (token, expert) assignments that fell to
experts this replica holds: 100 x ``moe_local_assignments`` over
``moe_assignments``.  Even routing gives held / published (8 of 256:
3.125); more means this share's experts are busier than a deployment's
mean."""

from harness.window import engine_delta
from layer_metrics.mla_work import share


def read(ctx):
    if not share(ctx.get("config") or {}):
        return None
    local = engine_delta(ctx, "moe_local_assignments")
    every = engine_delta(ctx, "moe_assignments")
    if local is None or not every:
        return None
    return 100.0 * local / every
