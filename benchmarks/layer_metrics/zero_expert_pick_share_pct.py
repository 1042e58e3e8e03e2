"""Share of the window's router picks that fell on identity
("zero-computation") experts: 100 x ``moe_zero_assignments`` over
``moe_assignments``, both counted by the programs.  Even routing over
512 real and 256 identity outputs gives 33.3; every point of it is
expert bytes and FLOPs a token did not ask for."""

from harness.window import engine_delta
from layer_metrics.longcat_work import double


def read(ctx):
    if not double(ctx.get("config") or {}):
        return None
    zero = engine_delta(ctx, "moe_zero_assignments")
    every = engine_delta(ctx, "moe_assignments")
    if zero is None or not every:
        return None
    return 100.0 * zero / every
