"""The latent decode kernel as a share of its roofline.  Needed work =
the cached rows the decode lane-steps of the traced interval read, over
all layers, counted by the program
(``engine_stats()["latent_kv_tokens"]``) x one row's 1,152 B and 139,264
FLOP (``mla_work``: 576 values, one pass, rows not whole pages); the
least time is the larger of bytes over HBM bytes/s and FLOPs over bf16
FLOP/s; the share is that over the traced seconds of the kernel.  The
kernel moves 640 lanes a row for the 576 it needs (the pool's tiling),
so it cannot read over 90 by bytes alone.

Counter and kernel seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.mla_work import latent, latent_kernel_seconds, least_seconds


def read(ctx):
    trace, config = ctx.get("trace"), ctx.get("config") or {}
    if not trace or not trace.get("ops") or not latent(config) or not ctx.get("peaks"):
        return None
    rows = engine_delta(ctx, "latent_kv_tokens", span="trace")
    _calls, seconds = latent_kernel_seconds(trace, config)
    if not rows or not seconds:
        return None
    return 100.0 * least_seconds(config, rows, ctx["peaks"]) / seconds
