"""The prefill's scan of the state-space layers as a share of its
roofline.  Needed work = the padded positions x state-space layers the
prefill calls of the traced interval scanned, counted by the program
(``engine_stats()["ssm_prefill_positions"]``: what the device computed) x
the larger of a position's 61,568 B (x~, z, Delta, B, C in, y out, in
their resting types) over HBM bytes/s and the recurrence's 9 E N =
737,280 FLOP over bf16 FLOP/s (``ssm_work``); the share is that over the
traced seconds of the scan's operations (``ssm_work.is_scan``: the kernel
``ssm_scan`` by its first output ``(prompts, L, E)``, XLA's loop by its
carried ``(prompts, N, E)``).  No vector-unit peak is published and none
of the recurrence can run on the matrix unit, so the bound lies far under
any program's reach: the share under-reads and cannot over-read.

Counters and seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.ssm_work import context, is_scan, scan_least_seconds, seconds_of


def read(ctx):
    found = context(ctx)
    if not found or not ctx.get("peaks"):
        return None
    trace, z = found
    positions = engine_delta(ctx, "ssm_prefill_positions", span="trace")
    seconds = seconds_of(trace, z, is_scan)
    if not positions or not seconds:
        return None
    return 100.0 * scan_least_seconds(z, positions, ctx["peaks"]) / seconds
