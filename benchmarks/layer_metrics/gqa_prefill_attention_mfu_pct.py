"""The prefill attention kernel's share of the chip's bf16 peak.  Needed
work = the (query, key) pairs the prompts of the traced interval need —
a full layer's every causal pair, a window layer's at most
``sliding_window_size`` keys a query, at the interval's mean prompt
(``prefill_tokens`` / ``prefills``; ``smallthinker_work.prefill_pairs``)
— x ``4 x heads x head_dim`` FLOP a pair; the share is that over peak
bf16 FLOP/s x the traced seconds of the prefill attention kernel
(``smallthinker_work.is_prefill_attention``: a ``pallas_kernel`` whose
output is ``(prompts x heads, bucket, head_dim)``).  No padding and no
masked pair is counted, so a bucket's padding and the diagonal blocks'
masked halves read as loss.

Counters and seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.smallthinker_work import (
    context, is_prefill_attention, pair_flops, prefill_pairs, seconds_of)


def read(ctx):
    found = context(ctx)
    if not found or not ctx.get("peaks"):
        return None
    trace, z = found
    c = {name: engine_delta(ctx, name, span="trace") for name in ("prefills", "prefill_tokens")}
    seconds = seconds_of(trace, z, is_prefill_attention)
    if not c["prefills"] or not c["prefill_tokens"] or not seconds:
        return None
    return 100.0 * pair_flops(z) * prefill_pairs(z, c) / (ctx["peaks"]["bf16_flops"] * seconds)
