"""Share of the chip's bf16 peak that the whole traced interval reached.
Needed work = the FLOPs the tokens of the traced interval need, prefill
positions and decode lane-steps alike, from the configuration's sizes and
the program's counters (``step_work``: matrices a token passes, routed
experts by the assignments served here, attention by the rows the program
says it read; no padding, no masked position, no recomputation); the
share is that over peak bf16 FLOP/s x the traced interval's ``window_s``
(first start to last end of any device event: the interval and not the
busy time, so a gain on the host's side moves it as it moves
``out_tok_s``), over the chips used.

It reads no operation's name, no program's name and no shape out of the
trace: whichever code does the work, the same tokens in the same seconds
read the same.  A kernel's roofline goes silent when a later change takes
the kernel off the path; this share still bounds what that change can
claim.  Low where a decode step streams weights for a few rows; it cannot
pass 100.

Counters and seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.step_work import COUNTERS, family, needed_flops


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    kind = family(ctx.get("config") or {})
    if not trace or not trace.get("window_s") or not peaks or not kind:
        return None
    counters = {name: engine_delta(ctx, name, span="trace") for name in COUNTERS[kind]}
    if any(v is None for v in counters.values()):
        return None
    flops = needed_flops(ctx["config"], counters)
    if not flops:
        return None
    chips = (ctx.get("device") or {}).get("count") or 1
    return 100.0 * flops / (peaks["bf16_flops"] * chips * trace["window_s"])
