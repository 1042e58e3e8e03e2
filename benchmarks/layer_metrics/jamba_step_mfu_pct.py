"""``step_mfu_pct`` for the Jamba family: the share of the chip's bf16
peak that the whole traced interval reached.  Needed work = the FLOPs the
tokens of the traced interval need, prefill positions and decode
lane-steps alike, from the configuration's sizes and the program's
counters (``ssm_work.needed_flops``: a state-space layer's four matrices,
recurrence and convolution, an attention layer's projections, the SwiGLU
of every layer, the tied head over the whole vocabulary once a lane-step
and once a prompt, decode attention by the K/V rows the program says it
read, prefill attention by the prompts' causal pairs in the two attention
layers); the share is that over peak bf16 FLOP/s x the traced interval's
``window_s`` x the chips used — ``step_mfu_pct``'s definition, letter for
letter.

It reads no operation's name, no program's name and no shape out of the
trace (neither ``trace["ops"]`` nor ``trace["modules"]``), so it bounds a
later claim in its cell when a change takes a kernel off the path.  It
stands in a file of its own because ``step_work.py`` knows this family's
keys under no name, as ``olmo_hybrid_step_mfu_pct`` does.

Counters and seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.ssm_work import COUNTERS, needed_flops, sizes


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    if not trace or not trace.get("window_s") or not peaks or not sizes(ctx.get("config") or {}):
        return None
    counters = {name: engine_delta(ctx, name, span="trace") for name in COUNTERS}
    if any(v is None for v in counters.values()):
        return None
    flops = needed_flops(ctx["config"], counters)
    if not flops:
        return None
    chips = (ctx.get("device") or {}).get("count") or 1
    return 100.0 * flops / (peaks["bf16_flops"] * chips * trace["window_s"])
