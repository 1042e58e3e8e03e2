"""``step_mfu_pct`` for the Ling-3.0-flash family: the share of the
chip's bf16 peak that the whole traced interval reached.  Needed work =
the FLOPs the tokens of the traced interval need, prefill positions and
decode lane-steps alike, from the configuration's sizes and the program's
counters (``kda_work.needed_flops``: every matrix a token passes here,
local assignments x three expert matrices, the recurrence, the latent
rows read, the prompts' causal pairs); the share is that over peak bf16
FLOP/s x the traced interval's ``window_s`` x the chips used —
``step_mfu_pct``'s definition, letter for letter.

It reads no operation's name, no program's name and no shape out of the
trace (a test seals that), so it bounds a later claim in its cell when a
change takes a kernel off the path.  It stands in a file of its own
because ``step_work.py`` knows this family's layers under no name (its
``latent_share`` arithmetic would count every layer a latent attention),
as ``olmo_hybrid_step_mfu_pct`` does; a ``benchmark`` PR should fold them
into ``step_work`` under the one name ``step_mfu_pct``.

Counters and seconds are both of the traced interval."""

from harness.window import engine_delta
from layer_metrics.kda_work import COUNTERS, needed_flops, sizes


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
