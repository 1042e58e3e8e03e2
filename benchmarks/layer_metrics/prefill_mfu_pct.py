"""Share of the chip's bf16 peak the prefill programs reach: FLOPs the
prompts prefilled in the traced interval need (``peaks.gpt2_prefill_flops``,
from the engine's ``prefill_tokens`` and ``prefills`` deltas) over peak
FLOP/s x the programs' device time.  Compute-bound side of the roofline."""

from harness.peaks import gpt2_prefill_flops
from harness.window import engine_delta, module_seconds


def read(ctx):
    got = module_seconds(ctx, "prefill")
    tokens = engine_delta(ctx, "prefill_tokens", "trace")
    prompts = engine_delta(ctx, "prefills", "trace")
    if not got or not got[1] or not tokens or not prompts or not ctx["peaks"]:
        return None
    flops = gpt2_prefill_flops(ctx["config"]["model"], tokens, prompts)
    return 100.0 * flops / ctx["peaks"]["bf16_flops"] / got[1]
