"""Share of the positions the prefill programs computed whose attention
ran in the fused causal kernel (``ops/kernels.py causal_attention``):
the engine's ``prefill_fused_positions`` over ``prefill_padded_tokens``,
as deltas over the whole window, in percent.  100 in the latent cells,
whose every prefill starts at position zero in a bucket of at least one
query block (512).  An engine none of whose prefills took the kernel
(the multi-head models, the CPU, a commit before PR 33) reads nothing."""

from harness.window import engine_delta


def read(ctx):
    fused, padded = engine_delta(ctx, "prefill_fused_positions"), engine_delta(ctx, "prefill_padded_tokens")
    if not fused or not padded:
        return None
    return 100.0 * fused / padded
