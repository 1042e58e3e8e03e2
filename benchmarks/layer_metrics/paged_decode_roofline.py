"""The paged-decode kernel's share of its roofline.  It is bound by
memory: per step it must read every cached key and value of every
decoding lane (``peaks.gpt2_decode_attention_bytes``).  Needed bytes =
that, at the mean cached tokens the clients' streams held through the
traced interval, x the decode steps traced; the least time is needed
bytes over the chip's HBM bytes/s; the share is that over the kernel's
traced device time."""

from harness.peaks import gpt2_decode_attention_bytes
from harness.window import cached_tokens_mean, kernel_seconds, module_seconds


def read(ctx):
    got, chunks = kernel_seconds(ctx), module_seconds(ctx, "chunk")
    held = cached_tokens_mean(ctx)
    if not got or not got[1] or not chunks or not held or not ctx["peaks"]:
        return None
    steps = chunks[0] * ctx["config"]["engine"]["steps_per_call"]
    needed = gpt2_decode_attention_bytes(ctx["config"]["model"], held) * steps
    return 100.0 * needed / ctx["peaks"]["hbm_bytes_per_s"] / got[1]
