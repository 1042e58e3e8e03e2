"""The paged-decode kernel's share of its roofline, in a cell whose
programs hold other Pallas kernels too.  ``paged_decode_roofline``'s
arithmetic (needed bytes = every cached key and value of every decoding
lane, ``peaks.gpt2_decode_attention_bytes`` at the mean cached tokens
the clients' streams held through the traced interval, x the decode
steps traced, over the chip's HBM bytes/s) over the traced seconds of
the paged kernel alone: the ``pallas_kernel`` operations with an output
of three or more dims (``moe_work``'s rule)."""

from harness.peaks import gpt2_decode_attention_bytes
from harness.window import cached_tokens_mean, module_seconds
from layer_metrics.moe_work import paged_kernel_seconds


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("ops") or not ctx.get("peaks"):
        return None
    _calls, seconds = paged_kernel_seconds(trace)
    chunks, held = module_seconds(ctx, "chunk"), cached_tokens_mean(ctx)
    if not seconds or not chunks or not held:
        return None
    steps = chunks[0] * ctx["config"]["engine"]["steps_per_call"]
    needed = gpt2_decode_attention_bytes(ctx["config"]["model"], held) * steps
    return 100.0 * needed / ctx["peaks"]["hbm_bytes_per_s"] / seconds
