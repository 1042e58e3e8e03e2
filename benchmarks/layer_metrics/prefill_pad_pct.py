"""Share of the positions the prefill programs computed that held no
prompt token: a call pays its group rounded up to a power of two times
its prompt bucket (``prefill_padded_tokens``) for the true tokens of
its prompts (``prefill_tokens``); both as deltas over the whole
window."""

from harness.window import engine_delta


def read(ctx):
    true, padded = engine_delta(ctx, "prefill_tokens"), engine_delta(ctx, "prefill_padded_tokens")
    if true is None or not padded:
        return None
    return 100.0 * (1.0 - true / padded)
