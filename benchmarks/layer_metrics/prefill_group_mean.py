"""Prompts an admission prefills together on average: the engine's
``prefills`` over ``prefill_chunks`` (prefill device calls), as deltas
over the window.  A call pads its group to a power of two and each
prompt to its bucket, so who is admitted with whom decides what a
prompt token costs."""

from harness.window import engine_delta


def read(ctx):
    prompts, calls = engine_delta(ctx, "prefills"), engine_delta(ctx, "prefill_chunks")
    if not prompts or not calls:
        return None
    return prompts / calls
