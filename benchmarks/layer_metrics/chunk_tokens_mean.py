"""Tokens a decode chunk emits on average: ``engine_stats()`` tokens
over chunks, as deltas over the window (occupancy x steps at best)."""

from harness.window import engine_delta


def read(ctx):
    tokens, chunks = engine_delta(ctx, "tokens"), engine_delta(ctx, "chunks")
    if not tokens or not chunks:
        return None
    return tokens / chunks
