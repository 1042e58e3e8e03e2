"""Share of the decode attention's page loop that holds a live page:
the engine's ``decode_live_pages`` (pages the decoding lanes' caches
held, summed over lane-steps) over ``decode_page_slots`` (steps
launched x lanes x table width, summed over length buckets), as deltas
over the window, in percent.  The rest of the loop is table slots past
a lane's length or on an empty lane: what a kernel whose time follows
the slots wastes and one whose time follows the live pages skips.  An
engine without the counters (before PR 27) reads nothing."""

from harness.window import engine_delta


def read(ctx):
    live, slots = engine_delta(ctx, "decode_live_pages"), engine_delta(ctx, "decode_page_slots")
    if live is None or not slots:
        return None
    return 100.0 * live / slots
