"""Share of the decode chunks that were enqueued while an earlier
wave's tokens were still unread: the engine's ``waves_overlapped`` over
``chunks``, as deltas over the window, in percent.  Such a chunk was on
the device's queue when the wave before it ended, so the host's harvest,
record and next admission ran under it instead of in front of it.  An
engine without the counter (before PR 29) reads nothing."""

from harness.window import engine_delta


def read(ctx):
    over, chunks = engine_delta(ctx, "waves_overlapped"), engine_delta(ctx, "chunks")
    if over is None or not chunks:
        return None
    return 100.0 * over / chunks
