"""Share of the window in which the engine had work and nothing in
flight: the engine's ``host_gap_s`` (return of a wave's last blocking
readback to the next dispatch of a prefill or chunk program) as a delta
over the window, over the window's length.  The device's idle share as
the program sees it."""

from harness.window import engine_delta


def read(ctx):
    gap = engine_delta(ctx, "host_gap_s")
    t0, t1 = ctx["window"]
    if gap is None or t1 <= t0:
        return None
    return 100.0 * gap / (t1 - t0)
