"""The untraced stretch of a traced run: from the window's opening to
the last ``engine_stats()`` sample taken before the trace started
(``benchmarks/run.py`` samples about once a second until then, 30 % into
the window) — some 15 s of a system no profiler perturbs, inside every
``--trace 1`` run.  A snapshot carries its own clock since PR 35
(``clock_s``, the engine's ``time.monotonic()`` as the counters were
read), which is what places a sample before the trace and what a rate
over the stretch divides by.  A program whose snapshots carry no clock
(the parent of PR 35) has no such stretch, and every reader of it reads
nothing."""


def stretch(ctx):
    """``(first, last)`` snapshots of the untraced stretch, or None."""
    engine = ctx.get("engine") or {}
    window, trace = engine.get("window"), engine.get("trace")
    if not window or not trace or not window[0] or not trace[0]:
        return None
    first, edge = window[0], trace[0].get("clock_s")
    if edge is None or "clock_s" not in first:
        return None
    last = None
    for sample in engine.get("samples") or ():
        if sample and first["clock_s"] < sample.get("clock_s", edge) < edge:
            if last is None or sample["clock_s"] > last["clock_s"]:
                last = sample
    return None if last is None else (first, last)


def delta(ctx, key):
    """``last - first`` of one counter over the untraced stretch; None
    without the stretch or without the counter."""
    pair = stretch(ctx)
    if pair is None or key not in pair[0] or key not in pair[1]:
        return None
    return pair[1][key] - pair[0][key]


def ratio(ctx, part, whole, scale=1.0):
    """``scale * d(part) / d(whole)`` over the stretch; None where
    either is missing or nothing was counted."""
    top, bottom = delta(ctx, part), delta(ctx, whole)
    if top is None or not bottom:
        return None
    return scale * top / bottom
