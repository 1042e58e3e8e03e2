"""The device's idle time as the program stamps it (PR 50), shared by
its readers.  ``engine_stats()`` carries ``device_busy_s`` and
``device_idle_s`` — from a completion stamp a dispatched program of the
wave loop: busy from a program's start (its enqueue, or the completion
before it) to its completion, idle from a completion to the next
enqueue — and ``device_idle_by_s``, the idle by where the engine thread
was (``no_work``: no stream admitted or queued; ``admit``, ``wait``,
``harvest``, ``record``, ``between``; ``prefill.pack`` / ``.call`` /
``.tail``; ``launch.plan`` / ``.call`` / ``.post``).

A share is ``100 x d(idle, or some of its parts) / (d(idle) + d(busy))``
between two snapshots: of the untraced stretch of a traced run
(``untraced.stretch``: the clock ``out_tok_s`` is earned on) or of the
traced interval (``ctx["engine"]["trace"]``: what to hold against the
trace's own ``1 - busy_s / window_s``).  ``0.0`` where the counters are
there and nothing was idle; None only where a snapshot lacks them (a
program from before PR 50) or the span does not exist — and in a CPU
rehearsal (``ctx["peaks"]`` is None): a device's time is never read off
a run that had none."""

from layer_metrics.untraced import stretch

LAUNCH = ("launch.plan", "launch.call", "launch.post")
PREFILL = ("admit", "prefill.pack", "prefill.call", "prefill.tail")
KEYS = ("device_busy_s", "device_idle_s", "device_idle_by_s")


def on_a_chip(ctx):
    """Whether the run measured a chip (``benchmarks/run.py`` looks the
    device's peaks up unless it rehearses on the CPU)."""
    return ctx.get("peaks") is not None


def snapshots(ctx, span):
    """``(first, last)`` of ``span`` (``"untraced"``, or a key of
    ``ctx["engine"]``: ``"trace"``, ``"window"``), each with the device
    clock's counters; else None."""
    if not on_a_chip(ctx):
        return None
    if span == "untraced":
        pair = stretch(ctx)
    else:
        pair = (ctx.get("engine") or {}).get(span)
    if not pair or not pair[0] or not pair[1]:
        return None
    if any(key not in snapshot for snapshot in pair for key in KEYS):
        return None
    return pair


def idle_pct(ctx, span, where=None, other_than=()):
    """The device's idle share of ``span`` in percent: all of it, or the
    part booked to the names in ``where``, or all but ``other_than``."""
    pair = snapshots(ctx, span)
    if pair is None:
        return None
    first, last = pair
    idle = last["device_idle_s"] - first["device_idle_s"]
    whole = idle + last["device_busy_s"] - first["device_busy_s"]
    if whole <= 0:
        return 0.0  # no program completed in the span: nothing was idle
    if where is not None or other_than:
        by0, by1 = first["device_idle_by_s"], last["device_idle_by_s"]
        names = where if where is not None else [k for k in by1 if k not in other_than]
        idle = sum(by1.get(k, 0.0) - by0.get(k, 0.0) for k in names)
    return 100.0 * idle / whole
