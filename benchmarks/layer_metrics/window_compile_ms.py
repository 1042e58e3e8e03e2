"""Milliseconds of backend (XLA) compiles inside the window, whoever
made them: ``d(xla_compile_s)`` between the window's two snapshots.  The
engine's entry points are warmed before the window; what is left is an
eager operation (a scatter, a gather) meeting an index shape for the
first time, 40-90 ms each.  0.0 where nothing compiled; None on a
program that does not count them, and in a CPU rehearsal."""

from harness.window import engine_delta
from layer_metrics.idle_work import on_a_chip


def read(ctx):
    seconds = engine_delta(ctx, "xla_compile_s") if on_a_chip(ctx) else None
    return None if seconds is None else 1000.0 * seconds
