"""One module per ``config.kind``, found by that name.  A kind knows
what a request of its configurations holds, which sample is checked
against the plain reference and how, what set-up must warm, and where
the program's counters are read:

* ``multiset(traffic) -> [item]``: the fixed work of a traffic file;
* ``content(model, seed, index, item)``: request ``index``'s payload;
* ``fields(item) -> dict``: what a request's record says of its item;
* ``serve_sample(served, work, seed) -> [sample]``: the seeded sample,
  served (JSON-able; the reference process judges it);
* ``judge(ref, params, model, samples) -> verdict`` (in the reference's
  process; ``ok`` decides ``correct``), ``verdict_line(verdict)`` and
  ``compared(verdict) -> {name: [number, limit]}``, which the result's
  line and the last lines on standard error repeat;
* ``warm_up(served, server, work, seed) -> report`` with ``missing``;
* ``counters(served) -> dict | None``: one reading of the program's
  counters, taken at the window's and the trace's edges.

A kind imports neither jax nor the program at module level.
"""
