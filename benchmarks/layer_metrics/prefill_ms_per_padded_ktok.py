"""Device time of the prefill programs per thousand positions they
computed, both from the trace: a program named
``jit_paged_prefill[_cached]_b<bucket>_k<k>...`` computes ``bucket * k``
positions a run, so the traced interval's positions are the sum of
``count * bucket * k`` over those programs.  Same clock, same interval,
no counter read over HTTP at the trace's edges."""

import re

SHAPE = re.compile(r"paged_prefill(?:_cached)?_b(\d+)_k(\d+)")


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("modules"):
        return None
    positions, seconds = 0.0, 0.0
    for name, slot in trace["modules"].items():
        shape = SHAPE.search(name)
        if shape:
            positions += slot["count"] * int(shape.group(1)) * int(shape.group(2))
            seconds += slot["seconds"]
    if not positions:
        return None
    return 1e3 * seconds / (positions / 1000.0)
