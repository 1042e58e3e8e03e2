"""A metric a later PR might add: token events that arrived inside the
window.  Here it proves that a reader is found by name alone."""


def read(ctx):
    t0, t1 = ctx["window"]
    return sum(1 for r in ctx["records"] for t, _n in r["events"] if t0 <= t < t1)
