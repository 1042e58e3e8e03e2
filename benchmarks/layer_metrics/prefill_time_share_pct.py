"""Share of the device's busy time in the traced interval that the
prefill programs took: what admission (with its padding to buckets and
to power-of-two groups) leaves for decoding."""

from harness.window import module_seconds


def read(ctx):
    got = module_seconds(ctx, "prefill")
    if not got or not ctx["trace"].get("busy_s"):
        return None
    return 100.0 * got[1] / ctx["trace"]["busy_s"]
