"""Share of the device's busy time spent in the paged-decode kernel."""

from harness.window import kernel_seconds


def read(ctx):
    got = kernel_seconds(ctx)
    if not got or not ctx["trace"].get("busy_s"):
        return None
    return 100.0 * got[1] / ctx["trace"]["busy_s"]
