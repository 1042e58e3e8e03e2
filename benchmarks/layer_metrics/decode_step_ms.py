"""Device time of one decode step: the traced chunk programs' summed
time over executions x steps per chunk."""

from harness.window import module_seconds


def read(ctx):
    got = module_seconds(ctx, "chunk")
    if not got or not got[0]:
        return None
    count, seconds = got
    return 1e3 * seconds / (count * ctx["config"]["engine"]["steps_per_call"])
