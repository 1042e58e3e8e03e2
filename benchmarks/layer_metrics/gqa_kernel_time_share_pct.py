"""Share of the device's busy time spent in the grouped-query decode
page loop: the traced seconds of the operations that produce a decode
step's attended values (``smallthinker_work.is_decode_attention``) over
``busy_s``."""

from layer_metrics.smallthinker_work import context, is_decode_attention, seconds_of


def read(ctx):
    found = context(ctx)
    if not found or not found[0].get("busy_s"):
        return None
    trace, z = found
    seconds = seconds_of(trace, z, is_decode_attention)
    if not seconds:
        return None
    return 100.0 * seconds / trace["busy_s"]
