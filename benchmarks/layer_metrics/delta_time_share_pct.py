"""Share of the device's busy time spent in the linear-attention layers'
own operations: the convolution, the prefill's chunked scan and the
decode state update (``delta_work``'s three rules; the layers'
projections and FFN are matmuls any layer has and are left out).
Nothing to read, and no number, for a configuration without such
layers."""

from layer_metrics.delta_work import context, is_conv, is_scan, is_step, seconds_of


def read(ctx):
    found = context(ctx)
    if not found or not found[0].get("busy_s"):
        return None
    trace, z = found
    seconds = sum(seconds_of(trace, z, rule) for rule in (is_conv, is_scan, is_step))
    if not seconds:
        return None
    return 100.0 * seconds / trace["busy_s"]
