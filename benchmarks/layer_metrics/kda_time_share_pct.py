"""Share of the device's busy time spent in the KDA layers' own
operations: the channel gate, the convolution, the prefill's chunked scan
and the decode state update (``kda_work``'s four rules; the layers' other
projections and their FFN are matmuls any layer has and are left out).
Nothing to read, and no number, for a configuration without such
layers."""

from layer_metrics.kda_work import context, is_conv, is_gate, is_scan, is_step, seconds_of


def read(ctx):
    found = context(ctx)
    if not found or not found[0].get("busy_s"):
        return None
    trace, z = found
    seconds = sum(seconds_of(trace, z, rule)
                  for rule in (is_gate, is_conv, is_scan, is_step))
    if not seconds:
        return None
    return 100.0 * seconds / trace["busy_s"]
