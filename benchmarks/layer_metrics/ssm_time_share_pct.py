"""Share of the device's busy time spent in the state-space layers' own
operations: the convolution and the selection (``ssm_work.is_mixer_rows``),
the prefill's scan and the decode state update (the layers' in and out
projections and FFN are matmuls any layer has and are left out).  Nothing
to read, and no number, for a configuration without such layers."""

from layer_metrics.ssm_work import context, is_mixer_rows, is_scan, is_step, seconds_of


def read(ctx):
    found = context(ctx)
    if not found or not found[0].get("busy_s"):
        return None
    trace, z = found
    seconds = sum(seconds_of(trace, z, rule)
                  for rule in (is_mixer_rows, is_scan, is_step))
    if not seconds:
        return None
    return 100.0 * seconds / trace["busy_s"]
