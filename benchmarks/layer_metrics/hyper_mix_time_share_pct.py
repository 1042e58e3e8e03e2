"""Share of the device's busy time spent mixing a residual of several
rows (``hyper_work``'s rule: the ``hyper_pre_mix`` / ``hyper_post_mix``
kernels, or XLA's operations of the rows' shape).  Nothing to read, and
no number, for a configuration whose residual is one row."""

from layer_metrics.hyper_work import mixing_seconds, streams


def read(ctx):
    trace, config = ctx.get("trace"), ctx.get("config") or {}
    if not trace or not trace.get("busy_s") or not trace.get("ops") or not streams(config):
        return None
    calls, seconds = mixing_seconds(trace, config)
    if not calls:
        return None
    return 100.0 * seconds / trace["busy_s"]
