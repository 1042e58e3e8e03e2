"""Share of the device's busy time spent in the latent decode kernel:
the ``pallas_kernel`` whose output is ``(lanes, heads, kv_lora_rank)``
(``mla_work``'s rule)."""

from layer_metrics.mla_work import latent, latent_kernel_seconds


def read(ctx):
    trace, config = ctx.get("trace"), ctx.get("config") or {}
    if not trace or not trace.get("busy_s") or not trace.get("ops") or not latent(config):
        return None
    calls, seconds = latent_kernel_seconds(trace, config)
    if not calls:
        return None
    return 100.0 * seconds / trace["busy_s"]
