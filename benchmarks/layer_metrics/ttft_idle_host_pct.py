"""The part of ``ttft_device_idle_pct`` in which the engine HAD work
(everything but ``no_work``): its own harvest, record, admission,
packing and dispatch under an idle device — what a host-side cut of the
prefill path can win back."""

from layer_metrics.idle_work import idle_pct


def read(ctx):
    return idle_pct(ctx, "untraced", other_than=("no_work",))
