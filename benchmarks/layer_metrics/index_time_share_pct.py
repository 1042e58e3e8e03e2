"""Share of the device's busy time spent scoring cached indexer keys and
taking the best ``index_topk`` of them, a decode step's (``dots3_work``'s
rules: the keys' gather, the heads' products, their weighted sum; the
sort and what is computed on its ``(lanes, topk)`` outputs)."""

from layer_metrics.dots3_work import context, is_index_score, is_topk, seconds_of


def read(ctx):
    found = context(ctx)
    if not found or not found[0].get("busy_s"):
        return None
    trace, z = found
    seconds = seconds_of(trace, z, is_index_score) + seconds_of(trace, z, is_topk)
    return 100.0 * seconds / trace["busy_s"] if seconds else None
