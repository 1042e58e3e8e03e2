"""Share of the device's busy time the new mechanism has of the cell: a
decode step's indexer scoring, its top-k, the attention that reads the
chosen rows, and the window layers' kernel (``dots3_work``'s four rules,
each operation counted once; the scoring and the chosen rows' attention
in XLA's operations or in a Pallas kernel alike)."""

from layer_metrics.dots3_work import (
    context, is_index_score, is_sparse_attention, is_topk, is_window_kernel)


def read(ctx):
    found = context(ctx)
    if not found or not found[0].get("busy_s"):
        return None
    trace, z = found
    rules = (is_index_score, is_topk, is_sparse_attention, is_window_kernel)
    seconds = sum(v["seconds"] for k, v in trace["ops"].items()
                  if any(rule(k, z) for rule in rules))
    return 100.0 * seconds / trace["busy_s"] if seconds else None
