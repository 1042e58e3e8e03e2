"""Rows a full layer's decode attention read as a share of the rows cached
for those lane-steps: the engine's ``sparse_rows_read`` over
``sparse_rows_cached`` (both summed over active lane-steps and the full
layers, ``PagedEngine._sparse_step``), as deltas over the window, in
percent.  100 where no lane holds ``index_topk`` rows yet; ``index_topk``
over the mean context once every lane does.  A program without the
counters reads nothing."""

from harness.window import engine_delta


def read(ctx):
    read_, cached = engine_delta(ctx, "sparse_rows_read"), engine_delta(ctx, "sparse_rows_cached")
    if read_ is None or not cached:
        return None
    return 100.0 * read_ / cached
