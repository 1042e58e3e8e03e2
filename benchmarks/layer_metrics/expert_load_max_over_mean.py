"""How uneven routing is: the cumulative assignments of the busiest
(layer, expert) pair over the mean of all pairs, as the engine counts
them since it started (``moe_load_max`` / ``moe_load_mean``, read at the
window's end; 1.0 is an even load).  Better lower: the busiest expert's
group is the longest of a grouped matmul."""


def read(ctx):
    pair = (ctx.get("engine") or {}).get("window")
    after = pair[1] if pair else None
    if not after or not after.get("moe_load_mean"):
        return None
    return after["moe_load_max"] / after["moe_load_mean"]
