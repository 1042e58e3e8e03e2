"""What the state-space layers' state takes of the bytes a replica keeps
for its streams: ``ssm_state_bytes`` (every slot's state as it rests, the
program's gauge) over that plus the mapped K/V pages' bytes
(``pool_pages_used`` x one page's K and V in every attention layer,
65,536 B), the mean of about one ``engine_stats()`` sample a second
through the window.  The state is there whatever the context; the pages
grow with it, at 1 KB a token: the share says which of the two a decode
step's bytes follow."""

from layer_metrics.ssm_work import page_bytes, sizes


def read(ctx):
    z = sizes(ctx.get("config") or {})
    if not z:
        return None
    shares = []
    for s in ctx["engine"]["samples"]:
        if s and s.get("ssm_state_bytes") and "pool_pages_used" in s:
            state = float(s["ssm_state_bytes"])
            shares.append(100.0 * state / (state + s["pool_pages_used"] * page_bytes(z)))
    if not shares:
        return None
    return sum(shares) / len(shares)
