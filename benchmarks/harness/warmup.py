"""Which compiled programs a cell's traffic can reach, and the requests
that land on each.

The paged engine compiles one program per static shape: a prefill per
``(prompt bucket, group size k)`` and a decode chunk per ``(steps,
bucket spec)``.  The rules below are a copy of the engine's rounding
(``PagedEngine._pages_pow2``, ``_plan_buckets``, ``_prefill_group`` at
PR 21), applied to the cell's fixed multiset of lengths, so set-up
warms what the window can reach and nothing else.  If the program's
rules move, the run's ``window_compiles`` line says so.
"""

from __future__ import annotations

import re


def pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def horizon(ctx_tokens: int, engine: dict) -> int:
    """Block-table columns of a chunk whose longest lane holds
    ``ctx_tokens``: pages to the end of the chunk, rounded up to a
    power of two, capped at the per-stream table."""
    pages = -(-(ctx_tokens + engine["steps_per_call"]) // engine["page_size"])
    return min(pow2_at_least(max(1, pages)), engine["max_len"] // engine["page_size"])


def prefill_bucket(prompt_len: int, engine: dict) -> int:
    return next(b for b in engine["prompt_buckets"] if b >= prompt_len)


def reachable(engine: dict, work: list, clients: int, group_max: int) -> dict:
    """``{"prefill": {(bucket, k)}, "chunk": {spec}}`` for the multiset
    ``work`` of (prompt, answer) lengths sent by ``clients`` callers.

    Groups: up to ``group_max`` same-bucket prompts join one wave for a
    bucket that holds a tenth of the requests or more, two otherwise,
    and never more than there are callers.  Chunk specs: every horizon
    a stream passes through alone, and every pair short half < long
    half once two callers exist."""
    steps, slots = engine["steps_per_call"], engine["max_slots"]
    share = {}
    for prompt, _answer in work:
        b = prefill_bucket(prompt, engine)
        share[b] = share.get(b, 0) + 1
    prefill = set()
    for b, n in share.items():
        top = group_max if 10 * n >= len(work) else 2
        k = 1
        while k <= min(top, pow2_at_least(clients)):
            prefill.add((b, k))
            k *= 2
    horizons = set()
    for prompt, answer in work:
        ctx = prompt
        while ctx < prompt + answer:
            horizons.add(horizon(ctx, engine))
            ctx += steps
    chunk = {((slots, h),) for h in horizons}
    if clients >= 2 and slots >= 2:
        half = slots // 2
        chunk |= {((half, h0), (slots - half, h1))
                  for h0 in horizons for h1 in horizons if h0 < h1}
    return {"prefill": prefill, "chunk": chunk}


def landing(h: int, engine: dict, shortest: int, longest: int) -> tuple:
    """``(prompt_len, new_tokens)`` of a request, with a prompt length the
    cell itself sends, that decodes at horizon ``h``: a prompt whose
    first two chunks both run there if one exists (the middle one), else
    the longest prompt with enough new tokens to grow into it."""
    steps = engine["steps_per_call"]
    both = [p for p in range(shortest, longest + 1)
            if horizon(p, engine) == h == horizon(p + steps, engine)]
    if both:
        return both[len(both) // 2], 2 * steps
    grow = next((c for c in range(longest, engine["max_len"]) if horizon(c, engine) == h),
                None)
    if grow is None:
        raise ValueError(f"no context of this cell decodes at horizon {h}")
    return longest, -(-(grow - longest) // steps) * steps + 2 * steps


def waves(engine: dict, targets: dict, work: list) -> list:
    """Warm-up as a list of waves; a wave is ``{"blocker": bool,
    "requests": [(prompt_len, new_tokens), ...], "for": label}`` whose
    requests are sent together.  With ``blocker`` a short stream is kept
    decoding meanwhile, so the requests arrive inside a chunk and are
    admitted, and prefilled as one group, at the next wave boundary.
    Every request has a prompt length of the cell's own range, so
    warm-up meets no program the window cannot."""
    steps = engine["steps_per_call"]
    shortest = min(p for p, _a in work)
    biggest = max(p for p, _a in work)
    longest = {}
    for prompt, _answer in work:
        b = prefill_bucket(prompt, engine)
        longest[b] = max(longest.get(b, 0), prompt)
    out = []
    for spec in sorted(targets["chunk"]):
        out.append({"blocker": False, "for": f"chunk {spec}",
                    "requests": [landing(h, engine, shortest, biggest) for _lanes, h in spec]})
    for bucket, k in sorted(targets["prefill"]):
        # every group size that pads to k: the engine's eager helpers
        # (key and logit scatters) compile once per true size
        for size in range(k // 2 + 1, k + 1):
            out.append({"blocker": True, "for": f"prefill ({bucket}, {k}) as {size}",
                        "requests": [(longest[bucket], steps)] * size})
    return out


_PREFILL = re.compile(r"jit compile: program=paged_prefill \[bucket=(\d+),k=(\d+)\]")
_CHUNK = re.compile(r"jit compile: program=paged_chunk \[steps=(\d+),buckets=(\(.*?\))\]")


def warmed(log_text: str) -> dict:
    """The programs the server's jit sentinel says it has met, from its
    log: the same two sets as :func:`reachable`."""
    prefill = {(int(b), int(k)) for b, k in _PREFILL.findall(log_text)}
    chunk = set()
    for _steps, spec in _CHUNK.findall(log_text):
        pairs = re.findall(r"\((\d+), (\d+)\)", spec)
        chunk.add(tuple((int(a), int(b)) for a, b in pairs))
    return {"prefill": prefill, "chunk": chunk}
