"""What the client saw inside the measured window, from the load
generator's records.  Every rate counts events by their arrival stamp;
every latency is over the requests *sent* inside the window."""

from __future__ import annotations


def sent_in_window(ctx: dict) -> list:
    t0, t1 = ctx["window"]
    return [r for r in ctx["records"] if "t_send" in r and t0 <= r["t_send"] < t1]


BURST_GAP_S = 0.05


def burst_span(ctx: dict):
    """``(units, seconds)`` of the longest stretch of the window that
    begins and ends where a burst of reply events ends.

    The engine delivers tokens a decode chunk at a time: ~250 token
    events within ~13 ms, then 0.5-1.3 s of silence (PERF.md §6, PR
    23).  A window cut at fixed instants holds n or n + 1 such bursts,
    and the rate jumps by one chunk in ~50.  A burst's tokens are the
    work of the interval before it, so the stretch from the end of the
    window's first burst to the end of its last holds exactly the
    bursts after the first: all the tokens of that stretch over all of
    its time.  A burst ends where nothing arrives for ``BURST_GAP_S``.
    Where replies never pause that long (or fewer than two bursts end
    in the window) this is the whole window and every event in it."""
    t0, t1 = ctx["window"]
    events = sorted((t, n) for r in ctx["records"] for t, n in r["events"] if t0 <= t < t1)
    stamps = [t for t, _n in events] + [t1]
    ends = [a for a, b in zip(stamps, stamps[1:]) if b - a > BURST_GAP_S]
    if len(ends) < 2:
        return sum(n for _t, n in events), t1 - t0
    return sum(n for t, n in events if ends[0] < t <= ends[-1]), ends[-1] - ends[0]


def ttfts_ms(ctx: dict) -> list:
    """Send to first token event, for requests sent inside the window
    whose first event arrived (the others were still waiting for a slot
    when the window closed)."""
    return [1e3 * (r["events"][0][0] - r["t_send"])
            for r in sent_in_window(ctx) if r["events"]]


def token_gaps_ms(ctx: dict) -> list:
    """Per completed request sent in the window: mean time per token
    after its first event (events carry a chunk of tokens each)."""
    out = []
    for r in sent_in_window(ctx):
        if r["ok"] and len(r["events"]) > 1:
            later = sum(n for _t, n in r["events"][1:])
            out.append(1e3 * (r["events"][-1][0] - r["events"][0][0]) / later)
    return out


def failed_in_window(ctx: dict) -> list:
    """Requests sent in the window that failed: an error other than our
    own cut at the window's end."""
    return [r for r in sent_in_window(ctx) if r.get("error") and not r.get("aborted")]


def engine_delta(ctx: dict, key: str, span: str = "window"):
    """``after - before`` of one ``engine_stats()`` counter over the
    window (``span="window"``) or the traced interval (``"trace"``)."""
    pair = ctx["engine"].get(span)
    if not pair or pair[0] is None or pair[1] is None:
        return None
    if key not in pair[0] or key not in pair[1]:
        return None
    return pair[1][key] - pair[0][key]


def module_seconds(ctx: dict, program: str):
    """(executions, device seconds) of the traced programs of one kind
    (``"chunk"``, ``"prefill"``): those whose module name holds one of
    the words the configuration's ``programs`` lists for that kind.
    None when nothing was traced or nothing matches."""
    trace = ctx.get("trace")
    if not trace or not trace.get("modules"):
        return None
    words = ctx["config"].get("programs", {}).get(program, [program])
    hits = [v for k, v in trace["modules"].items() if any(w in k for w in words)]
    if not hits:
        return None
    return sum(v["count"] for v in hits), sum(v["seconds"] for v in hits)


def kernel_seconds(ctx: dict):
    """(calls, device seconds) of the traced Pallas kernels: the
    operations ``trace_reduce.stable_op_name`` calls ``pallas_kernel_*``
    (on the generation path the paged-decode kernel is the only one)."""
    trace = ctx.get("trace")
    if not trace or not trace.get("ops"):
        return None
    hits = [v for k, v in trace["ops"].items() if k.startswith("pallas_kernel")]
    if not hits:
        return None
    return sum(v["count"] for v in hits), sum(v["seconds"] for v in hits)


def cached_tokens_mean(ctx: dict, points: int = 40):
    """Mean over the traced interval of the tokens the decoding streams
    held in the cache, as the clients saw them: a stream counts from one
    chunk before its first event (its prefill) to its last event, with
    its prompt plus the tokens received so far."""
    span = ctx.get("trace_span")
    if not span:
        return None
    ta, tb = span
    total = 0.0
    for i in range(points):
        t = ta + (i + 0.5) * (tb - ta) / points
        for r in ctx["records"]:
            if not r["events"]:
                continue
            first, last = r["events"][0][0], r["events"][-1][0]
            lead = (r["events"][1][0] - first) if len(r["events"]) > 1 else 0.0
            if first - lead <= t <= last:
                total += r["prompt_len"] + sum(n for te, n in r["events"] if te <= t)
    return total / points
