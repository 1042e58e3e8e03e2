"""``generation_share_long``: ``generation_share`` for a cell whose
answers outgrow their prompts — contexts that reach block-table widths
no prompt of the cell starts at.  The sample and the judgement are
``generation_share``'s, unchanged (the same functions); only the
warm-up differs.

**Why another warm-up.**  ``generation.warm_up`` lands a request on a
chunk shape by its *prompt* length, or lets the longest prompt grow
into it, and warms a two-bucket shape ``((half, h0), (rest, h1))`` by
sending both requests together.  Where ``h1`` is reached only by
growth (``think-saturated``: prompts to 1,024, contexts to 3,072, so
the 48-page table is a thousand decode steps away) the short request
has long finished when the long one arrives: the pair never meets, the
warm-up retries its three rounds (273 s of set-up on the chip) and the
window compiles the shape itself (PERF.md section 6, PR 32).  Here one
stream is grown to each such width once, and while it decodes there
the short partners are sent beside it, one after another.

It also leaves out a prefill group the engine will never form: the
engine caps a prefill call's padded positions by the memory the device
has left and says the cap in ``/health/status``
(``prefill_positions_max``; a program that does not say it has none).
"""

from __future__ import annotations

import threading

from harness import warmup
from harness.kinds.generation_share import (  # noqa: F401 — the kind's interface
    OFF_SHARE_MAX,
    SAMPLE_NEW,
    TIE_STDS,
    WORST_GAP_STDS,
    compared,
    content,
    counters,
    fields,
    judge,
    multiset,
    run_wave,
    serve_sample,
    verdict_line,
)
from harness.protocols import RequestFailed
from harness.served import BenchFailure, http_json

# a width the longest prompt reaches within this many decode chunks is
# warmed as ``generation`` warms it (the request just runs that long)
NEAR_CHUNKS = 8


def positions_cap(served):
    """The most padded positions one prefill call takes, as the engine
    says it (``lane_report()``), or None."""
    try:
        status = http_json(f"{served.base}/health/status")
        for nodes in status["predictors"].values():
            for node in nodes.values():
                return node.get("prefill_positions_max")
    except (OSError, ValueError, KeyError, AttributeError):
        pass
    return None


def grow_beside(served, engine: dict, h: int, partners: list, work: list, seed: int,
                serial: list) -> None:
    """Grow one stream from the cell's longest prompt until it decodes
    at table width ``h`` (alone: the one-bucket shape), then send a
    request that decodes at each width of ``partners`` beside it."""
    steps = engine["steps_per_call"]
    shortest, longest = min(p for p, _a in work), max(p for p, _a in work)
    prompt_len, asked = warmup.landing(h, engine, shortest, longest)
    reach = asked - 2 * steps                      # tokens until the lane is at width h
    item = (prompt_len, served.traffic["max_total"] - prompt_len)
    serial[0] += 1
    prompt = content(served.config["model"], seed, serial[0], item)
    conn = served.protocol.connect(served.plan)
    events, errors, there, cut = [], [], threading.Event(), threading.Event()
    got = [0]

    def grown():  # called once an event, after it was appended
        got[0] += events[-1][1]
        if got[0] >= reach + steps:
            there.set()

    def long_stream():
        try:
            served.protocol.call(conn, served.plan, prompt, item, events, grown)
        except (RequestFailed, OSError, ValueError) as e:
            if not cut.is_set():
                errors.append(f"{type(e).__name__}: {e}")
        finally:
            there.set()
            served.protocol.close(conn)

    thread = threading.Thread(target=long_stream, daemon=True)
    thread.start()
    try:
        there.wait(timeout=900)
        if errors or not thread.is_alive():
            raise BenchFailure(f"the stream grown to width {h} ended early: {errors[:1]}")
        for h0 in partners:
            run_wave(served, {"blocker": False, "for": f"chunk (({h0}), ({h})) beside a grown stream",
                              "requests": [warmup.landing(h0, engine, shortest, longest)]},
                     seed, serial)
    finally:
        cut.set()
        served.protocol.abort(conn)
        thread.join(timeout=900)


def warm_up(served, server, work: list, seed: int) -> dict:
    """``generation.warm_up`` over the shapes it can land, then one
    grown stream per far width with its partners beside it; report what
    was met."""
    engine, traffic = served.config["engine"], served.traffic
    targets = warmup.reachable(engine, work, traffic["clients"], traffic["warm_group_max"])
    cap = positions_cap(served)
    if cap:
        targets["prefill"] = {(b, k) for b, k in targets["prefill"] if k == 1 or b * k <= cap}
    steps = engine["steps_per_call"]
    shortest, longest = min(p for p, _a in work), max(p for p, _a in work)
    widths = {h for spec in targets["chunk"] for _lanes, h in spec}
    far = {h for h in widths
           if warmup.landing(h, engine, shortest, longest)[1] > (NEAR_CHUNKS + 2) * steps}
    serial = [1 << 40]  # warm-up content never collides with a window request's
    met = warmup.warmed(server.log_text())  # what the checked sample met already
    missing = {k: targets[k] - met[k] for k in targets}
    rounds = 0
    while (missing["prefill"] or missing["chunk"]) and rounds < 3:
        rounds += 1
        near = {"prefill": missing["prefill"],
                "chunk": {s for s in missing["chunk"] if not far & {h for _l, h in s}}}
        for wave in warmup.waves(engine, near, work):
            wave["blocker_prompt"] = shortest
            run_wave(served, wave, seed, serial)
        for h in sorted(far):
            mine = [s for s in missing["chunk"] if s[-1][1] == h]
            if mine:
                grow_beside(served, engine, h, sorted(s[0][1] for s in mine if len(s) == 2),
                            work, seed, serial)
        met = warmup.warmed(server.log_text())
        missing = {k: targets[k] - met[k] for k in targets}
    return {"targets": {k: len(v) for k, v in targets.items()},
            "met": {k: len(v) for k, v in met.items()}, "rounds": rounds,
            "grown": sorted(far),
            "missing": {k: sorted(map(str, v)) for k, v in missing.items() if v}}
