"""``generation_state``: ``generation_share_long`` for a model whose
linear-attention layers keep a **state a lane** beside the K/V pages —
its warm-up (streams grown to the widths only growth reaches), a checked
sample chosen so that the state's pad rule and its decode path are on the
judged path, and ``generation_share``'s rule under limits of its own.

**Why another sample.**  ``generation.serve_sample`` asks 8 tokens of
each prompt: one decode chunk, which a state that was wrong after the
prefill (the pad positions of a bucket scanned into it, another row's
state written to the slot) or went wrong over a hundred steps (a decay
left out, a state kept too narrow) would pass.  Here three prompts of the
cell's own lengths are each answered with ``SAMPLE_NEW`` = 128 tokens,
every one decoded through the state and the pages: the shortest alone (a
group of one in a bucket nearly twice its length), then the median and
the longest together behind a blocker beside an unjudged companion (a
group of prompts of different lengths in one padded call).  A judged
prompt whose length is a prefill bucket's own is moved one token down
where the traffic allows, so at least the first two are shorter than the
bucket they are padded to.  The reference (``reference/olmo_hybrid.py``:
the recurrence position by position, no state carried) judges 384
positions on logits, teacher-forced.

**Why other limits.**  ``generation_share``'s limits were set for a
router's step function.  This model has none: its logits are continuous
in its rounding, and a sound bfloat16 program reads nothing off.  The
readings (``tools/precision_readings.py --config olmo-hybrid-7b
--positions 634 --judged 128``: the published widths on the CPU, a
prompt of 506 and 128 judged tokens, seeds 3000005003 and 3000007019; on
the chip the first traced run read 0 of 384 off, the worst gap 0.021: my
chip run, PR 48):

===================  =================  ================  =========
variant              off (> 0.09) %     worst gap (std)   verdict
===================  =================  ================  =========
stated (bf16 x bf16)  0.0 / 0.0          0.051 / 0.038     ok
**e4m3 operands**     28.1 / 25.0        0.71 / 0.61       NOT ok
state in bfloat16     0.0 / 0.0          0.015 / 0.009     ok (!)
beta without its 2    56.3 / 60.9        1.17 / 1.58       not ok
alpha left at 1       100 / 99.2         6.08 / 5.71       not ok
full layers rotated   38.3 / ...         0.79 / ...        not ok
pre-norm              99.2 / ...         5.68 / ...        not ok
a stale row           99.2 / ...         5.85 / ...        not ok
===================  =================  ================  =========

* ``OFF_SHARE_MAX`` 3 %: between the stated precision (0 of 128 on both
  seeds, 0 of 384 on the chip) and the nearest precision below it that
  this family is deployed in, 8-bit operands (25.0 and 28.1 %), with
  room on both sides: at 384 positions 3 % is 11 of them.
* ``WORST_GAP_STDS`` 1.0 (``generation_share`` has 2.0): for a program
  wrong at a few positions only.  A sound program's worst position read
  0.051 deviations; a token served from the row before lies 3.5
  deviations under the top in the median and 19 in 20 of them over 1.85.

**What no count of served tokens can tell**: the state kept in bfloat16.
It moves the logits by 0.006 deviations (rms), half of what the stated
precision's own rounding does (0.012), and changes 1 to 4 of 128 tokens,
none by over 0.015: both limits pass it, as they would pass any limit
that passes the stated precision.  The CPU tests hold it on logits
(``tests/test_olmo_hybrid_paged.py``, ``tests/test_delta_ops.py``: a
float32 engine against the float32 reference at 3e-4, which a bfloat16
state misses by over ten times); PERF.md section 7 says so.
"""

from __future__ import annotations

from harness import warmup
from harness.kinds import generation_share as _share
from harness.kinds import generation_share_long as _long
from harness.kinds.generation_share_long import (  # noqa: F401 — the kind's interface
    TIE_STDS,
    compared,
    content,
    counters,
    fields,
    multiset,
    run_wave,
    verdict_line,
)
from harness.served import http_json

SAMPLE_NEW = 128  # tokens asked of each prompt: 384 judged positions
OFF_SHARE_MAX = 0.03
WORST_GAP_STDS = 1.0


def judged_lengths(work: list, buckets: list) -> list:
    """The shortest, the median and the longest prompt length of the
    cell, each moved down to the nearest length the cell also sends that
    is no bucket's own (left where there is none)."""
    prompts = sorted({p for p, _a in work})
    every = sorted(p for p, _a in work)
    out = []
    for n in (every[0], every[len(every) // 2], every[-1]):
        fits = [p for p in prompts if p <= n and p not in buckets]
        out.append(fits[-1] if n in buckets and fits else n)
    return out


def serve_sample(served, work: list, seed: int) -> list:
    """Three judged prompts (:func:`judged_lengths`), each answered with
    ``SAMPLE_NEW`` tokens or as many as ``max_total`` leaves it: the
    shortest alone, then the median and the longest beside a companion of
    the longest's length behind a blocker (one padded call of three
    lengths)."""
    short, median, longest = judged_lengths(
        work, served.config["engine"]["prompt_buckets"])
    most = served.traffic["max_total"]
    serial = [1 << 41]  # the sample's content never collides with a window request's
    out = []
    for blocker, lens, judged in ((False, [short], 1),
                                  (True, [median, longest, longest], 2)):
        wave = {"blocker": blocker, "blocker_prompt": short, "for": "the checked sample",
                "requests": [(n, min(SAMPLE_NEW, most - n)) for n in lens]}
        answers = run_wave(served, wave, seed, serial)[:judged]
        out += [{"prompt": p, "tokens": t} for p, t in answers]
    return out


def ctx_buckets(served) -> int:
    """The length buckets the engine splits a chunk's lanes into, as it
    says them (``lane_report()["ctx_buckets"]``); 2, ``warmup.py``'s own
    assumption, for a program that does not say."""
    try:
        status = http_json(f"{served.base}/health/status")
        for nodes in status["predictors"].values():
            for node in nodes.values():
                return int(node.get("ctx_buckets") or 2)
    except (OSError, ValueError, KeyError, AttributeError, TypeError):
        pass
    return 2


def warm_up(served, server, work: list, seed: int) -> dict:
    """``generation_share_long.warm_up`` — the shapes it can land, then
    one stream grown to each far width — over the chunk programs this
    engine forms: where it runs one length bucket a chunk
    (:func:`ctx_buckets`) the two-bucket specs ``warmup.reachable``
    lists are never compiled, in warm-up or in the window, and are left
    out of the targets (they would be retried three rounds and reported
    missing)."""
    if ctx_buckets(served) != 1:
        return _long.warm_up(served, server, work, seed)
    engine, traffic = served.config["engine"], served.traffic
    targets = warmup.reachable(engine, work, traffic["clients"], traffic["warm_group_max"])
    targets["chunk"] = {spec for spec in targets["chunk"] if len(spec) == 1}
    cap = _long.positions_cap(served)
    if cap:
        targets["prefill"] = {(b, k) for b, k in targets["prefill"] if k == 1 or b * k <= cap}
    steps = engine["steps_per_call"]
    shortest, longest = min(p for p, _a in work), max(p for p, _a in work)
    far = {h for ((_lanes, h),) in targets["chunk"]
           if warmup.landing(h, engine, shortest, longest)[1] > (_long.NEAR_CHUNKS + 2) * steps}
    serial = [1 << 40]  # warm-up content never collides with a window request's
    met = warmup.warmed(server.log_text())  # what the checked sample met already
    missing = {k: targets[k] - met[k] for k in targets}
    rounds = 0
    while (missing["prefill"] or missing["chunk"]) and rounds < 3:
        rounds += 1
        near = {"prefill": missing["prefill"],
                "chunk": {s for s in missing["chunk"] if s[0][1] not in far}}
        for wave in warmup.waves(engine, near, work):
            wave["blocker_prompt"] = shortest
            run_wave(served, wave, seed, serial)
        for h in sorted(far & {s[0][1] for s in missing["chunk"]}):
            _long.grow_beside(served, engine, h, [], work, seed, serial)
        met = warmup.warmed(server.log_text())
        missing = {k: targets[k] - met[k] for k in targets}
    return {"targets": {k: len(v) for k, v in targets.items()},
            "met": {k: len(v & targets[k]) for k, v in met.items()}, "rounds": rounds,
            "grown": sorted(far),
            "missing": {k: sorted(map(str, v)) for k, v in missing.items() if v}}


def judge(ref, params, model: dict, samples: list) -> dict:
    """``generation_share.judge``'s gaps (teacher-forced, each served
    token under the reference's top-1 in deviations of its position's
    logits), held to this kind's limits."""
    v = _share.judge(ref, params, model, samples)
    v.update(off_share_max=OFF_SHARE_MAX, worst_gap_max=WORST_GAP_STDS,
             ok=(v["off"] <= OFF_SHARE_MAX * v["positions"]
                 and v["worst_gap_stds"] <= WORST_GAP_STDS))
    return v
