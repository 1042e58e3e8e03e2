"""``generation_share_state``: ``generation_state``'s warm-up and checked
sample under ``generation_share``'s judgement, for a model whose
linear-attention layers keep a **state a lane** beside latent rows AND
whose FFN is behind a router's step function (Ling-3.0-flash: Kimi Delta
Attention, five layers to every latent-attention layer, over 512
sigmoid-routed experts of which the replica holds 16).

**The sample** is ``generation_state``'s: three judged prompts of the
cell's own lengths, none its prefill bucket's own length, each answered
with ``SAMPLE_NEW`` = 128 tokens decoded through the state and the latent
rows — the shortest alone (a group of one in a bucket nearly twice its
length: the pad rule), then the median and the longest together behind a
blocker beside an unjudged companion (prompts of different lengths in one
padded call: each row's own state).  The reference
(``reference/ling3_flash.py``: the recurrence position by position, no
state carried, latent attention over up-projected keys and values) judges
384 positions on logits, teacher-forced.

**The judgement** is ``generation_share``'s, because a router is in the
model again: a sound bfloat16 program takes some routing decisions the
other way than float32 does, and where the expert is one this replica
holds the logits jump.  A position is *off* when its served token is
neither the reference's top-1 nor within ``TIE_STDS`` of it; the sample
is correct when at most ``OFF_SHARE_MAX`` of its positions are off and
none by more than ``WORST_GAP_STDS``.

**The limits, from two readings each** (``tools/precision_readings.py
--config ling-3.0-flash --positions 1152 --judged 128``: the published
widths on the CPU, a prompt of 1,024 and 128 judged tokens; the table is
in PERF.md section 6, PR 52, with the chip's own readings beside it):

* ``OFF_SHARE_MAX`` 3 %: between what the stated precision reads (bf16
  operands: 0 to 0.8 % on the CPU seeds, and on the chip) and what the
  nearest precision below it reads, 8-bit operands (e4m3), with room on
  both sides; at 384 positions 3 % is 11 of them.
* ``WORST_GAP_STDS`` 2.0 (``generation_share``'s): for a program wrong at
  a few positions only (a stale row, another lane's state); a sound
  program's worst position reads under 1, a token served from the row
  before lies several deviations under the top.

Of the model's own wrong programs (``reference/ling3_flash.py
VARIANTS``), the decay averaged over a head's channels, the softplus gate
in the bounded one's place, beta times 2 and the head-wise gate left out
each fail ``OFF_SHARE_MAX``.  **What no count of served tokens can
tell**: the state kept in bfloat16, which moves the logits by less than
the stated precision's own rounding does (as in ``generation_state``);
the CPU tests hold it on logits (``tests/test_ling3_paged.py``,
``tests/test_kda_ops.py``); PERF.md section 7 says so.
"""

from __future__ import annotations

from harness.kinds import generation_share as _share
from harness.kinds.generation_state import (  # noqa: F401 — the kind's interface
    SAMPLE_NEW,
    TIE_STDS,
    compared,
    content,
    counters,
    fields,
    multiset,
    run_wave,
    serve_sample,
    verdict_line,
)
from harness.kinds import generation_state as _state

OFF_SHARE_MAX = 0.03
WORST_GAP_STDS = 2.0
# prompts one prefill call may hold that are warmed whatever the traffic's
# ``warm_group_max`` says of a window's waves (``warm_up``)
RAMP_GROUP = 4


def warm_up(served, server, work: list, seed: int) -> dict:
    """``generation_state.warm_up`` over groups of ``RAMP_GROUP`` prompts
    at least.  The traffic's ``warm_group_max`` (2 in this kind's cell)
    is what a wave of the WINDOW seldom passes — a stream decodes ~2,000
    tokens, so a wave frees half a slot in the mean — but the ramp admits
    16 callers a step and the engine groups as many of a bucket as its
    cap on a call's positions lets it: the cell's first run on the chip
    compiled ``paged_prefill [bucket=2048,k=4]`` inside the ramp (~100 s
    of a ``ramp_s`` of 127, my chip run, PR 52), and four streams ending
    in one wave (one window in ~16 by the mean rate) would compile it
    inside the window.  Groups the cap does not let form are left out by
    ``generation_state``'s own rule."""
    traffic = served.traffic
    served.traffic = dict(traffic, warm_group_max=max(
        int(traffic["warm_group_max"]), RAMP_GROUP))
    try:
        return _state.warm_up(served, server, work, seed)
    finally:
        served.traffic = traffic


def judge(ref, params, model: dict, samples: list) -> dict:
    """``generation_share.judge``'s gaps (teacher-forced, each served
    token under the reference's top-1 in deviations of its position's
    logits), held to this kind's limits."""
    v = _share.judge(ref, params, model, samples)
    v.update(off_share_max=OFF_SHARE_MAX, worst_gap_max=WORST_GAP_STDS,
             ok=(v["off"] <= OFF_SHARE_MAX * v["positions"]
                 and v["worst_gap_stds"] <= WORST_GAP_STDS))
    return v
