"""``generation_state_deep``: ``generation_state``'s checked sample,
warm-up and rule under an off-share limit read at this configuration's
own depth — a model whose every layer is served (AI21-Jamba2-3B: 26
state-space layers and 2 attention layers, 56 residual adds, a tied head
over 65,536 rows), where ``generation_state``'s was read at eight layers.

**The sample and the warm-up** are ``generation_state``'s, unchanged:
three judged prompts of the cell's own lengths (129 / 255 / 508 here),
none its prefill bucket's own length, each answered with ``SAMPLE_NEW`` =
128 tokens decoded through the state, the convolution's tail and the
pages — the shortest alone, then the median and the longest together
behind a blocker beside an unjudged companion (prompts of different
lengths in one padded call: the pad rule on ``Delta``, each row's own
state); the one-bucket chunk targets.  The reference
(``reference/jamba.py``: the recurrence position by position, no state
carried) judges 384 positions on logits, teacher-forced.

**Why another limit.**  ``generation_state``'s ``OFF_SHARE_MAX`` 3 % stood
between a sound program's 0 of 384 and 8-bit operands' 25 %.  Here the
stated precision's own rounding moves the logits by 0.06 deviations (rms;
0.012 there): twenty-eight layers of bfloat16 products under a head of
unit-variance logits, whose top two lie within a tenth of a deviation of
each other at one position in ten.  A sound program so reads 1.6 % of its
positions over ``TIE_STDS`` in the CPU's emulation and **1.3 to 3.9 % on
the chip** (5 to 15 of 384 in the builder's first seven runs, mean 10:
two of the seven were past 3 %, 11 of 384, and came out not ``correct``
under ``generation_state`` with nothing wrong in them).  The
readings (``tools/precision_readings.py --config jamba2-3b --positions
634 --judged 128``: the published widths on the CPU, a prompt of 506 and
128 judged tokens, seeds 3000005003 / 3000007019; the chip's own are in
PERF.md section 6, PR 54):

=====================  ================  ================  =========
variant                off (> 0.09) %    worst gap (std)   verdict
=====================  ================  ================  =========
stated (bf16 x bf16)   1.6 / 1.6         0.18 / 0.12       ok
**e4m3 operands**      89.8 / 94.5       2.92 / 3.42       NOT ok
state in bfloat16      0.0 / 0.0         0.06 / 0.07       ok (!)
attention rotated      7.0 / 10.2        0.34 / 0.35       not ok
one K/V head as 20     14.8 / 16.4       0.48 / 0.36       not ok
conv bias left out     90.6              2.90              not ok
inner norms left out   100               7.57              not ok
A_log without -exp     100 (NaN logits)  6.96              not ok
softplus left out      100 (NaN logits)  6.96              not ok
D x left out           100               6.44              not ok
an untied head         100               7.12              not ok
a stale row            99.2              6.61              not ok
=====================  ================  ================  =========

* ``OFF_SHARE_MAX`` 6 %: between the largest the program read over its
  seeds (3.9 % on the chip; 1.6 % on the CPU) and what the nearest
  precision below it reads, 8-bit operands (90 to 95 %), with room on
  both sides: at 384 positions 6 % is 23 of them, four deviations over a
  count whose mean is 10 and deviation 3.2.  The subtlest wrong programs
  — two attention layers of 28 rotated, the one K/V head read as twenty
  — still read over it (7 to 16 %).
* ``WORST_GAP_STDS`` 1.0 (``generation_state``'s): a sound program's worst
  position read 0.18 on the CPU and 0.15 to 0.30 on the chip; 8-bit
  operands read 2.9 to 3.4, a token served from the row before 6.6.

**What no count of served tokens can tell**: the state kept in bfloat16
(it moves the logits by 0.036 deviations, under the stated precision's
own 0.062, and reads *fewer* positions off); the CPU tests hold it on
logits (``tests/test_jamba_paged.py``, ``tests/test_ssm_ops.py``: a
float32 engine against the float32 reference at 1e-4, which a bfloat16
state misses tenfold); PERF.md section 7 says so.
"""

from __future__ import annotations

from harness.kinds import generation_share as _share
from harness.kinds.generation_state import (  # noqa: F401 — the kind's interface
    SAMPLE_NEW,
    TIE_STDS,
    WORST_GAP_STDS,
    compared,
    content,
    counters,
    fields,
    judged_lengths,
    multiset,
    run_wave,
    serve_sample,
    verdict_line,
    warm_up,
)

OFF_SHARE_MAX = 0.06


def judge(ref, params, model: dict, samples: list) -> dict:
    """``generation_share.judge``'s gaps (teacher-forced, each served
    token under the reference's top-1 in deviations of its position's
    logits), held to this kind's limits."""
    v = _share.judge(ref, params, model, samples)
    v.update(off_share_max=OFF_SHARE_MAX, worst_gap_max=WORST_GAP_STDS,
             ok=(v["off"] <= OFF_SHARE_MAX * v["positions"]
                 and v["worst_gap_stds"] <= WORST_GAP_STDS))
    return v
