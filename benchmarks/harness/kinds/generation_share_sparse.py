"""``generation_share_sparse``: ``generation_share_long`` for a cell whose
prompts are thousands of tokens long and whose full layers attend over
rows a learned indexer picks — its warm-up (streams grown to the table
widths only growth reaches, a prefill group the engine's cap never forms
left out), a checked sample of **one** judged prompt, the cell's
**median**, served alone and answered with 256 tokens, and
``generation_share``'s two limits beside a third that sees the selection.

**Why another sample.**  ``generation_share.serve_sample`` judges the
cell's shortest, median and longest prompt.  In ``long-doc-saturated``
those are 2,049 / 3,078 / 4,096 tokens: 9.6 k positions of a float32
forward pass over 2.2 G parameters on the CPU beside the server, where a
traced warm run has 360 s for everything and the reference's pass lies on
the way to the window (set-up = 108 s + the pass: my chip runs, PR 38).
Two of them took the reference 331 s and the run 635 s.  The shortest
alone (2,049 + 128: 79-85 s) was this kind's first sample, and judged
where the selection hardly bites: of 2,049-2,176 candidates it drops
under a tenth, and a program that read every row passed (0.0 / 0.39 % off:
PERF.md section 7, PR 38).  The median drops a third of its rows at every
judged position (2,048 of 3,078-3,333), and the reference computes the
last layer's queries for the judged rows only (``reference/dots3_note.py
logits(tail=)``).  Every judged position holds over 2,048 candidates and a
window that has slid; the batched admission and the chunk of two length
buckets are met by the warm-up, unjudged (the CPU tests hold both on
logits: tests/test_dots3_paged.py).

**Why a third limit.**  ``OFF_SHARE_MAX`` and ``WORST_GAP_STDS`` were set
for a router that takes 13.5 % of its decisions the other way under
rounding (``generation_share``).  This model's served tokens lie far
closer to the reference: on the chip the worst of 896 judged positions
read 0.012 deviations under the top-1 (seven runs at the first sample,
PR 38; 0.065 of 1,792 at this one).  A program
that leaves the selection out and reads every row moves the logits by
0.036 deviations at 3,072-4,224 rows, five times what the stated
precision does (0.007), yet only 0.8-1.0 % of its tokens fall over
``TIE_STDS`` (0.09): it passes both limits at every context the cell has
(``tools/precision_readings.py --config dots3-note-prev --judged 256``,
CPU; PERF.md section 6, PR 38).  So a position is also counted *near off*
when its served token lies over ``NEAR_STDS`` under the top-1, and the
sample is correct only if at most ``NEAR_SHARE_MAX`` of its positions are.
The readings both limits stand between are in PERF.md section 6 (PR 38)
and beside the constants below.

**What no limit on served tokens can tell**: a window one position short
or wide moves the logits by 0.0027 deviations, under half of what the
stated precision does; the CPU tests hold it on logits.
"""

from __future__ import annotations

import numpy as np

from harness.kinds.generation_share_long import (  # noqa: F401 — the kind's interface
    OFF_SHARE_MAX,
    TIE_STDS,
    WORST_GAP_STDS,
    compared,
    content,
    counters,
    fields,
    multiset,
    run_wave,
    warm_up,
)

SAMPLE_NEW = 256  # tokens asked of the one prompt: 256 judged positions, four page edges
# A served token over NEAR_STDS under the reference's top-1 is *near off*;
# at most NEAR_SHARE_MAX of the positions may be: 5 of 256.  Between two
# readings at this sample's size (3,078 + 256; PERF.md section 6, PR 38):
# the stated precision reads 0 and 1 of 256 in its emulation on the CPU
# (seeds 3800000503 / ...507; worst gap 0.018 / 0.020) and the program on
# the chip 0, 1, 0, 0, 0, 0, 1 of 256 in seven runs (worst gap 0.065; 0 of
# 896 at the first sample); the reference with the selection left out reads
# 11 and 9 of 256 (4.3 / 3.5 %; it passes OFF_SHARE_MAX at 0.39 % and
# WORST_GAP_STDS at 0.10 / 0.12), with 8-bit operands 50 and 43 (and 12.1 /
# 9.4 % over TIE_STDS: the nearest precision below fails two limits of the
# three and passes the third, 0.28 against 2.0).
NEAR_STDS = 0.02
NEAR_SHARE_MAX = 0.02


def serve_sample(served, work: list, seed: int) -> list:
    """The median prompt alone, answered with ``SAMPLE_NEW`` tokens."""
    prompts = sorted(p for p, _a in work)
    median = prompts[len(prompts) // 2]
    serial = [1 << 41]  # the sample's content never collides with a window request's
    wave = {"blocker": False, "for": "the checked sample",
            "requests": [(median, min(SAMPLE_NEW, served.traffic["max_total"] - median))]}
    prompt, tokens = run_wave(served, wave, seed, serial)[0]
    return [{"prompt": prompt, "tokens": tokens}]


def verdict(gaps) -> dict:
    """The three limits over the judged positions' gaps (each served
    token's distance under the reference's top-1, in deviations of its
    position's logits)."""
    gaps = np.asarray(gaps, np.float64)
    n, off, near = len(gaps), int((gaps > TIE_STDS).sum()), int((gaps > NEAR_STDS).sum())
    worst = float(gaps.max())
    return {"ok": (off <= OFF_SHARE_MAX * n and worst <= WORST_GAP_STDS
                   and near <= NEAR_SHARE_MAX * n),
            "positions": n, "exact": int((gaps <= 0).sum()),
            "worst_gap_stds": worst, "worst_gap_max": WORST_GAP_STDS, "tie_stds": TIE_STDS,
            "off": off, "off_share": off / n, "off_share_max": OFF_SHARE_MAX,
            "near_stds": NEAR_STDS, "near": near, "near_share": near / n,
            "near_share_max": NEAR_SHARE_MAX}


def judge(ref, params, model: dict, samples: list) -> dict:
    """Teacher-forced as ``generation_share.judge``, the reference's last
    layer queried at the judged rows alone."""
    gaps, failed = [], []
    for s in samples:
        prompt, answer = s["prompt"], s["tokens"]
        rows = np.asarray(ref.logits(params, model, prompt + answer[:-1], tail=len(answer)))
        for j, tok in enumerate(answer):
            row = rows[j]
            gap = float(row.max() - row[tok]) / float(row.std())
            gaps.append(gap)
            if gap > NEAR_STDS:
                failed.append({"prompt_len": len(prompt), "at": j, "top1": int(row.argmax()),
                               "served": int(tok), "gap_stds": gap})
    return dict(verdict(gaps), prompt_lens=[len(s["prompt"]) for s in samples],
                failed=sorted(failed, key=lambda p: -p["gap_stds"])[:8])


def verdict_line(v: dict) -> str:
    return (f"{v['exact']}/{v['positions']} served tokens (prompts of {v['prompt_lens']}) are "
            f"the reference's top-1; {v['off']} lie over {v['tie_stds']} standard deviations "
            f"under it ({100 * v['off_share']:.2f} % against {100 * v['off_share_max']:g} %), "
            f"{v['near']} over {v['near_stds']} ({100 * v['near_share']:.2f} % against "
            f"{100 * v['near_share_max']:g} %), the worst {v['worst_gap_stds']:.4f} against "
            f"{v['worst_gap_max']}; ok={v['ok']}")
