"""``generation_share_whole``: ``generation_share_long`` for a replica that
holds **whole expert layers** behind a renormalised sigmoid router — its
warm-up (the prefill cap known, streams grown to the widths only growth
reaches), a checked sample of **one** judged prompt, the cell's longest,
answered with 256 tokens, and ``generation_share``'s rule under two
limits of its own.

**Why another sample.**  ``generation_share.serve_sample`` judges the
cell's shortest, median and longest prompt with 128 tokens each: in
``doc-answer-saturated`` 2,049 / ~3,070 / 4,096 tokens, 9.6 k positions
of a float32 forward pass over 3.97 G parameters on the CPU beside the
server, where a traced run has 360 s for everything
(``generation_share_sparse`` and ``generation_share_window`` met the same
wall).  The longest prompt alone is the ``b4096_k1`` prefill — the call
at the engine's cap, 235 MB of rows a mixed sub-layer — and 256 decode
steps at the widest table, through the cache, against the reference's
full forward pass on logits; ``reference/xing4.py logits(tail=)`` returns
the judged rows alone.  The ``b3072_k1`` program and the chunk of two
length buckets are met by the warm-up, unjudged; the CPU tests hold all
three programs on logits (``tests/test_xing4_paged.py``).

**Why other limits.**  ``generation_share`` holds a served token to the
float32 reference's top-1, or within ``TIE_STDS`` = 0.09 deviations of
it, at all but 3 % of the positions.  That was set where a replica holds
8 of 256 experts: a routing decision that bfloat16 operands take the
other way than float32 (13.5 % of them do, at any width) moves the
logits only where the expert is one of the few held here.  A replica
that holds all 64 sees every one of them, and Xing4.0's router
renormalises four sigmoid scores and doubles them: a chosen expert
carries half of the routed sum.  With seeded weights two experts at a
near-tie are unrelated functions (a trained router's are alike), so a
sound bfloat16 program reads over 0.09 at a tenth of its positions.  The
readings (``tools/precision_readings.py --config xing4.0-29b-a4b
--positions 4352 --judged 256``, the CPU, two seeds; the configuration's
``assumed.judgement`` has the table):

* ``OFF_SHARE_MAX`` 18 %: the stated precision reads 10.5 and 10.2 %
  of 256 positions off; the reference with the operands of every weight
  matmul rounded to 8 bits (e4m3) 50.8 and 55.1 %, the Sinkhorn stopped
  at one iteration 32.0 and 28.9 %, the input-dependent term dropped
  77.0 and 70.7 %, H_post without its 2 91.4 and 90.6 %.  18 % stands
  between the largest sound reading and the smallest of those (10.5 and
  28.9: their geometric mean is 17.4); at 256 positions that is 46 of
  them, four standard deviations of the count from either.
* ``FAR_SHARE_MAX`` of positions over ``FAR_STDS`` = 2.0 deviations, in
  place of ``generation_share``'s limit on the single worst gap: a sound
  program's worst position read 2.51 deviations (one flipped decision
  of a layer whose chosen experts weigh a half each), so no limit on one
  position can stand between that and a token served from the row
  before (3.8 deviations under the top in the median, 19 in 20 over
  2.0).  Counted, the two are far apart: a sound program has 0 or 1 of 256
  positions over 2.0 (8-bit operands 6 and 1), one decode chunk (8 steps) of stale rows 7 or 8, a
  stale page 60: the limit is 2 %, 5 of 256.  It is for a program wrong
  at a few positions only; 8-bit operands pass it on one seed of two.
"""

from __future__ import annotations

from harness.kinds.generation_share_long import (  # noqa: F401 — the kind's interface
    TIE_STDS,
    content,
    counters,
    fields,
    multiset,
    run_wave,
    warm_up,
)

SAMPLE_NEW = 256  # tokens asked of the one prompt: 256 judged positions, four page edges
OFF_SHARE_MAX = 0.18
FAR_STDS = 2.0
FAR_SHARE_MAX = 0.02


def serve_sample(served, work: list, seed: int) -> list:
    """The cell's longest prompt alone, answered with ``SAMPLE_NEW``
    tokens (or as many as ``max_total`` leaves it)."""
    n = max(p for p, _a in work)
    serial = [1 << 41]  # the sample's content never collides with a window request's
    wave = {"blocker": False, "for": "the checked sample",
            "requests": [(n, min(SAMPLE_NEW, served.traffic["max_total"] - n))]}
    prompt, tokens = run_wave(served, wave, seed, serial)[0]
    return [{"prompt": prompt, "tokens": tokens}]


def verdict(gaps) -> dict:
    """The judgement of per-position gaps (deviations under the
    reference's top-1): the two shares beside their limits."""
    gaps = [float(g) for g in gaps]
    off = sum(g > TIE_STDS for g in gaps)
    far = sum(g > FAR_STDS for g in gaps)
    return {"ok": off <= OFF_SHARE_MAX * len(gaps) and far <= FAR_SHARE_MAX * len(gaps),
            "off": off, "off_share": off / len(gaps), "off_share_max": OFF_SHARE_MAX,
            "far": far, "far_share": far / len(gaps), "far_share_max": FAR_SHARE_MAX,
            "far_stds": FAR_STDS, "worst_gap_stds": max(gaps)}


def judge(ref, params, model: dict, samples: list) -> dict:
    """Teacher-forced as ``generation_share.judge``: each served token's
    gap under the reference's top-1 given the same prefix, in deviations
    of that position's logits; held to this kind's two limits."""
    import numpy as np

    gaps, exact = [], 0
    for s in samples:
        prompt, answer = s["prompt"], s["tokens"]
        rows = np.asarray(ref.logits(params, model, prompt + answer[:-1], tail=len(answer)))
        for row, tok in zip(rows, answer):
            gaps.append(float(row.max() - row[tok]) / float(row.std()))
            exact += int(row.argmax()) == int(tok)
    return {"positions": len(gaps), "exact": exact, "tie_stds": TIE_STDS,
            "prompt_lens": [len(s["prompt"]) for s in samples], **verdict(gaps)}


def verdict_line(v: dict) -> str:
    return (f"{v['exact']}/{v['positions']} served tokens (a prompt of {v['prompt_lens']}) are "
            f"the reference's top-1; {v['off']} lie over {v['tie_stds']} standard deviations "
            f"under it ({100 * v['off_share']:.2f} % against {100 * v['off_share_max']:g} %), "
            f"{v['far']} over {v['far_stds']} ({100 * v['far_share']:.2f} % against "
            f"{100 * v['far_share_max']:g} %), the worst {v['worst_gap_stds']:.4f}; ok={v['ok']}")


def compared(v: dict) -> dict:
    """``{name: [number, limit]}``: the two shares ``correct`` compared."""
    return {"off_share": [v["off_share"], v["off_share_max"]],
            "far_share": [v["far_share"], v["far_share_max"]]}
