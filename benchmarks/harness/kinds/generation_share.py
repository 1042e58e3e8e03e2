"""``generation_share``: the ``generation`` kind with its rule held over
a sample sixteen times as long and at all but a stated share of the
positions, for a model whose output is not continuous in its rounding.

Everything but the sample's length and the judgement is
``generation``'s; the reference is as plain.  **Why another
judgement.**  ``generation.judge`` asks that *every* one of 24 served
tokens be the float32 reference's top-1 or within ``TIE_STDS`` of it.
That holds a bfloat16 program whose logits move by hundredths of a
deviation under rounding.  A DeepSeek-V3 router is a step function of
its input: it keeps 8 of 256 experts by ``sigmoid + bias`` after keeping
4 of 8 groups, a token's 8th and 9th candidates are often thousandths
apart, and a chosen expert enters with ~2.5 / 8 of weight.  At the
published widths bfloat16 matmul operands (the stated precision) take
13.5 % of routing decisions the other way than float32 does; where the
expert is one this replica holds, the logits jump by 0.1 to 0.4
deviations.  So a sound program reads over ``TIE_STDS`` at 0.3 to 0.8 %
of its positions (PERF.md section 6, PR 30: on the chip 1 of 216 and
0 to 3 of 384 in each of 17 runs; 2 and 3 of 512 in an emulation of
the stated precision on the CPU, ``tools/precision_readings.py``; the
largest gap 0.91), and ``generation``'s rule fails one sound run in
ten.

**The judgement.**  A position is *off* when its served token is
neither the reference's top-1 nor within ``TIE_STDS`` of it.  The sample
is correct when at most ``OFF_SHARE_MAX`` of its positions are off and
none is off by more than ``WORST_GAP_STDS``.  Both limits stand between
two readings (PERF.md section 6, PR 30):

* ``OFF_SHARE_MAX`` 3 %: sound programs read 0.3 to 0.8 % (above).  The
  reference with the operands of every weight matmul rounded to 8 bits
  (e4m3, the precision below bfloat16 that this family is deployed in)
  reads 21.3 and 25.2 % off on two seeds; the wrong program nearest to
  a sound one, the correction bias left out of the selection, 17.2 and
  20.1 %; no shared expert 93.9 and 95.7 %; a row one position early
  99.6 %.  At 384 positions 3 % is 11 of them: a sound program at 0.8 %
  brings 3 and passes 11 in all but one run in 10,000, the bias left
  out brings 66 or more.
* ``WORST_GAP_STDS`` 2.0: the largest gap a sound program read is 0.91
  (one of 6,500 positions served on the chip; the next 0.59); a token
  served from the wrong row lies 3.8 deviations under the top in the
  median, and 19 in 20 of them over 2.0.  This limit is for a program
  wrong at a few positions only (a stale page); the 8-bit reference
  (0.80, 0.83) and the bias left out (0.94, 1.23) pass it.

What neither limit can tell from the stated precision is the step
nearest below it, a bfloat16 residual stream: its logits lie 1.23 to
1.29 times as far from float32's (0.0161 and 0.0179 deviations against
0.0125 and 0.0145 over 512 positions), which moves 20 and 24 of 512
served tokens off the top-1 against 24 and 20 and puts the very same 2
and 3 off, and differs less than seeds and context lengths do (the
stated precision reads 0.019 over a sequence's first 256 positions and
0.011 over its fourth).  No count of served tokens separates the two,
and no limit on the logits themselves would (PERF.md section 7).
"""

from __future__ import annotations

import numpy as np

from harness.kinds.generation import (  # noqa: F401 — the kind's interface
    TIE_STDS,
    compared,
    content,
    counters,
    fields,
    multiset,
    run_wave,
    warm_up,
)

SAMPLE_NEW = 128  # tokens asked of each prompt: 384 judged positions, two page edges each
OFF_SHARE_MAX = 0.03
WORST_GAP_STDS = 2.0


def serve_sample(served, work: list, seed: int) -> list:
    """``generation.serve_sample``'s three prompts (the shortest alone,
    then the median and the longest beside a companion behind a
    blocker), each answered with ``SAMPLE_NEW`` tokens, or as many as
    the traffic's ``max_total`` leaves it."""
    prompts = sorted(p for p, _a in work)
    most = served.traffic["max_total"]
    serial = [1 << 41]  # the sample's content never collides with a window request's
    out = []
    for blocker, lens, judged in ((False, prompts[:1], 1),
                                  (True, [prompts[len(prompts) // 2]] + 2 * prompts[-1:], 2)):
        wave = {"blocker": blocker, "blocker_prompt": prompts[0], "for": "the checked sample",
                "requests": [(n, min(SAMPLE_NEW, most - n)) for n in lens]}
        answers = run_wave(served, wave, seed, serial)[:judged]
        out += [{"prompt": p, "tokens": t} for p, t in answers]
    return out


def judge(ref, params, model: dict, samples: list) -> dict:
    """Teacher-forced as ``generation.judge``: each served token's gap
    under the reference's top-1 given the same prefix, in deviations of
    that position's logits."""
    positions = []
    for s in samples:
        prompt, answer = s["prompt"], s["tokens"]
        rows = np.asarray(ref.logits(params, model, prompt + answer[:-1], tail=len(answer)))
        for j, tok in enumerate(answer):
            row = rows[j]
            gap = float(row.max() - row[tok]) / float(row.std())
            positions.append({"prompt_len": len(prompt), "at": j, "top1": int(row.argmax()),
                              "served": int(tok), "gap_stds": gap, "ok": gap <= TIE_STDS})
    off = sorted((p for p in positions if not p["ok"]), key=lambda p: -p["gap_stds"])
    worst = max(p["gap_stds"] for p in positions)
    return {"ok": len(off) <= OFF_SHARE_MAX * len(positions) and worst <= WORST_GAP_STDS,
            "positions": len(positions),
            "exact": sum(p["top1"] == p["served"] for p in positions),
            "prompt_lens": [len(s["prompt"]) for s in samples],
            "worst_gap_stds": worst, "worst_gap_max": WORST_GAP_STDS, "tie_stds": TIE_STDS,
            "off": len(off), "off_share": len(off) / len(positions),
            "off_share_max": OFF_SHARE_MAX, "failed": off[:8]}


def verdict_line(v: dict) -> str:
    return (f"{v['exact']}/{v['positions']} served tokens (prompts of {v['prompt_lens']}) are "
            f"the reference's top-1; {v['off']} lie over {v['tie_stds']} standard deviations "
            f"under it ({100 * v['off_share']:.2f} % against {100 * v['off_share_max']:g} %), "
            f"the worst {v['worst_gap_stds']:.4f} against {v['worst_gap_max']}; ok={v['ok']}")
