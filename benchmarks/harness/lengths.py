"""The work a cell offers: a fixed multiset of request sizes from the
traffic file, and a seeded order.

Request *i* of *N* takes the (i + 1/2)/N quantile of the stated
distribution, so the multiset of (prompt, answer) lengths is the same
for every seed.  ``--seed`` decides only which client sends which
request in which order (and, elsewhere, the token ids and weights):
two seeds offer the same work in a different order.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist


def quantile(dist: dict, q: float) -> int:
    """The q-quantile of one length distribution of a traffic file."""
    kind = dist["dist"]
    if kind == "fixed":
        return int(dist["value"])
    lo, hi = int(dist["min"]), int(dist["max"])
    if kind == "uniform":
        value = lo + q * (hi - lo)
    elif kind == "lognormal":
        value = float(dist["median"]) * math.exp(
            float(dist["sigma"]) * NormalDist().inv_cdf(q))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return max(lo, min(hi, int(round(value))))


def multiset(traffic: dict) -> list:
    """N (prompt_len, new_tokens) pairs, the same for every seed.

    Prompt and answer quantiles are paired through a fixed permutation
    (seeded by the traffic file's own ``pairing_seed``, not ``--seed``)
    so long prompts do not always carry long answers.  A pair that
    would pass ``max_total`` has its answer cut to fit."""
    n = int(traffic["requests"])
    prompts = [quantile(traffic["prompt_tokens"], (i + 0.5) / n) for i in range(n)]
    answers = [quantile(traffic["new_tokens"], (i + 0.5) / n) for i in range(n)]
    random.Random(int(traffic.get("pairing_seed", 0))).shuffle(answers)
    cap = int(traffic["max_total"])
    return [(p, max(1, min(a, cap - p))) for p, a in zip(prompts, answers)]


def schedule(work: list, seed: int) -> list:
    """The multiset ``work`` in this seed's order.  Client ``c`` of ``C``
    sends requests c, c + C, c + 2C, ... of the list, wrapping round."""
    work = list(work)
    random.Random(seed).shuffle(work)
    return work
