"""``generation_share_window``: ``generation_share_long`` for a cell whose
prompts run to thousands of tokens over layers that attend a WINDOW — its
warm-up (streams grown to the table widths only growth reaches, a prefill
group the engine's cap never forms left out), ``generation_share``'s two
limits unchanged, and a checked sample of **one** judged prompt that has
passed the window: the shortest prompt of the cell's multiset longer than
``PAST_WINDOW`` tokens, served alone and answered with 256 tokens.

**Why another sample.**  ``generation_share.serve_sample`` judges the
cell's shortest, median and longest prompt with 128 tokens each.  In
``mixed-window-saturated`` those are 1,025 / ~4,100 / 8,192 tokens: 13.7 k
positions of a float32 forward pass over 1.58 G parameters on the CPU
beside the server, where a traced warm run has 360 s for everything
(``generation_share_sparse`` met the same wall at 9.6 k).  And two of the
three would be judged where the window layers have not slid, or barely.
Here every judged row's window has slid by 256 positions or more
(``PAST_WINDOW`` = 4,096 + 256), so the window layers read through a table
whose first pages went back to the allocator, while the full layers —
which carry no positions at all — see every one of ~4,600 rows: prefill,
then decode through both pools, against the reference's full forward
pass, on logits.  The reference computes its last layer's queries at the
judged rows alone (``reference/smallthinker.py logits(tail=)``).  The
batched admission and the chunk of two length buckets are met by the
warm-up, unjudged; the CPU tests hold both on logits
(``tests/test_smallthinker_paged.py``).

What the two limits tell from the stated precision, and what they cannot,
is in the configuration's ``assumed.judgement`` with its readings
(``tools/precision_readings.py --config smallthinker-21b-a3b``).
"""

from __future__ import annotations

from harness.kinds.generation_share_long import (  # noqa: F401 — the kind's interface
    OFF_SHARE_MAX,
    TIE_STDS,
    WORST_GAP_STDS,
    compared,
    content,
    counters,
    fields,
    judge,
    multiset,
    run_wave,
    verdict_line,
    warm_up,
)

SAMPLE_NEW = 256  # tokens asked of the one prompt: 256 judged positions, four page edges
PAST_WINDOW = 4096 + 256  # the judged prompt is longer: every judged row's window has slid


def sample_prompt(work: list) -> int:
    """The shortest prompt of the multiset longer than ``PAST_WINDOW``
    (the longest where none is: a toy cell's)."""
    prompts = sorted(p for p, _a in work)
    return next((p for p in prompts if p > PAST_WINDOW), prompts[-1])


def serve_sample(served, work: list, seed: int) -> list:
    """That prompt alone, answered with ``SAMPLE_NEW`` tokens."""
    n = sample_prompt(work)
    serial = [1 << 41]  # the sample's content never collides with a window request's
    wave = {"blocker": False, "for": "the checked sample",
            "requests": [(n, min(SAMPLE_NEW, served.traffic["max_total"] - n))]}
    prompt, tokens = run_wave(served, wave, seed, serial)[0]
    return [{"prompt": prompt, "tokens": tokens}]
