"""``generation``: a language model behind a token stream on the paged
engine.  A work item is ``(prompt length, new tokens)``; a request's
content is seeded token ids, unique to its index."""

from __future__ import annotations

import threading

import numpy as np

from harness import lengths, warmup
from harness.protocols import RequestFailed
from harness.served import BenchFailure, http_json

SAMPLE_NEW = 8  # tokens asked of each prompt of the reference-checked sample

# Why a tolerance at all: the weights are random, so the gap between the
# two largest of ~50k logits is often a few hundredths of their spread,
# and the program computes in bfloat16 (8 bits of mantissa) through
# dozens of residual layers.  Why this one: a wrong program (a missing
# layer, a shifted position, a stale cache page) serves a token whose
# reference logit lies ~4 deviations under the top; the largest gap of a
# tie broken by rounding in 58 runs on the chip was 0.045 (PERF.md §6,
# PR 23).  Twice that.
TIE_STDS = 0.09

multiset = lengths.multiset


def content(model: dict, seed: int, index: int, item) -> list:
    """Request ``index``'s token ids under ``seed``: unique content, so
    no two prompts of a run share a page-long prefix."""
    rng = np.random.default_rng([int(seed) % (1 << 63), int(index)])
    return rng.integers(0, model["vocab_size"], size=int(item[0])).tolist()


def fields(item) -> dict:
    return {"prompt_len": item[0], "asked": item[1]}


def counters(served):
    """The one paged engine's ``engine_stats()``, or None."""
    try:
        doc = http_json(f"{served.base}/debug/engine")
    except (OSError, ValueError):
        return None
    for nodes in doc.values():
        for stats in nodes.values():
            return stats
    return None


# ---------------------------------------------------------------------------
# waves: requests sent together, outside the window
# ---------------------------------------------------------------------------

def run_wave(served, wave: dict, seed: int, serial: list) -> list:
    """Send one wave and wait for its answers: ``[(prompt, tokens)]``.
    With ``blocker`` a short stream is kept decoding meanwhile, so the
    requests arrive inside a chunk and are admitted, and prefilled as
    one group, at the next wave boundary."""
    model, protocol = served.config["model"], served.protocol
    errors, answers = [], [None] * len(wave["requests"])

    def one(slot, item, index):
        try:
            prompt = content(model, seed, index, item)
            answers[slot] = (prompt, served.request(prompt, item))
        except (RequestFailed, OSError, ValueError) as e:
            errors.append(f"{type(e).__name__}: {e}")

    stop_blocker = threading.Event()
    decoding = threading.Event()
    held = []  # the blocker's open connection, cut when the wave is done

    def blocker():
        item = (wave["blocker_prompt"], 8 * served.config["engine"]["steps_per_call"])
        while not stop_blocker.is_set():
            serial[0] += 1
            try:
                served.request(content(model, seed, serial[0], item), item, decoding.set, held)
            except (RequestFailed, OSError, ValueError) as e:
                if not stop_blocker.is_set():
                    errors.append(f"blocker: {type(e).__name__}: {e}")
                decoding.set()
                return

    block = None
    if wave["blocker"]:
        block = threading.Thread(target=blocker, daemon=True)
        block.start()
        decoding.wait(timeout=600)
    threads = []
    for slot, item in enumerate(wave["requests"]):
        serial[0] += 1
        threads.append(threading.Thread(target=one, args=(slot, item, serial[0]), daemon=True))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    stop_blocker.set()
    for conn in held:
        protocol.abort(conn)
    if block is not None:
        block.join(timeout=900)
    if errors or any(t.is_alive() for t in threads):
        raise BenchFailure(f"wave for {wave['for']} failed: {errors[:3]}")
    return answers


def serve_sample(served, work: list, seed: int) -> list:
    """The seeded sample, three prompts of the cell's own lengths (so
    it meets only programs the cell needs anyway): the shortest alone (a
    group of one), then the median and the longest sent together behind
    a blocker, with a second prompt of the longest length beside them so
    that the longest is prefilled in a batched admission.  That
    companion is served and not judged: the reference's forward pass
    over a long prompt is what the sample costs."""
    prompts = sorted(p for p, _a in work)
    serial = [1 << 41]  # the sample's content never collides with a window request's
    out = []
    for blocker, lens, judged in ((False, prompts[:1], 1),
                                  (True, [prompts[len(prompts) // 2]] + 2 * prompts[-1:], 2)):
        wave = {"blocker": blocker, "blocker_prompt": prompts[0], "for": "the checked sample",
                "requests": [(n, SAMPLE_NEW) for n in lens]}
        answers = run_wave(served, wave, seed, serial)[:judged]
        out += [{"prompt": p, "tokens": t} for p, t in answers]
    return out


def warm_up(served, server, work: list, seed: int) -> dict:
    """Warm the programs the cell can reach; retry a wave whose requests
    did not land together; report what was met."""
    engine, traffic = served.config["engine"], served.traffic
    targets = warmup.reachable(engine, work, traffic["clients"], traffic["warm_group_max"])
    serial = [1 << 40]  # warm-up content never collides with a window request's
    met = warmup.warmed(server.log_text())  # what the checked sample met already
    missing = {k: targets[k] - met[k] for k in targets}
    rounds = 0
    while (missing["prefill"] or missing["chunk"]) and rounds < 3:
        rounds += 1
        for wave in warmup.waves(engine, missing, work):
            wave["blocker_prompt"] = min(p for p, _a in work)
            run_wave(served, wave, seed, serial)
        met = warmup.warmed(server.log_text())
        missing = {k: targets[k] - met[k] for k in targets}
    return {"targets": {k: len(v) for k, v in targets.items()},
            "met": {k: len(v) for k, v in met.items()}, "rounds": rounds,
            "missing": {k: sorted(map(str, v)) for k, v in missing.items() if v}}


# ---------------------------------------------------------------------------
# the comparison that decides ``correct`` (runs in the reference's process)
# ---------------------------------------------------------------------------

def judge(ref, params, model: dict, samples: list) -> dict:
    """The served token at each position must be the reference's top-1
    given the same prefix (the served tokens are fed back, so one early
    difference does not condemn the rest), or a near-tie: the
    reference's own logit for the served token lies within ``TIE_STDS``
    standard deviations (of that position's logits over the vocabulary)
    of its top-1."""
    positions, worst = [], 0.0
    for s in samples:
        prompt, answer = s["prompt"], s["tokens"]
        scores = np.asarray(ref.logits(params, model, prompt + answer[:-1], tail=len(answer)))
        for j, tok in enumerate(answer):
            row = scores[j]
            gap = float(row.max() - row[tok]) / float(row.std())
            worst = max(worst, gap)
            positions.append({"prompt_len": len(prompt), "top1": int(row.argmax()),
                              "served": int(tok), "gap_stds": gap, "ok": gap <= TIE_STDS})
    return {"ok": all(p["ok"] for p in positions), "positions": len(positions),
            "exact": sum(p["top1"] == p["served"] for p in positions),
            "prompt_lens": [len(s["prompt"]) for s in samples],
            "worst_gap_stds": worst, "tie_stds": TIE_STDS,
            "failed": [p for p in positions if not p["ok"]][:8]}


def verdict_line(v: dict) -> str:
    return (f"{v['exact']}/{v['positions']} served tokens (prompts of {v['prompt_lens']}) are "
            f"the reference's top-1; worst gap {v['worst_gap_stds']:.4f} standard deviations "
            f"against a tolerance of {v['tie_stds']}; ok={v['ok']}")


def compared(v: dict) -> dict:
    """``{name: [number, limit]}``: each number a verdict of this kind or
    of one that builds on it compared, beside its limit (the worst gap
    against ``worst_gap_max``, or against ``tie_stds`` where every
    position must be a near-tie; the shares against their ``_max``)."""
    out = {"worst_gap_stds": [v["worst_gap_stds"], v.get("worst_gap_max", v["tie_stds"])]}
    for share in ("off_share", "near_share"):
        if share in v:
            out[share] = [v[share], v[share + "_max"]]
    return out
