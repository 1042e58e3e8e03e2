"""``graph``: a ``jaxserver`` model behind the dynamic batcher.  A work
item is ``(rows,)``; a request's content is that many seeded feature
rows.  The server warms its own programs at load, so warm-up is empty."""

from __future__ import annotations

import numpy as np

from harness import lengths
from harness.served import http_json

SAMPLES = 4
ATOL = 1e-4  # float32 on both sides; the orders of summation differ


def multiset(traffic: dict) -> list:
    n = int(traffic["requests"])
    return [(lengths.quantile(traffic["rows"], (i + 0.5) / n),) for i in range(n)]


def content(model: dict, seed: int, index: int, item) -> list:
    rng = np.random.default_rng([int(seed) % (1 << 63), int(index)])
    return rng.uniform(-1.0, 1.0, size=(int(item[0]), model["features"])).tolist()


def fields(item) -> dict:
    return {"rows": item[0]}


def counters(served):
    """The batcher block of ``/health/status``, or None."""
    try:
        status = http_json(f"{served.base}/health/status")
    except (OSError, ValueError):
        return None
    for nodes in status["predictors"].values():
        for node in nodes.values():
            return node.get("batcher")
    return None


def serve_sample(served, work: list, seed: int) -> list:
    out = []
    for j in range(SAMPLES):
        item = work[j % len(work)]
        rows = content(served.config["model"], seed, (1 << 41) + j, item)
        out.append({"rows": rows, "scores": served.request(rows, item)})
    return out


def warm_up(served, server, work: list, seed: int) -> dict:
    return {"missing": {}}


def judge(ref, params, model: dict, samples: list) -> dict:
    worst = 0.0
    for s in samples:
        want = np.asarray(ref.scores(params, model, s["rows"]))
        worst = max(worst, float(np.abs(want - np.asarray(s["scores"])).max()))
    return {"ok": worst <= ATOL, "rows": sum(len(s["rows"]) for s in samples),
            "worst_abs": worst, "atol": ATOL}


def verdict_line(v: dict) -> str:
    return (f"{v['rows']} served score rows lie within {v['worst_abs']:.2e} of the "
            f"reference's against a tolerance of {v['atol']}; ok={v['ok']}")


def compared(v: dict) -> dict:
    return {"worst_abs": [v["worst_abs"], v["atol"]]}
