#!/usr/bin/env python3
"""Compare a served sample with a configuration's plain reference.

A CPU-pinned process of its own (``JAX_PLATFORMS=cpu`` set by the
caller), started while the server loads: it builds the reference's
weights for the seed (``reference/<config.reference>.py``:
``make_params(model, seed)``), waits for ``--served`` (the sample as the
configuration's kind served it) and has the kind judge it
(``harness/kinds/<config.kind>.py``: ``judge(ref, params, model,
samples)``; the tolerance and its reason are there).  The verdict's
``ok`` decides ``correct``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))  # the program: a reference may take its initialiser

from harness import manifest  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--served", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--wait-s", type=float, default=900.0)
    args = ap.parse_args()
    config = manifest.load_json(args.config)
    ref = manifest.module("reference", config["reference"])
    kind = manifest.module("harness/kinds", config["kind"])

    t0 = time.monotonic()
    params = ref.make_params(config["model"], args.seed)
    t_params = time.monotonic() - t0
    deadline = time.monotonic() + args.wait_s
    while not os.path.exists(args.served):
        if time.monotonic() > deadline:
            sys.stderr.write("[reference] no served sample arrived\n")
            return 1
        time.sleep(0.05)
    samples = manifest.load_json(args.served)
    t0 = time.monotonic()
    out = kind.judge(ref, params, config["model"], samples)
    out.update(params_s=t_params, forward_s=time.monotonic() - t0)
    with open(args.out + ".tmp", "w") as f:
        json.dump(out, f)
    os.replace(args.out + ".tmp", args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
