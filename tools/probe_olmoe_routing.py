#!/usr/bin/env python3
"""How often does bfloat16 serving route a token as float32 would?

Routing is discrete: a near-tie at the 8th/9th expert can fall
differently in the served precision (bf16 residual stream and matmuls,
f32 router) and in the float32 reference.  For each seed this makes the
served weights (``models/spec.py init_params``), runs the paged
LM's whole-prefill forward on one prompt as the engine's programs do
(bf16, eagerly, so the router's choices can be read), runs
``benchmarks/reference/olmoe.py`` on the same tree at ``highest``
precision, and prints one JSON line: the share of (token, layer) expert
SETS that are equal, the share of positions whose served top-1 token is
the reference's, and the worst gap of a served top-1 under the
reference's, in standard deviations of that position's logits (the
benchmark's ``correct`` allows 0.09).

On the chip at the published widths (8 layers):
``python tools/probe_olmoe_routing.py --seeds 5``; ``--rehearse`` is a
toy size on the CPU for control flow.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=5)
    ap.add_argument("--tokens", type=int, default=256)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--bf16-residual", action="store_true",
                    help="the residual stream in bf16, as GPT-2's is: what "
                         "the spec's residual_f32 is compared with")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from reference import olmoe as ref
    from seldon_core_tpu.models.paged import get_paged_lm_class
    from seldon_core_tpu.models.spec import init_params
    from seldon_core_tpu.ops import moe

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(json.dumps({"error": f"no TPU here ({dev.platform}); --rehearse for a toy run"}))
        return 1
    if dev.platform == "tpu":
        with open(os.path.join(ROOT, "benchmarks", "configs", "olmoe-1b-7b.json")) as f:
            model = json.load(f)["model"]
        n = args.tokens
    else:
        model = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                     num_experts=8, num_experts_per_tok=2, intermediate_size=32,
                     rms_norm_eps=1e-5, rope_theta=10000, vocab_size=97)
        n = 24
    spec, sizes = ref.spec_and_config(model)
    if args.bf16_residual:
        from dataclasses import replace

        spec = replace(spec, residual_f32=False)
    lm = get_paged_lm_class()(dtype=jnp.bfloat16, max_len=1024, spec=spec, **sizes)
    page, pages = 64, -(-n // 64) + 1
    pool = jnp.zeros((sizes["num_layers"], pages, page, sizes["d_model"]), jnp.bfloat16)
    table = jnp.arange(1, pages, dtype=jnp.int32)[None]

    served_routing = []
    plain_route = moe.route

    def recording_route(h, w, k):
        gates, experts = plain_route(h, w, k)
        served_routing.append(np.asarray(experts))
        return gates, experts

    first = 3000000700
    for seed in range(first, first + args.seeds):
        params = init_params(spec, sizes, seed)  # traces the block: before the recorder
        tokens = np.random.default_rng(seed).integers(0, model["vocab_size"], size=n)
        served_routing.clear()
        moe.route = recording_route
        try:
            out = lm.apply({"params": params}, jnp.asarray(tokens)[None], jnp.arange(n)[None],
                           pool, pool, table, jnp.zeros((1,), jnp.int32))
        finally:
            moe.route = plain_route
        served = np.asarray(out[0][0])
        ref_routing = []
        want = np.asarray(ref.logits(params, model, tokens, routing=ref_routing))
        equal = [set(a) == set(b) for sr, rr in zip(served_routing, ref_routing)
                 for a, b in zip(sr.tolist(), rr.tolist())]
        top = served.argmax(-1)
        gaps = (want.max(-1) - want[np.arange(n), top]) / want.std(-1)
        print(json.dumps({
            "seed": seed, "device": dev.device_kind, "tokens": n,
            "residual": "bf16" if args.bf16_residual else "f32",
            "layers": sizes["num_layers"],
            "routing_sets_equal_share": float(np.mean(equal)),
            "routing_sets": len(equal),
            "top1_equal_share": float(np.mean(top == want.argmax(-1))),
            "worst_gap_stds": float(gaps.max()),
            "max_abs_logit_diff_over_std": float(np.abs(served - want).max() / want.std()),
        }), flush=True)
        del params
    moe.route = plain_route
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
