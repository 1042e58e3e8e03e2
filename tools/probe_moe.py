#!/usr/bin/env python3
"""Time the routed expert layer (ops/moe.py) alone, at OLMoE's widths.

On the chip: ``python tools/probe_moe.py`` prints one JSON line per row
count (32 = a decode step of 32 lanes, up to 4096 = the largest prefill
group of the chat cells) with the layer's milliseconds, the experts its
random routing hit, the expert-weight bytes those need and the share of
the HBM roofline that is (decode is bound by streaming the experts hit),
and the FLOPs of the routed rows over the bf16 peak (prefill).  Off the
chip ``--rehearse`` runs a toy size for control flow only and prints no
rate.  ``--gmm`` also times JAX's Pallas megablox grouped matmul on the
same sorted rows, the alternative ``ragged_dot`` was chosen over.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9   # TPU v5e, Google Cloud documentation
BF16_FLOPS = 197e12


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--gmm", action="store_true")
    ap.add_argument("--rows", type=int, nargs="*", default=[32, 256, 1024, 4096])
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.ops import moe

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.rehearse:
        print(json.dumps({"error": f"no TPU here ({dev.platform}); --rehearse for a toy run"}))
        return 1
    d, f, e, k = (2048, 1024, 64, 8) if on_chip else (64, 32, 8, 2)
    rows = args.rows if on_chip else [4, 32]
    key = jax.random.key(0)
    ks = jax.random.split(key, 5)
    dt = jnp.bfloat16
    w_router = jax.random.normal(ks[0], (d, e), jnp.float32) * d ** -0.5
    w_gate = (jax.random.normal(ks[1], (e, d, f), jnp.float32) * d ** -0.5).astype(dt)
    w_up = (jax.random.normal(ks[2], (e, d, f), jnp.float32) * d ** -0.5).astype(dt)
    w_down = (jax.random.normal(ks[3], (e, f, d), jnp.float32) * f ** -0.5).astype(dt)

    weights = (w_router, w_gate, w_up, w_down)  # arguments: a closure would
    # bake 0.8 GB of constants into each executable

    @jax.jit
    def layer(h, w_router, w_gate, w_up, w_down):
        gates, experts = moe.route(h, w_router, k)
        out = moe.expert_ffn(h.astype(dt), w_gate, w_up, w_down, gates, experts)
        return out, moe.expert_histogram(experts, e)

    @jax.jit
    def gmm_layer(h, w_router, w_gate, w_up, w_down):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        gates, experts = moe.route(h, w_router, k)
        flat = experts.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
        xs = h.astype(dt)[order // k]
        tile = (min(128, xs.shape[0]), 512, 512)
        g = gmm(xs, w_gate, sizes, preferred_element_type=dt, tiling=tile)
        u = gmm(xs, w_up, sizes, preferred_element_type=dt, tiling=tile)
        a = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(dt)
        o = gmm(a, w_down, sizes, preferred_element_type=dt, tiling=tile)
        o = o[jnp.argsort(order)].reshape(h.shape[0], k, -1)
        return jnp.einsum("tkd,tk->td", o.astype(jnp.float32), gates), sizes

    def timed(fn, h, reps):
        out = fn(h, *weights)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(h, *weights)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps, out

    for n in rows:
        h = jax.random.normal(jax.random.fold_in(ks[4], n), (n, d), jnp.float32)
        seconds, (out, hist) = timed(layer, h, 50 if n <= 256 else 10)
        hit = int((np.asarray(hist) > 0).sum())
        line = {"rows": n, "assignments": n * k, "experts_hit": hit,
                "device": dev.device_kind, "impl": "ragged_dot"}
        if on_chip:
            weight_bytes = hit * 3 * d * f * 2
            flops = 2.0 * n * k * 3 * d * f
            line.update(ms=1e3 * seconds,
                        weight_stream_roofline_pct=100 * weight_bytes / HBM_BYTES_PER_S / seconds,
                        routed_flops_pct_of_peak=100 * flops / BF16_FLOPS / seconds)
        print(json.dumps(line), flush=True)
        if args.gmm and on_chip:
            try:
                seconds, _ = timed(gmm_layer, h, 50 if n <= 256 else 10)
                print(json.dumps({"rows": n, "impl": "megablox_gmm", "ms": 1e3 * seconds}),
                      flush=True)
            except Exception as exc:  # noqa: BLE001 — the alternative may not lower
                print(json.dumps({"rows": n, "impl": "megablox_gmm",
                                  "error": f"{type(exc).__name__}: {str(exc)[:300]}"}),
                      flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
