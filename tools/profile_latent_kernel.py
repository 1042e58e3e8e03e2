#!/usr/bin/env python3
"""Time the latent decode kernel alone at a cell's shapes (chip).

The whole ``(layers, pages, 64, 640)`` pool, one call a layer in a scan
— as a decode step of the chunk program makes them — over lanes whose
cached lengths are drawn from ``--ctx``: prints microseconds a call and
a live page, and the share of the DMA roofline (rows needed x 1,152 B
over 819 GB/s).  ``--step-tokens`` sets
``ops/kernels.LATENT_STEP_TOKENS`` (tokens one step of the page loop
reduces) for a sweep; ``--chosen K`` hands the kernel a mask that keeps
``K`` of each lane's cached rows (a selection's: the rows moved are the
lengths', the rows needed ``min(K, length)``); ``--interpret`` rehearses
on the CPU.

    python tools/profile_latent_kernel.py --lanes 64 --table-pages 32 \
        --ctx 600-2000 --step-tokens 128,256,512 --json chiprun_out/x.jsonl

``--index`` times the indexer's scoring of a decode step's cached keys
instead (``ops/kernels.py index_scores_decode`` over the whole ``(layers,
pages, 64, 128)`` key pool, and XLA's form beside it: the gather of every
table column and ``ops/mla.py index_scores``): microseconds a call and the
share of ``keys x 256 B`` over 819 GB/s, a line each of ``--shapes``
(lanes x table pages), ``--index-ctx`` (a lane's cached keys, +-10 %, cut
at the table's span) and ``--step-tokens`` (``INDEX_STEP_TOKENS``).

    python tools/profile_latent_kernel.py --index --layers 3 --pages 14337 \
        --step-tokens 512,1024,2048 --json chiprun_out/index_probe.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def index_probe(args) -> list:
    """The ``--index`` table: one record a shape, context and form."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.ops import kernels, mla

    ps, heads, dim, scale = 64, 64, 128, 1 / 90.5
    rng = np.random.default_rng(0)
    pool = jax.random.normal(
        jax.random.key(0), (args.layers, args.pages, ps, dim), jnp.bfloat16)
    steps = [int(x) for x in args.step_tokens.split(",")]
    out = []
    for shape in args.shapes.split(","):
        lanes, table_pages = (int(x) for x in shape.split("x"))
        q = jax.random.normal(jax.random.key(1), (lanes, 1, heads, dim), jnp.bfloat16)
        w = jax.random.normal(jax.random.key(2), (lanes, 1, heads), jnp.float32)
        tables = jnp.asarray(rng.permutation(np.arange(1, args.pages))[
            : lanes * table_pages].reshape(lanes, table_pages).astype(np.int32))

        def kernel(pool, tables, lengths, layer):
            return kernels.index_scores_decode(
                q[:, 0], w[:, 0], pool, tables, lengths, layer=layer,
                page_size=ps, scale=scale).reshape(lanes, -1)

        def xla(pool, tables, lengths, layer):
            keys = pool[layer, tables].reshape(lanes, -1, dim)
            return mla.index_scores(q, w, keys, scale)[:, 0]

        for ctx in (int(x) for x in args.index_ctx.split(",")):
            if ctx > 1.1 * table_pages * ps:
                continue
            lengths = np.minimum(rng.integers(
                int(0.9 * ctx), int(1.1 * ctx) + 1, size=lanes), table_pages * ps)
            keys = int(lengths.sum())
            lengths = jnp.asarray(lengths.astype(np.int32))
            cut = jnp.arange(table_pages * ps)[None, :] < lengths[:, None]
            seen = {}
            for form, step_tokens in [("xla", 0)] + [("kernel", n) for n in steps]:
                if step_tokens:  # a static argument of the call
                    kernels.INDEX_STEP_TOKENS = step_tokens
                score = kernel if form == "kernel" else xla

                @jax.jit
                def layers(pool, tables, lengths, score=score):
                    def one(carry, layer):
                        return carry + score(pool, tables, lengths, layer), ()
                    total, _ = jax.lax.scan(
                        one, jnp.zeros((lanes, table_pages * ps), jnp.float32),
                        jnp.arange(args.layers))
                    return total

                seen[form] = jnp.where(cut, jax.block_until_ready(
                    layers(pool, tables, lengths)), 0.0)
                t0 = time.perf_counter()
                for _ in range(args.repeats):
                    r = layers(pool, tables, lengths)
                jax.block_until_ready(r)
                call_us = 1e6 * (time.perf_counter() - t0) / args.repeats / args.layers
                rec = {"form": form, "lanes": lanes, "table_pages": table_pages,
                       "ctx": ctx, "step_tokens": step_tokens, "keys": keys,
                       "call_us": round(call_us, 1),
                       "key_ns": round(1e3 * call_us / keys, 3),
                       "hbm_roofline_pct": round(
                           100 * keys * 2 * dim / 819e9 / (call_us * 1e-6), 1),
                       "max_abs_diff_from_xla": float(
                           jnp.abs(seen[form] - seen["xla"]).max()),
                       "device": jax.devices()[0].device_kind}
                print(json.dumps(rec), flush=True)
                out.append(rec)
    return out


def record(path: str, records: list) -> None:
    """Append the records to ``--json``'s file, where one was named."""
    if path:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "a") as f:
            f.writelines(json.dumps(rec) + "\n" for rec in records)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--index", action="store_true",
                    help="time the indexer's scoring, not the latent kernel")
    ap.add_argument("--shapes", default="64x64,64x112,128x112")
    ap.add_argument("--index-ctx", default="2100,3800,7100")
    ap.add_argument("--lanes", type=int, default=64)
    ap.add_argument("--table-pages", type=int, default=32)
    ap.add_argument("--ctx", default="600-2000", help="N or LO-HI cached tokens a lane")
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--pages", type=int, default=8193)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--rank", type=int, default=512)
    ap.add_argument("--values", type=int, default=576)
    ap.add_argument("--step-tokens", default="128")
    ap.add_argument("--chosen", type=int, default=0,
                    help="mask the lanes' rows down to K chosen each")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--json", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.ops import kernels

    if args.interpret:
        kernels.interpret_mode = lambda: True
    if args.index:
        record(args.json, index_probe(args))
        return 0
    ps, lanes_w = 64, -(-args.values // 128) * 128
    lo, _, hi = args.ctx.partition("-")
    lo, hi = int(lo), int(hi or lo)
    rng = np.random.default_rng(0)
    lengths = rng.integers(lo, hi + 1, size=args.lanes).astype(np.int32)
    lengths = np.minimum(lengths, args.table_pages * ps)
    tables = rng.permutation(np.arange(1, args.pages))[
        : args.lanes * args.table_pages].reshape(args.lanes, args.table_pages).astype(np.int32)
    pool = jax.random.normal(
        jax.random.key(0), (args.layers, args.pages, ps, lanes_w), jnp.bfloat16)
    q = (jax.random.normal(jax.random.key(1), (args.lanes, args.heads, lanes_w),
                           jnp.float32) * 0.05).astype(jnp.bfloat16)
    live = int(sum(-(-int(n) // ps) for n in lengths))
    rows = moved = int(lengths.sum())
    masks = ()
    if args.chosen:
        cached = np.arange(args.table_pages * ps)[None, :] < lengths[:, None]
        # the K smallest of a uniform draw over the lane's cached rows
        draw = np.where(cached, rng.random(cached.shape), 2.0)
        keep = cached & (draw <= np.sort(draw, axis=1)[:, args.chosen - 1:args.chosen])
        masks = (jnp.asarray(keep),)
        rows = int(keep.sum())
    out = []
    for step_tokens in (int(x) for x in args.step_tokens.split(",")):
        kernels.LATENT_STEP_TOKENS = step_tokens  # a static argument of the call

        @jax.jit
        def layers(q, pool, tables, lengths, *mask):
            def one(carry, layer):
                acc, m, l = kernels.latent_attention_decode(
                    q, pool, tables, lengths, layer=layer, page_size=ps, rank=args.rank,
                    **dict(zip(("chosen",), mask)))
                return carry + acc.sum() + m.max() + l.sum(), ()
            total, _ = jax.lax.scan(one, jnp.float32(0), jnp.arange(args.layers))
            return total

        operands = (q, pool, jnp.asarray(tables), jnp.asarray(lengths), *masks)
        jax.block_until_ready(layers(*operands))
        t0 = time.perf_counter()
        for _ in range(args.repeats):
            r = layers(*operands)
        jax.block_until_ready(r)
        call_us = 1e6 * (time.perf_counter() - t0) / args.repeats / args.layers
        floor_us = 1e6 * rows * 2 * args.values / 819e9
        rec = {"lanes": args.lanes, "table_pages": args.table_pages, "ctx": args.ctx,
               "heads": args.heads, "chosen": args.chosen,
               "step_tokens": step_tokens, "live_pages": live, "rows": rows,
               "rows_moved": moved, "moved_row_ns": round(1e3 * call_us / max(moved, 1), 2),
               "call_us": round(call_us, 1), "live_page_us": round(call_us / max(live, 1), 3),
               "dma_roofline_pct": round(100 * floor_us / call_us, 1),
               "device": jax.devices()[0].device_kind}
        print(json.dumps(rec), flush=True)
        out.append(rec)
    record(args.json, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
