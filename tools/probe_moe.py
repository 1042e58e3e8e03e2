#!/usr/bin/env python3
"""Time the grouped expert SwiGLU (ops/moe.py) alone, at a routed
configuration's widths.

On the chip: ``python tools/probe_moe.py --widths olmoe|gigachat|xing4|...``
prints one JSON line per (assignment rows, implementation): the three
matmuls' milliseconds a call (a ``scan`` of calls in one program, so no
dispatch is in it), the groups hit, the expert-weight bytes those need
and the share of the HBM roofline that is (a decode step is bound by
streaming the experts hit), the rows' FLOPs over the bf16 peak (a
prefill group), and how far its real rows lie from ``ragged_dot``'s
(bf16 operands either way; the streaming kernel keeps gate and up in
float32 where ``ragged_dot`` rounds them to bf16).  The implementations: ``ragged_dot`` (XLA's own Mosaic
grouped matmul), ``megablox_gmm`` (JAX's Pallas grouped matmul at
``tiling=(128, 512, 512)``, ``--gmm``; at every ``m,k-divisor,n`` of
``--gmm-tiling``, ``k`` as whole K over the divisor and ``n`` capped at
the matmul's own N), ``stream`` (ops/moe.py
``stream_swiglu``: its row tile, block shape and segment rows in the
line; with ``--row-tile`` / ``--block-mb`` one ``stream_matmul`` a
matmul at each given shape instead) and ``tiled`` (ops/moe.py
``tiled_swiglu``, the lane over the ridge: its row tile and row block
in the line; with ``--tiled-tile`` / ``--tiled-block`` at each given
shape instead; ``--no-tiled`` leaves it out).  ``--rule`` adds what
``grouped_swiglu`` itself runs at that shape.

The rows arrive sorted by group with uneven sizes drawn from ``--seed``
as the cells draw them: ``olmoe`` — ``rows / 8`` tokens each choosing 8
of 64 experts, near even (a decode step of 32 lanes is 256 rows, ~63
experts hit); ``gigachat`` — one pass of a replica's 8 held experts of
256, ``rows`` = four times an even share of ``rows`` tokens' top-8, so
a quarter of the rows are real, the rest lie past the groups, and the
held experts are chosen as unevenly as the cell's (~6 of 8 hit by 128
tokens); ``xing4`` and ``smallthinker`` — ``--real-share`` of the rows
(all of them by default) dealt to the groups by lognormal weights as
uneven as their cells' routing (``SKEW``: a busiest expert at ~2.5 and
~1.4 times the mean), the rest past the groups; ``--real-share`` with
the held widths deals that share of the rows by ``HELD_PROFILE``.  Off
the chip ``--rehearse`` runs a toy size through the
Pallas interpreter for control flow only and prints no rate.

``--held`` times the WHOLE held pass instead (``moe.expert_ffn_held``:
sort, row gather, the grouped SwiGLU, the gathers back to the tokens
under their select), a line per (``--tokens``, rows a pass): a
replica's share at any ``--widths`` (``xing4`` and ``olmoe`` hold every
expert) routed near-evenly from
``--seed``, the pass sized by :func:`moe.held_rows_cap`, at the power of
two over ``HELD_ROWS_HEADROOM`` even shares (the rule under the ridge) and
at each ``--cap-over-even`` times the even share in whole 512s (under 1 the
local assignments need a second pass: what that costs); ``--sized rule``
keeps the rule's own size alone.  It is the
table ``HELD_ROWS_RIDGE_HEADROOM`` was chosen from.
"""

from __future__ import annotations

import argparse
import itertools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9   # TPU v5e, Google Cloud documentation
BF16_FLOPS = 197e12

# (groups held, experts routed over, top-k, d_model, expert width)
WIDTHS = {"olmoe": (64, 64, 8, 2048, 1024), "gigachat": (8, 256, 8, 7168, 2048),
          "dots3": (8, 256, 8, 5120, 1536), "smallthinker": (16, 64, 6, 2560, 768),
          "xing4": (64, 64, 4, 3584, 1024)}
# how unevenly a replica's 8 held experts are chosen (PERF.md section 6,
# PR 30: ~6 of 8 hit a decode step, max over mean 2.7-2.9)
HELD_PROFILE = (2.7, 1.8, 1.3, 1.0, 0.7, 0.4, 0.08, 0.02)
# the spread of the lognormal weights a call's rows are dealt by: Xing4's
# cell reads ``expert_load_max_over_mean`` 2.5-2.6 over 64 experts,
# SmallThinker's 1.37 over 16 (ledger, PRs 45 and 42)
SKEW = {"xing4": 0.4, "smallthinker": 0.2}


def draw_sizes(widths: str, rows: int, seed: int, groups: int, top_k: int,
               real_share=None, skew=None):
    """Group sizes of one call of ``rows`` sorted rows (see the module
    docstring); their sum is ``rows`` for ``olmoe``, about a quarter of
    it for ``gigachat`` and ``real_share`` of it where that is given."""
    import numpy as np

    rng = np.random.default_rng(seed)
    if widths in SKEW or real_share is not None or skew is not None:
        real = rows if real_share is None else int(round(rows * real_share))
        skew = SKEW.get(widths) if skew is None else skew
        weight = (np.exp(rng.normal(0.0, skew, groups)) if skew is not None
                  else rng.permutation(np.resize(HELD_PROFILE, groups)))
        return rng.multinomial(real, weight / weight.sum()).astype(np.int32)
    if widths == "olmoe":
        tokens = max(1, rows // top_k)
        logits = rng.normal(0.0, 0.15, groups) + rng.gumbel(size=(tokens, groups))
        chosen = np.argsort(-logits, axis=-1)[:, :top_k]
        sizes = np.bincount(chosen.ravel(), minlength=groups)
        sizes[0] += rows - sizes.sum()      # rows no multiple of top-k
        return sizes.astype(np.int32)
    profile = rng.permutation(np.resize(HELD_PROFILE, groups))
    p = np.minimum(1.0, profile * top_k / WIDTHS[widths][1])
    sizes = rng.binomial(rows, p)
    while sizes.sum() > rows:               # a pass holds ``rows`` at most
        sizes[np.argmax(sizes)] -= 1
    return sizes.astype(np.int32)


def held_sweep(args, dev, on_chip: bool) -> int:
    """``--held``: one line per (tokens, rows a pass), see the module
    docstring."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.ops import moe

    held, outputs, top_k, d, f = WIDTHS[args.widths]
    tokens_list, reps = args.tokens, args.reps
    if not on_chip:
        d, f, tokens_list, reps = 128, 256, [256], 2
    dt = jnp.bfloat16
    ks = jax.random.split(jax.random.key(args.seed % (1 << 31)), 4)
    weights = tuple(
        (jax.random.normal(k, shape, jnp.float32) * shape[1] ** -0.5).astype(dt)
        for k, shape in zip(ks, ((held, d, f), (held, d, f), (held, f, d))))
    rule = moe.held_rows_cap
    rng = np.random.default_rng(args.seed)
    for tokens in tokens_list:
        logits = rng.normal(0.0, 0.15, outputs) + rng.gumbel(size=(tokens, outputs))
        experts_np = np.argsort(-logits, axis=-1)[:, :top_k].astype(np.int32)
        n_local = int((experts_np < held).sum())
        even = tokens * top_k * held / outputs
        experts = jnp.asarray(experts_np)
        gates = jnp.full((tokens, top_k), 1.0 / top_k, jnp.float32)
        h = jax.random.normal(jax.random.fold_in(ks[3], tokens), (tokens, d),
                              jnp.float32).astype(dt)
        # the rule under the ridge, whatever the size
        pow2 = max(moe.HELD_ROWS_MIN,
                   1 << math.ceil(math.log2(moe.HELD_ROWS_HEADROOM * even)))
        caps = {"rule": rule(tokens, top_k, held, outputs), "4x_pow2": pow2}
        for over in args.cap_over_even:
            caps[f"{over}x"] = max(512, math.ceil(over * even / 512) * 512)
        for label, cap in caps.items():
            if args.sized and label not in args.sized:
                continue
            moe.held_rows_cap = lambda *_a, cap=cap: cap   # read as the pass is traced

            @jax.jit
            def many(h, wg, wu, wd, gates, experts):
                def step(h, _):
                    out = moe.expert_ffn_held(h, wg, wu, wd, gates, experts, 0, outputs)
                    return (h + 1e-3 * out.astype(h.dtype)).astype(h.dtype), ()
                return jax.lax.scan(step, h, None, length=reps)[0]

            line = {"widths": args.widths, "tokens": tokens, "cap": cap, "sized": label,
                    "even": even, "local": n_local, "passes": -(-n_local // cap),
                    "impl": moe.expert_matmul_impl(cap, held, d, f, dt, moe.matmul_backend()),
                    "device": dev.device_kind}
            try:
                jax.block_until_ready(many(h, *weights, gates, experts))
                t0 = time.perf_counter()
                jax.block_until_ready(many(h, *weights, gates, experts))
                if on_chip:
                    line["ms_a_layer"] = 1e3 * (time.perf_counter() - t0) / reps
            except Exception as exc:  # noqa: BLE001 — a size may not fit
                line["error"] = f"{type(exc).__name__}: {str(exc)[:300]}"
            finally:
                moe.held_rows_cap = rule
            print(json.dumps(line), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--widths", choices=sorted(WIDTHS), default="olmoe")
    ap.add_argument("--gmm", action="store_true")
    ap.add_argument("--rule", action="store_true")
    ap.add_argument("--no-stream", action="store_true")
    ap.add_argument("--rows", type=int, nargs="*",
                    default=[128, 256, 512, 1024, 2048, 4096, 8192])
    ap.add_argument("--row-tile", type=int, nargs="*", default=None)
    ap.add_argument("--block-mb", type=float, nargs="*", default=None)
    ap.add_argument("--gmm-tiling", nargs="*", default=[],
                    help="m,k-divisor,n of a megablox tiling, e.g. 256,1,1024")
    ap.add_argument("--no-tiled", action="store_true")
    ap.add_argument("--tiled-tile", type=int, nargs="*", default=None)
    ap.add_argument("--tiled-block", type=int, nargs="*", default=None)
    ap.add_argument("--real-share", type=float, default=None)
    ap.add_argument("--skew", type=float, default=None)
    ap.add_argument("--act", default="silu")
    ap.add_argument("--held", action="store_true")
    ap.add_argument("--tokens", type=int, nargs="*",
                    default=[2048, 3072, 4096, 6144, 8192])
    ap.add_argument("--cap-over-even", type=float, nargs="*",
                    default=[2.0, 1.5, 1.25, 0.75])
    ap.add_argument("--sized", nargs="*", default=None,
                    help="of --held's sizes (rule, 4x_pow2, 1.5x, ...) only these")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=16)
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
    if args.held:
        if not on_chip:
            moe.matmul_backend = lambda: "interpret"
        return held_sweep(args, dev, on_chip)
    groups, _routed, top_k, d, f = WIDTHS[args.widths]
    rows_list = args.rows
    if not on_chip:
        d, f, rows_list, args.reps = 128, 256, [32, 64], 2
        moe.matmul_backend = lambda: "interpret"
    # no shape given: what the rule's path runs (``stream_swiglu``: its
    # own row tile, block and segments); else one kernel call a matmul
    # at each given shape (the rows must fit one segment)
    swept = bool(args.row_tile or args.block_mb)
    row_tiles = args.row_tile or [None]
    blocks = [int(mb * (1 << 20)) for mb in args.block_mb] if args.block_mb \
        else [moe.STREAM_BLOCK_BYTES]
    ks = jax.random.split(jax.random.key(args.seed % (1 << 31)), 4)
    dt = jnp.bfloat16
    w_gate = (jax.random.normal(ks[0], (groups, d, f), jnp.float32) * d ** -0.5).astype(dt)
    w_up = (jax.random.normal(ks[1], (groups, d, f), jnp.float32) * d ** -0.5).astype(dt)
    w_down = (jax.random.normal(ks[2], (groups, f, d), jnp.float32) * f ** -0.5).astype(dt)
    weights = (w_gate, w_up, w_down)  # arguments: a closure would bake
    # 0.8 GB of constants into each executable

    def ragged(x, wg, wu, wd, sizes):
        return moe.ragged_swiglu(x, wg, wu, wd, sizes, dt, args.act)

    def megablox(m, k_over, n):
        def tile(rows, k, width):
            return (min(m, rows), k // k_over if k_over else min(512, k), min(n, width))

        def fn(x, wg, wu, wd, sizes):
            from jax.experimental.pallas.ops.tpu.megablox import gmm

            up_tile, down_tile = tile(x.shape[0], d, f), tile(x.shape[0], f, d)
            g = gmm(x, wg, sizes, preferred_element_type=dt, tiling=up_tile)
            u = gmm(x, wu, sizes, preferred_element_type=dt, tiling=up_tile)
            a = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(dt)
            return gmm(a, wd, sizes, preferred_element_type=jnp.float32, tiling=down_tile)
        return fn, {"tiling_gate_up": list(tile(1 << 30, d, f)),
                    "tiling_down": list(tile(1 << 30, f, d))}

    def stream(row_tile, block_bytes):
        def fn(x, wg, wu, wd, sizes):
            if not swept:
                return moe.stream_swiglu(x, wg, wu, wd, sizes, interpret=not on_chip,
                                         act=args.act)
            kw = dict(interpret=not on_chip, block_bytes=block_bytes,
                      row_tile=row_tile or moe.stream_row_tile(x.shape[0], groups))
            act = moe.stream_matmul(x, (wg, wu), sizes, **kw)
            return moe.stream_matmul(act, (wd,), sizes, **kw)
        return fn

    def tiled(row_tile, row_block):
        def fn(x, wg, wu, wd, sizes):
            if row_tile is None and row_block is None:
                return moe.tiled_swiglu(x, wg, wu, wd, sizes, interpret=not on_chip,
                                        act=args.act)
            tile, block = tiled_shape(x.shape[0], row_tile, row_block)
            kw = dict(interpret=not on_chip, row_block=block, row_tile=tile)
            visits = moe.tiled_visits(sizes, x.shape[0], block)
            act = moe.tiled_matmul(x, (wg, wu), visits, out_dtype=dt, act=args.act, **kw)
            return moe.tiled_matmul(act, (wd,), visits, **kw)
        return fn

    def tiled_shape(n, row_tile, row_block):
        """The lane's own row tile and row block where none is given."""
        return row_tile or moe.TILED_ROW_TILE, row_block or moe.tiled_row_block(n, d, f)

    def rule(x, wg, wu, wd, sizes):
        return moe.grouped_swiglu(x, wg, wu, wd, sizes)

    def timed(fn, x, sizes):
        """Seconds a call, from a scan of ``reps`` calls that each read
        the one before (nothing to hoist, no dispatch between)."""
        @jax.jit
        def many(x, wg, wu, wd, sizes):
            def step(x, _):
                out = fn(x, wg, wu, wd, sizes)
                return (x + 1e-3 * out.astype(x.dtype)).astype(x.dtype), ()
            return jax.lax.scan(step, x, None, length=args.reps)[0]

        jax.block_until_ready(many(x, *weights, sizes))
        t0 = time.perf_counter()
        jax.block_until_ready(many(x, *weights, sizes))
        return (time.perf_counter() - t0) / args.reps

    for n in rows_list:
        sizes_np = draw_sizes(args.widths, n, args.seed + n, groups, top_k,
                              args.real_share, args.skew)
        sizes = jnp.asarray(sizes_np)
        hit, real = int((sizes_np > 0).sum()), int(sizes_np.sum())
        x = jax.random.normal(jax.random.fold_in(ks[3], n), (n, d), jnp.float32).astype(dt)
        impls = [("ragged_dot", ragged, {})]
        for m, k_over, width in ([(128, 0, 512)] if args.gmm else []) + [
                tuple(int(v) for v in t.split(",")) for t in args.gmm_tiling]:
            impls.append(("megablox_gmm", *megablox(m, k_over, width)))
        if not args.no_stream:
            for row_tile in row_tiles:
                for block in blocks:
                    impls.append(("stream", stream(row_tile, block), {
                        "row_tile": row_tile or moe.stream_row_tile(n, groups),
                        "segment_rows": n if swept else moe.stream_segment_rows(max(d, f)),
                        "block_gate_up": [d, moe.stream_block(d, f, block_bytes=block)],
                        "block_down": [f, moe.stream_block(f, d, block_bytes=block)]}))
        if not args.no_tiled:
            for row_tile, row_block in itertools.product(
                    args.tiled_tile or [None], args.tiled_block or [None]):
                tile, block = tiled_shape(n, row_tile, row_block)
                impls.append(("tiled", tiled(row_tile, row_block), {
                    "row_tile": tile, "row_block": block,
                    "block_gate_up": [d, moe.stream_block(d, f)],
                    "block_down": [f, moe.stream_block(f, d)]}))
        if args.rule:
            impls.append(("grouped_swiglu", rule, {
                "runs": moe.expert_matmul_impl(n, groups, d, f, dt, moe.matmul_backend())}))
        base = want = None
        for name, fn, extra in impls:
            line = {"widths": args.widths, "rows": n, "real_rows": real, "groups": groups,
                    "groups_hit": hit, "device": dev.device_kind, "impl": name, **extra}
            try:
                seconds = timed(fn, x, sizes)
                out = np.asarray(jax.jit(fn)(x, *weights, sizes))[:real]
            except Exception as exc:  # noqa: BLE001 — an alternative may not lower
                line["error"] = f"{type(exc).__name__}: {str(exc)[:300]}"
                print(json.dumps(line), flush=True)
                continue
            want = out if name == "ragged_dot" else want
            line["max_abs_diff_from_ragged_dot"] = float(np.abs(out - want).max(initial=0.0))
            if on_chip:
                base = seconds if name == "ragged_dot" else base
                line.update(
                    ms=1e3 * seconds,
                    weight_stream_roofline_pct=100 * hit * 3 * d * f * 2
                    / HBM_BYTES_PER_S / seconds,
                    routed_flops_pct_of_peak=100 * 2.0 * real * 3 * d * f
                    / BF16_FLOPS / seconds,
                    over_ragged_dot=base / seconds if base else None)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
