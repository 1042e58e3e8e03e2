#!/usr/bin/env python3
"""Time the attention of a prefill from position zero alone, at the
benchmark cells' prefill shapes.

On the chip: ``python tools/profile_prefill_attention.py [--cells gpt2
olmoe gigachat longcat] [--blocks 256x256 512x256 ...]`` prints one JSON
line per (shape, implementation): milliseconds a layer-call (a ``scan``
of calls in one program, each call's queries depending on the one
before, so no dispatch is in it and nothing is hoisted) and the share of
the chip's bf16 peak that the causal attention's needed FLOPs are at
that time (``B * h * L^2 * (d_qk + d_v)``: half the square).  The
implementations:

- ``xla_table`` (multi-head shapes): the form every from-zero prefill
  traced before PR 33 — the slot's pages gathered through the block
  table, scored, masked out by ``lengths = 0``, concatenated with the
  segment's own scores, one float32 softmax over both;
- ``xla``: XLA's einsums over the segment alone (the multi-head block's
  ``_segment_attention`` form: bf16 scores, f32 softmax; a latent
  shape: ``ops/mla.py naive_attention`` whole, ``QUERY_BLOCK`` queries
  at a time, the K and V up-projections included);
- ``fused``: ``ops/kernels.py causal_attention`` at each ``--blocks``
  pair (query block x key block; default: the kernel's own), for a
  latent shape inside ``naive_attention`` as the engine runs it.

An **indexed** cell (``dots3``: a full layer whose rows attend the 2,048
positions an indexer picks) times ``ops/mla.py indexed_attention`` whole
— the selection and the attention under it — as ``xla`` (a block of 128
queries scored against every key) and ``fused`` (the blocks select, the
causal kernel weighs under their mask), and the kernel alone on a mask
made beforehand by the same rule: ``kernel_chosen`` (packing included)
beside ``kernel_plain`` (no mask: what the mask costs the kernel).

``max_abs_err`` is the implementation's distance from a float32 softmax
over the same bf16 operands (unit-normal q, k, v), over the first
prompt's rows.  Off the chip ``--rehearse`` runs a toy size through the
Pallas interpreter for control flow only and prints no rate.  The
record also lands in ``chiprun_out/prefill_attention.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BF16_FLOPS = 197e12     # TPU v5e, Google Cloud documentation
PAGE = 64

# cell -> (heads, d_qk, d_v, latent rank or 0, [(k, bucket), ...]): the
# prefill groups the cells' traffic forms (PERF.md section 5)
CELLS = {
    "gpt2": (20, 64, 64, 0, [(4, 1024), (2, 1024), (1, 1024), (4, 512), (2, 512),
                             (1, 512), (4, 256), (1, 256)]),
    "olmoe": (16, 128, 128, 0, [(4, 512), (2, 512), (1, 512), (4, 256), (1, 256)]),
    "gigachat": (64, 192, 192, 512, [(1, 2048), (2, 1024), (1, 1024)]),
    "longcat": (64, 192, 128, 512, [(2, 1024), (1, 1024), (4, 512), (1, 512)]),
}
TOY = {"toy": (2, 16, 16, 0, [(2, 128)]), "toy_latent": (2, 24, 16, 32, [(1, 128)]),
       "toy_indexed": (2, 24, 16, 32, [(1, 256)])}
# cell -> (index heads, index dim, topk): the cells whose layer selects
INDEXED = {"dots3": (64, 128, 2048), "toy_indexed": (2, 16, 96)}
CELLS["dots3"] = (128, 192, 128, 512, [(1, 3072), (1, 4096), (2, 3072), (2, 4096)])


def xla_table(q, k, v, pool_k, pool_v, table, scale):
    """The multi-head gather path of a from-zero prefill before PR 33
    (models/paged/blocks.py): every cached page scored and masked out."""
    import jax
    import jax.numpy as jnp

    nb, seg, heads, hd = q.shape
    gk = pool_k[table].reshape(nb, -1, heads, hd)
    gv = pool_v[table].reshape(nb, -1, heads, hd)
    cache_len = gk.shape[1]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q * scale, gk)
    ss = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    neg = jnp.finfo(sc.dtype).min
    lengths = jnp.zeros((nb,), jnp.int32)
    sc = jnp.where((jnp.arange(cache_len)[None, :] < lengths[:, None])[:, None, None, :],
                   sc, neg)
    ss = jnp.where((jnp.arange(seg)[None, :] <= jnp.arange(seg)[:, None])[None, None],
                   ss, neg)
    w = jax.nn.softmax(jnp.concatenate([sc, ss], -1).astype(jnp.float32), -1).astype(q.dtype)
    return (jnp.einsum("bhqk,bkhd->bqhd", w[..., :cache_len], gv)
            + jnp.einsum("bhqk,bkhd->bqhd", w[..., cache_len:], v))


def xla_segment(q, k, v, scale):
    """``_segment_attention``'s XLA form (models/paged/blocks.py)."""
    import jax
    import jax.numpy as jnp

    seg = q.shape[1]
    ss = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    ss = jnp.where((jnp.arange(seg)[None, :] <= jnp.arange(seg)[:, None])[None, None],
                   ss, jnp.finfo(ss.dtype).min)
    w = jax.nn.softmax(ss.astype(jnp.float32), -1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def oracle(q, k, v, scale, seen=None):
    """Float32 causal softmax of the first prompt's rows (under its
    ``(L, L)`` mask ``seen``, where the layer selects)."""
    import numpy as np

    q, k, v = (np.asarray(x[0], np.float32) for x in (q, k, v))
    s = np.einsum("qhd,khd->hqk", q, k) * scale
    s = np.where(np.tril(np.ones(s.shape[-2:], bool)) if seen is None
                 else np.asarray(seen), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,khd->qhd", w / w.sum(-1, keepdims=True), v)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--cells", nargs="+", default=sorted(CELLS))
    ap.add_argument("--blocks", nargs="*", default=[],
                    help="QxK pairs for the fused kernel (default: its own)")
    ap.add_argument("--groups", nargs="*", default=[],
                    help="KxBUCKET prefill groups instead of the cells' own")
    ap.add_argument("--calls", type=int, default=12, help="layer-calls a program")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--skip", nargs="*", default=[], choices=["xla_table", "xla", "fused"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.ops import kernels, mla

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        print("no TPU here: pass --rehearse for a control-flow run", file=sys.stderr)
        return 1
    cells = TOY if args.rehearse else {c: CELLS[c] for c in args.cells}
    blocks = [tuple(int(x) for x in b.split("x")) for b in args.blocks] or [(None, None)]
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open(os.path.join("chiprun_out", "prefill_attention.jsonl"), "a")
    dtype = jnp.bfloat16
    own_blocks = (kernels.CAUSAL_BLOCK_Q, kernels.CAUSAL_BLOCK_K)

    def timed(fn, q, rest):
        """Seconds a call of ``fn(q, *rest)``, ``args.calls`` chained."""
        def many(q, *rest):
            def body(carry, _):
                out = fn(carry, *rest)
                width = min(out.shape[-1], carry.shape[-1])
                bump = jnp.zeros_like(carry).at[..., :width].set(
                    out[..., :width] * jnp.asarray(1e-3, out.dtype))
                return carry + bump, None
            return jax.lax.scan(body, q, None, length=args.calls)[0]

        many = jax.jit(many)
        jax.block_until_ready(many(q, *rest))
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(many(q, *rest))
            best = min(best, time.perf_counter() - t0)
        return best / args.calls

    for cell, (heads, d_qk, d_v, rank, groups) in cells.items():
        scale = float(d_qk) ** -0.5
        groups = [tuple(int(x) for x in g.split("x")) for g in args.groups] or groups
        for group, seg in groups:
            key = jax.random.key(args.seed)
            kq, kk, kv, kp = jax.random.split(key, 4)
            flops = group * heads * seg * seg * (d_qk + d_v)
            arms = {}
            heads_judged = slice(None)
            if rank:
                # a latent shape: q halves, the segment's rows, W_uk, W_uv
                rope = d_qk - 128 if d_qk > 128 else d_qk // 3
                nope = d_qk - rope
                q = jax.random.normal(kq, (group, seg, heads, d_qk), dtype)
                rows = jax.random.normal(kk, (group, seg, rank + rope), dtype)
                w_uk = (jax.random.normal(kv, (heads, rank, nope), dtype)
                        * rank ** -0.5).astype(dtype)
                w_uv = (jax.random.normal(kp, (heads, rank, d_v), dtype)
                        * rank ** -0.5).astype(dtype)
                k_full = jnp.concatenate([
                    jnp.einsum("bcr,hrn->bchn", rows[..., :rank], w_uk,
                               preferred_element_type=jnp.float32).astype(dtype),
                    jnp.broadcast_to(rows[:, :, None, rank:],
                                     (group, seg, heads, rope))], -1)
                v_full = jnp.einsum("bcr,hrv->bchv", rows[..., :rank], w_uv,
                                    preferred_element_type=jnp.float32).astype(dtype)

                def own(bq, bk):
                    # (the latent forms take the kernel's own blocks:
                    # read as each arm is traced)
                    kernels.CAUSAL_BLOCK_Q, kernels.CAUSAL_BLOCK_K = (
                        (bq, bk) if bq else own_blocks)

            if cell in INDEXED:
                # an indexed layer: the indexer's operands beside the
                # latent ones, and the rule's own mask, made a block of
                # 128 queries at a time, for the kernel-alone arms
                ih, idim, topk = INDEXED[cell]
                ki, kw, kx = jax.random.split(jax.random.key(args.seed + 1), 3)
                q_idx = jax.random.normal(ki, (group, seg, ih, idim), dtype)
                w_idx = jax.random.normal(kw, (group, seg, ih), jnp.float32)
                k_idx = jax.random.normal(kx, (group, seg, idim), dtype)
                i_scale = ih ** -0.5 * idim ** -0.5
                at = jnp.arange(seg)
                bq_sel = min(128, seg)

                @jax.jit
                def select(qi, wi, first):
                    under = at[None, None, :] <= (first + at[:bq_sel])[None, :, None]
                    return mla.kth_mask(
                        mla.index_scores(qi, wi, k_idx, i_scale),
                        jnp.broadcast_to(under, (group, bq_sel, seg)), topk)

                mask = jnp.concatenate([
                    select(q_idx[:, f:f + bq_sel], w_idx[:, f:f + bq_sel], f)
                    for f in range(0, seg, bq_sel)], axis=1)
                # (four heads: the whole float32 square of 128 is 8.6 GB)
                heads_judged = slice(0, 4)
                want = oracle(q[:, :, :4], k_full[:, :, :4], v_full[:, :, :4],
                              scale, mask[0])
                rest = (rows, w_uk, w_uv, q_idx, w_idx, k_idx, k_full, v_full, mask)

                def indexed(fused, bq=None, bk=None):
                    def fn(q, rows, w_uk, w_uv, q_idx, w_idx, k_idx, _k, _v, _m):
                        own(bq, bk)
                        return mla.indexed_attention(
                            q[..., :nope], q[..., nope:], rows, w_uk, w_uv,
                            scale, dtype, q_idx, w_idx, k_idx, i_scale, topk,
                            fused=fused)
                    return fn

                def alone(masked, bq=None, bk=None):
                    def fn(q, _r, _uk, _uv, _qi, _wi, _ki, k, v, m):
                        return kernels.causal_attention(
                            q, k, v, scale, block_q=bq, block_k=bk,
                            **({"chosen": m} if masked else {}))
                    return fn

                arms["xla"] = indexed(False)
                for bq, bk in blocks:
                    tag = f"_{bq}x{bk}" if bq else ""
                    arms["fused" + tag] = indexed(True, bq, bk)
                    arms["kernel_chosen" + tag] = alone(True, bq, bk)
                    arms["kernel_plain" + tag] = alone(False, bq, bk)
            elif rank:
                rest = (rows, w_uk, w_uv)

                def naive(fused, bq=None, bk=None):
                    def fn(q, rows, w_uk, w_uv):
                        own(bq, bk)
                        return mla.naive_attention(
                            q[..., :nope], q[..., nope:], None,
                            jnp.zeros((group,), jnp.int32), rows, w_uk, w_uv,
                            scale, dtype, fused=fused)
                    return fn

                want = oracle(q, k_full, v_full, scale)
                arms["xla"] = naive(False)
                for bq, bk in blocks:
                    arms[f"fused_{bq}x{bk}" if bq else "fused"] = naive(True, bq, bk)
            else:
                q, k, v = (jax.random.normal(r, (group, seg, heads, d), dtype)
                           for r, d in ((kq, d_qk), (kk, d_qk), (kv, d_v)))
                pages = max(1, seg // PAGE)
                pool_k = jax.random.normal(kp, (group * pages + 1, PAGE, heads * d_qk), dtype)
                table = jnp.arange(group * pages, dtype=jnp.int32).reshape(group, pages) + 1
                rest = (k, v)
                want = oracle(q, k, v, scale)
                sc = jnp.asarray(scale, dtype)
                arms["xla_table"] = lambda q, k, v: xla_table(
                    q, k, v, pool_k, pool_k, table, sc)
                arms["xla"] = lambda q, k, v: xla_segment(q, k, v, sc)
                for bq, bk in blocks:
                    arms[f"fused_{bq}x{bk}" if bq else "fused"] = (
                        lambda q, k, v, bq=bq, bk=bk: kernels.causal_attention(
                            q, k, v, scale, block_q=bq, block_k=bk))
            for name, fn in arms.items():
                if name.split("_")[0] in args.skip or name in args.skip:
                    continue
                line = {"cell": cell, "k": group, "bucket": seg, "heads": heads,
                        "d_qk": d_qk, "d_v": d_v, "impl": name}
                try:
                    got = np.asarray(
                        jax.jit(fn)(q, *rest)[0][:, heads_judged], np.float32)
                    line["max_abs_err"] = float(np.abs(got - want).max())
                    if on_chip:
                        s = timed(fn, q, rest)
                        line["ms_a_call"] = 1e3 * s
                        line["bf16_peak_pct"] = 100.0 * flops / s / BF16_FLOPS
                except Exception as e:  # a shape Mosaic or HBM refuses
                    line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                print(json.dumps(line), flush=True)
                sink.write(json.dumps(line) + "\n")
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
