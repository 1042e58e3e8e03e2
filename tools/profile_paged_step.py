"""Decompose the paged decode step's per-step cost on the real chip.

The serving lane runs ~4 ms/step at 16 streams (d512/L8) while the
decode compute is ~10 us — the step is op-overhead-bound, and the docs
attribute the remaining paged-vs-scan gap to per-step fixed cost
(docs/architecture.md, r4 table).  This harness times the step's
components in isolation, each as a scan over N iterations inside one
jit (one dispatch, one readback — per-call cost cannot pollute the
per-step number):

  forward   — the paged transformer apply only
  write     — the 2xB-slot DUS pool write only
  sample    — RNG split + sample_batch only
  bookkeep  — the where/mask carry updates only
  full      — the real engine step

Run:  python tools/profile_paged_step.py [--steps 64] [--slots 16]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--pages", type=int, default=128)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel degree (0 = SELDON_TPU_TP "
                    "default, 1 = force single-chip)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.paged import PagedEngine
    from seldon_core_tpu.models.transformer import TransformerLM

    lm = TransformerLM(
        vocab_size=args.vocab, d_model=args.d_model,
        num_layers=args.layers, num_heads=args.heads,
        max_len=args.max_len, dtype=jnp.bfloat16)
    init_params = lm.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]

    eng = PagedEngine(
        init_params,
        vocab_size=args.vocab,
        d_model=args.d_model,
        num_layers=args.layers,
        num_heads=args.heads,
        max_len=args.max_len,
        page_size=args.page_size,
        num_pages=args.pages,
        max_slots=args.slots,
        steps_per_call=args.steps,
        tp=args.tp or None,
    )

    B, L = args.slots, args.layers
    h, hd = args.heads, args.d_model // args.heads
    params = eng.params
    # match the engine's pool layout (flat (L, pages, ps, d); split
    # (L, pages, ps, h, hd) under the grid kernel impl) AND its
    # sharding — under a TP mesh the chunk program pins heads-sharded
    # pools on its signature, so replicated zeros would pay a reshard
    # copy every timed call.  Created ALREADY sharded (jit with
    # out_shardings, same pattern as shard_decode_state): an eager
    # jnp.zeros would materialise the full pool on one device first.
    def _make_pool(ref):
        return jax.jit(
            lambda: jnp.zeros(ref.shape, ref.dtype),
            out_shardings=ref.sharding,
        )()

    pk = _make_pool(eng.cache.pages_k)
    pv = _make_pool(eng.cache.pages_v)
    logits = jnp.zeros((B, args.vocab), jnp.float32)
    # every slot mid-generation at a distinct length
    lengths = jnp.asarray(
        np.random.default_rng(0).integers(64, 256, size=B), jnp.int32)
    horizon = 8  # pages visible per slot (256/32 rounded up, pow2)
    block_tables = jnp.asarray(
        np.arange(1, B * horizon + 1).reshape(B, horizon) % args.pages,
        jnp.int32)
    keys = jax.random.key_data(
        jax.vmap(jax.random.PRNGKey)(jnp.arange(B, dtype=jnp.uint32)))
    done = jnp.zeros((B,), bool)
    emitted = jnp.zeros((B,), jnp.int32)
    max_new = jnp.full((B,), 10_000, jnp.int32)
    temps = jnp.zeros((B,), jnp.float32)
    top_ks = jnp.zeros((B,), jnp.int32)
    eos_ids = jnp.full((B,), -1, jnp.int32)

    token0 = jnp.zeros((B,), jnp.int32)

    def forward_only(params, pk, pv, lengths):
        def step(carry, _):
            lengths, acc = carry
            new_logits, nk, nv = eng.module.apply(
                {"params": params}, token0[:, None],
                jnp.minimum(lengths[:, None], args.max_len - 1),
                pk, pv, block_tables, lengths,
            )
            # fold outputs into the carry so nothing is dead code
            acc = acc + new_logits[:, 0, 0] + nk.sum() + nv.sum()
            return (lengths + 1, acc), ()

        (lengths, acc), _ = jax.lax.scan(
            step, (lengths, jnp.zeros((B,), jnp.float32)), None,
            length=args.steps)
        return acc

    def write_only(pk, pv, lengths):
        nk = jnp.ones((L, B, 1, h, hd), jnp.bfloat16)
        nv = nk

        def step(carry, _):
            pk, pv, lengths = carry
            pk, pv = eng.cache.write(
                pk, pv, nk, nv, block_tables, lengths,
                jnp.ones((B, 1), bool))
            return (pk, pv, lengths + 1), ()

        (pk, pv, lengths), _ = jax.lax.scan(
            step, (pk, pv, lengths), None, length=args.steps)
        return pk.sum() + pv.sum()

    def sample_only(logits, keys):
        def step(carry, _):
            logits, keys = carry
            typed = jax.random.wrap_key_data(keys)
            split = jax.vmap(jax.random.split)(typed)
            token = eng._sample_batch(logits, split[:, 1], temps, top_ks)
            keys = jax.random.key_data(split[:, 0])
            logits = logits + token[:, None].astype(jnp.float32) * 1e-9
            return (logits, keys), ()

        (logits, keys), _ = jax.lax.scan(
            step, (logits, keys), None, length=args.steps)
        return logits.sum()

    def bookkeep_only(logits, lengths, done, emitted):
        def step(carry, _):
            logits, lengths, done, emitted = carry
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            active = ~done
            token = jnp.where(active, token, eos_ids)
            emitted = emitted + active.astype(jnp.int32)
            done = done | (token == eos_ids) | (emitted >= max_new)
            logits = jnp.where(active[:, None], logits, logits)
            lengths = lengths + active.astype(jnp.int32)
            return (logits, lengths, done, emitted), token

        (logits, lengths, done, emitted), toks = jax.lax.scan(
            step, (logits, lengths, done, emitted), None, length=args.steps)
        return toks.sum() + lengths.sum()

    ident_perm = jnp.arange(args.slots, dtype=jnp.int32)
    full = eng._get_chunk(args.steps, ((args.slots, horizon),))

    def barrier(out):
        # completion barrier: fetch a value that depends on the
        # computation (block_until_ready alone was once observed to
        # return early on a remote backend; the fetch is always honest)
        return np.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[:1]

    def timed(name, fn, *a, n_steps=None, **kw):
        n_steps = n_steps or args.steps
        barrier(fn(*a, **kw))  # compile + drain
        best = float("inf")
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            barrier(fn(*a, **kw))
            best = min(best, time.perf_counter() - t0)
        per_step_us = best / n_steps * 1e6
        print(f"{name:>12}: {best*1e3:8.2f} ms total  {per_step_us:8.1f} us/step"
              f"  ({args.slots/best*n_steps:,.0f} tok/s)")
        return best

    print(f"B={B} L={L} d={args.d_model} steps={args.steps} "
          f"tp={eng.tp_degree} (one dispatch per timing)")
    timed("forward", jax.jit(forward_only), params, pk, pv, lengths)
    timed("write", jax.jit(write_only), pk, pv, lengths)
    timed("sample", jax.jit(sample_only), logits, keys)
    timed("bookkeep", jax.jit(bookkeep_only), logits, lengths, done, emitted)
    # full chunk donates pk/pv; pass copies so reruns stay valid
    def full_fresh():
        return full(params, jnp.copy(pk), jnp.copy(pv), logits, lengths,
                    block_tables, keys, done, emitted, max_new, temps,
                    top_ks, eos_ids, ident_perm)
    timed("full", full_fresh)

    # -------- two-point slope: a single timing conflates per-call
    # (dispatch + readback) and per-step cost.  Marginal per-step
    # cost = (t(4N) - t(N)) / 3N; the intercept is the per-call
    # penalty.  This is the number kernel work should attack.
    print("\ntwo-point marginal per-step cost (per-call term removed):")
    hi = 4 * args.steps

    def slope(name, build):
        t_lo = timed(f"{name}@{args.steps}", *build(args.steps))
        t_hi = timed(f"{name}@{hi}", *build(hi), n_steps=hi)
        per_step = (t_hi - t_lo) / (hi - args.steps)
        print(f"{name:>10}: {per_step*1e6:8.1f} us/step marginal, "
              f"{(t_lo - per_step*args.steps)*1e3:6.1f} ms per-call intercept"
              f"  ({args.slots/per_step:,.0f} tok/s asymptotic)")

    def build_forward(n):
        def fo(params, pk, pv, lengths):
            def step(carry, _):
                lengths, acc = carry
                new_logits, nk, nv = eng.module.apply(
                    {"params": params}, token0[:, None],
                    jnp.minimum(lengths[:, None], args.max_len - 1),
                    pk, pv, block_tables, lengths,
                )
                acc = acc + new_logits[:, 0, 0] + nk.sum() + nv.sum()
                return (lengths + 1, acc), ()

            (lengths, acc), _ = jax.lax.scan(
                step, (lengths, jnp.zeros((B,), jnp.float32)), None, length=n)
            return acc
        return jax.jit(fo), params, pk, pv, lengths

    def build_full(n):
        fn = eng._get_chunk(n, ((args.slots, horizon),))

        def run():
            return fn(params, jnp.copy(pk), jnp.copy(pv), logits, lengths,
                      block_tables, keys, done, emitted, max_new, temps,
                      top_ks, eos_ids, ident_perm)
        return (run,)

    slope("forward", build_forward)
    slope("full", build_full)


if __name__ == "__main__":
    main()
