"""Profile the hierarchical KV tier (r22): demote/promote bandwidth
per level plus a churn demo of the tier absorbing HBM reclaim.

Two arms:

1. **Bandwidth table** (default): splits the tier data path into its
   stages and times each one over ``--pages`` real engine pages —
   device→host gather, SRT1 container pack, host-level put/pop,
   container unpack, disk-level spill/read (when ``--spill-dir`` is
   given), and the donated-scatter import back into the pool.  Each
   row reports pages/s and GiB/s so the demote and promote costs can
   be compared level by level (the promote path is pop + unpack +
   scatter; the demote path is gather + pack + put).
2. **``--churn``**: thrashes two session sets through an HBM pool
   sized for ONE session, tier on vs tier off, same traffic.  Tier
   off, every revisit re-pays full prefill; tier on, the evicted
   chains demote to host RAM and promote back at transfer cost.  The
   table shows per-round demotions/promotions and the end-to-end
   revisit speedup, with greedy outputs asserted bit-exact between
   the arms (f32 default — same single-regime caveat as
   tools/profile_prefix_cache.py).

Run:  python tools/profile_kv_tier.py [--pages 16] [--spill-dir /tmp/kvspill]
      python tools/profile_kv_tier.py --churn [--rounds 4] [--dtype f32]
      SELDON_TPU_KV_DTYPE=int8 python tools/profile_kv_tier.py   # int8+scales
"""

import argparse
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _row(name, pages, nbytes, dt):
    gib = nbytes / (1 << 30)
    return (f"{name:<26} {pages:>6} {pages / dt:>10.1f} "
            f"{gib / dt:>9.3f} {dt * 1e3 / max(1, pages):>9.3f}")


def bandwidth(args, eng, np, jnp):
    """Stage-by-stage timing over real resident pages."""
    from seldon_core_tpu.codec.bufview import pack_kv_handoff
    from seldon_core_tpu.models.kvtier import HostKvTier

    # collect the page chain the warm-up request registered
    with eng._lock:
        entries = [
            (e.key, e.parent, e.tokens, page)
            for page, e in sorted(eng.cache.page_entry.items())
        ]
    entries = entries[: args.pages]
    if not entries:
        raise SystemExit("warm-up request registered no prefix pages")
    pages = np.asarray([e[3] for e in entries], np.int32)
    P = len(pages)

    # -- demote side: device->host gather, then per-page container pack
    t0 = time.perf_counter()
    idx = jnp.asarray(pages)
    k = np.asarray(eng.cache.pages_k[:, idx])
    v = np.asarray(eng.cache.pages_v[:, idx])
    ks = vs = None
    if eng.cache.int8:
        ks = np.asarray(eng.cache.scales_k[:, idx])
        vs = np.asarray(eng.cache.scales_v[:, idx])
    t_gather = time.perf_counter() - t0

    blobs = []
    t0 = time.perf_counter()
    for i, (key, parent, toks, _pg) in enumerate(entries):
        payload = {
            "prompt": np.asarray(toks, np.int32),
            "last_logits": np.zeros((1,), np.float32),
            "k": k[:, i:i + 1], "v": v[:, i:i + 1],
            "page_size": eng.page_size, "layout": "flat",
        }
        if ks is not None:
            payload["k_scales"] = ks[:, i:i + 1]
            payload["v_scales"] = vs[:, i:i + 1]
        blobs.append(pack_kv_handoff(payload))
    t_pack = time.perf_counter() - t0
    nbytes = sum(len(b) for b in blobs)

    # -- host level: put then pop (pop includes the CRC-verified unpack)
    tier = HostKvTier(budget_bytes=nbytes * 4)
    t0 = time.perf_counter()
    for (key, parent, toks, _pg), blob in zip(entries, blobs):
        tier.put(key, parent, toks, blob)
    t_put = time.perf_counter() - t0
    t0 = time.perf_counter()
    payloads = [
        tier.pop(key, parent, toks)[0]
        for key, parent, toks, _pg in entries
    ]
    t_pop = time.perf_counter() - t0

    from seldon_core_tpu.codec.bufview import unpack_kv_handoff
    t0 = time.perf_counter()
    for b in blobs:
        unpack_kv_handoff(b)
    t_unpack = time.perf_counter() - t0

    # -- disk level: zero host budget forces every put straight to disk
    t_spill = t_read = None
    if args.spill_dir:
        spill = os.path.join(args.spill_dir, "profile")
        shutil.rmtree(spill, ignore_errors=True)
        dtier = HostKvTier(
            budget_bytes=0, spill_dir=spill,
            spill_budget_bytes=nbytes * 4,
        )
        t0 = time.perf_counter()
        for (key, parent, toks, _pg), blob in zip(entries, blobs):
            dtier.put(key, parent, toks, blob)
        t_spill = time.perf_counter() - t0
        t0 = time.perf_counter()
        for key, parent, toks, _pg in entries:
            assert dtier.pop(key, parent, toks)[2] == "disk"
        t_read = time.perf_counter() - t0
        shutil.rmtree(spill, ignore_errors=True)

    # -- promote side: the donated-scatter import back into the pool,
    # exactly the program _tier_promote_ready runs (back into the SAME
    # pages the chain occupies, so pool content is unchanged)
    kc = np.concatenate([np.asarray(p["k"]) for p in payloads], axis=1)
    vc = np.concatenate([np.asarray(p["v"]) for p in payloads], axis=1)
    fn = eng._import_kv_jit.get(P)
    if fn is None:
        fn = eng._import_kv_jit[P] = eng._build_import_kv(P)

    def scatter():
        kd = jnp.asarray(kc, eng.cache.pool_dtype)
        vd = jnp.asarray(vc, eng.cache.pool_dtype)
        if eng.cache.int8:
            kd = (kd, jnp.asarray(np.concatenate(
                [np.asarray(p["k_scales"]) for p in payloads], axis=1)))
            vd = (vd, jnp.asarray(np.concatenate(
                [np.asarray(p["v_scales"]) for p in payloads], axis=1)))
        pk, pv = fn(eng.params, *eng._kv_args(), kd, vd, jnp.asarray(pages))
        eng.cache.store(pk, pv)

    scatter()  # compile outside the timed region
    t0 = time.perf_counter()
    scatter()
    t_scatter = time.perf_counter() - t0

    hdr = (f"{'stage':<26} {'pages':>6} {'pages/s':>10} "
           f"{'GiB/s':>9} {'ms/page':>9}")
    print(f"\nKV tier bandwidth — {P} pages x {eng.page_size} tokens, "
          f"{nbytes / (1 << 20):.1f} MiB of containers, "
          f"pool={'int8+scales' if eng.cache.int8 else args.dtype}")
    print(hdr)
    print("-" * len(hdr))
    print(_row("demote: gather (d2h)", P, nbytes, t_gather))
    print(_row("demote: container pack", P, nbytes, t_pack))
    print(_row("demote: host put", P, nbytes, t_put))
    if t_spill is not None:
        print(_row("demote: disk spill", P, nbytes, t_spill))
    print(_row("promote: host pop+unpack", P, nbytes, t_pop))
    if t_read is not None:
        print(_row("promote: disk read+unpack", P, nbytes, t_read))
    print(_row("promote: unpack alone", P, nbytes, t_unpack))
    print(_row("promote: scatter (h2d)", P, nbytes, t_scatter))


def churn(args, make_engine, np):
    """Two session sets through a one-session pool, tier on vs off."""
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, args.vocab, size=(args.prompt,)).astype(np.int32)
        for _ in range(2)
    ]

    def run(offload: bool):
        per_req = -(-(args.prompt + args.new) // args.page_size)
        eng = make_engine(offload=offload, num_pages=per_req + 2)
        outs, walls = [], []
        for rnd in range(args.rounds):
            for p in prompts:  # A then B: each admission evicts the other
                t0 = time.perf_counter()
                s = eng.submit(p, max_new_tokens=args.new)
                eng.run()
                walls.append(time.perf_counter() - t0)
                outs.append(np.asarray(s.result))
        stats = eng.engine_stats()
        eng.close()
        return outs, walls, stats

    on_outs, on_walls, on = run(offload=True)
    off_outs, off_walls, _ = run(offload=False)
    for a, b in zip(on_outs, off_outs):
        assert np.array_equal(a, b), \
            "greedy outputs must be bit-exact tier-on vs tier-off"

    # first visit of each session is a cold miss in both arms; every
    # later visit is the returning-session shape the tier exists for
    revisit_on = sum(on_walls[2:])
    revisit_off = sum(off_walls[2:])
    hits = on["kv_tier_host_hits"] + on["kv_tier_disk_hits"]
    total = hits + on["kv_tier_misses"]
    print(f"\nchurn — 2 sessions x {args.rounds} rounds through a "
          f"one-session pool ({args.prompt}-token prompts)")
    print(f"  tier on : revisit wall {revisit_on:.2f}s   "
          f"demotions={on['kv_tier_demotions']} "
          f"promotions={on['kv_tier_promotions']} "
          f"host_hits={on['kv_tier_host_hits']} "
          f"hit_rate={hits / max(1, total):.2f} "
          f"bytes_demoted={on['kv_tier_bytes_demoted']}")
    print(f"  tier off: revisit wall {revisit_off:.2f}s (full re-prefill "
          f"every visit)")
    print(f"  promote speedup: {revisit_off / max(1e-9, revisit_on):.2f}x — "
          f"outputs bit-exact both arms")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pages", type=int, default=16,
                    help="pages in the bandwidth sample")
    ap.add_argument("--prompt", type=int, default=512,
                    help="prompt tokens per session")
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=4,
                    help="--churn revisit rounds per session")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--dtype", choices=("f32", "bf16"), default="f32")
    ap.add_argument("--spill-dir", default="",
                    help="also time the disk level under this directory")
    ap.add_argument("--churn", action="store_true",
                    help="run the two-session thrash demo instead")
    args = ap.parse_args()

    if args.churn and args.dtype != "f32":
        ap.error("--churn asserts bit-exactness; use --dtype f32")

    spill_tmp = None
    if args.spill_dir == "":
        args.spill_dir = spill_tmp = tempfile.mkdtemp(prefix="kvtier_prof_")

    os.environ["SELDON_TPU_KV_HOST_BUDGET_GIB"] = "2"

    import numpy as np
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.paged import PagedEngine
    from seldon_core_tpu.models.transformer import TransformerLM

    dtype = jnp.float32 if args.dtype == "f32" else jnp.bfloat16
    cfg = dict(
        vocab_size=args.vocab, d_model=args.d_model,
        num_layers=args.layers, num_heads=args.heads, max_len=args.max_len,
    )
    lm = TransformerLM(dtype=dtype, **cfg)
    params = lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]

    def make_engine(offload: bool, num_pages=None):
        os.environ["SELDON_TPU_KV_OFFLOAD"] = "1" if offload else "0"
        return PagedEngine(
            params, dtype=dtype, page_size=args.page_size,
            max_slots=2, steps_per_call=8, num_pages=num_pages,
            prefix_cache=True, **cfg,
        )

    try:
        if args.churn:
            churn(args, make_engine, np)
        else:
            need = args.pages * args.page_size
            eng = make_engine(offload=True)
            rng = np.random.default_rng(0)
            prompt = rng.integers(0, args.vocab, size=(need,)).astype(np.int32)
            s = eng.submit(prompt, max_new_tokens=args.new)
            eng.run()
            assert s.result is not None
            bandwidth(args, eng, np, jnp)
            eng.close()
    finally:
        os.environ.pop("SELDON_TPU_KV_OFFLOAD", None)
        if spill_tmp:
            shutil.rmtree(spill_tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
