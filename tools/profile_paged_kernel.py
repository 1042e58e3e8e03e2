"""Profile the fused Pallas paged-decode lane against the XLA gather
lane on the raw attention step (r18, ROADMAP 1).

Times `paged_attention_decode` (kernel) vs the dense gather+softmax
XLA program on identical pool state — N iterations inside one jit per
arm (one dispatch, one readback, so per-call cost cannot pollute
the per-step number) — and prints the capacity-side arithmetic next to
the timing: HBM bytes/step at bf16 vs int8 page storage and the Mosaic
grid-step count of each kernel impl.

Off-TPU the kernel runs in interpret mode: a correctness harness, not
a timing one — the tool still prints the host-arithmetic terms but
labels the timing columns accordingly.  The bench's compact
`paged_kernel_x` gate (>= 1.5) is adjudicated from the engine-level
`kernel_lane` blob on a TPU run, not from this micro-probe; this tool
exists to decompose WHERE a regression lives (kernel step vs engine
overhead) when that gate moves.

Run:  python tools/profile_paged_kernel.py [--streams 16] [--ctx 512]
      [--impl stream|grid] [--kv-dtype bf16|int8] [--steps 32]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time_arm(fn, args, steps, repeats):
    """Best-of-N wall over a scan-of-steps jit: returns per-step us."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best / steps * 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, default=16)
    ap.add_argument("--ctx", type=int, default=512)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--layers", type=int, default=8,
                    help="layer count for the HBM bytes/step term "
                    "(the micro-probe times ONE layer's attention)")
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--impl", choices=("stream", "grid"), default="stream")
    ap.add_argument("--kv-dtype", choices=("bf16", "int8"), default="bf16")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()

    os.environ["SELDON_TPU_PAGED_KERNEL_IMPL"] = args.impl

    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.paged import paged_hbm_accounting
    from seldon_core_tpu.ops.kernels import paged_attention_decode

    B, h, hd, ps = args.streams, args.heads, args.head_dim, args.page_size
    pages_per = -(-args.ctx // ps)
    num_pages = B * pages_per + 1
    on_tpu = jax.default_backend() == "tpu"

    rng = np.random.default_rng(0)
    dt = jnp.bfloat16
    q = jnp.asarray(rng.normal(size=(B, h, hd)), dt)
    # ONE layer of pool, held as the whole-pool form the wrapper takes
    # (L = 1), in the layout the impl reads: flat for stream, split for
    # grid — the form each rests in under the engine (pool_is_flat)
    pool_shape = ((num_pages, ps, h * hd) if args.impl == "stream"
                  else (num_pages, ps, h, hd))
    pk = jnp.asarray(rng.normal(size=pool_shape), dt)
    pv = jnp.asarray(rng.normal(size=pool_shape), dt)
    tables = jnp.asarray(
        1 + np.arange(B * pages_per).reshape(B, pages_per) % (num_pages - 1),
        jnp.int32)
    lengths = jnp.full((B,), args.ctx, jnp.int32)

    kv_scales = None
    if args.kv_dtype == "int8":
        def quantise(pool):
            f = pool.astype(jnp.float32).reshape(num_pages, -1)
            amax = jnp.maximum(jnp.max(jnp.abs(f), axis=1) / 127.0, 1e-8)
            q = jnp.clip(jnp.round(f / amax[:, None]), -127, 127)
            return q.astype(jnp.int8).reshape(pool.shape), amax

        pk, amax = quantise(pk)
        pv, vmax = quantise(pv)
        kv_scales = (amax, vmax)

    steps = args.steps

    @jax.jit
    def kernel_arm(q, pk, pv, tables, lengths):
        def step(c, _):
            acc, m, el = paged_attention_decode(
                c, pk[None], pv[None], tables, lengths, layer=0,
                page_size=ps,
                kv_scales=(None if kv_scales is None
                           else tuple(s[None] for s in kv_scales)))
            return (acc / jnp.maximum(el, 1e-9)[..., None]).astype(c.dtype), 0
        out, _ = jax.lax.scan(step, q, None, length=steps)
        return out

    @jax.jit
    def xla_arm(q, pk, pv, tables, lengths):
        def step(c, _):
            gk = pk[tables].reshape(B, pages_per * ps, h, hd)
            gv = pv[tables].reshape(B, pages_per * ps, h, hd)
            if kv_scales is not None:
                gk = (gk.astype(jnp.float32)
                      * kv_scales[0][tables].reshape(B, pages_per, 1, 1, 1)
                      .repeat(ps, 1).reshape(B, pages_per * ps, 1, 1))
                gv = (gv.astype(jnp.float32)
                      * kv_scales[1][tables].reshape(B, pages_per, 1, 1, 1)
                      .repeat(ps, 1).reshape(B, pages_per * ps, 1, 1))
            s = jnp.einsum("bhd,bkhd->bhk", c.astype(jnp.float32),
                           gk.astype(jnp.float32))
            mask = jnp.arange(pages_per * ps)[None, :] < lengths[:, None]
            s = jnp.where(mask[:, None, :], s, -jnp.inf)
            w = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bhk,bkhd->bhd", w, gv.astype(jnp.float32))
            return out.astype(c.dtype), 0
        out, _ = jax.lax.scan(step, q, None, length=steps)
        return out

    arm_args = (q, pk, pv, tables, lengths)
    kern_us = _time_arm(kernel_arm, arm_args, steps, args.repeats)
    xla_us = _time_arm(xla_arm, arm_args, steps, args.repeats)

    acct_kw = dict(
        num_layers=args.layers, d_model=h * hd, page_size=ps,
        ctx_len=args.ctx, streams=B, chunk_impl="pool",
        dtype_bytes=2)
    bf16_bytes = paged_hbm_accounting(**acct_kw)["pool_bytes"]
    int8_bytes = paged_hbm_accounting(kv_dtype="int8", **acct_kw)["pool_bytes"]
    grid_steps = B if args.impl == "stream" else B * pages_per

    lane = "TPU" if on_tpu else "interpret (CORRECTNESS ONLY, not a timing)"
    print(f"paged-decode kernel probe — impl={args.impl} "
          f"kv_dtype={args.kv_dtype} lane={lane}")
    print(f"  streams={B} ctx={args.ctx} heads={h} head_dim={hd} "
          f"page_size={ps} pages/seq={pages_per}")
    print(f"  kernel per-step: {kern_us:10.1f} us")
    print(f"  XLA    per-step: {xla_us:10.1f} us")
    print(f"  kernel_x       : {xla_us / max(kern_us, 1e-9):10.2f}x"
          + ("" if on_tpu else "   (interpret-mode ratio — not citable)"))
    print(f"  mosaic grid steps/launch: {grid_steps}"
          f"  (DMA loop depth {pages_per} per lane)" )
    print(f"  HBM pool bytes ({args.layers}L model): "
          f"bf16 {bf16_bytes:,}  int8 {int8_bytes:,}  "
          f"ratio {bf16_bytes / max(int8_bytes, 1):.2f}x")


if __name__ == "__main__":
    main()
