"""Time the paged-decode kernel alone, at a cell's shapes, with the
lanes' lengths drawn as the cell holds them.

One call of ``ops/kernels.paged_attention_decode`` per layer inside a
scan of ``--steps`` decode steps over a whole ``(layers, pages,
page_size, heads*head_dim)`` pool — one dispatch, one readback, so the
number is the kernel's device time and not the host's.  Printed per
arm: time a call, a *loop slot* (lanes x table width: what the page
loop's static trip count covers) and a *live page* (sum over lanes of
``ceil(length / page_size)``: what the cache holds), and the share of
the DMA roofline (bytes of the live pages, K and V, over the chip's
819 GB/s, over the time).  If a call's time follows the slots, the loop
computes on dead slots; if it follows the live pages, it does not
(PERF.md §6, PR 27).

Lengths: ``--live-lanes`` of the ``--lanes`` hold a context, the rest
hold 0 tokens (a released slot).  ``--ctx`` is one length (``342``), a
range drawn uniformly (``512-992``) or a list cycled over the live
lanes (``130,342,700``).  ``--full`` adds the all-lanes-full arm
(every lane at table width x page size) for comparison.

Off-TPU the kernel runs interpreted: the arithmetic is checked, the
times mean nothing and are labelled so.

Run:  python tools/profile_paged_kernel.py --geometry gpt2 --lanes 16
      --table-pages 8 --ctx 342 --full
      python tools/profile_paged_kernel.py --geometry olmoe --lanes 32
      --table-pages 16 --live-lanes 4 --ctx 512-992
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9  # TPU v5e (benchmarks/harness/peaks.py)

# heads, head_dim, layers: the two geometries the benchmark serves
GEOMETRIES = {"gpt2": (20, 64, 36), "olmoe": (16, 128, 8)}


def parse_ctx(text, live, cap, rng):
    """The live lanes' lengths from ``--ctx``: ``N``, ``LO-HI`` (uniform
    draw) or ``A,B,C`` (cycled), clipped to the table's capacity."""
    if "-" in text:
        lo, hi = (int(x) for x in text.split("-"))
        out = rng.integers(lo, hi + 1, size=live)
    else:
        vals = [int(x) for x in text.split(",")]
        out = np.asarray([vals[i % len(vals)] for i in range(live)])
    return np.minimum(out, cap).astype(np.int32)


def _time_arm(fn, args, calls, repeats):
    """Best-of-N wall over one jit of ``calls`` kernel calls: us a call."""
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e6


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--geometry", choices=sorted(GEOMETRIES), default="gpt2")
    ap.add_argument("--heads", type=int, default=None)
    ap.add_argument("--head-dim", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--num-pages", type=int, default=513)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--lanes", type=int, default=16)
    ap.add_argument("--table-pages", type=int, default=8)
    ap.add_argument("--live-lanes", type=int, default=None,
                    help="lanes that hold a context (default: all)")
    ap.add_argument("--ctx", default="342",
                    help="N | LO-HI | A,B,C: cached tokens of a live lane")
    ap.add_argument("--full", action="store_true",
                    help="also time every lane at the table's capacity")
    ap.add_argument("--kv-dtype", choices=("bf16", "int8"), default="bf16")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None,
                    help="append one JSON line per arm to this file")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops.kernels import paged_attention_decode

    g_heads, g_hd, g_layers = GEOMETRIES[args.geometry]
    h = args.heads or g_heads
    hd = args.head_dim or g_hd
    layers = args.layers or g_layers
    ps, lanes, width = args.page_size, args.lanes, args.table_pages
    num_pages = args.num_pages
    d = h * hd
    live = lanes if args.live_lanes is None else args.live_lanes
    on_tpu = jax.default_backend() == "tpu"
    rng = np.random.default_rng(args.seed)

    quantized = args.kv_dtype == "int8"
    pool_dtype = jnp.int8 if quantized else jnp.bfloat16
    shape = (layers, num_pages, ps, d)
    key = jax.random.key(args.seed)
    kk, kv, kq = jax.random.split(key, 3)
    if quantized:
        pk = jax.random.randint(kk, shape, -127, 128, jnp.int8)
        pv = jax.random.randint(kv, shape, -127, 128, jnp.int8)
        scales = (jnp.full((layers, num_pages), 0.01, jnp.float32),) * 2
    else:
        pk = jax.random.normal(kk, shape, pool_dtype)
        pv = jax.random.normal(kv, shape, pool_dtype)
        scales = None
    q = jax.random.normal(kq, (lanes, h, hd), jnp.bfloat16) * hd ** -0.5
    # distinct pages a lane, as the allocator hands them out (page 0 is
    # the trash page); more table slots than pages wrap around
    tables = jnp.asarray(
        1 + rng.permutation(lanes * width).reshape(lanes, width)
        % (num_pages - 1), jnp.int32)

    steps = args.steps

    @jax.jit
    def arm(q, pk, pv, tables, lengths):
        def step(c, _):
            def layer(c, li):
                acc, _m, el = paged_attention_decode(
                    c, pk, pv, tables, lengths, layer=li, page_size=ps,
                    kv_scales=scales)[:3]
                out = acc / jnp.maximum(el, 1e-9)[..., None]
                return (0.5 * c + 0.5 * out.astype(c.dtype)), None
            c, _ = jax.lax.scan(layer, c, jnp.arange(layers, dtype=jnp.int32))
            return c, None
        out, _ = jax.lax.scan(step, q, None, length=steps)
        return out

    cap = width * ps
    arms = []
    lens = np.zeros((lanes,), np.int32)
    lens[rng.permutation(lanes)[:live]] = parse_ctx(args.ctx, live, cap, rng)
    arms.append((f"live {live}/{lanes} ctx {args.ctx}", lens))
    if args.full:
        arms.append(("all lanes full", np.full((lanes,), cap, np.int32)))

    lane_note = "TPU" if on_tpu else "interpret (CORRECTNESS ONLY, not a timing)"
    print(f"paged-decode kernel — kv={args.kv_dtype} "
          f"{h}x{hd} layers={layers} pool={num_pages}x{ps} "
          f"table={lanes}x{width} lane={lane_note}")
    page_bytes = 2 * ps * d * np.dtype(pool_dtype).itemsize
    for name, lens in arms:
        us = _time_arm(arm, (q, pk, pv, tables, jnp.asarray(lens)),
                       steps * layers, args.repeats)
        slots = lanes * width
        pages = int(np.sum(-(-lens // ps)))
        roof = pages * page_bytes / HBM_BYTES_PER_S * 1e6
        rec = {
            "arm": name, "geometry": f"{h}x{hd}",
            "kv": args.kv_dtype, "lanes": lanes, "table_pages": width,
            "live_lanes": int((lens > 0).sum()),
            "tokens": int(lens.sum()), "loop_slots": slots,
            "live_pages": pages, "us_per_call": round(us, 2),
            "us_per_slot": round(us / slots, 4),
            "us_per_live_page": round(us / max(pages, 1), 4),
            "dma_roofline_pct": round(100.0 * roof / us, 2),
            "on_tpu": on_tpu,
        }
        print(f"  {name:28s} {us:9.1f} us a call | {us / slots:7.3f} us a "
              f"slot ({slots}) | {us / max(pages, 1):7.3f} us a live page "
              f"({pages}) | {100.0 * roof / us:5.1f} % of the DMA roofline")
        if args.json:
            os.makedirs(os.path.dirname(os.path.abspath(args.json)), exist_ok=True)
            with open(args.json, "a") as f:
                f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
