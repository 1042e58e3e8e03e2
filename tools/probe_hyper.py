#!/usr/bin/env python3
"""Time ``ops/hyper.py``'s pair alone on the chip, XLA's form beside the
Pallas kernels, at a configuration's widths:

    python tools/probe_hyper.py [--streams 4] [--width 3584]
        [--positions 4096 3072 128] [--reps 20] [--json chiprun_out/x.json]

For each size and form: device ms of the pair (``hyper_pre`` then
``hyper_post`` fed by it) and of ``hyper_post`` alone, ``--reps`` calls
chained inside ONE program (a call a dispatch measured the host: 0.5 ms
whatever the size), the least time the bytes allow
(``hyper.position_bytes`` over 819 GB/s) and the share of it reached, and
the largest difference between the two forms' outputs.  ``--rehearse`` runs tiny shapes on the
CPU with the kernels interpreted.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

HBM_BYTES_PER_S = 819e9  # TPU v5e (benchmarks/harness/peaks.py)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--width", type=int, default=3584)
    ap.add_argument("--positions", type=int, nargs="+",
                    default=[4096, 3072, 2048, 1024, 128])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--json", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.width, args.positions, args.reps = 128, [256, 8], 2

    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.ops import hyper

    if args.rehearse:
        hyper.backend = lambda: "interpret"
    elif jax.default_backend() != "tpu":
        print("no TPU: a timing here would not be the chip's", file=sys.stderr)
        return 1
    n, c = args.streams, args.width
    k = hyper.coefficients(n)
    kw = dict(iters=20, eps=1e-6, lo=-30.0, hi=30.0)
    keys = jax.random.split(jax.random.key(0), 5)
    params = {"phi": jax.random.uniform(keys[0], (k, n * c), jnp.float32, -1, 1)
              * (3.0 / (n * c)) ** 0.5,
              "bias": jax.random.uniform(keys[1], (k,), jnp.float32, -0.1, 0.1),
              "scale": jax.random.uniform(keys[2], (3,), jnp.float32, 0.5, 1.5)}
    out = []
    for t in args.positions:
        x = jax.random.normal(keys[3], (n, t, c), jnp.float32)
        y = jax.random.normal(keys[4], (t, c), jnp.float32).astype(jnp.bfloat16)
        got = {}
        for impl in ("xla", "pallas"):
            h, h_post, h_res = hyper.hyper_pre(x, params, impl=impl, **kw)
            mixed = hyper.hyper_post(x, y, h_post, h_res, impl=impl)
            got[impl] = [np.asarray(a) for a in (h, h_post, h_res, mixed)]

            # the device's time, not the host's dispatch: ``reps`` calls
            # inside one program, each fed by the one before it
            def pair(x, p, impl=impl):
                def body(_, rows):
                    h, a, b = hyper.hyper_pre(rows, p, impl=impl, **kw)
                    return hyper.hyper_post(rows, h.astype(jnp.bfloat16) * 0.25,
                                            a * 0.5, b, impl=impl)
                return jax.lax.fori_loop(0, args.reps, body, x)

            def post_only(x, y, a, b, impl=impl):
                return jax.lax.fori_loop(
                    0, args.reps,
                    lambda _, rows: hyper.hyper_post(rows, y, a, b, impl=impl), x)

            timed = {}
            for name, fn, operands in (
                    ("pair", jax.jit(pair, donate_argnums=0), (params,)),
                    ("post", jax.jit(post_only, donate_argnums=0),
                     (y, h_post * 0.5, h_res))):
                jax.block_until_ready(fn(x + 0, *operands))  # compile
                rows = jax.block_until_ready(x + 0)
                t0 = time.perf_counter()
                jax.block_until_ready(fn(rows, *operands))
                timed[name] = (time.perf_counter() - t0) / args.reps * 1e3
            pair_ms, post_ms = timed["pair"], timed["post"]
            least_pair = hyper.position_bytes(n, c) * t / HBM_BYTES_PER_S * 1e3
            least_post = (hyper.position_bytes(n, c) - 4 * n * c) * t / HBM_BYTES_PER_S * 1e3
            row = {"positions": t, "impl": impl, "pair_ms": pair_ms, "post_ms": post_ms,
                   "pre_ms_by_difference": pair_ms - post_ms,
                   "pair_roofline_pct": 100 * least_pair / pair_ms,
                   "post_roofline_pct": 100 * least_post / post_ms,
                   **({"rehearsal_on_the_cpu": True} if args.rehearse else {})}
            out.append(row)
            print(json.dumps(row), flush=True)
        diff = max(float(np.abs(a - b).max()) for a, b in zip(got["xla"], got["pallas"]))
        print(json.dumps({"positions": t, "max_abs_diff_xla_pallas": diff}), flush=True)
        out.append({"positions": t, "max_abs_diff_xla_pallas": diff})
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
