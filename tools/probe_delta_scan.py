#!/usr/bin/env python3
"""Time ``ops/delta.py chunked_scan`` alone on the chip, XLA's form beside
the kernel ``delta_chunk_scan``, at a cell's published widths:

    python tools/probe_delta_scan.py [--widths olmo ling] [--length 2048]
        [--prompts 1] [--heads-step 6 10] [--side 1 2] [--reps 10]
        [--json chiprun_out/delta_scan.json]

For each width (``olmo``: 30 heads of 96 x 192, one decay a head; ``ling``:
32 of 128 x 128, a decay a key channel) and each form: device ms a call —
``--reps`` calls chained inside ONE program, each fed the one before it, so
neither a dispatch nor the host is in the time —, ns a position and, for
the kernel, the largest absolute difference of its outputs and final state
from XLA's on the same inputs.  ``--rehearse`` runs tiny shapes on the CPU
with the kernel interpreted.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

WIDTHS = {"olmo": (30, 96, 192, False), "ling": (32, 128, 128, True)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--widths", nargs="+", default=sorted(WIDTHS), choices=sorted(WIDTHS))
    ap.add_argument("--length", type=int, default=2048)
    ap.add_argument("--prompts", type=int, default=1)
    ap.add_argument("--heads-step", type=int, nargs="+", default=[0],
                    help="heads a grid step (0: the kernel's own choice)")
    ap.add_argument("--side", type=int, nargs="+", default=[0],
                    help="heads side by side in the inverse (0: the kernel's own choice)")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--json", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.length, args.reps = 128, 2

    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops import delta

    if not args.rehearse and jax.default_backend() != "tpu":
        print("no TPU: a timing here would not be the chip's", file=sys.stderr)
        return 1
    interpret = args.rehearse
    device = jax.devices()[0]
    rows = []
    for name in args.widths:
        heads, dk, dv, channel = WIDTHS[name]
        if args.rehearse:
            heads, dk, dv = 4, 8, 128
        b, length = args.prompts, args.length
        ks = jax.random.split(jax.random.key(len(name)), 5)
        q = delta.l2norm(jax.random.normal(ks[0], (b, length, heads, dk))) * dk ** -0.5
        k = delta.l2norm(jax.random.normal(ks[1], (b, length, heads, dk)))
        v = jax.random.normal(ks[2], (b, length, heads, dv))
        if channel:
            la = -5.0 * jax.nn.sigmoid(
                2.0 * jax.random.normal(ks[3], (b, length, heads, dk)) - 1.0)
            beta = jax.random.uniform(ks[4], (b, length, heads))
        else:
            la = -0.5 * jax.random.uniform(ks[3], (b, length, heads))
            beta = 2.0 * jax.random.uniform(ks[4], (b, length, heads))

        def xla(q, k, v, la, beta):
            before, delta.scan_impl = delta.scan_impl, lambda *_a, **_k: "xla"
            try:
                return delta.chunked_scan(q, k, v, la, beta)
            finally:
                delta.scan_impl = before

        def kernel(hb, side):
            return lambda q, k, v, la, beta: delta._scan_pallas(
                q, k, v, la, beta, interpret=interpret,
                heads_step=hb or None, side=side or None)

        forms = [("xla", xla)] + [
            (f"pallas hb={hb or 'own'} side={side or 'own'}", kernel(hb, side))
            for hb in args.heads_step for side in args.side
            if not hb or (heads % hb == 0 and hb % max(side, 1) == 0)]
        want = None
        for label, form in forms:
            def chained(q, k, v, la, beta, form=form):
                def one(_i, carry):
                    out, state = form(q, k, v + 1e-9 * carry[0], la, beta)
                    return out, state
                zeros = (jnp.zeros((b, length, heads, dv), jnp.float32),
                         jnp.zeros((b, heads, dk, dv), jnp.float32))
                return jax.lax.fori_loop(0, args.reps, one, zeros)

            try:
                got = jax.block_until_ready(jax.jit(form)(q, k, v, la, beta))
                run = jax.jit(chained)
                jax.block_until_ready(run(q, k, v, la, beta))
                t0 = time.perf_counter()
                jax.block_until_ready(run(q, k, v, la, beta))
                ms = 1000.0 * (time.perf_counter() - t0) / args.reps
            except Exception as e:  # noqa: BLE001 — a form the compiler refuses is a finding
                rows.append({"widths": name, "form": label, "error": str(e)[:400]})
                print(json.dumps(rows[-1]), flush=True)
                continue
            row = {"widths": name, "form": label, "prompts": b, "length": length,
                   "ms_a_call": ms, "ns_a_position": 1e6 * ms / (b * length)}
            if want is None:
                want = got
            else:
                row["out_max_abs_diff"] = float(jnp.abs(got[0] - want[0]).max())
                row["state_max_abs_diff"] = float(jnp.abs(got[1] - want[1]).max())
            rows.append(row)
            print(json.dumps(row), flush=True)
    result = {"device": {"platform": device.platform, "kind": device.device_kind},
              "reps": args.reps, "rows": rows}
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result["device"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
