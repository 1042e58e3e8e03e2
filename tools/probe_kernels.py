"""Compile every Pallas kernel in ops/kernels.py at serving widths and
check it against a host float64 oracle.

What Mosaic accepts is for the chip to say: CPU tests run these kernels
in interpret mode, which checks the arithmetic and nothing about
lowering.  This tool is the on-chip counterpart — one process, every
kernel variant the serving paths can select, each compiled (never
interpreted) in its own try block so one refusal does not hide the
next.  Widths follow the StreamingLM smoke (h8 x hd64, page 64, 16
slots, max_len 1024) and the ResNet/ViT servers.

The paged stream kernel is also probed at the benchmark's two
geometries (GPT-2-large's 20 heads of 64, OLMoE's 16 of 128): its
``max_abs_err`` against the float64 oracle is the number a change to
the kernel's arithmetic must not raise.  ``--root <checkout>`` imports
``seldon_core_tpu`` from another checkout (a ``git archive`` of the
parent commit), so the SAME cases, inputs and oracle give the parent's
kernel's errors to lay beside the change's (PERF.md §6, PR 27).

Prints one line per case and writes the full record (compiler message
included) to ``chiprun_out/kernel_probe.json`` (``--out`` names
another file).  Exit code 1 when any case failed, 2 when the backend is
not a TPU (pass ``--interpret`` to rehearse the script itself on CPU).

Run:  python tools/probe_kernels.py [--interpret] [--only NAME ...]
      [--root .bench_checkout/parent --out chiprun_out/kernel_probe_parent.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

H, HD, PS, SLOTS, MAX_LEN = 8, 64, 64, 16, 1024
PAGES_PER = MAX_LEN // PS
NUM_PAGES = SLOTS * PAGES_PER + 1


LAYERS, LAYER = 2, 1  # a whole pool of two layers; the kernel reads the second


def _paged_inputs(rng, pool_dtype, h, hd):
    """A whole pool + block tables with ragged lengths: empty lanes,
    one single token, exactly one page, one token more, partial pages
    and a full table.  The device pool is ``(L, pages, ps, h*hd)``; the
    oracle gets layer ``LAYER`` of what the pool stores, heads apart."""
    import jax.numpy as jnp

    batch = SLOTS
    q = rng.normal(size=(batch, h, hd)).astype(np.float32) * hd ** -0.5
    pk = rng.normal(size=(LAYERS, NUM_PAGES, PS, h, hd)).astype(np.float32)
    pv = rng.normal(size=(LAYERS, NUM_PAGES, PS, h, hd)).astype(np.float32)
    tables = rng.permutation(np.arange(1, NUM_PAGES))[: batch * PAGES_PER]
    tables = tables.reshape(batch, PAGES_PER).astype(np.int32)
    lengths = rng.integers(1, MAX_LEN, size=(batch,)).astype(np.int32)
    lengths[:7] = (0, 1, PS, MAX_LEN - 1, PS + 1, MAX_LEN, 0)
    scales = None
    if pool_dtype == jnp.int8:
        sk = np.abs(pk).max(axis=(2, 3, 4)) / 127.0
        sv = np.abs(pv).max(axis=(2, 3, 4)) / 127.0
        pk_q = np.clip(np.round(pk / sk[..., None, None, None]), -127, 127)
        pv_q = np.clip(np.round(pv / sv[..., None, None, None]), -127, 127)
        scales = (sk.astype(np.float32), sv.astype(np.float32))
        pk_dev, pv_dev = jnp.asarray(pk_q, jnp.int8), jnp.asarray(pv_q, jnp.int8)
        pk, pv = pk_q * sk[..., None, None, None], pv_q * sv[..., None, None, None]
    else:
        pk_dev, pv_dev = jnp.asarray(pk, pool_dtype), jnp.asarray(pv, pool_dtype)
        # the oracle sees what the pool stores, not what was drawn
        pk = np.asarray(pk_dev.astype(jnp.float32))
        pv = np.asarray(pv_dev.astype(jnp.float32))
    pk_dev = pk_dev.reshape(LAYERS, NUM_PAGES, PS, h * hd)
    pv_dev = pv_dev.reshape(LAYERS, NUM_PAGES, PS, h * hd)
    return q, pk[LAYER], pv[LAYER], pk_dev, pv_dev, tables, lengths, scales


def _paged_oracle(q, pk, pv, tables, lengths):
    batch, pages = tables.shape
    gk = pk[tables].reshape(batch, pages * PS, *pk.shape[2:]).astype(np.float64)
    gv = pv[tables].reshape(batch, pages * PS, *pv.shape[2:]).astype(np.float64)
    s = np.einsum("bhd,bkhd->bhk", q.astype(np.float64), gk)
    mask = np.arange(pages * PS)[None, :] < lengths[:, None]
    s = np.where(mask[:, None, :], s, -np.inf)
    m = s.max(-1)
    with np.errstate(invalid="ignore"):
        w = np.where(mask[:, None, :], np.exp(s - m[..., None]), 0.0)
    l = w.sum(-1)
    out = np.einsum("bhk,bkhd->bhd", w, gv) / np.where(l > 0, l, 1.0)[..., None]
    return out, l


def _paged_case(pool="bf16", lora=False, h=H, hd=HD):
    d = h * hd

    def run():
        import jax
        import jax.numpy as jnp

        from seldon_core_tpu.ops.kernels import paged_attention_decode

        rng = np.random.default_rng(0)
        pool_dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32, "int8": jnp.int8}[pool]
        q, pk, pv, pk_dev, pv_dev, tables, lengths, scales = _paged_inputs(
            rng, pool_dtype, h, hd)
        kw = {}
        if scales is not None:
            kw["kv_scales"] = tuple(jnp.asarray(s) for s in scales)
        delta_ref = None
        q_eff = q  # what the oracle attends with
        if lora:
            rank, slots = 8, 4
            x = rng.normal(size=(SLOTS, d)).astype(np.float32)
            a = rng.normal(size=(LAYERS, slots, d, rank)).astype(np.float32) * 0.05
            b = rng.normal(size=(LAYERS, slots, rank, 3 * d)).astype(np.float32) * 0.05
            a[:, 0] = 0.0  # slot 0 = no adapter
            b[:, 0] = 0.0
            idx = (np.arange(SLOTS) % slots).astype(np.int32)
            q_scale = hd ** -0.5
            kw["lora"] = (
                jnp.asarray(x), jnp.asarray(np.swapaxes(a, -1, -2)),
                jnp.asarray(b), jnp.asarray(idx), q_scale,
            )
            delta_ref = np.einsum("bd,bdr,bre->be", x, a[LAYER][idx], b[LAYER][idx])
            # the kernel receives the UNADAPTED pre-scaled q and folds
            # the delta's q third itself
            q_eff = q + q_scale * delta_ref[:, :d].reshape(SLOTS, h, hd)
        fn = jax.jit(lambda *a_: paged_attention_decode(
            *a_, layer=LAYER, page_size=PS, **kw))
        outs = fn(jnp.asarray(q), pk_dev, pv_dev, jnp.asarray(tables),
                  jnp.asarray(lengths))
        outs = jax.block_until_ready(outs)
        acc, l = np.asarray(outs[0], np.float64), np.asarray(outs[2], np.float64)
        ref, _ = _paged_oracle(q_eff, pk, pv, tables, lengths)
        got = acc / np.where(l > 0, l, 1.0)[..., None]
        live = lengths > 0
        err = float(np.max(np.abs(got[live] - ref[live])))
        detail = {"max_abs_err": err}
        # a dead lane must carry the neutral flash state, not NaN
        ok = err < 2e-3 and np.all(l[~live] == 0.0) and np.all(np.isfinite(got))
        if lora:
            d_err = float(np.max(np.abs(np.asarray(outs[3], np.float64) - delta_ref)))
            detail["delta_max_abs_err"] = d_err
            ok = ok and d_err < 1e-3
        return ok, detail

    return run


def _flash_case(seq, causal):
    def run():
        import jax
        import jax.numpy as jnp

        from seldon_core_tpu.ops.kernels import flash_attention

        rng = np.random.default_rng(1)
        shape = (2, seq, H, HD)
        q, k, v = (rng.normal(size=shape).astype(np.float32) for _ in range(3))
        out = jax.block_until_ready(jax.jit(
            lambda a, b, c: flash_attention(a, b, c, causal=causal)
        )(*map(jnp.asarray, (q, k, v))))
        s = np.einsum("bqhd,bkhd->bhqk", q.astype(np.float64), k) / np.sqrt(HD)
        if causal:
            s = np.where(np.tril(np.ones((seq, seq), bool)), s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        ref = np.einsum("bhqk,bkhd->bqhd", w / w.sum(-1, keepdims=True), v)
        err = float(np.max(np.abs(np.asarray(out, np.float64) - ref)))
        # default-precision f32 dots run as bf16 MXU passes on the chip
        return err < 5e-2, {"max_abs_err": err}

    return run


def _normalize_case(side):
    def run():
        import jax
        import jax.numpy as jnp

        from seldon_core_tpu.ops.kernels import fused_normalize, imagenet_affine

        rng = np.random.default_rng(2)
        x = rng.integers(0, 256, size=(8, side, side, 3)).astype(np.uint8)
        scale, shift = imagenet_affine()
        try:
            out = jax.block_until_ready(jax.jit(
                lambda a: fused_normalize(a, scale, shift, out_dtype=jnp.float32)
            )(jnp.asarray(x)))
        except ValueError as e:
            # refused before tracing: the shape cannot be selected at all
            return "VMEM" in str(e), {"fenced": str(e)[:200]}
        ref = x.astype(np.float64) * scale + shift
        err = float(np.max(np.abs(np.asarray(out, np.float64) - ref)))
        return err < 1e-4, {"max_abs_err": err}

    return run


def _int8_matmul_case():
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops.kernels import int8_matmul, quantize_weights

    rng = np.random.default_rng(3)
    x = rng.normal(size=(100, 500)).astype(np.float32)  # ragged M and K
    w_q, scale = quantize_weights(rng.normal(size=(500, 2048)).astype(np.float32))
    out = jax.block_until_ready(jax.jit(int8_matmul)(
        jnp.asarray(x), jnp.asarray(w_q), jnp.asarray(scale)))
    ref = x.astype(np.float64) @ (w_q.astype(np.float64) * scale)
    rel = float(np.max(np.abs(np.asarray(out, np.float64) - ref)) / np.max(np.abs(ref)))
    return rel < 2e-2, {"max_rel_err": rel}


CASES = {
    "paged_stream_bf16": _paged_case(),
    "paged_stream_f32": _paged_case(pool="f32"),
    "paged_stream_int8kv": _paged_case(pool="int8"),
    "paged_stream_lora": _paged_case(lora=True),
    # the benchmark's geometries: GPT-2-large, OLMoE
    "paged_stream_bf16_20x64": _paged_case(h=20, hd=64),
    "paged_stream_bf16_16x128": _paged_case(h=16, hd=128),
    "paged_stream_int8kv_20x64": _paged_case(pool="int8", h=20, hd=64),
    "paged_stream_lora_20x64": _paged_case(lora=True, h=20, hd=64),
    "flash_256": _flash_case(256, causal=False),
    "flash_256_causal": _flash_case(256, causal=True),
    "flash_197_vit": _flash_case(197, causal=False),
    "fused_normalize_32": _normalize_case(32),
    "fused_normalize_224": _normalize_case(224),
    "int8_matmul_ragged": _int8_matmul_case,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--interpret", action="store_true",
                    help="rehearse on a non-TPU backend (interpret mode)")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--root", default=ROOT,
                    help="checkout to import seldon_core_tpu from")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "kernel_probe.json"))
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.root))

    from seldon_core_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.interpret:
        print(f"probe_kernels: backend is {dev.platform!r}, not a TPU "
              "(--interpret rehearses the script on CPU)", file=sys.stderr)
        return 2

    record = {"device": device, "jax": jax.__version__,
              "root": os.path.abspath(args.root), "cases": {}}
    failed = 0
    for name, fn in CASES.items():
        if args.only and name not in args.only:
            continue
        t0 = time.perf_counter()
        try:
            ok, detail = fn()
            entry = {"ok": bool(ok), **detail}
        except Exception as e:  # noqa: BLE001 — the compiler's refusal IS the result
            entry = {"ok": False, "error": f"{type(e).__name__}: {e}"[:4000],
                     "traceback": traceback.format_exc()[-6000:]}
        entry["seconds"] = round(time.perf_counter() - t0, 2)
        record["cases"][name] = entry
        failed += not entry["ok"]
        brief = {k: v for k, v in entry.items() if k != "traceback"}
        if "error" in brief:
            brief["error"] = brief["error"][:300]
        print(f"{'PASS' if entry['ok'] else 'FAIL'} {name} {json.dumps(brief)}", flush=True)

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({"device": device, "failed": failed,
                      "cases": len(record["cases"])}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
