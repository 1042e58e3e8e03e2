#!/usr/bin/env python3
"""The readings behind the limits of ``benchmarks/harness/kinds/
generation_share.py``: what a configuration's plain float32 reference
(GigaChat3.1's, with ``--config longcat-flash-omni`` LongCat-Flash's, with
``--config dots3-note-prev --positions 3334 --judged 256`` dots3-note's:
there the last ``--judged`` positions are judged (without it every one
past ``index_topk``), as the cell's sample is — a prompt of 3,078 and 256
served tokens — each variant is also put through the cell's own kind
(``harness/kinds/generation_share_sparse.py verdict``: ``ok``, ``near``),
and the wrong programs are the reference's own ``variant``s — the
selection left out, the window one position short or wide, the gate or
the rescale left out; with ``--config smallthinker-21b-a3b --positions
4864 --judged 256`` SmallThinker's: a prompt of 4,608 past the 4,096
window and 256 judged tokens, through ``generation_share``'s two limits
as ``harness/kinds/generation_share_window.py`` applies them, the wrong
programs ``reference/smallthinker.py``'s ``VARIANTS`` — the router fed
from the post-attention norm, ``silu`` for ``relu``, a full layer
rotated, a window layer left whole, a K/V head mapped ``h % 4``; with
``--config olmo-hybrid-7b --positions 634 --judged 128`` Olmo-Hybrid's: a
prompt of 506 (its cell's longest judged) and 128 judged tokens, through
the two limits of ``harness/kinds/generation_state.py``, the wrong
programs ``reference/olmo_hybrid.py``'s ``VARIANTS`` — the state kept in
bfloat16, beta without its factor 2, the decay left at 1, the full layers
rotated, the norms moved to the sub-layers' inputs; with ``--config
ling-3.0-flash --positions 1152 --judged 128`` Ling-3.0-flash's: a prompt
of 1,024 and 128 judged tokens through the two limits of
``harness/kinds/generation_share_state.py``, the wrong programs
``reference/ling3_flash.py``'s ``VARIANTS`` — the decay averaged over a
head's channels, the softplus gate, beta times 2, the state kept in
bfloat16, the head-wise gate left out; with ``--config jamba2-3b
--positions 634 --judged 128`` Jamba2-3B's: a prompt of 506 (its cell's
longest judged) and 128 judged tokens through the two limits of
``harness/kinds/generation_state.py``, the wrong programs
``reference/jamba.py``'s ``VARIANTS`` — the three inner norms left out,
``A_log`` without its ``-exp``, the softplus, the convolution's bias or
``D x`` left out, the state kept in bfloat16, the attention layers
rotated, an untied head, the one K/V head read as twenty; with
``--config xing4.0-29b-a4b --positions 4352 --judged 256`` Xing4.0's: the
longest prompt of its cell and 256 judged tokens, through the two limits
of its kind (``harness/kinds/generation_share_whole.py verdict``), the
wrong programs
``reference/xing4_wrong.py``'s mixings — the Sinkhorn stopped at one
iteration, H_post without its 2, the input-dependent term dropped, H_res
the identity, H_pre applied after the norm, bfloat16 coefficients — and
the mean at the exit, which gives the same logits)
says of the tokens that the same forward pass serves in a lower
precision, or with a fault.  CPU only (``JAX_PLATFORMS=cpu``), 1-2
minutes a variant at 512 positions; nothing here is a device number.

    python tools/precision_readings.py --seed 3000005003 --positions 512

One sequence of seeded token ids is run through the configuration's
``reference/<name>.py`` (the judge) and through this file's copy of its
forward pass with roundings put in, and each variant's greedy token at every
position is judged as the kind judges a served one: its gap under the
reference's top-1 in deviations of that position's logits.  Printed per
variant: positions whose token is not the top-1, positions *off* (gap
over ``TIE_STDS``), the largest gap, the gaps' 5th and 50th percentile,
and the logits' own distance from float32's (rms over the vocabulary, in
deviations: the median position).

Variants (``--variants`` picks, default all):

* ``stated``: what the configuration states — every matmul's operands
  and result rounded to bfloat16, norms, softmax, router and the
  residual stream in float32;
* ``bf16-residual``: the step nearest below — the residual stream
  rounded to bfloat16 too;
* ``e4m3``: the operands of every weight matmul rounded to 8 bits
  (float8 e4m3, scaled to the row's / the matrix's largest value), the
  attention's own products in bfloat16;
* ``no-bias``, ``no-shared``: the stated precision with the correction
  bias left out of the selection | without the shared expert (for
  LongCat-Flash, which has none: without the identity experts' part);
* ``early-row``: the reference's own top-1 of the position before (a
  stale page, a shifted row).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]

VARIANTS = ("stated", "bf16-residual", "e4m3", "no-bias", "no-shared", "early-row")


def roundings(residual_bf16: bool, e4m3: bool):
    """``(f32, b16, act, w, res)``: a leaf as float32; a matmul's result
    rounded to bfloat16; an operand of a weight matmul, a weight as the
    matmul reads it, and the residual stream, each as the variant
    rounds it."""
    import jax.numpy as jnp

    def f32(a):
        return jnp.asarray(a).astype(jnp.float32)

    def b16(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    def q8(a, axis):
        unit = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0 + 1e-30
        return (a / unit).astype(jnp.float8_e4m3fn).astype(jnp.float32) * unit

    def act(a):
        return q8(a, -1) if e4m3 else b16(a)

    def w(a):
        return q8(f32(a), None) if e4m3 else f32(a)

    def res(a):
        return b16(a) if residual_bf16 else a

    return f32, b16, act, w, res


def rounded_logits_longcat(ref, params, model, tokens, residual_bf16=False, e4m3=False,
                           bias=True, shared=True):
    """``reference/longcat_flash.py logits`` with its matmuls' operands
    and results rounded: the same equations, line for line (``shared``
    false leaves the identity experts' part out)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eps, heads = model["rms_norm_eps"], model["num_attention_heads"]
    nope, rdim, rank = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                        model["kv_lora_rank"])
    d = model["hidden_size"]
    held, offset = model["n_routed_experts"], model.get("expert_offset", 0)
    real = ref.real_experts(model)
    s_q, s_kv = (d / model["q_lora_rank"]) ** 0.5, (d / rank) ** 0.5
    scale = (nope + rdim) ** -0.5
    freq = jnp.asarray(1.0 / model["rope_theta"] ** (np.arange(0, rdim, 2) / rdim),
                       jnp.float32)
    f32, b16, act, w, res = roundings(residual_bf16, e4m3)

    def rms_norm(x, scale_):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * f32(scale_)

    def rotate(x, pos):
        x1, x2 = x[..., 0::2], x[..., 1::2]
        ang = pos.astype(jnp.float32).reshape(-1, *([1] * (x.ndim - 2)), 1) * freq
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def swiglu(h, gate, up, down):
        h = act(h)
        return act(b16(jax.nn.silu(h @ w(gate)) * (h @ w(up)))) @ w(down)

    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        n = tokens.shape[0]
        pos = jnp.arange(n)
        x = res(f32(params["tok_embed"]["embedding"][tokens]))
        for layer in range(model["num_layers"]):
            p = params[f"block_{layer}"]
            for i in (0, 1):
                h = rms_norm(x, p[f"attn_norm_{i}"]["scale"])
                c_q = rms_norm(b16(act(h) @ w(p[f"q_a_{i}"]["kernel"])),
                               p[f"q_a_norm_{i}"]["scale"])
                q = (b16(act(c_q) @ w(p[f"q_b_{i}"]["kernel"])) * s_q).reshape(
                    n, heads, nope + rdim)
                kva = b16(act(h) @ w(p[f"kv_a_{i}"]["kernel"]))
                c_kv = b16(rms_norm(kva[:, :rank], p[f"kv_a_norm_{i}"]["scale"]) * s_kv)
                k_r = b16(rotate(kva[:, rank:], pos))
                q_nope, q_r = q[..., :nope], b16(rotate(q[..., nope:], pos))
                k_nope = b16(jnp.einsum("cr,hrn->hcn", c_kv, f32(p[f"kv_b_k_{i}"])))
                v = b16(jnp.einsum("cr,hrv->hcv", c_kv, f32(p[f"kv_b_v_{i}"])))
                out = []
                for lo in range(0, n, ref.QUERY_BLOCK):
                    hi = min(n, lo + ref.QUERY_BLOCK)
                    s = (jnp.einsum("qhn,hcn->hqc", q_nope[lo:hi], k_nope)
                         + jnp.einsum("qhr,cr->hqc", q_r[lo:hi], k_r)) * scale
                    s = jnp.where((pos[None, :] <= pos[lo:hi, None])[None], s, -jnp.inf)
                    out.append(jnp.einsum("hqc,hcv->qhv", b16(jax.nn.softmax(s, axis=-1)), v))
                attn = b16(jnp.concatenate(out, axis=0).reshape(n, -1))
                x = res(x + b16(act(attn) @ w(p[f"attn_proj_{i}"]["kernel"])))

                g = rms_norm(x, p[f"ffn_norm_{i}"]["scale"])
                if i == 0:
                    probs = jax.nn.softmax(g @ f32(p["router"]), axis=-1)
                    score_bias = np.asarray(p["score_bias"], np.float32)
                    weights, chosen = ref.route(model, probs,
                                                score_bias if bias else 0 * score_bias)
                    m = jnp.asarray(np.where(chosen >= real, weights, 0.0).sum(-1))[:, None] * g \
                        if shared else jnp.zeros_like(x)
                    for e in range(held):
                        rows, slot = np.nonzero(chosen == e + offset)
                        if rows.size:
                            part = swiglu(g[rows], p["experts_gate"][e], p["experts_up"][e],
                                          p["experts_down"][e])
                            m = m.at[rows].add(part * weights[rows, slot][:, None])
                x = res(x + swiglu(g, p[f"mlp_gate_{i}"], p[f"mlp_up_{i}"], p[f"mlp_down_{i}"]))
            x = res(x + m)
        return rms_norm(x, params["final_norm"]["scale"]) @ f32(params["head"]["kernel"])


def rounded_logits(ref, params, model, tokens, residual_bf16=False, e4m3=False,
                   bias=True, shared=True):
    """``reference/deepseek_v3.py logits`` with its matmuls' operands and
    results rounded: the same equations, line for line."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eps, heads = model["rms_norm_eps"], model["num_attention_heads"]
    nope, rdim, rank = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                        model["kv_lora_rank"])
    held, offset = model["n_routed_experts"], model.get("expert_offset", 0)
    scale = ref.softmax_scale(model)
    freq = jnp.asarray(ref.inv_freq(model), jnp.float32)

    f32, b16, act, w, res = roundings(residual_bf16, e4m3)

    def rms_norm(x, scale_):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * f32(scale_)

    def rotate(x, pos):
        x1, x2 = x[..., 0::2], x[..., 1::2]
        ang = pos.astype(jnp.float32).reshape(-1, *([1] * (x.ndim - 2)), 1) * freq
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def swiglu(h, gate, up, down):
        h = act(h)
        return act(b16(jax.nn.silu(h @ w(gate)) * (h @ w(up)))) @ w(down)

    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        n = tokens.shape[0]
        pos = jnp.arange(n)
        x = res(f32(params["tok_embed"]["embedding"][tokens]))
        for i in range(model["num_hidden_layers"]):
            p = params[f"block_{i}"]
            h = rms_norm(x, p["attn_norm"]["scale"])
            c_q = rms_norm(b16(act(h) @ w(p["q_a"]["kernel"])), p["q_a_norm"]["scale"])
            q = b16(act(c_q) @ w(p["q_b"]["kernel"])).reshape(n, heads, nope + rdim)
            kva = b16(act(h) @ w(p["kv_a"]["kernel"]))
            c_kv = b16(rms_norm(kva[:, :rank], p["kv_a_norm"]["scale"]))
            k_r = b16(rotate(kva[:, rank:], pos))
            q_nope, q_r = q[..., :nope], b16(rotate(q[..., nope:], pos))
            k_nope = b16(jnp.einsum("cr,hrn->hcn", c_kv, f32(p["kv_b_k"])))
            v = b16(jnp.einsum("cr,hrv->hcv", c_kv, f32(p["kv_b_v"])))
            out = []
            for lo in range(0, n, ref.QUERY_BLOCK):
                hi = min(n, lo + ref.QUERY_BLOCK)
                s = (jnp.einsum("qhn,hcn->hqc", q_nope[lo:hi], k_nope)
                     + jnp.einsum("qhr,cr->hqc", q_r[lo:hi], k_r)) * scale
                s = jnp.where((pos[None, :] <= pos[lo:hi, None])[None], s, -jnp.inf)
                out.append(jnp.einsum("hqc,hcv->qhv", b16(jax.nn.softmax(s, axis=-1)), v))
            attn = b16(jnp.concatenate(out, axis=0).reshape(n, -1))
            x = res(x + b16(act(attn) @ w(p["attn_proj"]["kernel"])))

            h = rms_norm(x, p["ffn_norm"]["scale"])
            if i < model["first_k_dense_replace"]:
                x = res(x + swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"]))
                continue
            scores = jax.nn.sigmoid(h @ f32(p["router"]))
            score_bias = np.asarray(p["score_bias"], np.float32)
            weights, chosen = ref.route(model, scores, score_bias if bias else 0 * score_bias)
            y = swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"]) \
                if model["n_shared_experts"] and shared else jnp.zeros_like(x)
            for e in range(held):
                rows, slot = np.nonzero(chosen == e + offset)
                if rows.size:
                    part = swiglu(h[rows], p["experts_gate"][e], p["experts_up"][e],
                                  p["experts_down"][e])
                    y = y.at[rows].add(part * weights[rows, slot][:, None])
            x = res(x + y)
        return rms_norm(x, params["final_norm"]["scale"]) @ f32(params["head"]["kernel"])


def rounded_logits_dots3(ref, params, model, tokens, residual_bf16=False, e4m3=False,
                         **_unused):
    """``reference/dots3_note.py logits`` with its matmuls' operands and
    results rounded as the variant says: the stated precision (bfloat16
    operands and results, the indexer's q, key and products among them;
    norms, softmax, gate, router and the residual stream float32) or
    8-bit operands.  The selection is made on the rounded scores, so a
    rounding that moves a row's 2,048th place moves its set."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    f32, b16, act, w, res = roundings(residual_bf16, e4m3)
    eps, d = model["rms_norm_eps"], model["hidden_size"]

    def mm(a, m):
        return b16(act(a) @ w(m))

    def rms_norm(v, scale_):
        return v / jnp.sqrt((v * v).mean(-1, keepdims=True) + eps) * f32(scale_)

    def swiglu(h, gate, up, down):
        return mm(b16(jax.nn.silu(mm(h, gate)) * mm(h, up)), down)

    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        n = tokens.shape[0]
        pos = jnp.arange(n)
        x = f32(params["tok_embed"]["embedding"][tokens])
        for i in range(model["num_hidden_layers"]):
            p, kind = params[f"block_{i}"], ref.kinds_of(model)[i]
            sz = ref.sizes(model, kind)
            heads, rank, q_rank = (sz["num_attention_heads"], sz["kv_lora_rank"],
                                   sz["q_lora_rank"])
            nope, rdim = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"]
            freq = jnp.asarray(
                1.0 / sz["rope_theta"] ** (np.arange(0, rdim, 2) / rdim), jnp.float32)

            def rotate(v, at=pos, freq=freq):
                v1, v2 = v[..., 0::2], v[..., 1::2]
                ang = at.astype(jnp.float32).reshape(-1, *([1] * (v.ndim - 2)), 1) * freq
                cos, sin = jnp.cos(ang), jnp.sin(ang)
                return jnp.concatenate([v1 * cos - v2 * sin, v2 * cos + v1 * sin], axis=-1)

            y = rms_norm(x, p["attn_norm"]["scale"])
            c_q = rms_norm(mm(y, p["q_a"]["kernel"]), p["q_a_norm"]["scale"])
            q = b16(mm(c_q, p["q_b"]["kernel"]) * (d / q_rank) ** 0.5).reshape(
                n, heads, nope + rdim)
            kva = mm(y, p["kv_a"]["kernel"])
            c_kv = b16(rms_norm(kva[:, :rank], p["kv_a_norm"]["scale"]) * (d / rank) ** 0.5)
            k_r = b16(rotate(kva[:, rank:]))
            q_nope, q_r = q[..., :nope], b16(rotate(q[..., nope:]))
            k_nope = b16(jnp.einsum("cr,hrn->hcn", act(c_kv), w(p["kv_b_k"])))
            v = b16(jnp.einsum("cr,hrv->hcv", act(c_kv), w(p["kv_b_v"])))
            at = np.arange(n)
            seen = at[None, :] <= at[:, None]
            if kind == "window":
                seen &= at[None, :] > at[:, None] - model["sliding_window_size"]
            else:
                ih, idim = model["index_n_heads"], model["index_head_dim"]
                q_i = mm(c_q, p["index_q"]["kernel"]).reshape(n, ih, idim)
                q_i = b16(jnp.concatenate([rotate(q_i[..., :rdim]), q_i[..., rdim:]], -1))
                k_i = mm(y, p["index_k"]["kernel"])
                mean = k_i.mean(-1, keepdims=True)
                k_i = ((k_i - mean) / jnp.sqrt(((k_i - mean) ** 2).mean(-1, keepdims=True) + eps)
                       * f32(p["index_k_norm"]["scale"]) + f32(p["index_k_norm"]["bias"]))
                k_i = b16(jnp.concatenate([rotate(k_i[:, :rdim]), k_i[:, rdim:]], -1))
                w_i = mm(y, p["index_w"]["kernel"])
                index = []
                for lo in range(0, n, ref.QUERY_BLOCK):
                    hit = jax.nn.relu(jnp.einsum(
                        "qjd,cd->qjc", act(q_i[lo:lo + ref.QUERY_BLOCK]), act(k_i)))
                    index.append(jnp.einsum("qjc,qj->qc", hit, w_i[lo:lo + ref.QUERY_BLOCK]))
                seen = ref.select(model, np.asarray(jnp.concatenate(index, 0))
                                  * np.float32(ih ** -0.5 * idim ** -0.5))
            seen = jnp.asarray(seen)
            out = []
            for lo in range(0, n, ref.QUERY_BLOCK):
                hi = min(n, lo + ref.QUERY_BLOCK)
                s = (jnp.einsum("qhn,hcn->hqc", q_nope[lo:hi], k_nope)
                     + jnp.einsum("qhr,cr->hqc", q_r[lo:hi], k_r)) * (nope + rdim) ** -0.5
                prob = b16(jax.nn.softmax(jnp.where(seen[None, lo:hi], s, -jnp.inf), axis=-1))
                out.append(b16(jnp.einsum("hqc,hcv->qhv", prob, v)))
            gate = jax.nn.sigmoid(mm(y, p["attn_gate"]["kernel"]))
            attn = b16(jnp.concatenate(out, 0) * gate[:, :, None]).reshape(n, -1)
            x = res(x + mm(attn, p["attn_proj"]["kernel"]))
            h = rms_norm(x, p["ffn_norm"]["scale"])
            if i < model["first_k_dense_replace"]:
                x = res(x + swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"]))
                continue
            weights, picked = ref.route(
                model, jax.nn.sigmoid(h @ f32(p["router"])), p["score_bias"])
            offset = model.get("expert_offset", 0)
            routed = jnp.zeros_like(x)
            for e in range(model["n_routed_experts"]):
                rows, slot = np.nonzero(picked == e + offset)
                if rows.size:
                    part = swiglu(h[rows], p["experts_gate"][e], p["experts_up"][e],
                                  p["experts_down"][e])
                    routed = routed.at[rows].add(part * weights[rows, slot][:, None])
            x = res(x + routed + swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"]))
        xn = rms_norm(x, params["final_norm"]["scale"])
        return mm(xn, params["head"]["kernel"])


def smallthinker_rounding(e4m3: bool):
    """``reference/smallthinker.py``'s ``rounding=`` hook: ``(operand,
    result, weight)`` as the stated precision rounds them (bfloat16
    operands and results, float32 accumulation; the router, norms,
    softmax and residual stream float32), or with the operands of every
    weight matmul in 8 bits."""
    f32, b16, act, w, _res = roundings(False, e4m3)
    return (act if e4m3 else b16), b16, w


# dots3-note's wrong programs, each the reference itself with one thing
# changed (``reference/dots3_note.py`` ``variant``), in float32
DOTS3_WRONG = {"no-selection": "no_selection", "window-short": "window_short",
               "window-wide": "window_wide", "no-gate": "no_gate",
               "no-rescale": "no_rescale"}


def smallthinker_readings(args, config, ref, params, tokens) -> int:
    """The stated precision, 8-bit operands, each wrong program of the
    reference's own and a stale row, judged at the last ``--judged``
    positions (every one without it) as the cell's kind judges them."""
    import numpy as np

    from harness import manifest

    model, n = config["model"], len(tokens)
    kind = manifest.module("harness/kinds", config["kind"])
    tail = {"tail": args.judged} if args.judged else {}
    judged = args.judged or n
    plain = np.asarray(ref.logits(params, model, tokens, **tail))
    std = plain.std(-1)
    names = (["stated", "e4m3", *ref.VARIANTS, "early-row"]
             if args.variants == ",".join(VARIANTS) else args.variants.split(","))
    for name in names:
        far = None
        if name == "early-row":
            served = np.concatenate([plain[:1].argmax(-1), plain[:-1].argmax(-1)])
        else:
            how = ({"variant": name} if name in ref.VARIANTS
                   else {"rounding": smallthinker_rounding(name == "e4m3")})
            got = np.asarray(ref.logits(params, model, tokens, **tail, **how))
            served = got.argmax(-1)
            far = float(np.median(np.sqrt(((got - plain) ** 2).mean(-1)) / std))
        gaps = (plain.max(-1) - plain[np.arange(judged), served]) / std
        off = int((gaps > kind.TIE_STDS).sum())
        ok = off <= kind.OFF_SHARE_MAX * judged and float(gaps.max()) <= kind.WORST_GAP_STDS
        print(json.dumps({"variant": name, "seed": args.seed, "positions": judged, "ok": bool(ok),
                          "not_top1": int((gaps > 0).sum()), "off": off,
                          "off_share_pct": 100.0 * off / judged,
                          "worst_gap_stds": float(gaps.max()),
                          "gap_stds_p05_p50": [float(q) for q in np.quantile(gaps, [0.05, 0.5])],
                          "logits_rms_stds": far}), flush=True)
    return 0


def xing4_readings(args, config, ref, params, tokens) -> int:
    """The stated precision, 8-bit operands, each wrong mixing of
    ``reference/xing4_wrong.py`` and a stale row, judged at the last
    ``--judged`` positions (every one without it) by the cell's kind."""
    import numpy as np

    from harness import manifest
    from reference import xing4_wrong

    model, n = config["model"], len(tokens)
    kind = manifest.module("harness/kinds", config["kind"])
    tail = {"tail": args.judged} if args.judged else {}
    judged = args.judged or n
    plain = np.asarray(ref.logits(params, model, tokens, **tail))
    std = plain.std(-1)
    wrongs = [*xing4_wrong.WRONG, *xing4_wrong.SAME_LOGITS]
    names = (["stated", "e4m3", *wrongs, "early-row"]
             if args.variants == ",".join(VARIANTS) else args.variants.split(","))
    for name in names:
        far = None
        if name == "early-row":
            served = np.concatenate([plain[:1].argmax(-1), plain[:-1].argmax(-1)])
        elif name in wrongs:
            with xing4_wrong.wrong(name, model, ref) as other:
                got = np.asarray(ref.logits(params, other, tokens, **tail))
        else:
            got = np.asarray(ref.logits(
                params, model, tokens, **tail,
                rounding=smallthinker_rounding(name == "e4m3")))
        if name != "early-row":
            served = got.argmax(-1)
            far = float(np.median(np.sqrt(((got - plain) ** 2).mean(-1)) / std))
        gaps = (plain.max(-1) - plain[np.arange(judged), served]) / std
        v = kind.verdict(gaps)  # the cell's own comparison
        print(json.dumps({"variant": name, "seed": args.seed, "positions": judged,
                          "ok": bool(v["ok"]), "not_top1": int((gaps > 0).sum()),
                          "off": v["off"], "off_share_pct": 100.0 * v["off_share"],
                          "far": v["far"], "far_share_pct": 100.0 * v["far_share"],
                          "worst_gap_stds": float(gaps.max()),
                          "gap_stds_p50_p90": [float(q) for q in np.quantile(gaps, [0.5, 0.9])],
                          "logits_rms_stds": far}), flush=True)
    return 0


def main() -> int:
    import numpy as np

    from harness import manifest
    from harness.kinds.generation_share import TIE_STDS

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="gigachat3.1-702b-a36b")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--positions", type=int, default=512)
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--judged", type=int, default=0,
                    help="judge the last N positions only (dots3-note)")
    args = ap.parse_args()
    config = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs", args.config + ".json"))
    model = config["model"]
    ref = manifest.module("reference", config["reference"])
    params = ref.make_params(model, args.seed)
    n = args.positions
    tokens = np.random.default_rng(args.seed).integers(0, model["vocab_size"], size=n).tolist()
    dots3 = config["reference"] == "dots3_note"
    if config["reference"] in ("smallthinker", "olmo_hybrid", "ling3_flash", "jamba"):
        # (Olmo-Hybrid, Ling-3.0-flash, Jamba: the same hooks — ``rounding=``
        # and the reference's own ``VARIANTS`` — under their kinds' two limits)
        return smallthinker_readings(args, config, ref, params, tokens)
    if config["reference"] == "xing4":
        return xing4_readings(args, config, ref, params, tokens)
    # (dots3-note: judged where a row has over index_topk candidates and a
    # slid window, as the cell's sample is: the last --judged positions, or
    # every one past index_topk)
    first = (n - args.judged if args.judged else model["index_topk"] + 1) if dots3 else 0
    tail = {"tail": n - first} if dots3 and args.judged else {}
    plain = np.asarray(ref.logits(params, model, tokens, **tail))
    plain = plain if tail else plain[first:]
    std = plain.std(-1)
    how = {"stated": {}, "bf16-residual": {"residual_bf16": True}, "e4m3": {"e4m3": True},
           "no-bias": {"bias": False}, "no-shared": {"shared": False}}
    names = args.variants.split(",")
    if dots3 and args.variants == ",".join(VARIANTS):
        names = ["stated", "e4m3", *DOTS3_WRONG, "early-row"]
    for name in names:
        if name == "early-row":
            served, far = np.concatenate([plain[:1].argmax(-1), plain[:-1].argmax(-1)]), None
        elif dots3 and name in DOTS3_WRONG:
            got = np.asarray(ref.logits(params, model, tokens, variant=DOTS3_WRONG[name],
                                        **tail))
            got = got if tail else got[first:]
            served = got.argmax(-1)
            far = float(np.median(np.sqrt(((got - plain) ** 2).mean(-1)) / std))
        elif dots3:
            got = np.asarray(rounded_logits_dots3(
                ref, params, model, tokens, **how[name]))[first:]
            served = got.argmax(-1)
            far = float(np.median(np.sqrt(((got - plain) ** 2).mean(-1)) / std))
        else:
            rounded = (rounded_logits_longcat if config["reference"] == "longcat_flash"
                       else rounded_logits)
            got = np.asarray(rounded(ref, params, model, tokens, **how[name]))
            served = got.argmax(-1)
            far = float(np.median(np.sqrt(((got - plain) ** 2).mean(-1)) / std))
        gaps = (plain.max(-1) - plain[np.arange(n - first), served]) / std
        kind = {}
        if dots3:  # ... and through the cell's own comparison
            v = manifest.module("harness/kinds", config["kind"]).verdict(gaps)
            kind = {"ok": v["ok"], "near": v["near"], "near_share_pct": 100 * v["near_share"]}
        print(json.dumps({"variant": name, "seed": args.seed, "positions": n - first, **kind,
                          "not_top1": int((gaps > 0).sum()), "off": int((gaps > TIE_STDS).sum()),
                          "off_share_pct": 100.0 * float((gaps > TIE_STDS).mean()),
                          "worst_gap_stds": float(gaps.max()),
                          "gap_stds_p05_p50": [float(q) for q in np.quantile(gaps, [0.05, 0.5])],
                          "logits_rms_stds": far}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
