"""Plain reference for Xing4.0-29B-A4B (``model_type: xing4_0``): the
DeepSeek-V3 block at Xing4.0's sizes under a residual of ``hc_mult`` = 4
rows, mixed round every attention and every FFN by manifold-constrained
hyper-connections (mHC, arXiv:2512.24880, on hyper-connections,
arXiv:2409.19606).  The whole forward pass in float32 ``jax.numpy`` at
``highest`` matmul precision: naive attention, no cache, no kernel, no
batching, a loop over the experts; the mixing written here from the
equations, one small function a step.

Per token, with ``n`` = ``hc_mult`` rows ``X`` in ``R^{n x C}`` and, a
sub-layer (``F`` = attention, then ``F`` = FFN; ``F`` includes its own
RMSNorm with its learned scale), its own ``phi``, ``b``, ``alpha``:

    entry   X_0: every row the token's embedding
    x~      = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)
    H~_pre  = alpha_pre  (x~ phi_pre)  + b_pre        (n)
    H~_post = alpha_post (x~ phi_post) + b_post       (n)
    H~_res  = alpha_res mat(x~ phi_res) + b_res       (n x n)
    H_pre   = sigmoid(H~_pre);  H_post = 2 sigmoid(H~_post)
    H_res   = SK(clip(H~_res, clamp_min, clamp_max)):  M = exp(.), then
              hc_sinkhorn_iters times
              M <- M / (rowsum M + hc_eps);  M <- M / (colsum M + hc_eps)
    X'      = H_res X + H_post^T (x) F(H_pre X)
    exit    the n rows summed, then the final RMSNorm and the head

Attention, the dense and routed FFNs, the router and the share are
``reference/deepseek_v3.py``'s (its frequencies, softmax scale and
selection are imported unchanged; its layer is restated here because
there it ends in ``x = x + ...``).  Restated for the time a run has,
with the same sums: a query block scores the keys up to its own last
query (the rest are masked) against ``k_i = [c_kv W_uk,i ; k_r]`` in one
product, and an expert's rows are computed in whole ``ROW_PAD`` s (the
pad repeats row 0 at weight 0), so the CPU compiles three shapes where
a shape an expert and layer took 26 of a layer's 28 s (PERF.md section 6).

What ``config.json`` does not say, each under ``assumed`` in the
configuration: the entry and exit (arXiv:2409.19606: the rows start as
copies and leave as their sum); that ``hc_eps`` is the RMSNorm's epsilon
and stands in each of the Sinkhorn's divisors; that the clamp precedes
the ``exp``; that a step normalises rows, then columns; that the
RMSNorm of ``vec(X)`` has no learned scale.  **Departures from the
paper**: ``phi`` rests as one matrix (pre, post, res
coefficients) with ``b`` and ``alpha`` beside it, the same products; the
seeded ``alpha`` lie in [0.5, 1.5) and ``b`` within +-0.1 (the paper
starts ``alpha`` at 0.01 and ``b_res`` at the identity: trained values
are not in the repository, and a program that kept only ``b`` has to
fail the comparison), and ``phi`` rests transposed, ``(2n + n^2, nC)``;
MTP, depth, vocabulary and weights as
``deepseek_v3.py`` states them.

``rounding`` (``(operand, result, weight)``, as ``reference/
smallthinker.py``'s) is for ``tools/precision_readings.py``: the same
forward pass with every matrix product's operands and results rounded.
Without it nothing is rounded.  The rows, the mixing's coefficients, the
router, norms and softmax are float32 either way.
"""

from __future__ import annotations

from reference import deepseek_v3 as base

QUERY_BLOCK = base.QUERY_BLOCK
ROW_PAD = 128  # an expert's rows are computed in whole 128s (``ffn``)


def spec_and_config(model: dict):
    """The program's ``(ModelSpec, sizes)`` for a ``model`` block holding
    the source's keys (``n_routed_experts`` the experts HELD here, as
    ``deepseek_v3.spec_and_config``)."""
    from seldon_core_tpu.models.spec import model_spec

    scaling = model["rope_scaling"]
    spec = model_spec(
        "xing4_0", num_experts=base.router_width(model),
        experts_per_tok=model["num_experts_per_tok"],
        expert_width=model["moe_intermediate_size"],
        dense_layers=model["first_k_dense_replace"],
        dense_width=model["intermediate_size"],
        shared_experts=model["n_shared_experts"], n_group=model["n_group"],
        topk_group=model["topk_group"],
        routed_scale=model["routed_scaling_factor"],
        experts_held=model["n_routed_experts"],
        expert_offset=model.get("expert_offset", 0),
        q_rank=model["q_lora_rank"], kv_rank=model["kv_lora_rank"],
        nope_dim=model["qk_nope_head_dim"], rope_dim=model["qk_rope_head_dim"],
        v_dim=model["v_head_dim"], rope_theta=model["rope_theta"],
        rope_factor=scaling["factor"],
        rope_orig_len=scaling["original_max_position_embeddings"],
        rope_beta_fast=scaling["beta_fast"], rope_beta_slow=scaling["beta_slow"],
        rope_mscale_all_dim=scaling["mscale_all_dim"],
        norm_eps=model["rms_norm_eps"], hc_mult=model["hc_mult"],
        hc_sinkhorn_iters=model["hc_sinkhorn_iters"], hc_eps=model["hc_eps"],
        hc_res_min=model["mhc_h_res_clamp_min"],
        hc_res_max=model["mhc_h_res_clamp_max"])
    config = dict(vocab_size=model["vocab_size"], d_model=model["hidden_size"],
                  num_layers=model["num_hidden_layers"],
                  num_heads=model["num_attention_heads"])
    return spec, config


def make_params(model: dict, seed: int):
    """The served weights for ``seed``, each leaf as it rests."""
    from seldon_core_tpu.models.spec import init_params

    spec, config = spec_and_config(model)
    return init_params(spec, config, seed)


def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a).astype(jnp.float32)


def _plain():
    return _f32, (lambda a: a), _f32  # operand, result, weight


def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


# ---- the mixing, a function a step ----

def entry(embedding, model: dict):
    """``(T, C)`` -> ``(T, n, C)``: every row the token's embedding."""
    import jax.numpy as jnp

    return jnp.repeat(embedding[:, None, :], model["hc_mult"], axis=1)


def leave(x):
    """``(T, n, C)`` -> ``(T, C)``: the rows summed."""
    return x.sum(axis=1)


def sinkhorn(logits, model: dict):
    """``(T, n, n)`` logits -> ``exp`` of them clipped, normalised
    ``hc_sinkhorn_iters`` times by rows, then by columns."""
    import jax.numpy as jnp

    eps = model["hc_eps"]
    m = jnp.exp(jnp.clip(logits, model["mhc_h_res_clamp_min"],
                         model["mhc_h_res_clamp_max"]))
    for _ in range(model["hc_sinkhorn_iters"]):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def coefficients(x, p, model: dict):
    """``x`` ``(T, n, C)`` and a sub-layer's ``{phi, bias, scale}`` ->
    ``(H_pre (T, n), H_post (T, n), H_res (T, n, n))``."""
    import jax
    import jax.numpy as jnp

    t, n, c = x.shape
    flat = x.reshape(t, n * c)
    normed = flat / jnp.sqrt((flat * flat).mean(-1, keepdims=True) + model["hc_eps"])
    raw = normed @ _f32(p["phi"]).T                            # (T, 2n + n^2)
    alpha, bias = _f32(p["scale"]), _f32(p["bias"])
    pre = alpha[0] * raw[:, :n] + bias[:n]
    post = alpha[1] * raw[:, n:2 * n] + bias[n:2 * n]
    res = alpha[2] * raw[:, 2 * n:] + bias[2 * n:]
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            sinkhorn(res.reshape(t, n, n), model))


def read(x, h_pre, scale, eps):
    """What ``F``'s matrices act on: the RMSNorm of ``H_pre X``."""
    import jax.numpy as jnp

    return rms_norm(jnp.einsum("tn,tnc->tc", h_pre, x), scale, eps)


def write(x, y, h_post, h_res):
    """``X' = H_res X + H_post^T (x) y``."""
    import jax.numpy as jnp

    return (jnp.einsum("tij,tjc->tic", h_res, x)
            + h_post[:, :, None] * y[:, None, :])


# ---- F: DeepSeek-V3's attention and FFNs over a normed row ----

def attention(h, p, model: dict, pos, rounding=None):
    """Naive causal MLA over the normed rows ``h`` ``(T, C)``: its
    output ``(T, C)``, the residual add left to the caller."""
    import jax
    import jax.numpy as jnp

    act, rd, w = rounding or _plain()

    def mm(a, m):
        return rd(act(a) @ w(m))

    n = h.shape[0]
    heads = model["num_attention_heads"]
    nope, rdim = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, eps = model["kv_lora_rank"], model["rms_norm_eps"]
    scale = base.softmax_scale(model)
    freq = jnp.asarray(base.inv_freq(model), jnp.float32)

    def rotate(v, at):  # v: (n, ..., rdim), pairs interleaved
        v1, v2 = v[..., 0::2], v[..., 1::2]
        ang = at.astype(jnp.float32).reshape(-1, *([1] * (v.ndim - 2)), 1) * freq
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        return jnp.concatenate([v1 * cos - v2 * sin, v2 * cos + v1 * sin], axis=-1)

    c_q = rms_norm(mm(h, p["q_a"]["kernel"]), p["q_a_norm"]["scale"], eps)
    q = mm(c_q, p["q_b"]["kernel"]).reshape(n, heads, nope + rdim)
    kva = mm(h, p["kv_a"]["kernel"])
    c_kv = rd(rms_norm(kva[:, :rank], p["kv_a_norm"]["scale"], eps))
    k_r = rd(rotate(kva[:, rank:], pos))
    q = jnp.concatenate([q[..., :nope], rd(rotate(q[..., nope:], pos))], axis=-1)
    k_nope = rd(jnp.einsum("cr,hrn->hcn", act(c_kv), w(p["kv_b_k"])))
    k = jnp.concatenate(  # k_i = [c_kv W_uk,i ; k_r]: one rotated key under every head
        [k_nope, jnp.broadcast_to(k_r[None], (heads, n, rdim))], axis=-1)
    v = rd(jnp.einsum("cr,hrv->hcv", act(c_kv), w(p["kv_b_v"])))
    out = []
    for lo in range(0, n, QUERY_BLOCK):
        hi = min(n, lo + QUERY_BLOCK)  # the keys past a block's last query are masked: not scored
        s = jnp.einsum("qhd,hcd->hqc", q[lo:hi], k[:, :hi]) * scale
        s = jnp.where((pos[None, :hi] <= pos[lo:hi, None])[None], s, -jnp.inf)
        out.append(rd(jnp.einsum("hqc,hcv->qhv", rd(jax.nn.softmax(s, axis=-1)), v[:, :hi])))
    return mm(jnp.concatenate(out, axis=0).reshape(n, -1), p["attn_proj"]["kernel"])


def ffn(h, p, model: dict, layer: int, routing=None, rounding=None):
    """The layer's FFN over the normed rows ``h``: dense SwiGLU, or the
    held experts' part of the routed sum beside the shared expert."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    act, rd, w = rounding or _plain()

    def mm(a, m):
        return rd(act(a) @ w(m))

    def swiglu(rows, gate, up, down):
        return mm(rd(jax.nn.silu(mm(rows, gate)) * mm(rows, up)), down)

    if layer < model["first_k_dense_replace"]:
        return swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
    held, offset = model["n_routed_experts"], model.get("expert_offset", 0)
    weights, chosen = base.route(
        model, jax.nn.sigmoid(h @ _f32(p["router"])), p["score_bias"])
    if routing is not None:
        routing.append(chosen)
    y = (swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
         if model["n_shared_experts"] else jnp.zeros_like(h))
    for e in range(held):  # the experts that exist here, one by one
        rows, slot = np.nonzero(chosen == e + offset)
        if rows.size:
            # to a whole ROW_PAD with row 0 at weight 0: a few shapes to compile, not one an expert
            pad = np.zeros(-rows.size % ROW_PAD, rows.dtype)
            at = np.concatenate([rows, pad])
            weight = np.concatenate([weights[rows, slot], pad.astype(weights.dtype)])
            part = swiglu(h[at], p["experts_gate"][e], p["experts_up"][e],
                          p["experts_down"][e])
            y = y.at[at].add(part * weight[:, None])
    return y


def layer(x, p, model: dict, index: int, pos, routing=None, rounding=None):
    """One layer over the rows ``x`` ``(T, n, C)``: its two mixed
    sub-layers."""
    eps = model["rms_norm_eps"]
    h_pre, h_post, h_res = coefficients(x, p["hc_attn"], model)
    x = write(x, attention(read(x, h_pre, p["attn_norm"]["scale"], eps),
                           p, model, pos, rounding), h_post, h_res)
    h_pre, h_post, h_res = coefficients(x, p["hc_ffn"], model)
    return write(x, ffn(read(x, h_pre, p["ffn_norm"]["scale"], eps), p, model,
                        index, routing, rounding), h_post, h_res)


def logits(params, model: dict, tokens, tail=None, routing=None, rounding=None):
    """(T, vocab) float32 next-token logits for one sequence of ids;
    with ``tail`` only the last ``tail`` positions'."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0])
        x = entry(_f32(params["tok_embed"]["embedding"][tokens]), model)
        for i in range(model["num_hidden_layers"]):
            x = layer(x, params[f"block_{i}"], model, i, pos, routing, rounding)
        x = leave(x)
        x = x if tail is None else x[-tail:]
        act, rd, w = rounding or _plain()
        return rd(act(rms_norm(x, params["final_norm"]["scale"],
                               model["rms_norm_eps"])) @ w(params["head"]["kernel"]))
