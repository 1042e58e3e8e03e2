"""Plain reference for ``Ling-3.0-flash`` (``model_type: bailing_hybrid``):
the forward pass in float32 ``jax.numpy`` at ``highest`` matmul precision
— no kernel, no cache, no pages, no state carried between calls, no
batching, no chunks: a KDA layer's recurrence runs **position by
position** (``lax.scan`` over ``t``), its convolution is a plain sum of
four taps, an MLA layer is a causal softmax over up-projected keys and
values of the whole sequence (no absorbed form), the routed experts a
plain loop over the experts held.

Follows ``inclusionAI/Ling-3.0-flash`` ``config.json`` (the catalog row's
``config``): hidden 2,560, 32 heads, RMSNorm eps 1e-6 on each sub-layer's
INPUT (``assumed.norm_placement``: pre-norm), no biases, untied embedding
and head.  Layer ``i`` is latent attention where ``(i + 1) %
layer_group_size == 0`` and Kimi Delta Attention elsewhere
(``assumed.layer_rule``: the config's ``layer_group_size`` 6, not the
card's "3 : 1", is trusted; the derived ``layer_types`` stands in the
configuration file)::

    x' = x + A(RMSNorm_attn(x))         A: the layer's attention, below
    x'' = x' + F(RMSNorm_ffn(x'))       F: the layer's FFN, below

**A KDA layer** (arXiv:2510.26692, FLA's ``KimiDeltaAttention``; H = 32,
``d_k`` = ``d_v`` = 128), ``h`` its normed input: ``[q~ ; k~ ; v~] = h
W_qkv`` (4,096 each), each channel through a causal convolution of 4 taps
and SiLU (``linear_silu``), zeros before position 0; a head's ``q = q^ /
||q^|| d_k^-1/2``, ``k = k^ / ||k^||`` (eps 1e-6 under the root;
``assumed.qk_norm``: ``use_qk_norm`` read as these L2 norms); ``beta_t =
sigmoid((h W_b)_h)`` (no ``allow_neg_eigval`` key: no factor 2); the decay
is a VECTOR a head: ``a = h W_a`` (H x d_k outputs, one full matrix:
``no_kda_lora``), ``log alpha_t = kda_lower_bound x sigmoid(exp(A_log_h)
(a_t + dt_bias))`` in (-5, 0) a channel (``kda_safe_gate``;
``assumed.gate_form``); the head's state ``S`` ``(d_k, d_v)`` from zeros::

    S' = Diag(alpha_t) S_{t-1};  u = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u^T;          o_t = S_t^T q_t

and ``A = [RMSNorm_{d_v}(o_h) * w * sigmoid((h W_g)_h)]_h W_o`` with ``W_g``
``(hidden, H)``, one gate a head (``head_wise``; ``group_norm_size`` 1: the
norm is a head's; ``assumed.output_gates``: sigmoid).

**An MLA layer** (DeepSeek-V3's, ``q_lora_rank`` null): ``[c ; k_r] = h
W_kva`` (512 + 64), ``c = RMSNorm(c)`` (``kv_a_layernorm``), ``k_r``
rotated (interleaved pairs, theta 6e6, no scaling); ``q = h W_q`` -> 32
heads of 128 nope + 64 rope, the 64 rotated, **no q bottleneck**; ``k_i =
[c W_uk,i ; k_r]``, ``v_i = c W_uv,i`` (128); causal softmax of ``q_i . k_i
/ sqrt(192)``; a sigmoid gate a head from ``h`` on the attended values;
``W_o``.

**The FFN**: a dense SwiGLU of 6,144 in the first ``first_k_dense_replace``
layers; then ``s = sigmoid(h W_r)`` in float32 (512 outputs), selection
score ``s + b``, 8 groups of 64 scored by the sum of their two largest,
the 4 best groups kept, the 8 largest of what is left, weights ``s`` of
the chosen over their sum times 2.5; ``x + sum_{e chosen AND held} w_e
SwiGLU_e(h) + SwiGLU_shared(h)``.

Departures and assumptions, each in the configuration's ``reduced`` or
``assumed``:

* **Depth**: the first ``num_hidden_layers`` layers.  **The share**: the
  router scores all ``num_experts_published``; only experts
  ``expert_offset .. + num_experts`` exist here, and what an absent
  expert would have added is left out (in the program alike).  **MTP**
  (``num_nextn_predict_layers``) is not served.  The swiglu limit lists
  are 0 in every served layer; a non-zero entry is refused by name.
* ``W_q``, ``W_k``, ``W_v`` of a KDA layer rest as one matrix, ``W_kvb``
  split into ``W_uk`` / ``W_uv``: the same products.
* The weights are the served ones: the program's seeded initialiser
  (``models/spec.py init_params``) makes the same tree here on the CPU;
  every operand is promoted to float32 where it is used.  Nothing of
  ``seldon_core_tpu/ops`` is read: the forward pass below is its own.

``variant`` and ``rounding`` are for ``tools/precision_readings.py`` and
the tests alone: a deliberately wrong program (``decay_head``: the decay
averaged over a head's channels, Olmo-Hybrid's rule; ``softplus_gate``:
``-exp(A_log) softplus(.)`` in the bounded gate's place; ``beta_two``:
beta times 2; ``state_bf16``: the state rounded to bfloat16 after every
position; ``no_head_gate``: the KDA output gate left out; ``q_bottleneck``
is a wrong SPEC, made by the tests), or the same equations with the
matmuls' operands and results rounded.  With ``tail`` the final norm and
the head run over the last ``tail`` rows alone, in blocks of
:data:`HEAD_BLOCK`: the same numbers for those rows.
"""

from __future__ import annotations

HEAD_BLOCK = 128   # rows of logits made at once
QUERY_BLOCK = 512  # queries scored at once in an MLA layer
ROW_PAD = 128      # an expert's rows are computed in whole 128s (``ffn``)

VARIANTS = ("decay_head", "softplus_gate", "beta_two", "state_bf16",
            "no_head_gate")


def kinds_of(model: dict):
    """``("linear" | "full", ...)`` for the layers served:
    ``assumed.layer_rule``."""
    period = model["layer_group_size"]
    return tuple("full" if (i + 1) % period == 0 else "linear"
                 for i in range(model["num_hidden_layers"]))


def router_width(model: dict) -> int:
    return int(model.get("num_experts_published", model["num_experts"]))


def spec_and_config(model: dict):
    """The program's ``(ModelSpec, sizes)`` for a ``model`` block holding
    the source's keys.  ``num_experts`` counts the experts HELD here
    (guide section 4); ``num_experts_published`` states the router's
    width (absent: every expert is held) and ``expert_offset`` where the
    held ones start."""
    from seldon_core_tpu.models.spec import model_spec

    if model.get("num_kv_heads_for_linear_attn"):
        raise ValueError("bailing_hybrid: the engine's KDA layers have as many "
                         "key heads as heads (num_kv_heads_for_linear_attn 0)")
    if model.get("q_lora_rank"):
        raise ValueError("bailing_hybrid: q_lora_rank is null in the source")
    layers = model["num_hidden_layers"]
    spec = model_spec(
        "bailing_hybrid", num_experts=router_width(model),
        experts_per_tok=model["num_experts_per_tok"],
        expert_width=model["moe_intermediate_size"],
        dense_layers=model["first_k_dense_replace"],
        dense_width=model["intermediate_size"],
        shared_experts=model["num_shared_experts"], n_group=model["n_group"],
        topk_group=model["topk_group"],
        routed_scale=model["routed_scaling_factor"],
        experts_held=model["num_experts"],
        expert_offset=model.get("expert_offset", 0),
        kv_rank=model["kv_lora_rank"], nope_dim=model["qk_nope_head_dim"],
        rope_dim=model["qk_rope_head_dim"], v_dim=model["v_head_dim"],
        rope_theta=model["rope_theta"], norm_eps=model["rms_norm_eps"],
        layer_kinds=kinds_of(model), lin_heads=model["num_attention_heads"],
        lin_key_dim=model["head_dim"], lin_value_dim=model["head_dim"],
        lin_conv=model["short_conv_kernel_size"],
        lin_gate_floor=model["kda_lower_bound"],
        expert_swiglu_limits=model["expert_swiglu_limit_list"][:layers],
        shared_swiglu_limits=model["share_expert_swiglu_limit_list"][:layers])
    config = dict(vocab_size=model["vocab_size"], d_model=model["hidden_size"],
                  num_layers=layers, num_heads=model["num_attention_heads"])
    return spec, config


def make_params(model: dict, seed: int):
    """The served weights for ``seed``, in the types they rest in: each
    operand is promoted where it is used."""
    from seldon_core_tpu.models.spec import init_params

    spec, config = spec_and_config(model)
    return init_params(spec, config, seed)


def _plain():
    import jax.numpy as jnp

    def f32(a):
        return jnp.asarray(a).astype(jnp.float32)

    return f32, (lambda a: a), f32  # act, result, weight


def _rms_norm(v, scale, eps):
    import jax.numpy as jnp

    return v / jnp.sqrt((v * v).mean(-1, keepdims=True) + eps) * jnp.asarray(
        scale).astype(jnp.float32)


def kda_attention(p, model: dict, h, *, variant=None, rounding=None):
    """A KDA layer's ``A(h)`` for its normed input ``h``: ``(n, hidden)``."""
    import jax
    import jax.numpy as jnp

    act, rd, w = rounding or _plain()
    f32 = _plain()[0]
    heads = model["num_attention_heads"]
    dk = dv = model["head_dim"]  # assumed: num_kv_heads_for_linear_attn 0
    taps = model["short_conv_kernel_size"]
    n = h.shape[0]

    def mm(a, m):
        return rd(act(a) @ w(m))

    mixed = mm(h, p["qkv"]["kernel"])                            # (n, 3 H d)
    # the convolution: a plain sum of the taps over the inputs before it
    # (tap j weighs the input taps - 1 - j positions back; zeros before 0)
    back = jnp.concatenate([jnp.zeros((taps - 1, mixed.shape[1])), mixed])
    c = f32(p["conv"])
    mixed = jax.nn.silu(sum(back[j:j + n] * c[j] for j in range(taps)))
    q = mixed[:, :heads * dk].reshape(n, heads, dk)
    k = mixed[:, heads * dk:2 * heads * dk].reshape(n, heads, dk)
    v = mixed[:, 2 * heads * dk:].reshape(n, heads, dv)
    # assumed.qk_norm: use_qk_norm read as FLA's L2 norms a head
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    # beta: no allow_neg_eigval key, so no factor 2 (float32 as served)
    beta = jax.nn.sigmoid(h @ f32(p["b"]))
    if variant == "beta_two":
        beta = 2.0 * beta
    # the decay, one gate a key channel: a full matrix (no_kda_lora), its
    # product kept in float32 as served
    z = (act(h) @ w(p["a"]) + f32(p["dt_bias"])).reshape(n, heads, dk)
    rate = jnp.exp(f32(p["a_log"]))[:, None]
    if variant == "softplus_gate":  # Olmo-Hybrid's gate in the bounded one's place
        log_alpha = -rate * jax.nn.softplus(z)
    else:  # assumed.gate_form: kda_safe_gate = FLA's lower-bounded gate
        log_alpha = model["kda_lower_bound"] * jax.nn.sigmoid(rate * z)
    alpha = jnp.exp(log_alpha)                                   # (n, H, d_k)
    if variant == "decay_head":  # one decay a head: the channels' mean
        alpha = jnp.broadcast_to(alpha.mean(-1, keepdims=True), alpha.shape)

    def position(s, xs):  # the recurrence, one position: s (heads, dk, dv)
        q_t, k_t, v_t, a_t, b_t = xs
        s = a_t[:, :, None] * s                                  # Diag(alpha) S
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * u[:, None, :]
        if variant == "state_bf16":
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    # assumed.state: float32
    _s, out = jax.lax.scan(position, jnp.zeros((heads, dk, dv), jnp.float32),
                           (q, k, v, alpha, beta))
    out = _rms_norm(out, p["o_norm"]["scale"], model["rms_norm_eps"])
    if variant != "no_head_gate":
        # assumed.output_gates: sigmoid, one a head (head_wise)
        out = out * jax.nn.sigmoid(mm(h, p["gate"]["kernel"]))[:, :, None]
    return mm(out.reshape(n, heads * dv), p["attn_proj"]["kernel"])


def mla_attention(p, model: dict, h, *, rounding=None):
    """An MLA layer's ``A(h)`` for its normed input ``h``: ``(n, hidden)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    act, rd, w = rounding or _plain()
    heads, eps = model["num_attention_heads"], model["rms_norm_eps"]
    nope, rdim = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank = model["kv_lora_rank"]
    n = h.shape[0]
    pos = jnp.arange(n, dtype=jnp.float32)
    freq = 1.0 / (model["rope_theta"] ** (
        jnp.arange(0, rdim, 2, dtype=jnp.float32) / rdim))       # no scaling

    def mm(a, m):
        return rd(act(a) @ w(m))

    def rotate(x):  # x: (n, ..., rdim), pairs interleaved (rope_interleave)
        x1, x2 = x[..., 0::2], x[..., 1::2]
        ang = pos.reshape(-1, *([1] * (x.ndim - 2)), 1) * freq
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    # no q bottleneck: q_lora_rank null
    q = mm(h, p["q"]["kernel"]).reshape(n, heads, nope + rdim)
    kva = mm(h, p["kv_a"]["kernel"])
    # assumed.qk_norm: MLA's kv_a_layernorm, and no norm on up-projected keys
    c_kv = rd(_rms_norm(kva[:, :rank], p["kv_a_norm"]["scale"], eps))
    k_r = rd(rotate(kva[:, rank:]))                              # (n, rdim)
    q_nope, q_r = q[..., :nope], rd(rotate(q[..., nope:]))
    k_nope = rd(jnp.einsum("cr,hrn->hcn", act(c_kv), w(p["kv_b_k"])))
    v = rd(jnp.einsum("cr,hrv->hcv", act(c_kv), w(p["kv_b_v"])))
    at = np.arange(n)
    scale = (nope + rdim) ** -0.5
    out = []
    for lo in range(0, n, QUERY_BLOCK):
        hi = min(n, lo + QUERY_BLOCK)
        s = (jnp.einsum("qhn,hcn->hqc", act(q_nope[lo:hi]), act(k_nope))
             + jnp.einsum("qhr,cr->hqc", act(q_r[lo:hi]), act(k_r))) * scale
        seen = jnp.asarray(at[None, :] <= at[lo:hi, None])
        prob = rd(jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1))
        out.append(rd(jnp.einsum("hqc,hcv->qhv", act(prob), act(v))))
    attn = jnp.concatenate(out, axis=0)                          # (n, H, v)
    # assumed.output_gates: a sigmoid gate a head from the normed input
    attn = attn * jax.nn.sigmoid(mm(h, p["attn_gate"]["kernel"]))[:, :, None]
    return mm(attn.reshape(n, -1), p["attn_proj"]["kernel"])


def route(model: dict, scores, bias):
    """``(weights (n, k), chosen (n, k))`` numpy, from the sigmoid scores
    ``(n, E)``: the bias enters the selection alone (``noaux_tc``), a
    group's score is the sum of its two best, the chosen scores are
    renormalised and scaled."""
    import numpy as np

    scores, bias = np.asarray(scores, np.float32), np.asarray(bias, np.float32)
    n, e = scores.shape
    groups, keep, k = model["n_group"], model["topk_group"], model["num_experts_per_tok"]
    choice = (scores + bias).reshape(n, groups, e // groups)
    group_score = np.sort(choice, axis=-1)[..., -2:].sum(axis=-1)
    best = np.argsort(-group_score, axis=-1, kind="stable")[:, :keep]
    kept = np.zeros((n, groups), bool)
    np.put_along_axis(kept, best, True, axis=-1)
    choice = np.where(kept[:, :, None], choice, np.float32(0.0)).reshape(n, e)
    chosen = np.argsort(-choice, axis=-1, kind="stable")[:, :k]
    weights = np.take_along_axis(scores, chosen, axis=-1)
    if model["norm_topk_prob"]:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + np.float32(1e-20))
    return weights * np.float32(model["routed_scaling_factor"]), chosen


def ffn(p, model: dict, h, index: int, *, rounding=None, parts=None):
    """Layer ``index``'s ``F(h)``: the dense SwiGLU, or the held experts'
    part and the shared expert's.  ``parts`` (a dict) also receives the
    two under ``"routed"`` and ``"shared"``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    act, rd, w = rounding or _plain()
    f32 = _plain()[0]

    def swiglu(rows, gate, up, down):
        hidden = rd(jax.nn.silu(rd(act(rows) @ w(gate))) * rd(act(rows) @ w(up)))
        return rd(act(hidden) @ w(down))

    if index < model["first_k_dense_replace"]:
        return swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
    held, offset = model["num_experts"], model.get("expert_offset", 0)
    scores = jax.nn.sigmoid(h @ f32(p["router"]))                # float32 as served
    weights, chosen = route(model, scores, p["score_bias"])
    shared = (swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
              if model["num_shared_experts"] else jnp.zeros_like(h))
    routed = jnp.zeros_like(h)
    for e in range(held):  # the experts that exist here, one by one
        rows, slot = np.nonzero(chosen == e + offset)
        if rows.size == 0:
            continue
        # to a whole ROW_PAD with row 0 at weight 0: a few shapes to
        # compile, not one an expert and layer
        pad = np.zeros(-rows.size % ROW_PAD, rows.dtype)
        at = np.concatenate([rows, pad])
        weight = np.concatenate([weights[rows, slot], pad.astype(weights.dtype)])
        part = swiglu(h[at], p["experts_gate"][e], p["experts_up"][e],
                      p["experts_down"][e])
        routed = routed.at[at].add(part * weight[:, None])
    if parts is not None:
        parts.update(routed=routed, shared=shared)
    return routed + shared


def layer(p, model: dict, x, index: int, *, variant=None, rounding=None,
          parts=None):
    """Layer ``index``'s map of the residual stream ``x`` ``(n, hidden)``
    float32 with the parameters ``p`` (assumed.norm_placement: pre-norm)."""
    eps = model["rms_norm_eps"]
    h = _rms_norm(x, p["attn_norm"]["scale"], eps)
    if kinds_of(model)[index] == "linear":
        x = x + kda_attention(p, model, h, variant=variant, rounding=rounding)
    else:
        x = x + mla_attention(p, model, h, rounding=rounding)
    h = _rms_norm(x, p["ffn_norm"]["scale"], eps)
    return x + ffn(p, model, h, index, rounding=rounding, parts=parts)


def logits(params, model: dict, tokens, tail=None, variant=None, rounding=None):
    """``(T, vocab)`` float32 next-token logits for one sequence of ids;
    with ``tail`` only the last ``tail`` positions'."""
    import jax
    import jax.numpy as jnp

    f32 = _plain()[0]
    act, rd, w = rounding or _plain()
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = f32(params["tok_embed"]["embedding"][tokens])
        for i in range(model["num_hidden_layers"]):
            x = layer(params[f"block_{i}"], model, x, i, variant=variant,
                      rounding=rounding)
        x = x if tail is None else x[-tail:]
        x = _rms_norm(x, params["final_norm"]["scale"], model["rms_norm_eps"])
        head = w(params["head"]["kernel"])
        return jnp.concatenate([
            rd(act(x[lo:lo + HEAD_BLOCK]) @ head)
            for lo in range(0, x.shape[0], HEAD_BLOCK)], axis=0)
