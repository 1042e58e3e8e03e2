"""Plain reference for the LongCat-Flash double layer as
LongCat-Flash-Omni's language model configures it: the forward pass in
float32 ``jax.numpy`` at ``highest`` matmul precision, **naive attention
only** — no absorption, no cache, no kernel, no grouped matmul — and the
routed experts as a plain loop over the experts held.

Follows ``meituan-longcat/LongCat-Flash-Omni`` (``config.json``; HF
``modeling_longcat_flash.py``).  Per layer, with ``x`` the residual
stream, 64 heads, RMSNorm eps 1e-5, no biases, sub-layers ``i = 0, 1``:

    for i in 0, 1:
      h   = RMSNorm(x; w_in[i])
      c_q = RMSNorm(h W_qa[i]) (1536);  q = (c_q W_qb[i]) * s_q -> heads of 128 nope + 64 rope
      [c_kv ; k_r] = h W_kva[i] (512 + 64);  c_kv = RMSNorm(c_kv) * s_kv
          s_q = (6144 / 1536)^0.5 = 2,  s_kv = (6144 / 512)^0.5 = 3.4641
      RoPE (theta 1e7, no scaling, HF's interleaved-to-half form) on q's
          rope part and on k_r; k_r is ONE key shared by every head
      k_j = [c_kv W_uk[i],j ; k_r] (128 + 64),  v_j = c_kv W_uv[i],j (128)
      x   = x + concat_j(causal softmax(192^-0.5 q_j.k_j) v_j) W_o[i]   (64 x 128 -> 6144)
      g   = RMSNorm(x; w_post[i])
      if i == 0:  m = MoE(g)                 # the shortcut: taken here, added at the end
      x   = x + W_down[i](silu(g W_gate[i]) * (g W_up[i]))               (12,288)
    x = x + m

    MoE(g): p = softmax(g W_r) in float32 over 768 = 512 + 256; the 12
            largest of p + b chosen (b: e_score_correction_bias,
            selection only); w_e = 6 p_e, NOT renormalised
            m = sum_{e chosen, e < 512, e held} w_e SwiGLU_e(g)
                + (sum_{e chosen, e >= 512} w_e) g

then a final RMSNorm and an untied head without bias.

Departures from ``modeling_longcat_flash.py``, each in the
configuration's ``reduced`` or ``assumed``:

* **The language model only**: the audio and vision encoders and the
  codec decoder are not served.
* **Depth**: the layers the ``model`` block names (4 of 28).
* **The share** (guide §4): the router scores all
  ``n_routed_experts_published`` + ``zero_expert_num`` outputs; only
  real experts ``expert_offset .. + n_routed_experts`` exist here, and
  what an absent real expert would have added is left out (in the
  program alike); **the identity experts' part is computed for every
  token** (no matrices: a token's own chip computes it); the vocabulary
  is the slice ``vocab_size`` names.
* ``norm_topk_prob`` and ``router_bias`` are not in the catalog's
  ``config``: HF's defaults (false, false).
* ``W_kvb`` rests split into ``W_uk`` ``(heads, 512, 128)`` and ``W_uv``
  ``(heads, 512, 128)``: the same products.
* The weights are the served ones: the program's seeded initialiser
  (``models/spec.py``) makes the same tree here on the CPU, each leaf
  in the type the program rests it in; every operand is promoted to
  float32 where it is used.
* A cached row rests in 640 lanes (576 values and a zero tail); the
  reference has no cache and no tail.

``logits(..., routing=out_list)`` also appends each layer's chosen
expert sets; ``layer(...)`` is one layer's map ``x -> x'`` with its
parts, for the share test.
"""

from __future__ import annotations

# queries scored at once: (heads, 512, n) float32 is 268 MB at n = 2048
QUERY_BLOCK = 512


def spec_and_config(model: dict):
    """The program's ``(ModelSpec, sizes)`` for a ``model`` block
    holding the source's keys.  ``n_routed_experts`` counts the experts
    HELD here (guide section 4); ``n_routed_experts_published`` states
    the router's real outputs (absent: every expert is held) and
    ``expert_offset`` where the held ones start."""
    from seldon_core_tpu.models.spec import model_spec

    spec = model_spec(
        "longcat_flash", num_experts=real_experts(model),
        zero_experts=model["zero_expert_num"],
        experts_per_tok=model["moe_topk"],
        expert_width=model["expert_ffn_hidden_size"],
        dense_width=model["ffn_hidden_size"],
        routed_scale=model["routed_scaling_factor"],
        experts_held=model["n_routed_experts"],
        expert_offset=model.get("expert_offset", 0),
        q_rank=model["q_lora_rank"], kv_rank=model["kv_lora_rank"],
        nope_dim=model["qk_nope_head_dim"], rope_dim=model["qk_rope_head_dim"],
        v_dim=model["v_head_dim"], rope_theta=model["rope_theta"],
        norm_eps=model["rms_norm_eps"])
    config = dict(vocab_size=model["vocab_size"], d_model=model["hidden_size"],
                  num_layers=model["num_layers"],
                  num_heads=model["num_attention_heads"])
    return spec, config


def real_experts(model: dict) -> int:
    return int(model.get("n_routed_experts_published", model["n_routed_experts"]))


def make_params(model: dict, seed: int):
    """The served weights for ``seed``, each leaf as it rests."""
    from seldon_core_tpu.models.spec import init_params

    spec, config = spec_and_config(model)
    return init_params(spec, config, seed)


def route(model: dict, probs, bias):
    """``(weights (n, k), chosen (n, k))`` numpy, from the softmax
    probabilities ``(n, E + Z)``: the selection and the weights as HF
    makes them."""
    import numpy as np

    probs, bias = np.asarray(probs, np.float32), np.asarray(bias, np.float32)
    chosen = np.argsort(-(probs + bias), axis=-1, kind="stable")[:, :model["moe_topk"]]
    weights = np.take_along_axis(probs, chosen, axis=-1)
    return weights * np.float32(model["routed_scaling_factor"]), chosen


def layer(p, model: dict, x, pos, routing=None, held=None):
    """One double layer: ``(x', parts)`` float32 with ``parts`` a dict
    of what was added to the stream — ``routed`` (the held real
    experts'), ``identity`` (the identity experts') — so a test can add
    shares up.  ``held`` overrides the ``(offset, count)`` of the real
    experts ``p`` holds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eps, heads = model["rms_norm_eps"], model["num_attention_heads"]
    nope, rdim = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, d = model["kv_lora_rank"], model["hidden_size"]
    offset, count = held or (model.get("expert_offset", 0), model["n_routed_experts"])
    real = real_experts(model)
    s_q = (d / model["q_lora_rank"]) ** 0.5 if model["mla_scale_q_lora"] else 1.0
    s_kv = (d / rank) ** 0.5 if model["mla_scale_kv_lora"] else 1.0
    scale = (nope + rdim) ** -0.5
    freq = jnp.asarray(
        1.0 / model["rope_theta"] ** (np.arange(0, rdim, 2) / rdim), jnp.float32)
    n = x.shape[0]

    def f32(a):
        return jnp.asarray(a).astype(jnp.float32)

    def rms_norm(v, scale_):
        return v / jnp.sqrt((v * v).mean(-1, keepdims=True) + eps) * f32(scale_)

    def rotate(v, at):  # v: (n, ..., rdim), pairs interleaved
        v1, v2 = v[..., 0::2], v[..., 1::2]
        ang = at.astype(jnp.float32).reshape(-1, *([1] * (v.ndim - 2)), 1) * freq
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        return jnp.concatenate([v1 * cos - v2 * sin, v2 * cos + v1 * sin], axis=-1)

    def swiglu(h, gate, up, down):
        return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)

    parts = {}
    for i in (0, 1):
        h = rms_norm(x, p[f"attn_norm_{i}"]["scale"])
        c_q = rms_norm(h @ f32(p[f"q_a_{i}"]["kernel"]), p[f"q_a_norm_{i}"]["scale"])
        q = ((c_q @ f32(p[f"q_b_{i}"]["kernel"])) * s_q).reshape(n, heads, nope + rdim)
        kva = h @ f32(p[f"kv_a_{i}"]["kernel"])
        c_kv = rms_norm(kva[:, :rank], p[f"kv_a_norm_{i}"]["scale"]) * s_kv
        k_r = rotate(kva[:, rank:], pos)                           # (n, rdim)
        q_nope, q_r = q[..., :nope], rotate(q[..., nope:], pos)
        k_nope = jnp.einsum("cr,hrn->hcn", c_kv, f32(p[f"kv_b_k_{i}"]))
        v = jnp.einsum("cr,hrv->hcv", c_kv, f32(p[f"kv_b_v_{i}"]))
        out = []
        for lo in range(0, n, QUERY_BLOCK):
            hi = min(n, lo + QUERY_BLOCK)
            s = (jnp.einsum("qhn,hcn->hqc", q_nope[lo:hi], k_nope)
                 + jnp.einsum("qhr,cr->hqc", q_r[lo:hi], k_r)) * scale
            seen = pos[None, :] <= pos[lo:hi, None]
            s = jnp.where(seen[None], s, -jnp.inf)
            out.append(jnp.einsum("hqc,hcv->qhv", jax.nn.softmax(s, axis=-1), v))
        attn = jnp.concatenate(out, axis=0).reshape(n, -1)
        x = x + attn @ f32(p[f"attn_proj_{i}"]["kernel"])

        g = rms_norm(x, p[f"ffn_norm_{i}"]["scale"])
        if i == 0:
            probs = jax.nn.softmax(g @ f32(p["router"]), axis=-1)
            weights, chosen = route(model, probs, p["score_bias"])
            if routing is not None:
                routing.append(chosen)
            identity = jnp.asarray(
                np.where(chosen >= real, weights, 0.0).sum(-1))[:, None] * g
            routed = jnp.zeros_like(x)
            for e in range(count):  # the experts that exist here, one by one
                rows, slot = np.nonzero(chosen == e + offset)
                if rows.size == 0:
                    continue
                part = swiglu(g[rows], p["experts_gate"][e], p["experts_up"][e],
                              p["experts_down"][e])
                routed = routed.at[rows].add(part * weights[rows, slot][:, None])
            parts.update(routed=routed, identity=identity)
        x = x + swiglu(g, p[f"mlp_gate_{i}"], p[f"mlp_up_{i}"], p[f"mlp_down_{i}"])
    return x + parts["routed"] + parts["identity"], parts


def logits(params, model: dict, tokens, tail=None, routing=None):
    """(T, vocab) float32 next-token logits for one sequence of ids;
    with ``tail`` only the last ``tail`` positions'."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0])
        x = jnp.asarray(params["tok_embed"]["embedding"][tokens]).astype(jnp.float32)
        for i in range(model["num_layers"]):
            x, _parts = layer(params[f"block_{i}"], model, x, pos, routing)
        x = x if tail is None else x[-tail:]
        scale = jnp.asarray(params["final_norm"]["scale"]).astype(jnp.float32)
        head = jnp.asarray(params["head"]["kernel"]).astype(jnp.float32)
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale @ head
