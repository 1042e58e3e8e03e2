"""Plain reference for the DeepSeek-V3 block as GigaChat3.1-702B-A36B
configures it: the forward pass in float32 ``jax.numpy`` at ``highest``
matmul precision, **naive attention only** — no absorption, no cache, no
kernel, no grouped matmul — and the routed experts as a plain loop over
the experts held.

Follows ``ai-sage/GigaChat3.1-702B-A36B`` (``config.json``,
``model_type: deepseek_v3``; HF ``modeling_deepseek_v3.py``).  Per
layer, with ``x`` the residual stream and 64 heads:

    h = RMSNorm(x; w_in, eps)
    c_q = RMSNorm(h W_qa)  (1536);  q = c_q W_qb  -> heads of 128 nope + 64 rope
    [c_kv ; k_r] = h W_kva  (512 + 64);  c_kv = RMSNorm(c_kv)
    rotary embedding on q's rope part and on k_r, HF's interleaved-to-
        half form, YaRN inverse frequencies (factor 64 over 4096, beta
        32 / 1, theta 100,000; cos and sin unscaled: mscale /
        mscale_all_dim = 1); k_r is ONE key shared by every head
    k_i = [c_kv W_uk,i ; k_r]  (128 + 64),  v_i = c_kv W_uv,i  (192)
    causal softmax of s q_i.k_i,  s = 192^-0.5 m^2,  m = 0.1 ln(64) + 1
    x = x + concat_i(sum p v_i) W_o           (64 x 192 -> 7168)
    h = RMSNorm(x; w_post, eps)
    a dense layer:   x = x + W_down (silu(h W_gate) * (h W_up))   (18,432)
    an expert layer: s = sigmoid(h W_g) in float32 (256); selection
        score s + b; 8 groups of 32, a group's score the sum of its two
        largest, the 4 best groups kept (the others' scores set to 0);
        the 8 largest of what is left; weights s (not s + b) of the
        chosen over their sum, times 2.5
        x = x + sum_{e chosen AND held} w_e SwiGLU_e(h) + SwiGLU_shared(h)

then a final RMSNorm and an untied head without bias.

Departures from ``modeling_deepseek_v3.py``, each in the
configuration's ``reduced`` or ``assumed``:

* **MTP dropped**: ``num_nextn_predict_layers`` 1 is a 65th block that
  HF's forward pass does not run either; not served.
* **Depth**: the layers the ``model`` block names (1 dense + 5 expert).
* **The share** (guide §4): the router scores all
  ``n_routed_experts_published``; only experts ``expert_offset .. +
  n_routed_experts`` exist here, and what
  an absent expert would have added is left out (in the program alike);
  the vocabulary is the slice ``vocab_size`` names.
* ``W_kvb`` rests split into ``W_uk`` ``(heads, 512, 128)`` and ``W_uv``
  ``(heads, 512, 192)``: the same products.
* The weights are the served ones: the program's seeded initialiser
  (``models/spec.py``) makes the same tree here on the CPU, each leaf
  in the type the program rests it in (bfloat16 matrices, float32 norm
  scales, router and correction bias); every operand is promoted to
  float32 where it is used, so the 3.4 B parameters are never held
  twice.
* A cached row rests in 640 lanes (576 values and a zero tail); the
  reference has no cache and no tail.

``logits(..., routing=out_list)`` also appends each expert layer's
chosen expert sets.
"""

from __future__ import annotations

# queries scored at once: (heads, 512, n) float32 is 268 MB at n = 2048
QUERY_BLOCK = 512


def spec_and_config(model: dict):
    """The program's ``(ModelSpec, sizes)`` for a ``model`` block
    holding the source's keys.  ``n_routed_experts`` counts the experts
    HELD here (guide section 4); the share's two further keys state the
    router's published width, ``n_routed_experts_published`` (absent:
    every expert is held), and where the held ones start,
    ``expert_offset``."""
    from seldon_core_tpu.models.spec import model_spec

    scaling = model["rope_scaling"]
    spec = model_spec(
        "deepseek_v3", num_experts=router_width(model),
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
        norm_eps=model["rms_norm_eps"])
    config = dict(vocab_size=model["vocab_size"], d_model=model["hidden_size"],
                  num_layers=model["num_hidden_layers"],
                  num_heads=model["num_attention_heads"])
    return spec, config


def router_width(model: dict) -> int:
    return int(model.get("n_routed_experts_published", model["n_routed_experts"]))


def make_params(model: dict, seed: int):
    """The served weights for ``seed``, each leaf as it rests."""
    from seldon_core_tpu.models.spec import init_params

    spec, config = spec_and_config(model)
    return init_params(spec, config, seed)


def inv_freq(model: dict):
    """YaRN's inverse frequencies, from the closed form (numpy, f64)."""
    import math

    import numpy as np

    s = model["rope_scaling"]
    dim, base = model["qk_rope_head_dim"], model["rope_theta"]
    freqs = base ** (np.arange(0, dim, 2) / dim)
    low, high = (
        dim * math.log(s["original_max_position_embeddings"] / (b * 2 * math.pi))
        / (2 * math.log(base)) for b in (s["beta_fast"], s["beta_slow"]))
    low, high = max(math.floor(low), 0), min(math.ceil(high), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / ((high - low) or 0.001), 0, 1)
    return (1 / (s["factor"] * freqs)) * ramp + (1 / freqs) * (1 - ramp)


def softmax_scale(model: dict) -> float:
    import math

    s = model["rope_scaling"]
    m = 0.1 * s["mscale_all_dim"] * math.log(s["factor"]) + 1.0
    return (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5 * m * m


def route(model: dict, scores, bias):
    """``(weights (n, k), chosen (n, k))`` numpy, from the sigmoid
    scores ``(n, E)``: the selection and the weights as HF makes them."""
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


def logits(params, model: dict, tokens, tail=None, routing=None):
    """(T, vocab) float32 next-token logits for one sequence of ids;
    with ``tail`` only the last ``tail`` positions'."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eps = model["rms_norm_eps"]
    heads = model["num_attention_heads"]
    nope, rdim = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank = model["kv_lora_rank"]
    held, offset = model["n_routed_experts"], model.get("expert_offset", 0)
    scale = softmax_scale(model)
    freq = jnp.asarray(inv_freq(model), jnp.float32)

    def f32(a):
        return jnp.asarray(a).astype(jnp.float32)

    def rms_norm(x, scale_):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * f32(scale_)

    def rotate(x, pos):  # x: (n, ..., rdim), pairs interleaved
        x1, x2 = x[..., 0::2], x[..., 1::2]
        ang = pos.astype(jnp.float32).reshape(-1, *([1] * (x.ndim - 2)), 1) * freq
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def swiglu(h, gate, up, down):
        return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)

    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        n = tokens.shape[0]
        pos = jnp.arange(n)
        x = f32(params["tok_embed"]["embedding"][tokens])
        for i in range(model["num_hidden_layers"]):
            p = params[f"block_{i}"]
            h = rms_norm(x, p["attn_norm"]["scale"])
            c_q = rms_norm(h @ f32(p["q_a"]["kernel"]), p["q_a_norm"]["scale"])
            q = (c_q @ f32(p["q_b"]["kernel"])).reshape(n, heads, nope + rdim)
            kva = h @ f32(p["kv_a"]["kernel"])
            c_kv = rms_norm(kva[:, :rank], p["kv_a_norm"]["scale"])
            k_r = rotate(kva[:, rank:], pos)                       # (n, rdim)
            q_nope, q_r = q[..., :nope], rotate(q[..., nope:], pos)
            k_nope = jnp.einsum("cr,hrn->hcn", c_kv, f32(p["kv_b_k"]))
            v = jnp.einsum("cr,hrv->hcv", c_kv, f32(p["kv_b_v"]))
            out = []
            for lo in range(0, n, QUERY_BLOCK):
                hi = min(n, lo + QUERY_BLOCK)
                s = (jnp.einsum("qhn,hcn->hqc", q_nope[lo:hi], k_nope)
                     + jnp.einsum("qhr,cr->hqc", q_r[lo:hi], k_r)) * scale
                seen = pos[None, :] <= pos[lo:hi, None]
                s = jnp.where(seen[None], s, -jnp.inf)
                out.append(jnp.einsum("hqc,hcv->qhv", jax.nn.softmax(s, axis=-1), v))
            attn = jnp.concatenate(out, axis=0).reshape(n, -1)
            x = x + attn @ f32(p["attn_proj"]["kernel"])

            h = rms_norm(x, p["ffn_norm"]["scale"])
            if i < model["first_k_dense_replace"]:
                x = x + swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
                continue
            scores = jax.nn.sigmoid(h @ f32(p["router"]))
            weights, chosen = route(model, scores, p["score_bias"])
            if routing is not None:
                routing.append(chosen)
            y = swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"]) \
                if model["n_shared_experts"] else jnp.zeros_like(x)
            for e in range(held):  # the experts that exist here, one by one
                rows, slot = np.nonzero(chosen == e + offset)
                if rows.size == 0:
                    continue
                part = swiglu(h[rows], p["experts_gate"][e], p["experts_up"][e],
                              p["experts_down"][e])
                y = y.at[rows].add(part * weights[rows, slot][:, None])
            x = x + y
        x = x if tail is None else x[-tail:]
        return rms_norm(x, params["final_norm"]["scale"]) @ f32(params["head"]["kernel"])
