"""Plain reference for ``dots3-note-prev``'s language model (``model_type:
dots3_note``): the forward pass in float32 ``jax.numpy`` at ``highest``
matmul precision — naive attention only, full ``S x S`` masks made a
block of queries at a time, no cache, no pages, no kernel, no absorbed
form, no grouped matmul, the selection by a stable sort — and the routed
experts as a plain loop over the experts held.

Follows ``dots-studio/dots3-note-prev`` (``config.json``).  With ``x``
the residual stream, ``y = RMSNorm(x)``, eps 1e-5, no biases, and
``layer_types[i]`` naming layer ``i``'s attention:

    full_attention  (128 heads, nope 128, rope 64, v 128, q rank 1024,
                     kv rank 512, theta 80,000,000, no scaling)
      c_q = RMSNorm(y W_qa);  q = (c_q W_qb) * s_q -> heads of 128 + 64
      [c_kv ; k_r] = y W_kva;  c_kv = RMSNorm(c_kv) * s_kv
          s_q = (5120 / 1024)^0.5,  s_kv = (5120 / 512)^0.5
      RoPE (HF's interleaved-to-half form) on q's rope part and on k_r,
          ONE rotary key shared by every head
      k_j = [c_kv W_uk,j ; k_r],  v_j = c_kv W_uv,j
      the indexer (64 heads of 128, top 2,048):
          q^I = c_q W_q^I (64 x 128, each head's first 64 dims rotated)
          k^I = LayerNorm(y W_k^I) (128, its first 64 dims rotated)
          w   = y W_w (64)
          I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]) * 64^-0.5 * 128^-0.5
          S_t = the 2,048 positions s <= t of largest I[t, s], ties to
                the lower position; every s <= t while t < 2,048
      a_j = softmax over s in S_t of (192^-0.5 q_j . k_j[s]) v_j[s]
    sliding_attention  (the swa_* keys: 64 heads, nope 192, rope 64,
                        v 128, q rank 1024, kv rank 1024, theta 50,000)
      the same latent attention at those sizes (s_kv = (5120 / 1024)^0.5),
      no indexer, over s in t - 512 .. t (sliding_window_size 513: the
      token and the 512 before it), scale 256^-0.5
    both:  g = sigmoid(y W_g)  (one value a head: attention_gate_type
           headwise);  x = x + concat_j(g_j a_j) W_o

    h = RMSNorm(x)
    layer 0 (first_k_dense_replace 1):  x = x + W_down(silu(h W_gate) * (h W_up))   (13,824)
    else:  s = sigmoid(h W_r) in float32 over 256; the 8 largest of
           s + b chosen (noaux_tc: b selection only; one group);
           w_e = s_e / sum of the chosen s;  routed_scaling_factor 1
           x = x + sum_{e chosen, e held} w_e SwiGLU_e(h) + SwiGLU_shared(h)   (1,536 each)

then a final RMSNorm and an untied head without bias.

Departures and assumptions, each in the configuration's ``reduced`` or
``assumed``:

* **The language model only**: the vision and audio towers and the MTP
  head are not served.
* **Depth**: the first ``num_hidden_layers`` entries of ``layer_types``.
* **The share** (guide section 4): the router scores all
  ``n_routed_experts_published`` experts; only experts ``expert_offset
  .. + n_routed_experts`` exist here, and what an absent expert would
  have added is left out (in the program alike); the shared expert is
  computed for every token; the vocabulary is the slice ``vocab_size``
  names.
* ``apply_mla_qkv_lora_rescale`` is read as LongCat-Flash's pair of
  constants — an inference from the key's name: the only published
  convention for such a key that this repository knows.
* The gate is read from the layer's normed input ``y``.
* The indexer's key norm is a LayerNorm (scale and bias, eps 1e-5), its
  rotary part each head's first 64 dims in the interleaved form at the
  full layers' theta; the own position ``s = t`` competes like any other.
* The window's convention: ``sliding_window_size`` counts the token.
* ``W_kvb`` rests split into ``W_uk`` and ``W_uv``: the same products.
* The weights are the served ones: the program's seeded initialiser
  (``models/spec.py init_params``) makes the same tree here on the CPU;
  every operand is promoted to float32 where it is used.  Nothing else
  of ``models/`` is read: the forward pass below is its own.
* A cached row rests in whole 128-lane tiles; the reference has no
  cache.

``logits(..., chosen=out_list)`` also appends each full layer's ``S``
as a bool ``(T, T)`` array; ``layer(...)`` is one layer's map ``x ->
x'`` with the parts of its FFN, for the share test; ``variant`` is for
``tools/precision_readings.py`` alone (a deliberately wrong program).
With ``tail`` the LAST layer computes its queries, attention and FFN for
the last ``tail`` rows only (its keys, values and indexer keys for every
row): the same numbers for those rows, a sixth less of the work at the
cell's sample (3,078 + 256 tokens).
"""

from __future__ import annotations

# queries scored at once: (heads, 256, n) float32 is 0.5 GB at n = 4224
QUERY_BLOCK = 256


def kinds_of(model: dict):
    """``("full" | "window", ...)`` for the layers served."""
    return tuple("window" if t == "sliding_attention" else "full"
                 for t in model["layer_types"][:model["num_hidden_layers"]])


def spec_and_config(model: dict):
    """The program's ``(ModelSpec, sizes)`` for a ``model`` block holding
    the source's keys.  ``n_routed_experts`` counts the experts HELD
    here; ``n_routed_experts_published`` states the router's outputs and
    ``expert_offset`` where the held ones start."""
    from seldon_core_tpu.models.spec import model_spec

    spec = model_spec(
        "dots3_note", num_experts=real_experts(model),
        experts_per_tok=model["num_experts_per_tok"],
        expert_width=model["moe_intermediate_size"],
        dense_width=model["intermediate_size"],
        dense_layers=model["first_k_dense_replace"],
        shared_experts=model["n_shared_experts"],
        routed_scale=model["routed_scaling_factor"],
        experts_held=model["n_routed_experts"],
        expert_offset=model.get("expert_offset", 0),
        q_rank=model["q_lora_rank"], kv_rank=model["kv_lora_rank"],
        nope_dim=model["qk_nope_head_dim"], rope_dim=model["qk_rope_head_dim"],
        v_dim=model["v_head_dim"], rope_theta=model["rope_theta"],
        norm_eps=model["rms_norm_eps"], layer_kinds=kinds_of(model),
        window=model["sliding_window_size"],
        win_heads=model["swa_num_attention_heads"],
        win_q_rank=model["swa_q_lora_rank"], win_kv_rank=model["swa_kv_lora_rank"],
        win_nope_dim=model["swa_qk_nope_head_dim"],
        win_rope_dim=model["swa_qk_rope_head_dim"],
        win_v_dim=model["swa_v_head_dim"], win_rope_theta=model["swa_rope_theta"],
        index_heads=model["index_n_heads"], index_dim=model["index_head_dim"],
        index_topk=model["index_topk"])
    config = dict(vocab_size=model["vocab_size"], d_model=model["hidden_size"],
                  num_layers=model["num_hidden_layers"],
                  num_heads=model["num_attention_heads"])
    return spec, config


def real_experts(model: dict) -> int:
    return int(model.get("n_routed_experts_published", model["n_routed_experts"]))


def make_params(model: dict, seed: int):
    """The served weights for ``seed``, each leaf as it rests."""
    from seldon_core_tpu.models.spec import init_params

    spec, config = spec_and_config(model)
    return init_params(spec, config, seed)


def sizes(model: dict, kind: str) -> dict:
    """One attention kind's sizes under the full layers' names."""
    if kind == "full":
        return {k: model[k] for k in (
            "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rope_theta")}
    return {k: model["swa_" + k] for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "rope_theta")}


def route(model: dict, scores, bias):
    """``(weights (n, k), chosen (n, k))`` numpy from the sigmoid scores
    ``(n, E)``: the ``k`` largest of ``scores + bias`` (one group, ties
    to the lower expert), weighted by their own scores over their sum."""
    import numpy as np

    scores, bias = np.asarray(scores, np.float32), np.asarray(bias, np.float32)
    chosen = np.argsort(-(scores + bias), axis=-1, kind="stable")[
        :, :model["num_experts_per_tok"]]
    weights = np.take_along_axis(scores, chosen, axis=-1)
    if model.get("norm_topk_prob", True):
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * np.float32(model["routed_scaling_factor"]), chosen


def select(model: dict, index_scores):
    """``S`` as bool ``(m, T)`` numpy from the indexer's scores ``I`` of
    the last ``m`` rows (``m = T``: every row): row ``t`` keeps the
    ``index_topk`` positions ``s <= t`` of largest ``I[t, s]``, ties to
    the lower position (a stable sort); all of them while ``t <
    index_topk``."""
    import numpy as np

    scores = np.asarray(index_scores, np.float32)
    (m, n), topk = scores.shape, model["index_topk"]
    causal = np.tril(np.ones((n, n), bool))[n - m:]
    if n <= topk:
        return causal
    order = np.argsort(-np.where(causal, scores, -np.inf), axis=-1, kind="stable")
    kept = np.zeros((m, n), bool)
    np.put_along_axis(kept, order[:, :topk], True, axis=-1)
    return kept & causal


def layer(p, model: dict, i: int, x, pos, chosen=None, held=None, variant="", tail=None):
    """Layer ``i``: ``(x', parts)`` float32 with ``parts`` what its FFN
    added to the stream — ``routed`` (the held experts'), ``shared`` —
    so a test can add shares up.  ``held`` overrides the ``(offset,
    count)`` of the experts ``p`` holds.  With ``tail``, ``x'`` is the
    last ``tail`` rows alone: only they are queried (every row is a key)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eps, d = model["rms_norm_eps"], model["hidden_size"]
    kind = kinds_of(model)[i]
    sz = sizes(model, kind)
    heads, rank, q_rank = sz["num_attention_heads"], sz["kv_lora_rank"], sz["q_lora_rank"]
    nope, rdim = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"]
    rescale = model["apply_mla_qkv_lora_rescale"] and variant != "no_rescale"
    s_q = (d / q_rank) ** 0.5 if rescale else 1.0
    s_kv = (d / rank) ** 0.5 if rescale else 1.0
    scale = (nope + rdim) ** -0.5
    freq = jnp.asarray(
        1.0 / sz["rope_theta"] ** (np.arange(0, rdim, 2) / rdim), jnp.float32)
    n = x.shape[0]
    window = model["sliding_window_size"] + {"window_short": -1, "window_wide": 1}.get(variant, 0)

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

    y = rms_norm(x, p["attn_norm"]["scale"])
    m = n if tail is None else min(tail, n)                        # rows queried: the last m
    x, yq, qpos = x[n - m:], y[n - m:], pos[n - m:]
    c_q = rms_norm(yq @ f32(p["q_a"]["kernel"]), p["q_a_norm"]["scale"])
    q = ((c_q @ f32(p["q_b"]["kernel"])) * s_q).reshape(m, heads, nope + rdim)
    kva = y @ f32(p["kv_a"]["kernel"])
    c_kv = rms_norm(kva[:, :rank], p["kv_a_norm"]["scale"]) * s_kv
    k_r = rotate(kva[:, rank:], pos)                               # (n, rdim)
    q_nope, q_r = q[..., :nope], rotate(q[..., nope:], qpos)
    k_nope = jnp.einsum("cr,hrn->hcn", c_kv, f32(p["kv_b_k"]))
    v = jnp.einsum("cr,hrv->hcv", c_kv, f32(p["kv_b_v"]))

    seen = np.asarray(pos)[None, :] <= np.asarray(qpos)[:, None]   # (m, n) causal
    if kind == "window":
        seen &= np.asarray(pos)[None, :] > np.asarray(qpos)[:, None] - window
    elif variant != "no_selection":
        ih, idim = model["index_n_heads"], model["index_head_dim"]
        q_i = (c_q @ f32(p["index_q"]["kernel"])).reshape(m, ih, idim)
        q_i = jnp.concatenate([rotate(q_i[..., :rdim], qpos), q_i[..., rdim:]], axis=-1)
        k_i = y @ f32(p["index_k"]["kernel"])
        mean = k_i.mean(-1, keepdims=True)
        k_i = ((k_i - mean) / jnp.sqrt(((k_i - mean) ** 2).mean(-1, keepdims=True) + eps)
               * f32(p["index_k_norm"]["scale"]) + f32(p["index_k_norm"]["bias"]))
        k_i = jnp.concatenate([rotate(k_i[:, :rdim], pos), k_i[:, rdim:]], axis=-1)
        w_i = yq @ f32(p["index_w"]["kernel"])                     # (m, ih)
        index = []
        for lo in range(0, m, QUERY_BLOCK):
            hi = min(m, lo + QUERY_BLOCK)
            last = n - m + hi                                      # keys a block can see
            hit = jax.nn.relu(jnp.einsum("qjd,cd->qjc", q_i[lo:hi], k_i[:last]))
            index.append(jnp.pad(jnp.einsum("qjc,qj->qc", hit, w_i[lo:hi]),
                                 [(0, 0), (0, n - last)]))
        seen = select(model, np.asarray(
            jnp.concatenate(index, axis=0)) * np.float32(ih ** -0.5 * idim ** -0.5))
        if chosen is not None:
            chosen.append(seen)
    seen = jnp.asarray(seen)
    out = []
    for lo in range(0, m, QUERY_BLOCK):
        hi = min(m, lo + QUERY_BLOCK)
        # (the keys a block of queries can see at all: none after its last
        # row, none before its first row's window; the mask does the rest)
        k0, k1 = (max(0, n - m + lo - window + 1) if kind == "window" else 0), n - m + hi
        s = (jnp.einsum("qhn,hcn->hqc", q_nope[lo:hi], k_nope[:, k0:k1])
             + jnp.einsum("qhr,cr->hqc", q_r[lo:hi], k_r[k0:k1])) * scale
        s = jnp.where(seen[None, lo:hi, k0:k1], s, -jnp.inf)
        out.append(jnp.einsum("hqc,hcv->qhv", jax.nn.softmax(s, axis=-1),
                              v[:, k0:k1]))
    attn = jnp.concatenate(out, axis=0)                            # (m, heads, v)
    if variant != "no_gate":
        attn = attn * jax.nn.sigmoid(yq @ f32(p["attn_gate"]["kernel"]))[:, :, None]
    x = x + attn.reshape(m, -1) @ f32(p["attn_proj"]["kernel"])

    h = rms_norm(x, p["ffn_norm"]["scale"])
    if i < model["first_k_dense_replace"]:
        return x + swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"]), {}
    offset, count = held or (model.get("expert_offset", 0), model["n_routed_experts"])
    weights, picked = route(model, jax.nn.sigmoid(h @ f32(p["router"])), p["score_bias"])
    routed = jnp.zeros_like(x)
    for e in range(count):  # the experts that exist here, one by one
        rows, slot = np.nonzero(picked == e + offset)
        if rows.size == 0:
            continue
        part = swiglu(h[rows], p["experts_gate"][e], p["experts_up"][e],
                      p["experts_down"][e])
        routed = routed.at[rows].add(part * weights[rows, slot][:, None])
    shared = swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return x + routed + shared, {"routed": routed, "shared": shared}


def logits(params, model: dict, tokens, tail=None, chosen=None, variant=""):
    """(T, vocab) float32 next-token logits for one sequence of ids; with
    ``tail`` only the last ``tail`` positions'."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0])
        x = jnp.asarray(params["tok_embed"]["embedding"][tokens]).astype(jnp.float32)
        last = model["num_hidden_layers"] - 1
        for i in range(last + 1):
            x, _parts = layer(params[f"block_{i}"], model, i, x, pos, chosen,
                              variant=variant, tail=tail if i == last else None)
        scale = jnp.asarray(params["final_norm"]["scale"]).astype(jnp.float32)
        head = jnp.asarray(params["head"]["kernel"]).astype(jnp.float32)
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale @ head
