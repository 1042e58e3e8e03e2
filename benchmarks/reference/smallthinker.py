"""Plain reference for ``SmallThinker-21BA3B-Instruct``'s language model
(``model_name: smallthinker_21b_instruct``): the forward pass in float32
``jax.numpy`` at ``highest`` matmul precision — no kernel, no cache, no
pages, no batching, no grouped matmul; attention a block of queries at a
time (a ``(28, S, S)`` score array is 7.5 GB at 8,192 positions), K and
V repeated to the query heads in the plainest way there is; the routed
experts a plain loop over the experts held.

Follows ``PowerInfer/SmallThinker-21BA3B-Instruct`` (``config.json`` and
the catalog row's ``described_as``).  All layers are routed (no dense
layer).  ``sliding_window_layout[l]`` and ``rope_layout[l]`` are both 0
on layers 0, 4, 8, ... (full attention, NO positional encoding) and 1
elsewhere (window 4,096, RoPE).  RMSNorm eps 1e-6 throughout, no biases.
With ``x`` the residual stream of layer ``l``:

    y = RMSNorm_in(x)
    r = y W_r                     the ROUTER, here: 2,560 -> 64, float32,
                                  from the rows that feed q, k and v
    q = y W_q (-> 28 heads of 128 = 3,584),  k = y W_k,  v = y W_v
                                  (-> 4 heads of 128 = 512 each)
    rope_layout[l] == 1:  q and k rotated (rotate-half form, theta
                          1,500,000, no scaling: rope_scaling null);
    rope_layout[l] == 0:  NOT rotated
    query head h attends K/V head h // 7, scale 128^-0.5, causal;
    sliding_window_layout[l] == 1: row t attends s in t - 4,095 .. t;
                              == 0: every s <= t
    x' = x + concat_h(a_h) W_o    (3,584 -> 2,560)

    g = RMSNorm_post(x')
    p = softmax(r) over all 64; the top 6 by p, their weights divided by
        their sum (norm_topk_prob: the same numbers as a softmax over the
        six chosen logits, so either order of
        moe_primary_router_apply_softmax's reading gives these equations)
    out = x' + sum_{e chosen, e held} w_e W_down,e (relu(g W_gate,e) * (g W_up,e))
        experts of width 768, no shared expert

then a final RMSNorm and an untied head without bias
(``tie_word_embeddings`` false).

Departures and assumptions, each in the configuration's ``reduced`` or
``assumed``:

* **Depth**: the first ``num_hidden_layers`` entries of the two layouts.
* **The share** (guide section 4): the router scores all
  ``moe_num_primary_experts_published`` experts; only experts
  ``expert_offset .. + moe_num_primary_experts`` exist here, and what an
  absent expert would have added is left out (in the program alike); the
  vocabulary is the slice ``vocab_size`` names.
* No biases and no QK-norm (none among the config's keys); the
  activation is ReLU from ``described_as`` ("sparse ReGLU"), not a key;
  ``described_as``' "secondary experts" have no key in the config and
  are not served; the window counts the token (HF's mask convention).
* W_q, W_k, W_v rest as one ``(2,560, 3,584 + 512 + 512)`` matrix (the
  same products).
* The weights are the served ones: the program's seeded initialiser
  (``models/spec.py init_params``) makes the same tree here on the CPU;
  every operand is promoted to float32 where it is used.  Nothing else
  of ``models/`` is read: the forward pass below is its own.

``layer(...)`` is one layer's map ``x -> x'`` for the share test (every
expert of a published layer against four shares of 16).  ``variant``
and ``rounding`` are for ``tools/precision_readings.py`` and the tests
alone: a deliberately wrong program (``router_post``: the router fed
from the post-attention norm; ``silu``; ``rope_full``: a full layer
rotated; ``window_whole``: a window layer left whole; ``kv_mod``: query
head ``h`` on K/V head ``h % 4``), or the same equations with the
matmuls' operands and results rounded.  With ``tail`` the LAST layer
computes its queries, attention and experts for the last ``tail`` rows
only (its keys and values for every row): the same numbers for those
rows.
"""

from __future__ import annotations

# queries scored at once: (28, 256, n) float32 is 0.13 GB at n = 4,608
QUERY_BLOCK = 256

VARIANTS = ("router_post", "silu", "rope_full", "window_whole", "kv_mod")


def kinds_of(model: dict):
    """``("full" | "window", ...)`` for the layers served."""
    return tuple("window" if w else "full" for w in
                 model["sliding_window_layout"][:model["num_hidden_layers"]])


def spec_and_config(model: dict):
    """The program's ``(ModelSpec, sizes)`` for a ``model`` block holding
    the source's keys.  ``moe_num_primary_experts`` counts the experts
    HELD here; ``moe_num_primary_experts_published`` states the router's
    outputs and ``expert_offset`` where the held ones start."""
    from seldon_core_tpu.models.spec import model_spec

    if list(model["rope_layout"]) != list(model["sliding_window_layout"]):
        raise ValueError("smallthinker: the engine rotates the window layers "
                         "and no other (rope_layout == sliding_window_layout)")
    published = model.get("moe_num_primary_experts_published",
                          model["moe_num_primary_experts"])
    held = model["moe_num_primary_experts"]
    spec = model_spec(
        "smallthinker", num_experts=published,
        experts_per_tok=model["moe_num_active_primary_experts"],
        expert_width=model["moe_ffn_hidden_size"],
        experts_held=held if held != published else 0,
        expert_offset=model.get("expert_offset", 0),
        kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        rope_theta=model["rope_theta"], norm_eps=model["rms_norm_eps"],
        layer_kinds=kinds_of(model), window=model["sliding_window_size"])
    config = dict(vocab_size=model["vocab_size"], d_model=model["hidden_size"],
                  num_layers=model["num_hidden_layers"],
                  num_heads=model["num_attention_heads"])
    return spec, config


def make_params(model: dict, seed: int):
    """The served weights for ``seed``, in the types they rest in (a
    float32 copy of 1.58 G parameters would be 6.3 GB beside the server:
    each operand is promoted where it is used)."""
    from seldon_core_tpu.models.spec import init_params

    spec, config = spec_and_config(model)
    return init_params(spec, config, seed)


def _plain():
    import jax.numpy as jnp

    def f32(a):
        return jnp.asarray(a).astype(jnp.float32)

    return f32, (lambda a: a), f32  # act, result, weight


def layer(p, model: dict, x, index: int, *, rows=None, variant=None,
          rounding=None):
    """Layer ``index``'s map of the residual stream ``x`` ``(n, hidden)``
    float32 with the parameters ``p``: ``(n, hidden)``, or with ``rows``
    (a slice) those rows' alone — keys and values are made for every
    row, queries, attention and experts for ``rows``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    act, rd, w = rounding or _plain()
    f32 = _plain()[0]
    eps = model["rms_norm_eps"]
    heads, kv_heads, hd = (model["num_attention_heads"],
                           model["num_key_value_heads"], model["head_dim"])
    share = heads // kv_heads
    q_w, kv_w = heads * hd, kv_heads * hd
    held, offset = model["moe_num_primary_experts"], model.get("expert_offset", 0)
    top_k = model["moe_num_active_primary_experts"]
    rotated = bool(model["rope_layout"][index]) or variant == "rope_full"
    window = (model["sliding_window_size"]
              if model["sliding_window_layout"][index] and variant != "window_whole"
              else 0)

    def mm(a, m):
        return rd(act(a) @ w(m))

    def rms_norm(v, scale):
        return v / jnp.sqrt((v * v).mean(-1, keepdims=True) + eps) * f32(scale)

    def rotate(v, pos):  # v: (n, heads, hd)
        half = hd // 2
        inv = 1.0 / (model["rope_theta"] ** (jnp.arange(half, dtype=jnp.float32) / half))
        ang = pos[:, None].astype(jnp.float32) * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        v1, v2 = v[..., :half], v[..., half:]
        return jnp.concatenate([v1 * cos - v2 * sin, v2 * cos + v1 * sin], axis=-1)

    n = x.shape[0]
    pos = jnp.arange(n)
    rows = slice(0, n) if rows is None else rows
    y = rms_norm(x, p["attn_norm"]["scale"])
    qkv = mm(y, p["qkv"]["kernel"])
    q = qkv[rows, :q_w].reshape(-1, heads, hd)
    k = qkv[:, q_w:q_w + kv_w].reshape(n, kv_heads, hd)
    v = qkv[:, q_w + kv_w:].reshape(n, kv_heads, hd)
    if rotated:
        q, k = rd(rotate(q, pos[rows])), rd(rotate(k, pos))
    # query head h reads K/V head h // share
    of = (np.arange(heads) % kv_heads if variant == "kv_mod"
          else np.arange(heads) // share)
    k_h, v_h = k[:, of].transpose(1, 0, 2), v[:, of].transpose(1, 0, 2)  # (h, n, hd)
    q_at, key_at = np.arange(n)[rows], np.arange(n)
    out = []
    for lo in range(0, q.shape[0], QUERY_BLOCK):
        hi = min(q.shape[0], lo + QUERY_BLOCK)
        s = jnp.einsum("qhd,hkd->hqk", act(q[lo:hi]), act(k_h)) * hd ** -0.5
        seen = key_at[None, :] <= q_at[lo:hi, None]
        if window:
            seen &= key_at[None, :] > q_at[lo:hi, None] - window
        prob = rd(jax.nn.softmax(jnp.where(jnp.asarray(seen)[None], s, -jnp.inf), axis=-1))
        out.append(rd(jnp.einsum("hqk,hkd->qhd", act(prob), act(v_h))))
    attn = jnp.concatenate(out, 0).reshape(-1, q_w)
    x = x[rows] + mm(attn, p["attn_proj"]["kernel"])

    g = rms_norm(x, p["ffn_norm"]["scale"])
    # the router: float32, from the attention's input
    r = (g if variant == "router_post" else y[rows]) @ f32(p["router"])
    gates, chosen = jax.lax.top_k(jax.nn.softmax(r, axis=-1), top_k)
    gates = np.asarray(gates / gates.sum(-1, keepdims=True))
    chosen = np.asarray(chosen)
    gate_act = jax.nn.silu if variant == "silu" else jax.nn.relu
    routed = jnp.zeros_like(x)
    for e in range(held):
        at, slot = np.nonzero(chosen == e + offset)
        if at.size:
            ge = g[at]
            part = mm(rd(gate_act(mm(ge, p["experts_gate"][e]))
                         * mm(ge, p["experts_up"][e])), p["experts_down"][e])
            routed = routed.at[at].add(part * gates[at, slot][:, None])
    return x + routed


def logits(params, model: dict, tokens, tail=None, variant=None, rounding=None):
    """``(T, vocab)`` float32 next-token logits for one sequence of ids;
    with ``tail`` only the last ``tail`` positions'."""
    import jax
    import jax.numpy as jnp

    f32 = _plain()[0]
    act, rd, w = rounding or _plain()
    eps = model["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        n = tokens.shape[0]
        x = f32(params["tok_embed"]["embedding"][tokens])
        last = model["num_hidden_layers"] - 1
        for i in range(last + 1):
            rows = slice(n - tail, n) if tail is not None and i == last else None
            x = layer(params[f"block_{i}"], model, x, i, rows=rows,
                      variant=variant, rounding=rounding)
        x = x if tail is None else x[-tail:]
        x = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * f32(
            params["final_norm"]["scale"])
        return rd(act(x) @ w(params["head"]["kernel"]))
