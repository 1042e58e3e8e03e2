"""Plain reference for the OLMoE block: the forward pass in float32
``jax.numpy`` at ``highest`` matmul precision, with no cache, no
batching, no kernel, and the experts as a plain loop over the rows
routed to each.

Follows ``allenai/OLMoE-1B-7B-0125-Instruct`` (``config.json``,
``model_type: olmoe``; HF ``modeling_olmoe.py``).  Per layer, with ``x``
the residual stream:

    h = RMSNorm(x; w_in, eps)
    q, k, v = h W_q, h W_k, h W_v                 (no bias)
    q = RMSNorm(q; w_qn),  k = RMSNorm(k; w_kn)   over the whole 2048-wide
                                                  projection, before the heads
    split into heads of 128; rotary embedding on q and k (rotate-half,
        theta 10,000, position = absolute token index)
    causal softmax attention, scale 1/sqrt(head_dim);  x = x + o W_o
    h = RMSNorm(x; w_post, eps)
    r = h W_r in float32;  p = softmax(r) over the experts;  top-k of p
    y = sum_{e in topk} p_e W_down,e (silu(h W_gate,e) * (h W_up,e))
        with the p_e as they are (norm_topk_prob: false);  x = x + y

then a final RMSNorm and an untied head without bias.  K is what a
cache would hold after QK-norm and RoPE, V as projected.

Departures, each in the configuration's ``assumed``: QK-norm is in the
modelling code, not a ``config.json`` key; W_q, W_k, W_v rest as one
``(d, 3d)`` matrix (the same products); the weights are the served
ones — the program's seeded initialiser (``models/spec.py``) makes the
same tree here on the CPU, each leaf rounded to bfloat16 where the
program rests it in bfloat16, then held in float32.

``logits(..., routing=out_list)`` also appends each layer's chosen
expert sets, for the routing-agreement probe.
"""

from __future__ import annotations


def spec_and_config(model: dict):
    """The program's ``(ModelSpec, sizes)`` for a ``model`` block
    holding the source's keys."""
    from seldon_core_tpu.models.spec import model_spec

    spec = model_spec(
        "olmoe", num_experts=model["num_experts"],
        experts_per_tok=model["num_experts_per_tok"],
        expert_width=model["intermediate_size"],
        rope_theta=model["rope_theta"], norm_eps=model["rms_norm_eps"])
    config = dict(vocab_size=model["vocab_size"], d_model=model["hidden_size"],
                  num_layers=model["num_hidden_layers"],
                  num_heads=model["num_attention_heads"])
    return spec, config


def make_params(model: dict, seed: int):
    """The served weights for ``seed`` as a float32 tree."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.spec import init_params

    spec, config = spec_and_config(model)
    params = init_params(spec, config, seed)  # bf16 / f32 as served
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)


def logits(params, model: dict, tokens, tail=None, routing=None):
    """(T, vocab) float32 next-token logits for one sequence of ids;
    with ``tail`` only the last ``tail`` positions'."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eps = model["rms_norm_eps"]
    heads, top_k = model["num_attention_heads"], model["num_experts_per_tok"]

    def rms_norm(x, scale):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale

    def rotate(x, pos):  # x: (n, heads, hd)
        half = x.shape[-1] // 2
        inv = 1.0 / (model["rope_theta"] ** (jnp.arange(half, dtype=jnp.float32) / half))
        ang = pos[:, None].astype(jnp.float32) * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        n = tokens.shape[0]
        pos = jnp.arange(n)
        # (a tree still in the types it rests in is promoted to float32
        # operand by operand; the embedding's rows are the one place
        # that needs saying)
        x = params["tok_embed"]["embedding"][tokens].astype(jnp.float32)
        causal = jnp.tril(jnp.ones((n, n), bool))
        for i in range(model["num_hidden_layers"]):
            p = params[f"block_{i}"]
            h = rms_norm(x, p["attn_norm"]["scale"])
            q, k, v = jnp.split(h @ p["qkv"]["kernel"], 3, axis=-1)
            q = rms_norm(q, p["q_norm"]["scale"])
            k = rms_norm(k, p["k_norm"]["scale"])
            q, k, v = (a.reshape(n, heads, -1) for a in (q, k, v))
            q, k = rotate(q, pos), rotate(k, pos)
            q, k, v = (a.transpose(1, 0, 2) for a in (q, k, v))
            scores = q @ k.transpose(0, 2, 1) / jnp.sqrt(q.shape[-1])
            scores = jnp.where(causal[None], scores, -jnp.inf)
            attn = jax.nn.softmax(scores, axis=-1) @ v
            x = x + attn.transpose(1, 0, 2).reshape(n, -1) @ p["attn_proj"]["kernel"]

            h = rms_norm(x, p["ffn_norm"]["scale"])
            probs = jax.nn.softmax(h @ p["router"], axis=-1)
            gates, chosen = jax.lax.top_k(probs, top_k)
            gates, chosen = np.asarray(gates), np.asarray(chosen)
            if routing is not None:
                routing.append(chosen)
            y = jnp.zeros_like(x)
            for e in np.unique(chosen):
                rows, slot = np.nonzero(chosen == e)
                he = h[rows]
                out = (jax.nn.silu(he @ p["experts_gate"][e]) * (he @ p["experts_up"][e])
                       ) @ p["experts_down"][e]
                y = y.at[rows].add(out * gates[rows, slot][:, None])
            x = x + y
        x = x if tail is None else x[-tail:]
        return rms_norm(x, params["final_norm"]["scale"]) @ params["head"]["kernel"]
