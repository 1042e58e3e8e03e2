"""Plain reference for the GPT-2 block: the forward pass in float32
``jax.numpy``, with no cache, no batching and no kernel.

Follows Radford et al. 2019 / the ``gpt2-large`` ``config.json``:
learned token + position embeddings, pre-LayerNorm blocks of causal
multi-head attention and a 4x GELU (tanh form, ``gelu_new``) MLP, a
final LayerNorm and a linear head.  Departures, all because the served
program has them and the comparison is of arithmetic, not of a
checkpoint: the head is its own matrix with a bias (GPT-2 ties it to
the token embedding), and LayerNorm's epsilon is 1e-6 (GPT-2: 1e-5).

The weights are the served ones: the program's seeded initialiser makes
the same tree here on the CPU; each leaf is rounded to bfloat16, the
type the program computes in, and then held in float32.
"""

from __future__ import annotations

LN_EPS = 1e-6


def make_params(model: dict, seed: int):
    """The served weights for ``seed`` as a float32 tree (bf16-rounded)."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.generate import load_lm_params

    params = load_lm_params("", dict(
        vocab_size=model["vocab_size"], d_model=model["n_embd"],
        num_layers=model["n_layer"], num_heads=model["n_head"],
        max_len=model["n_positions"]), seed)
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)


def logits(params, model: dict, tokens, tail=None):
    """(T, vocab) float32 next-token logits for one sequence of ids;
    with ``tail`` only the last ``tail`` positions' (the head is a sixth
    of the work on a long prompt)."""
    import jax
    import jax.numpy as jnp

    def layer_norm(x, p):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]

    def dense(x, p):
        return x @ p["kernel"] + p["bias"]

    heads = model["n_head"]
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        n = tokens.shape[0]
        x = params["tok_embed"]["embedding"][tokens] + params["pos_embed"]["embedding"][:n]
        causal = jnp.tril(jnp.ones((n, n), bool))
        for i in range(model["n_layer"]):
            p = params[f"block_{i}"]
            q, k, v = jnp.split(dense(layer_norm(x, p["LayerNorm_0"]), p["qkv"]), 3, axis=-1)
            q, k, v = (a.reshape(n, heads, -1).transpose(1, 0, 2) for a in (q, k, v))
            scores = q @ k.transpose(0, 2, 1) / jnp.sqrt(q.shape[-1])
            scores = jnp.where(causal[None], scores, -jnp.inf)
            attn = jax.nn.softmax(scores, axis=-1) @ v
            x = x + dense(attn.transpose(1, 0, 2).reshape(n, -1), p["attn_proj"])
            y = jax.nn.gelu(dense(layer_norm(x, p["LayerNorm_1"]), p["mlp_in"]),
                            approximate=True)
            x = x + dense(y, p["mlp_out"])
        x = x if tail is None else x[-tail:]
        return dense(layer_norm(x, params["LayerNorm_0"]), params["head"])
