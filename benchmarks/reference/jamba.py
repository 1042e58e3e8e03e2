"""Plain reference for ``AI21-Jamba2-3B`` (``model_type: jamba``; HF
``transformers`` ``JambaForCausalLM``): the forward pass in float32
``jax.numpy`` at ``highest`` matmul precision — no kernel, no cache, no
pages, no state carried between calls, no batching, no chunks: a Mamba
layer's recurrence runs **position by position** (``lax.scan`` over ``t``
with the ``(E, N)`` state as carry), its convolution is a plain sum of
four taps plus its bias, an attention layer is a causal softmax over the
whole sequence with the one K/V head repeated, the head is the
embedding's transpose.

Follows ``ai21labs/AI21-Jamba2-3B`` ``config.json`` (the catalog row's
``config``): 28 layers, hidden 2,560, SwiGLU of 8,192 in every layer
(``num_experts`` 1), RMSNorm eps 1e-6, no biases but the convolution's
and ``dt_proj``'s, embedding and head tied.  With ``x`` the residual
stream ``(n, hidden)`` and every norm on a sub-layer's INPUT
(``assumed.pre_norm``)::

    x' = x + M(RMSNorm_attn(x))       M: the layer's mixer, below
    x'' = x' + W_down(silu(RMSNorm_ffn(x') W_gate) * (RMSNorm_ffn(x') W_up))

**Which layer is which** (``assumed.layer_types``): layer ``i`` is
attention where ``i % attn_layer_period == attn_layer_offset`` (7 and 21
of 28) and a Mamba mixer elsewhere — the ``jamba`` family's rule; the
config, not the catalog's ``described_as`` "7 : 1", is trusted.

**A Mamba mixer** (``E`` = ``mamba_expand`` x hidden = 5,120 channels,
``N`` = ``mamba_d_state`` = 16, ``R`` = ``mamba_dt_rank`` = 160), ``u``
the normed input: ``[x~ ; z] = u W_in`` (no bias); ``x_t = silu(sum_{j<4}
w_j * x~_{t-3+j} + b_conv)`` with zeros before position 0; ``[delta ; B ;
C] = x_t W_x`` (R + N + N, no bias); ``delta``, ``B``, ``C`` each through
an RMSNorm of its own with a learned scale (``assumed.inner_norms``);
``Delta_t = softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``; the state
``h`` ``(E, N)`` float32 from zeros::

    h_t = exp(Delta_t (x) 1 . A) . h_{t-1} + (Delta_t . x_t) (x) B_t
    y_t = h_t C_t + D . x_t

and ``M = (y . silu(z)) W_out`` (no bias).

**An attention layer** (20 query heads of 128 over ONE K/V head): ``q =
u W_q``, ``k = u W_k``, ``v = u W_v``; NO positional encoding of any kind
(the Mamba layers carry position); head ``h``: ``softmax(q_h k^T /
sqrt(128))`` causal, times ``v``; ``M = concat_h W_o``; no biases, no
QK-norm, no window.

Departures and assumptions, each in the configuration's ``reduced`` or
``assumed``:

* W_q, W_k, W_v rest as one matrix (the same products); ``A_log`` rests
  ``(N, E)``, the state's own layout, where HF's is ``(E, N)``.
* The weights are the served ones: the program's seeded initialiser
  (``models/spec.py init_params``) makes the same tree here on the CPU;
  every operand is promoted to float32 where it is used.  Nothing of
  ``seldon_core_tpu/ops`` is read: the forward pass below is its own.

``variant`` and ``rounding`` are for ``tools/precision_readings.py`` and
the tests alone: a deliberately wrong program (:data:`VARIANTS`), or the
same equations with the matmuls' operands and results rounded.  With
``tail`` the final norm and the head run over the last ``tail`` rows
alone, in blocks of :data:`HEAD_BLOCK`: the same numbers for those rows.
"""

from __future__ import annotations

HEAD_BLOCK = 128   # rows of logits made at once: (128, 65,536) float32 is 34 MB
QUERY_BLOCK = 512  # queries scored at once in an attention layer

# the wrong programs: the three inner norms left out; A_log used without
# its -exp; the softplus left out; the convolution's bias left out; D x
# left out; the state rounded to bfloat16 after every position; the
# attention layers rotated at theta 10,000; a head of its own (drawn, not
# the embedding's transpose); query head h reading K/V "head" h of 20
# (k and v cut into 20 slices of head_dim / 20, zero-padded: what a
# program that took the one head for twenty would read)
VARIANTS = ("no_inner_norms", "a_log_raw", "no_softplus", "no_conv_bias",
            "no_skip", "state_bf16", "rope_full", "untied_head", "kv_as_heads")


def kinds_of(model: dict):
    """``("ssm" | "full", ...)`` for the layers served (assumed: the
    ``jamba`` family's rule on ``attn_layer_period`` / ``_offset``)."""
    period, offset = model["attn_layer_period"], model["attn_layer_offset"]
    return tuple("full" if i % period == offset else "ssm"
                 for i in range(model["num_hidden_layers"]))


def spec_and_config(model: dict):
    """The program's ``(ModelSpec, sizes)`` for a ``model`` block holding
    the source's keys."""
    from seldon_core_tpu.models.spec import model_spec

    if model.get("mamba_proj_bias"):
        raise ValueError("jamba: a bias on the mixer's projections is not built")
    heads = model["num_attention_heads"]
    spec = model_spec(
        "jamba", kv_heads=model["num_key_value_heads"],
        head_dim=model["hidden_size"] // heads,   # assumed: no head_dim key
        dense_width=model["intermediate_size"], norm_eps=model["rms_norm_eps"],
        layer_kinds=kinds_of(model),
        ssm_inner=model["mamba_expand"] * model["hidden_size"],
        ssm_state=model["mamba_d_state"], ssm_conv=model["mamba_d_conv"],
        ssm_dt_rank=model["mamba_dt_rank"],
        ssm_conv_bias=bool(model["mamba_conv_bias"]),
        num_experts=model.get("num_experts", 1),
        experts_per_tok=model.get("num_experts_per_tok", 1))
    if not model.get("tie_word_embeddings", True):
        raise ValueError("jamba: an untied head is not the configuration's")
    config = dict(vocab_size=model["vocab_size"], d_model=model["hidden_size"],
                  num_layers=model["num_hidden_layers"], num_heads=heads)
    return spec, config


def make_params(model: dict, seed: int):
    """The served weights for ``seed``, in the types they rest in: each
    operand is promoted where it is used (assumed: seeded, ``A_log`` in
    [0, ln 16), softplus(``b_dt``) in [1e-3, 1e-1], ``D`` in [0.5, 1.5):
    Mamba's own initial ranges)."""
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


def mamba_mixer(p, model: dict, u, *, variant=None, rounding=None):
    """A Mamba layer's ``M(u)``: ``(n, hidden)``."""
    import jax
    import jax.numpy as jnp

    act, rd, w = rounding or _plain()
    f32 = _plain()[0]
    inner = model["mamba_expand"] * model["hidden_size"]
    cols, rank = model["mamba_d_state"], model["mamba_dt_rank"]
    taps, eps = model["mamba_d_conv"], model["rms_norm_eps"]
    n = u.shape[0]

    def mm(a, m):
        return rd(act(a) @ w(m))

    xz = mm(u, p["in_proj"]["kernel"])                           # (n, 2 E)
    x, z = xz[:, :inner], xz[:, inner:]
    # the convolution: a plain sum of the taps over the inputs before it
    # (tap j weighs the input taps - 1 - j positions back; zeros before 0)
    # plus its bias, then SiLU
    back = jnp.concatenate([jnp.zeros((taps - 1, inner)), x])
    c = f32(p["conv"])
    x = sum(back[j:j + n] * c[j] for j in range(taps))
    if model["mamba_conv_bias"] and variant != "no_conv_bias":
        x = x + f32(p["conv_bias"])
    x = jax.nn.silu(x)
    low = mm(x, p["x_proj"])                                     # (n, R + 2 N)
    delta, b, cc = low[:, :rank], low[:, rank:rank + cols], low[:, rank + cols:]
    if variant != "no_inner_norms":  # assumed: Jamba2 keeps modeling_jamba's
        delta = _rms_norm(delta, p["dt_norm"], eps)
        b = _rms_norm(b, p["b_norm"], eps)
        cc = _rms_norm(cc, p["c_norm"], eps)
    dt = mm(delta, p["dt_proj"]) + f32(p["dt_bias"])  # (a bias on dt_proj always)
    if variant != "no_softplus":
        dt = jax.nn.softplus(dt)
    a = f32(p["a_log"]).T                                        # (E, N)
    a = a if variant == "a_log_raw" else -jnp.exp(a)
    skip = f32(p["d_skip"])                                      # D a channel

    def position(h, xs):  # the recurrence, one position: h (E, N) float32
        x_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x_t)[:, None] * b_t[None, :]
        if variant == "state_bf16":
            h = h.astype(jnp.bfloat16).astype(jnp.float32)
        y = h @ c_t
        return h, (y if variant == "no_skip" else y + skip * x_t)

    _h, y = jax.lax.scan(position, jnp.zeros((inner, cols), jnp.float32),
                         (x, dt, b, cc))
    return mm(y * jax.nn.silu(z), p["attn_proj"]["kernel"])


def attention(p, model: dict, u, *, variant=None, rounding=None):
    """An attention layer's ``M(u)``: ``(n, hidden)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    act, rd, w = rounding or _plain()
    heads, kv_heads = model["num_attention_heads"], model["num_key_value_heads"]
    hidden = model["hidden_size"]
    hd = hidden // heads                       # assumed: head_dim = hidden / heads
    q_w, kv_w = heads * hd, kv_heads * hd
    n = u.shape[0]
    qkv = rd(act(u) @ w(p["qkv"]["kernel"]))
    q = qkv[:, :q_w].reshape(n, heads, hd)
    k = qkv[:, q_w:q_w + kv_w].reshape(n, kv_heads, hd)
    v = qkv[:, q_w + kv_w:].reshape(n, kv_heads, hd)
    if variant == "rope_full":
        half = hd // 2
        inv = 1.0 / (10_000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
        ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

        def rotate(t):
            t1, t2 = t[..., :half], t[..., half:]
            return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)

        q, k = rotate(q), rotate(k)
    # the one K/V head repeated: every query head reads it
    share = heads // kv_heads
    k, v = jnp.repeat(k, share, axis=1), jnp.repeat(v, share, axis=1)
    if variant == "kv_as_heads":
        # the wrong reading: the K/V row cut into as many "heads" as q has
        part = hd // share
        cut = (np.arange(hd)[None, :] // part) == np.arange(heads)[:, None]
        k, v = k * jnp.asarray(cut)[None], v * jnp.asarray(cut)[None]
    q, k = rd(q), rd(k)
    at = np.arange(n)
    out = []
    for lo in range(0, n, QUERY_BLOCK):
        hi = min(n, lo + QUERY_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", act(q[lo:hi]), act(k)) * hd ** -0.5
        seen = jnp.asarray(at[None, :] <= at[lo:hi, None])
        prob = rd(jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1))
        out.append(rd(jnp.einsum("hqk,khd->qhd", act(prob), act(v))))
    return rd(act(jnp.concatenate(out, 0).reshape(n, q_w))
              @ w(p["attn_proj"]["kernel"]))


def layer(p, model: dict, x, index: int, *, variant=None, rounding=None):
    """Layer ``index``'s map of the residual stream ``x`` ``(n, hidden)``
    float32 with the parameters ``p`` (assumed: pre-norm)."""
    import jax

    act, rd, w = rounding or _plain()
    eps = model["rms_norm_eps"]
    mixer = mamba_mixer if kinds_of(model)[index] == "ssm" else attention
    x = x + mixer(p, model, _rms_norm(x, p["attn_norm"]["scale"], eps),
                  variant=variant, rounding=rounding)
    y = _rms_norm(x, p["ffn_norm"]["scale"], eps)
    hidden = rd(jax.nn.silu(rd(act(y) @ w(p["mlp_gate"])))
                * rd(act(y) @ w(p["mlp_up"])))
    return x + rd(act(hidden) @ w(p["mlp_down"]))


def logits(params, model: dict, tokens, tail=None, variant=None, rounding=None):
    """``(T, vocab)`` float32 next-token logits for one sequence of ids;
    with ``tail`` only the last ``tail`` positions'."""
    import jax
    import jax.numpy as jnp

    f32 = _plain()[0]
    act, rd, w = rounding or _plain()
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        table = params["tok_embed"]["embedding"]
        x = f32(table[tokens])
        for i in range(model["num_hidden_layers"]):
            x = layer(params[f"block_{i}"], model, x, i, variant=variant,
                      rounding=rounding)
        x = x if tail is None else x[-tail:]
        x = _rms_norm(x, params["final_norm"]["scale"], model["rms_norm_eps"])
        head = w(table).T  # tied: the embedding's transpose
        if variant == "untied_head":
            head = jax.random.uniform(
                jax.random.key(7), head.shape, jnp.float32,
                -(3.0 / head.shape[0]) ** 0.5, (3.0 / head.shape[0]) ** 0.5)
        return jnp.concatenate([
            rd(act(x[lo:lo + HEAD_BLOCK]) @ head)
            for lo in range(0, x.shape[0], HEAD_BLOCK)], axis=0)
