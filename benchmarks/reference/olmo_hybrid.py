"""Plain reference for ``Olmo-Hybrid-7B`` (``model_type: olmo_hybrid``):
the forward pass in float32 ``jax.numpy`` at ``highest`` matmul precision
— no kernel, no cache, no pages, no state carried between calls, no
batching, no chunks: a linear layer's recurrence runs **position by
position** (``lax.scan`` over ``t``), its convolution is a plain sum of
four taps, a full layer is a causal softmax over the whole sequence.

Follows ``allenai/Olmo-Hybrid-7B`` ``config.json`` (the catalog row's
``config``): ``layer_types`` = (``linear_attention`` x 3,
``full_attention``) x 8, hidden 3,840, SwiGLU of 11,008 in every layer,
RMSNorm eps 1e-6, no biases, untied embedding and head.  With ``x`` the
residual stream ``(n, hidden)`` and every norm on a sub-layer's OUTPUT
(``assumed.norm_placement``)::

    x' = x + RMSNorm_attn(A(x))       A: the layer's attention, below
    x'' = x' + RMSNorm_ffn(W_down(silu(x' W_gate) * (x' W_up)))

**A full layer** (30 heads = 30 K/V heads of 128): ``q = RMSNorm_q(x
W_q)``, ``k = RMSNorm_k(x W_k)`` over the WHOLE 3,840-wide projection
(``assumed.qk_norm``), ``v = x W_v``; NO rotary embedding
(``rope_parameters.rope_theta`` null, ``assumed.positions``); head ``h``:
``softmax(q_h k_h^T / sqrt(128))`` causal, times ``v_h``; ``A = concat_h
W_o``.

**A linear layer** (Gated DeltaNet, arXiv:2412.06464; H = 30 heads,
``d_k`` = 96, ``d_v`` = 192): ``[q~ ; k~ ; v~] = x W_qkv`` (H d_k + H d_k
+ H d_v = 11,520 channels), each channel through a causal convolution of
4 taps and SiLU, ``q^_t = silu(sum_{j<4} c_j * q~_{t-3+j})`` with zeros
before position 0; a head's ``q = q^ / ||q^|| * d_k^-1/2``, ``k = k^ /
||k^||`` (eps 1e-6 under the root, FLA's); ``beta_t = 2 sigmoid((x
W_b)_h)`` (the 2 is ``linear_allow_neg_eigval``), ``alpha_t =
exp(-exp(A_log_h) softplus((x W_a)_h + dt_bias_h))``; the head's state ``S``
``(d_k, d_v)`` from zeros::

    S' = alpha_t S_{t-1};  u = beta_t (v_t - S'^T k_t);  S_t = S' + k_t u^T
    o_t = S_t^T q_t

and ``A = [RMSNorm_{d_v}(o_h) * silu((x W_g)_h)]_h W_o``.

Departures and assumptions, each in the configuration's ``reduced`` or
``assumed``:

* **Depth**: the first ``num_hidden_layers`` entries of ``layer_types``.
* W_q, W_k, W_v rest as one matrix in both layer kinds, W_a and W_b as
  one float32 ``(hidden, 2 H)`` matrix (the same products).
* The weights are the served ones: the program's seeded initialiser
  (``models/spec.py init_params``) makes the same tree here on the CPU;
  every operand is promoted to float32 where it is used.  Nothing else
  of ``models/`` or ``ops/`` is read: the forward pass below is its own.

``variant`` and ``rounding`` are for ``tools/precision_readings.py`` and
the tests alone: a deliberately wrong program (``state_bf16``: the state
rounded to bfloat16 after every position; ``beta_one``: beta without its
factor 2; ``alpha_one``: no decay; ``rope_full``: the full layers
rotated at theta 10,000; ``pre_norm``: the norms moved to the
sub-layers' inputs), or the same equations with the matmuls' operands and
results rounded.  With ``tail`` the final norm and the head run over the
last ``tail`` rows alone, in blocks of :data:`HEAD_BLOCK`: the same
numbers for those rows.
"""

from __future__ import annotations

HEAD_BLOCK = 128   # rows of logits made at once: (128, 100,352) float32 is 51 MB
QUERY_BLOCK = 512  # queries scored at once in a full layer

VARIANTS = ("state_bf16", "beta_one", "alpha_one", "rope_full", "pre_norm")


def kinds_of(model: dict):
    """``("linear" | "full", ...)`` for the layers served."""
    names = {"linear_attention": "linear", "full_attention": "full"}
    return tuple(names[t] for t in
                 model["layer_types"][:model["num_hidden_layers"]])


def spec_and_config(model: dict):
    """The program's ``(ModelSpec, sizes)`` for a ``model`` block holding
    the source's keys."""
    from seldon_core_tpu.models.spec import model_spec

    if model["linear_num_key_heads"] != model["linear_num_value_heads"]:
        raise ValueError("olmo_hybrid: the engine's linear layers have as many "
                         "key heads as value heads")
    heads = model["num_attention_heads"]
    spec = model_spec(
        "olmo_hybrid", kv_heads=model["num_key_value_heads"],
        head_dim=model["hidden_size"] // heads,
        dense_width=model["intermediate_size"], norm_eps=model["rms_norm_eps"],
        layer_kinds=kinds_of(model), lin_heads=model["linear_num_key_heads"],
        lin_key_dim=model["linear_key_head_dim"],
        lin_value_dim=model["linear_value_head_dim"],
        lin_conv=model["linear_conv_kernel_dim"],
        lin_neg_eigval=bool(model["linear_allow_neg_eigval"]))
    config = dict(vocab_size=model["vocab_size"], d_model=model["hidden_size"],
                  num_layers=model["num_hidden_layers"], num_heads=heads)
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


def linear_attention(p, model: dict, x, *, variant=None, rounding=None):
    """A linear layer's ``A(x)`` before its output norm: ``(n, hidden)``."""
    import jax
    import jax.numpy as jnp

    act, rd, w = rounding or _plain()
    f32 = _plain()[0]
    heads = model["linear_num_key_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    taps = model["linear_conv_kernel_dim"]
    n = x.shape[0]

    def mm(a, m):
        return rd(act(a) @ w(m))

    mixed = mm(x, p["qkv"]["kernel"])                            # (n, channels)
    # the convolution: a plain sum of the taps over the inputs before it
    # (tap j weighs the input taps - 1 - j positions back; zeros before 0)
    back = jnp.concatenate([jnp.zeros((taps - 1, mixed.shape[1])), mixed])
    c = f32(p["conv"])
    mixed = jax.nn.silu(sum(back[j:j + n] * c[j] for j in range(taps)))
    q = mixed[:, :heads * dk].reshape(n, heads, dk)
    k = mixed[:, heads * dk:2 * heads * dk].reshape(n, heads, dk)
    v = mixed[:, 2 * heads * dk:].reshape(n, heads, dv)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    ab = x @ f32(p["ab"])                                        # float32 as served
    two = 2.0 if model["linear_allow_neg_eigval"] and variant != "beta_one" else 1.0
    beta = two * jax.nn.sigmoid(ab[:, heads:])
    alpha = jnp.exp(-jnp.exp(f32(p["a_log"]))
                    * jax.nn.softplus(ab[:, :heads] + f32(p["dt_bias"])))
    if variant == "alpha_one":
        alpha = jnp.ones_like(alpha)

    def position(s, xs):  # the recurrence, one position: s (heads, dk, dv)
        q_t, k_t, v_t, a_t, b_t = xs
        s = a_t[:, None, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * u[:, None, :]
        if variant == "state_bf16":
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _s, out = jax.lax.scan(position, jnp.zeros((heads, dk, dv), jnp.float32),
                           (q, k, v, alpha, beta))
    out = _rms_norm(out, p["o_norm"]["scale"], model["rms_norm_eps"])
    gate = mm(x, p["gate"]["kernel"]).reshape(n, heads, dv)
    return mm((out * jax.nn.silu(gate)).reshape(n, heads * dv),
              p["attn_proj"]["kernel"])


def full_attention(p, model: dict, x, *, variant=None, rounding=None):
    """A full layer's ``A(x)`` before its output norm: ``(n, hidden)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    act, rd, w = rounding or _plain()
    heads, eps = model["num_attention_heads"], model["rms_norm_eps"]
    hidden = model["hidden_size"]
    hd = hidden // heads
    n = x.shape[0]
    qkv = rd(act(x) @ w(p["qkv"]["kernel"]))
    q = _rms_norm(qkv[:, :hidden], p["q_norm"]["scale"], eps).reshape(n, heads, hd)
    k = _rms_norm(qkv[:, hidden:2 * hidden], p["k_norm"]["scale"], eps).reshape(
        n, heads, hd)
    v = qkv[:, 2 * hidden:].reshape(n, heads, hd)
    if variant == "rope_full":
        half = hd // 2
        inv = 1.0 / (10_000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
        ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

        def rotate(t):
            t1, t2 = t[..., :half], t[..., half:]
            return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)

        q, k = rotate(q), rotate(k)
    q, k = rd(q), rd(k)
    at = np.arange(n)
    out = []
    for lo in range(0, n, QUERY_BLOCK):
        hi = min(n, lo + QUERY_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", act(q[lo:hi]), act(k)) * hd ** -0.5
        seen = jnp.asarray(at[None, :] <= at[lo:hi, None])
        prob = rd(jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1))
        out.append(rd(jnp.einsum("hqk,khd->qhd", act(prob), act(v))))
    return rd(act(jnp.concatenate(out, 0).reshape(n, hidden))
              @ w(p["attn_proj"]["kernel"]))


def layer(p, model: dict, x, index: int, *, variant=None, rounding=None):
    """Layer ``index``'s map of the residual stream ``x`` ``(n, hidden)``
    float32 with the parameters ``p``."""
    import jax

    act, rd, w = rounding or _plain()
    eps = model["rms_norm_eps"]
    attention = (linear_attention if kinds_of(model)[index] == "linear"
                 else full_attention)
    pre = variant == "pre_norm"

    def sublayer(x, f, scale):
        if pre:  # the wrong placement: the norm on the sub-layer's input
            return x + f(_rms_norm(x, scale, eps))
        return x + _rms_norm(f(x), scale, eps)

    def ffn(y):
        hidden = rd(jax.nn.silu(rd(act(y) @ w(p["mlp_gate"])))
                    * rd(act(y) @ w(p["mlp_up"])))
        return rd(act(hidden) @ w(p["mlp_down"]))

    x = sublayer(x, lambda y: attention(p, model, y, variant=variant,
                                        rounding=rounding),
                 p["attn_post_norm"]["scale"])
    return sublayer(x, ffn, p["ffn_post_norm"]["scale"])


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
