"""A ``ModelSpec`` (models/spec.py) as traced blocks: the flax modules
the paged engine's programs apply, parameter-compatible with
TransformerLM.  Nothing here knows how pages are allocated, what a
stream is or when a wave runs: a block is handed its pool, its tables
and its lengths as arguments."""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from seldon_core_tpu.models.spec import GPT2

from .lanes import paged_kernel_mode, paged_kernel_static_eligible


def _rest(spec, dtype):
    """The type ``init`` makes a spec's matrices and embeddings in
    (``apply`` takes the tree as it is given: the engine hands it
    one cast to the compute type, models/spec.py ``rest_tree``)."""
    return jnp.float32 if spec.weights_f32 else dtype

def _dense(precision, features, dtype, name, spec=GPT2):
    """Projection factory: ``precision="w8a8"`` swaps every decode
    projection (qkv, attn_proj, mlp_in/out, the unembed head) for
    the int8×int8 layer (ops/w8a8.py) — SAME params tree as
    nn.Dense, so the TransformerLM checkpoint-parity invariant
    holds across precisions.  The engine passes only ``params`` to
    apply, so activation scales are dynamic PER-TOKEN (abs-max over
    d only — never the slot axis, so one stream's quantisation grid
    cannot depend on co-scheduled traffic, and the width-1 decode
    and width-(k+1) speculative-verify programs quantise each token
    identically: greedy exactness holds, tested)."""
    if precision == "w8a8":
        from seldon_core_tpu.ops.w8a8 import W8A8Dense

        return W8A8Dense(features=features, dtype=dtype, name=name)
    return nn.Dense(features, use_bias=spec.bias, dtype=dtype,
                    param_dtype=_rest(spec, dtype), name=name)

# ---- what a ModelSpec (models/spec.py) changes in a block ---------
# Each helper traces exactly the GPT-2 operations for the GPT2 spec
# (the auto-named LayerNorms, the biased Dense, the GELU MLP), so
# GPT-2's programs lower as they did before a second model came.

def _norm(spec, name):
    if spec.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=spec.norm_eps, dtype=jnp.float32,
                          name=name)
    return nn.LayerNorm(dtype=jnp.float32)

def _rotates(mod):
    """Whether this block rotates q and k: the spec's positions, or
    its layer kind's where positions are a kind (a full layer of a
    grouped-query spec with kinds has none at all)."""
    kind = getattr(mod, "kind", None)
    if kind is not None and not mod.spec.latent:
        return kind.positions == "rope"
    return mod.spec.rope

def _heads(mod, q, k, v, positions, shape, kv_shape=None):
    """Split flat q/k/v into heads (``kv_shape``: k and v where they
    hold fewer heads than q); before that the spec's QK-norm
    (RMSNorm over the whole projection), after it its rotary
    embedding at the tokens' absolute positions — both on q and k
    only, both before K is cached."""
    spec = mod.spec
    if spec.qk_norm:
        q = nn.RMSNorm(epsilon=spec.norm_eps, dtype=jnp.float32,
                       name="q_norm")(q)
        k = nn.RMSNorm(epsilon=spec.norm_eps, dtype=jnp.float32,
                       name="k_norm")(k)
    kv_shape = kv_shape or shape
    q, k, v = q.reshape(shape), k.reshape(kv_shape), v.reshape(kv_shape)
    if _rotates(mod):
        from seldon_core_tpu.models.spec import rope

        q = rope(q, positions, spec.rope_theta)
        k = rope(k, positions, spec.rope_theta)
    if spec.qk_norm or _rotates(mod):  # both compute in f32
        q, k = q.astype(mod.dtype), k.astype(mod.dtype)
    return q, k, v

def _ffn(mod, x, proj, token_mask, router_logits=None):
    """The block's second half: ``x + FFN(norm(x))``.  Dense GELU
    MLP, or routed SwiGLU experts (ops/moe.py) — then the second
    value holds the layer's assignment histogram ``(int32[E],)``
    over the rows ``token_mask`` keeps (``()`` for a dense FFN)."""
    spec = mod.spec
    if spec.score == "sigmoid":
        return _ffn_grouped(mod, x, token_mask)
    if spec.ffn == "swiglu":
        return _ffn_swiglu(mod, x), ()
    d_model = x.shape[-1]
    y = _norm(spec, "ffn_norm")(x)
    if not spec.routed:
        y = proj("mlp_in", mod.mlp_ratio * d_model, y)
        y = nn.gelu(y)
        return x + proj("mlp_out", d_model, y), ()
    from seldon_core_tpu.ops import moe

    e, f = spec.num_experts, spec.expert_width
    init = nn.initializers.normal(0.02)
    rest = _rest(spec, mod.dtype)
    # (every expert, or a replica's share of them: spec.held)
    held = spec.held
    rows = y.reshape(-1, d_model)
    # what the spec adds to the call, and nothing where it adds
    # nothing: OLMoE's trace is as it was
    renorm = {"norm": True} if spec.norm_topk else {}
    act = {} if spec.expert_act == "silu" else {"act": spec.expert_act}
    if router_logits is None:
        w_router = mod.param("router", init, (d_model, e), jnp.float32)
        gates, experts = moe.route(
            rows, w_router, spec.experts_per_tok, **renorm)
    else:
        # the router read the attention's input: its logits came
        # with the call, (T, E) float32
        gates, experts = moe.route(
            None, None, spec.experts_per_tok,
            logits=router_logits.reshape(-1, e), **renorm)
    w_gate = mod.param("experts_gate", init, (held, d_model, f), rest)
    w_up = mod.param("experts_up", init, (held, d_model, f), rest)
    w_down = mod.param("experts_down", init, (held, f, d_model), rest)
    if spec.experts_held:
        out = moe.expert_ffn_held(
            rows.astype(mod.dtype), w_gate, w_up, w_down, gates, experts,
            spec.expert_offset, e, **act)
    else:
        out = moe.expert_ffn(
            rows.astype(mod.dtype), w_gate, w_up, w_down, gates, experts,
            **act)
    hist = moe.expert_histogram(
        experts, e,
        None if token_mask is None else token_mask.reshape(-1))
    return x + out.reshape(x.shape).astype(x.dtype), (hist,)

def _ffn_swiglu(mod, x):
    """``x + FFN(x)`` for a spec whose every layer holds a dense
    SwiGLU of ``spec.dense_width`` (``ffn == "swiglu"``): the norm on
    the FFN's input, or under ``spec.post_norm`` on its OUTPUT before
    the residual add and none on its input."""
    spec = mod.spec
    rows = x if spec.post_norm else _norm(spec, "ffn_norm")(x)
    out = _swiglu_ffn(
        mod, rows.reshape(-1, x.shape[-1]),
        ("mlp_gate", "mlp_up", "mlp_down"), spec.dense_width,
    ).reshape(x.shape)
    if spec.post_norm:
        out = _norm(spec, "ffn_post_norm")(out)
    return x + out.astype(x.dtype)

def _swiglu_ffn(mod, rows, names, width):
    """A dense SwiGLU FFN (or a shared expert) of ``width`` over
    ``rows`` ``(T, d)``, its gate, up and down matrices declared
    under ``names``: float32 ``(T, d)``."""
    from seldon_core_tpu.ops import moe

    d_model = rows.shape[-1]
    rest = _rest(mod.spec, mod.dtype)
    init = nn.initializers.normal(0.02)
    gate, up, down = names
    return moe.swiglu(
        rows.astype(mod.dtype),
        mod.param(gate, init, (d_model, width), rest),
        mod.param(up, init, (d_model, width), rest),
        mod.param(down, init, (width, d_model), rest))

def _held_experts(mod, d_model, outputs):
    """The parameters of a layer that holds a share of its routed
    experts: the float32 router over ``outputs`` and its correction
    bias, and the ``spec.held`` experts' gate, up and down
    matrices."""
    spec = mod.spec
    held, f = spec.held, spec.expert_width
    rest = _rest(spec, mod.dtype)
    init = nn.initializers.normal(0.02)
    return (mod.param("router", init, (d_model, outputs), jnp.float32),
            mod.param("score_bias", init, (outputs,), jnp.float32),
            mod.param("experts_gate", init, (held, d_model, f), rest),
            mod.param("experts_up", init, (held, d_model, f), rest),
            mod.param("experts_down", init, (held, f, d_model), rest))

def _mixed(mod, name, x, sublayer):
    """One sub-layer under a residual of several rows (ops/hyper.py):
    ``x`` ``(n, B, L, d)`` float32; ``sublayer(h)`` takes the row
    ``H_pre X`` ``(B, L, d)`` and gives its output (no ``x + ...``)
    and whatever else it returns.  The mixing's parameters are the
    sub-layer's own, under ``name``: ``phi``, ``bias``, ``scale``,
    float32."""
    from seldon_core_tpu.ops import hyper

    spec = mod.spec
    h, h_post, h_res = hyper.hyper_pre(
        x, HyperMix(name=name)(x), iters=spec.hc_sinkhorn_iters,
        eps=spec.hc_eps, lo=spec.hc_res_min, hi=spec.hc_res_max)
    y, *rest = sublayer(h)
    return (hyper.hyper_post(x, y, h_post, h_res), *rest)

def _ffn_grouped(mod, x, token_mask, mixed=False):
    """:func:`_ffn` for a spec whose router is DeepSeek-V3's: a
    dense SwiGLU layer (``mod.routed_layer`` false; its histogram
    is zeros, so the layers' stack keeps one shape), or sigmoid
    group-limited routing over ``spec.num_experts`` with this
    replica's ``spec.held`` experts computed (ops/moe.py
    ``expert_ffn_held``) beside a shared expert.  ``mixed`` (a
    residual of several rows): the FFN's output alone comes back,
    float32, for the caller to write through its mixing."""
    from seldon_core_tpu.ops import moe

    spec = mod.spec
    d_model = x.shape[-1]
    rows = _norm(spec, "ffn_norm")(x).reshape(-1, d_model)

    def swiglu(name, width):
        return _swiglu_ffn(
            mod, rows, (f"{name}_gate", f"{name}_up", f"{name}_down"), width)

    e = spec.num_experts
    if not mod.routed_layer:
        out = swiglu("mlp", spec.dense_width)
        hist = jnp.zeros((e,), jnp.int32)
    else:
        w_router, bias, w_gate, w_up, w_down = _held_experts(mod, d_model, e)
        gates, experts = moe.route_grouped(
            rows, w_router, bias, spec.experts_per_tok, spec.n_group,
            spec.topk_group, spec.norm_topk, spec.routed_scale)
        out = moe.expert_ffn_held(
            rows.astype(mod.dtype), w_gate, w_up, w_down, gates, experts,
            spec.expert_offset, e)
        if spec.shared_experts:
            out = out + swiglu(
                "shared", spec.shared_experts * spec.expert_width)
        hist = moe.expert_histogram(
            experts, e,
            None if token_mask is None else token_mask.reshape(-1))
    out = out.reshape(x.shape).astype(x.dtype)
    return (out if mixed else x + out), (hist,)

def _latent_block(mod, x, pool, tables, lengths, layer, positions,
                  token_mask, window=None):
    """A block of latent attention (MLA): ``(x, row, None, hist)``
    with ``row`` ``(B, L, W)`` this call's cache rows for the caller
    to write — one pool, no V — or, for a spec whose layer is
    double, :func:`_double_layer`'s two rows."""
    if mod.spec.double_layer:
        return _double_layer(mod, x, pool, tables, lengths, layer,
                             positions, token_mask)
    if mod.spec.kinds:
        # ``pool`` is the kind's pools (the full layers' rows and
        # indexer keys | the window layers' rows), ``layer`` the
        # layer's place among its kind's or None, and the rows come
        # back named by kind: ("full", row, key) | ("window", row)
        x, rows, *read = _latent_attention(
            mod, x, pool, tables, lengths, layer, positions,
            kind=mod.kind, window=window, counted=token_mask)
        x, hist = _ffn_grouped(mod, x, token_mask)
        return (x, (mod.kind.name, *rows), None, *hist, *read)
    if mod.spec.hc_mult:
        # ``x`` is the token's rows, stream-major (n, B, L, d): each
        # sub-layer reads a mix of them and writes back through one
        x, row = _mixed(mod, "hc_attn", x, lambda h: _latent_attention(
            mod, h, pool, tables, lengths, layer, positions, mixed=True))
        x, hist = _mixed(mod, "hc_ffn", x, lambda h: _ffn_grouped(
            mod, h, token_mask, mixed=True))
        return (x, row, None, *hist)
    x, row = _latent_attention(mod, x, pool, tables, lengths, layer,
                               positions)
    x, hist = _ffn_grouped(mod, x, token_mask)
    return (x, row, None, *hist)

def _double_layer(mod, x, pool, tables, lengths, layer, positions,
                  token_mask):
    """A LongCat-Flash layer: ``(x, (row_0, row_1), None, hist)``.
    Two halves, each a latent attention with its own cache row
    (attention ``2 * layer + i`` of the pool) and a dense SwiGLU FFN
    of ``spec.dense_width``; the routed experts read the FIRST
    half's post-attention norm and are added after the SECOND half
    (the shortcut: in a deployment their exchange overlaps the dense
    half-layer; here nothing orders the two branches but their
    data, and XLA schedules them as it likes)."""
    spec = mod.spec
    d_model = x.shape[-1]
    rows = []
    for i in range(2):
        x, row = _latent_attention(mod, x, pool, tables, lengths, layer,
                                   positions, sub=i)
        rows.append(row)
        g = _norm(spec, f"ffn_norm_{i}")(x).reshape(-1, d_model)
        if i == 0:
            shortcut, hist = _shortcut_experts(mod, g, token_mask)
        dense = _swiglu_ffn(
            mod, g, (f"mlp_gate_{i}", f"mlp_up_{i}", f"mlp_down_{i}"),
            spec.dense_width)
        x = x + dense.reshape(x.shape).astype(x.dtype)
    x = x + shortcut.reshape(x.shape).astype(x.dtype)
    return (x, tuple(rows), None, hist)

def _shortcut_experts(mod, rows, token_mask):
    """LongCat-Flash's routed experts over ``rows`` ``(T, d)``
    float32: ``(m (T, d) float32, hist)``.  The router scores
    ``spec.num_experts`` real and ``spec.zero_experts`` identity
    experts; this replica computes its ``spec.held`` real experts'
    part for the tokens routed to them (ops/moe.py
    ``expert_ffn_held``; an absent real expert adds nothing) and
    the identity experts' part for every token.  ``hist`` is
    ``int32[spec.hist_width]``: assignments per router output, then
    tokens by their number of real picks."""
    from seldon_core_tpu.ops import moe

    spec = mod.spec
    e, outputs = spec.num_experts, spec.router_outputs
    w_router, bias, w_gate, w_up, w_down = _held_experts(
        mod, rows.shape[-1], outputs)
    gates, experts = moe.route_zero(
        rows, w_router, bias, spec.experts_per_tok, spec.routed_scale)
    out = moe.expert_ffn_held(
        rows.astype(mod.dtype), w_gate, w_up, w_down, gates, experts,
        spec.expert_offset, outputs)
    out = out + moe.identity_experts(rows, gates, experts, e)
    mask = None if token_mask is None else token_mask.reshape(-1)
    hist = jnp.concatenate([
        moe.expert_histogram(experts, outputs, mask),
        moe.real_pick_histogram(experts, e, mask)])
    return out, hist

def _latent_attention(mod, x, pool, tables, lengths, layer, positions,
                      sub=None, kind=None, window=None, counted=None,
                      mixed=False):
    """``x + attention(norm(x))`` by latent attention (MLA): ``(x,
    row)`` with ``row`` ``(B, L, W)`` this call's cache rows
    ``[RMSNorm(c_kv) ; RoPE(k_r) ; 0]`` (``W`` = ``spec.cache_width``:
    the values in whole lane tiles) for the caller to write — one
    pool, no V.  ``pool`` is the whole ``(L, pages, ps, W)``
    pool with ``layer`` an int (the kernel lane) or one layer of it.
    ``sub`` (a double layer's half, 0 or 1) names the half's
    parameters ``<name>_<sub>`` and picks its cache row: attention
    ``2 * layer + sub`` of the whole pool, or row ``sub`` of the
    layer's two.

    Two attention paths in one model.  A segment (a prefill, a
    cached suffix) is **naive**: K and V are made per head from the
    latent rows — the cached prefix's, gathered through the block
    table, and the segment's own — and attended causally
    (``ops/mla.py naive_attention``).  A decode step is
    **absorbed**: ``W_uk`` folds into q and ``W_uv`` into the
    output, so the step reads each cached 576-wide row once for all
    heads — the latent kernel where the LM hands over the whole
    pool (``ops/kernels.latent_attention_decode``), a gather and two
    einsums elsewhere — and the step's own row joins by the flash
    rule.

    ``kind`` (a spec whose layers differ, models/spec.py
    ``AttnKind``): the layer's own heads, ranks, head widths and
    theta, and ``(x, rows)`` comes back with ``rows`` the layer's
    cache rows, ``(row,)`` or ``(row, index key)``.  A **window**
    kind reads ``pool`` (its kind's rows) through ``window`` =
    ``(tables (B, P_w), base (B,))`` — a lane's live pages and the
    position its table's first column starts at — over the
    ``kind.window - 1`` positions before the token; ``tables`` only
    says whether the call starts at position zero.  A **full** kind
    with an indexer (``kind.topk``) reads ``pool`` = ``(rows,
    indexer keys)``: a segment from zero attends each row's best
    ``topk`` positions (``ops/mla.py indexed_attention``: in the
    fused causal kernel under the chosen set's mask where
    ``prefill_attention_impl`` says so at this kind's widths); a decode
    step whose bucket holds a lane with ``topk`` cached positions or
    more scores the cached keys (where they rest, a page loop a lane,
    on the kernel lane: ``ops/kernels.index_scores_decode``; gathered
    through the table and ``ops/mla.py index_scores`` elsewhere),
    keeps the best ``topk`` of them and
    the step's own as a mask (``kth_mask``, the prefill's rule) and
    runs the same page loop under it — the kernel streams the lane's
    rows and the masked ones weigh exactly 0 (``chosen=``; the
    one-layer lane hands ``ctx_state`` the mask) — and any other
    bucket runs the page loop over every row, as a spec without an
    indexer.  A decode step of a kind also says what it read, as a
    third value ``int32[3]``: the cached indexer keys it scored, the
    cached rows its attention read (the lengths it handed the
    kernel; where it selected, the chosen set's cached members) and
    the rows the page loop streamed under a mask (the lengths again:
    over the rows read, what a kernel that skipped pages could
    save), over the lanes ``counted`` ``(B, 1)`` keeps.

    ``mixed`` (a residual of several rows, :func:`_mixed`): ``x`` is
    the row the mixing read, and the attention's output alone comes
    back in its place, for the caller to write through the mixing."""
    from dataclasses import replace as _replace

    from seldon_core_tpu.models.spec import (
        lane_tiles,
        rope_interleaved,
        yarn_inv_freq,
    )
    from seldon_core_tpu.ops import kernels, mla

    spec = mod.spec
    heads, rank = mod.num_heads, spec.kv_rank
    nope, rdim, vdim = spec.nope_dim, spec.rope_dim, spec.v_dim
    batch, seg_len, d_model = x.shape
    q_rank = spec.q_rank
    # the row in whole lane tiles
    lanes = spec.cache_width(d_model) if kind is None else kind.lanes
    whole = layer is not None
    topk = kind.topk if kind is not None else 0
    windowed = kind is not None and bool(kind.window)
    idx_pool = None
    if kind is not None:
        heads, rank, q_rank = kind.heads, kind.kv_rank, kind.q_rank
        nope, rdim, vdim = kind.nope_dim, kind.rope_dim, kind.v_dim
        if topk:
            pool, idx_pool = pool
        if windowed:
            # the window's table stands where the block table does:
            # one bucket of every lane, positions counted from the
            # table's first column
            from_zero = tables[0].shape[1] == 0
            w_tables, w_base = window
            tables = (w_tables[:, :0] if from_zero else w_tables,)
            # (never negative: an idle lane's length is 0 under
            # whatever base its slot's last stream left, and a lane
            # of negative length is neither empty nor live to the
            # kernel's hand-on chain)
            w_first = jnp.maximum(
                jnp.maximum(lengths - (kind.window - 1), 0) - w_base, 0)
            lengths = jnp.maximum(lengths - w_base, 0)
    tag = "" if sub is None else f"_{sub}"
    if sub is not None:
        if whole:
            layer = 2 * layer + sub
        else:
            pool = pool[sub]

    def proj(name, features, inp):
        return _dense(mod.precision, features, mod.dtype, name + tag,
                      spec)(inp)

    def rms(name):
        return nn.RMSNorm(epsilon=spec.norm_eps, dtype=jnp.float32,
                          name=name + tag)

    y = _norm(spec, "attn_norm" + tag)(x)
    if q_rank:
        c_q = rms("q_a_norm")(proj("q_a", q_rank, y))
        q = proj("q_b", heads * (nope + rdim), c_q.astype(mod.dtype))
    else:  # q_lora_rank null: one plain projection, no bottleneck
        q = proj("q", heads * (nope + rdim), y)
    q = q.reshape(batch, seg_len, heads, nope + rdim)
    kva = proj("kv_a", rank + rdim, y)
    c_kv = rms("kv_a_norm")(kva[..., :rank])
    if spec.mla_lora_scale:
        # constants on q (exact in bfloat16 at the published ranks:
        # 2) and on the normed latent as it is cached (float32
        # here, rounded once into the pool's type)
        s_q, s_kv = (spec.lora_scales(d_model) if kind is None
                     else spec.lora_scales(d_model, kind))
        q, c_kv = q * jnp.asarray(s_q, q.dtype), c_kv * s_kv
    inv = yarn_inv_freq(spec if kind is None else _replace(
        spec, rope_theta=kind.rope_theta, rope_dim=kind.rope_dim))
    q_nope = q[..., :nope]
    q_rope = rope_interleaved(q[..., nope:], positions, inv).astype(mod.dtype)
    k_rope = rope_interleaved(
        kva[..., None, rank:], positions, inv)[..., 0, :]
    # the cache row, as attention reads it: normed, rotated, in the
    # pool's type (this call attends its own rows in that type too,
    # so a prompt prefilled whole and one resumed from cached pages
    # see the same keys)
    tail = jnp.zeros((batch, seg_len, lanes - rank - rdim), mod.dtype)
    row = jnp.concatenate(
        [c_kv.astype(mod.dtype), k_rope.astype(mod.dtype), tail], axis=-1)
    rest = _rest(spec, mod.dtype)
    init = nn.initializers.normal(0.02)
    # W_kvb rests split: (heads, rank, nope) makes k_nope from c_kv
    # (or folds into q), (heads, rank, v) makes v (or unfolds the
    # attended latent)
    w_uk = mod.param("kv_b_k" + tag, init, (heads, rank, nope), rest)
    w_uv = mod.param("kv_b_v" + tag, init, (heads, rank, vdim), rest)
    scale = spec.softmax_scale if kind is None else kind.softmax_scale
    if topk:
        # the indexer: 64 heads of 128 from the normed q latent, one
        # key a token (LayerNorm'd) and one weight a head from the
        # layer's normed input; the first rope_dim dims rotated; q
        # and the key in the type the key is cached in
        ih, idim = spec.index_heads, spec.index_dim
        ilanes = lane_tiles(idim)
        i_scale = ih ** -0.5 * idim ** -0.5
        q_i = proj("index_q", ih * idim, c_q.astype(mod.dtype)).reshape(
            batch, seg_len, ih, idim)
        k_i = nn.LayerNorm(epsilon=spec.norm_eps, dtype=jnp.float32,
                           name="index_k_norm" + tag)(proj("index_k", idim, y))
        w_i = proj("index_w", ih, y).astype(jnp.float32)

        def rotated(v):  # (B, L, j, idim): the first rdim dims
            return jnp.concatenate([
                rope_interleaved(v[..., :rdim], positions, inv),
                v[..., rdim:].astype(jnp.float32),
                jnp.zeros(v.shape[:-1] + (ilanes - idim,), jnp.float32),
            ], axis=-1).astype(mod.dtype)

        q_i = rotated(q_i)
        key_row = rotated(k_i[:, :, None, :])[:, :, 0, :]   # (B, L, ilanes)

    def cached(tb):
        """A bucket's cached rows (nb, C, W), or None for a table
        of no width (a prefill from position 0 reads no cache)."""
        if tb.shape[1] == 0:
            return None
        rows = pool[layer, tb] if whole else pool[tb]
        return rows.reshape(tb.shape[0], -1, rows.shape[-1])

    outs, reads, off = [], [], 0
    for tb in tables:
        nb = tb.shape[0]
        sl = slice(off, off + nb)
        off += nb
        if seg_len > 1 and topk:
            if tb.shape[1]:
                raise ValueError(
                    "an indexed layer prefills from position zero: a "
                    "segment over cached rows is not built")
            fused = kernels.prefill_attention_impl(
                seg_len, nope + rdim, vdim, mod.dtype, 0, whole) == "fused"
            outs.append(mla.indexed_attention(
                q_nope[sl], q_rope[sl], row[sl], w_uk, w_uv, scale,
                mod.dtype, q_i[sl], w_i[sl], key_row[sl], i_scale, topk,
                fused=fused))
            continue
        if seg_len > 1:
            fused = kernels.prefill_attention_impl(
                seg_len, nope + rdim, vdim, mod.dtype, tb.shape[1],
                whole) == "fused"
            outs.append(mla.naive_attention(
                q_nope[sl], q_rope[sl], cached(tb), lengths[sl], row[sl],
                w_uk, w_uv, scale, mod.dtype, fused=fused,
                **({"window": kind.window} if windowed else {})))
            continue
        q_abs = jnp.einsum(
            "bhn,hrn->bhr", q_nope[sl][:, 0], w_uk.astype(mod.dtype),
            preferred_element_type=jnp.float32)
        q_full = (jnp.concatenate(
            [q_abs, q_rope[sl][:, 0].astype(jnp.float32),
             jnp.zeros((nb, heads, lanes - rank - rdim), jnp.float32)],
            axis=-1) * scale).astype(mod.dtype)            # (nb, h, W)
        own = row[sl]                                      # (nb, 1, W)
        offset = {"starts": w_first[sl]} if windowed else {}
        live = (jnp.ones((nb,), bool) if counted is None
                else counted[sl].reshape(nb))

        def tally(keys, rows, moved=0, live=live):
            """``int32[3]``: per-lane counts summed over the lanes
            that run."""
            return jnp.stack([jnp.where(live, n, 0).sum()
                              for n in (keys, rows, moved)]).astype(jnp.int32)

        def cached_state(q_full=q_full, tb=tb, sl=sl, offset=offset,
                         **chosen):
            """The flash state of the bucket's cached rows (a
            window's live ones; of them those a mask ``chosen``
            ``(nb, C)`` keeps, where one is handed over)."""
            if whole:
                return kernels.latent_attention_decode(
                    q_full, pool, tb, lengths[sl], layer=layer,
                    page_size=pool.shape[2], rank=rank, **offset, **chosen)
            rows = cached(tb)
            at = jnp.arange(rows.shape[1])[None, :]
            valid = at < lengths[sl][:, None]
            if offset:
                valid &= at >= w_first[sl][:, None]
            for mask in chosen.values():
                valid &= mask
            return mla.ctx_state(q_full, rows, valid, rank)

        def dense(q_full=q_full, sl=sl, own=own):
            """Every cached row (a window's live ones), then the
            step's own by the flash rule."""
            first = w_first[sl] if windowed else 0
            return mla.merge(
                cached_state(), mla.ctx_state(
                    q_full, own, jnp.ones(own.shape[:2], bool), rank)
            ), tally(0, jnp.maximum(lengths[sl] - first, 0))

        def sparse(q_full=q_full, tb=tb, sl=sl, own=own):
            """The indexer's best ``topk`` of the cached positions
            and the step's own, as a mask over the table's span
            (``step_mask``: ``kth_mask``, a prefill's rule): the
            page loop streams the lane's rows and weighs the chosen
            alone."""
            if whole:
                # the keys scored where they rest, a page loop a lane
                scores = kernels.index_scores_decode(
                    q_i[sl][:, 0], w_i[sl][:, 0], idx_pool, tb,
                    lengths[sl], layer=layer, page_size=pool.shape[2],
                    scale=i_scale).reshape(nb, -1)
            else:
                keys = idx_pool[tb]
                keys = keys.reshape(nb, -1, keys.shape[-1])
                scores = mla.index_scores(
                    q_i[sl], w_i[sl], keys, i_scale)[:, 0]
            own_sc = mla.index_scores(
                q_i[sl], w_i[sl], key_row[sl], i_scale)[:, 0, 0]
            is_cached, own_in = mla.step_mask(
                scores, own_sc, lengths[sl], topk)
            # (the kernel streams every row the indexer scored)
            scored = jnp.minimum(lengths[sl], scores.shape[1])
            return mla.merge(
                cached_state(chosen=is_cached),
                mla.ctx_state(q_full, own, own_in[:, None], rank)
            ), tally(scored, is_cached.sum(axis=-1), scored)

        if topk and tb.shape[1] * pool.shape[-2] > topk:
            latent, read = jax.lax.cond(
                mla.any_over(lengths[sl], topk), sparse, dense)
        else:
            latent, read = dense()
        reads.append(read)
        # (heads lead on both sides: the CPU backend has no bf16
        # thunk for the "bhr,hrv->bhv" form)
        out = jnp.einsum(
            "hbr,hrv->hbv", jnp.swapaxes(latent, 0, 1).astype(mod.dtype),
            w_uv.astype(mod.dtype), preferred_element_type=jnp.float32)
        outs.append(jnp.swapaxes(out, 0, 1).astype(mod.dtype)[:, None])
    attn = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    if spec.attn_gate:
        # one gate a head, from the layer's normed input
        gate = jax.nn.sigmoid(proj("attn_gate", heads, y).astype(jnp.float32))
        attn = (attn.astype(jnp.float32) * gate[..., None]).astype(mod.dtype)
    attn = attn.reshape(batch, seg_len, heads * vdim)
    out = proj("attn_proj", d_model, attn)
    x = out if mixed else x + out
    if kind is None:
        return x, row
    rows = (row, key_row) if topk else (row,)
    return (x, rows, sum(reads)) if reads else (x, rows)

def _grouped_block(mod, x, pk, pv, tables, lengths, layer, positions,
                   token_mask, window=None):
    """A block of grouped-query attention (a spec that sets
    ``kv_heads`` and ``head_dim``): ``num_heads`` query heads of
    ``head_dim`` — q and the output projection's input are
    ``num_heads x head_dim`` wide, whatever ``d_model`` is — over
    ``kv_heads`` K/V heads, query head ``c`` reading K/V head ``c //
    (num_heads / kv_heads)``; K and V are cached ``kv_heads x
    head_dim`` wide each, flat.  Returns ``(x, k, v, hist)`` with
    ``k`` / ``v`` ``(B, L, kv_heads x head_dim)`` for the caller to
    write, or for a spec with layer kinds ``(x, (kind name, k), v,
    hist, read)`` as :func:`_latent_block` does.

    ``mod.kind`` (a spec whose layers differ, models/spec.py
    ``AttnKind``): positions are the kind's — a window layer
    rotates q and k, a full layer of ``full_positions="none"`` does
    not — and a **window** kind reads ``pk`` / ``pv`` (its kind's
    pools) through ``window`` = ``(tables (B, P_w), base (B,))``, a
    lane's live pages and the position its table's first column
    starts at, over the ``kind.window - 1`` positions before the
    token (``tables`` only says whether the call starts at zero).

    A segment prefills from position zero: causal (or window)
    attention over itself, the fused kernel where
    ``ops/kernels.py prefill_attention_impl`` says so and
    ``ops/gqa.py segment_attention`` a block of queries at a time
    elsewhere — never an ``(heads, S, S)`` score array.  A decode
    step reads the cached rows through the page loop where the LM
    hands over the whole pools (``paged_attention_decode``: a page's
    K and V slices streamed once for the query heads of each group)
    and through a gather and two einsums elsewhere (``ops/gqa.py
    ctx_state``), and joins its own row by the flash rule.  A step of
    a kind also says what it read, ``int32[3]`` as
    :func:`_latent_attention`: 0, the cached rows its attention read
    (a window's live ones) over the lanes ``token_mask`` keeps, 0.

    The router of ``router_from="attn_input"`` reads ``y``, the
    rows that feed q, k and v: its logits are computed here and
    handed to :func:`_ffn`, whose experts act on the post-attention
    norm."""
    from seldon_core_tpu.ops import gqa, kernels, mla, moe

    spec, kind = mod.spec, mod.kind
    heads = mod.num_heads
    batch, seg_len, d_model = x.shape
    kv_heads, head_dim = spec.head_sizes(heads, d_model)
    q_w, kv_w = heads * head_dim, kv_heads * head_dim
    whole = layer is not None
    windowed = kind is not None and bool(kind.window)
    if windowed:
        # the window's table stands where the block table does: one
        # bucket of every lane, positions counted from the table's
        # first column (never negative: an idle lane's length is 0
        # under whatever base its slot's last stream left)
        from_zero = tables[0].shape[1] == 0
        w_tables, w_base = window
        tables = (w_tables[:, :0] if from_zero else w_tables,)
        w_first = jnp.maximum(
            jnp.maximum(lengths - (kind.window - 1), 0) - w_base, 0)
        lengths = jnp.maximum(lengths - w_base, 0)

    def proj(name, features, inp):
        return _dense(mod.precision, features, mod.dtype, name, spec)(inp)

    # (post-norm: the sub-layer reads the stream as it is, and its
    # output is normed before the residual add)
    y = x if spec.post_norm else _norm(spec, "attn_norm")(x)
    router_logits = None
    if spec.router_from == "attn_input":
        w_router = mod.param(
            "router", nn.initializers.normal(0.02),
            (d_model, spec.num_experts), jnp.float32)
        router_logits = moe.router_logits(
            y.reshape(-1, d_model), w_router)
    qkv = proj("qkv", q_w + 2 * kv_w, y)
    q, k, v = (qkv[..., :q_w], qkv[..., q_w:q_w + kv_w],
               qkv[..., q_w + kv_w:])
    q, k, v = _heads(mod, q, k, v, positions,
                     (batch, seg_len, heads, head_dim),
                     (batch, seg_len, kv_heads, head_dim))
    # K is cached as attention reads it (rotated where the layer
    # rotates), flat as the pool's row
    k_flat = k.reshape(batch, seg_len, kv_w)
    v_flat = v.reshape(batch, seg_len, kv_w)
    scale = float(head_dim) ** -0.5

    outs, reads, off = [], [], 0
    for tb in tables:
        nb = tb.shape[0]
        sl = slice(off, off + nb)
        off += nb
        if seg_len > 1:
            if tb.shape[1]:
                raise ValueError(
                    "a grouped-query layer prefills from position zero: "
                    "a segment over cached rows is not built")
            fused = kernels.prefill_attention_impl(
                seg_len, head_dim, head_dim, mod.dtype, 0, whole) == "fused"
            outs.append(gqa.segment_attention(
                q[sl], k[sl], v[sl], scale, mod.dtype, fused=fused,
                **({"window": kind.window} if windowed else {})))
            continue
        q1 = (q[sl][:, 0].astype(jnp.float32) * scale).astype(mod.dtype)
        first = w_first[sl] if windowed else 0
        if whole:
            cached = kernels.paged_attention_decode(
                q1, pk, pv, tb, lengths[sl], layer=layer,
                page_size=pk.shape[2],
                **({"starts": first} if windowed else {}))
        else:
            rows_k, rows_v = pk[tb], pv[tb]       # (nb, P, ps, kv_w)
            at = jnp.arange(rows_k.shape[1] * rows_k.shape[2])[None, :]
            valid = (at < lengths[sl][:, None]) & (
                at >= jnp.asarray(first).reshape(-1, 1))
            cached = gqa.ctx_state(
                q1, rows_k.reshape(nb, -1, kv_heads, head_dim),
                rows_v.reshape(nb, -1, kv_heads, head_dim), valid)
        own = gqa.ctx_state(q1, k[sl], v[sl], jnp.ones((nb, 1), bool))
        outs.append(mla.merge(cached, own).astype(mod.dtype)[:, None])
        live = (jnp.ones((nb,), bool) if token_mask is None
                else token_mask[sl].reshape(nb))
        rows_read = jnp.where(
            live, jnp.maximum(lengths[sl] - first, 0), 0).sum()
        reads.append(jnp.stack(
            [jnp.zeros((), jnp.int32), rows_read.astype(jnp.int32),
             jnp.zeros((), jnp.int32)]))
    attn = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    attn = proj("attn_proj", d_model, attn.reshape(batch, seg_len, q_w))
    if spec.post_norm:
        attn = _norm(spec, "attn_post_norm")(attn).astype(x.dtype)
    x = x + attn
    x, hist = _ffn(mod, x, proj, token_mask, router_logits)
    if kind is None:
        return (x, k_flat, v_flat, *hist)
    read = (sum(reads),) if reads else ()
    return (x, (kind.name, k_flat), v_flat, *hist, *read)

def _segment_attention(mod, q, k, v, scale):
    """Causal attention of a segment ``(B, L, h, hd)`` over itself
    alone (a prefill from position zero): ``(B, L, h, hd)``.  The
    gather path's own einsums without their cache half — bf16 scores
    masked with finfo.min, f32 softmax — on every backend and lane.
    The fused kernel (``ops/kernels.py causal_attention``) is not
    asked here: a v5e reads it level with these three fusions at
    the shapes the cells run (ms a GPT-2-large layer, XLA / kernel:
    ``b1024_k2`` 0.208 / 0.208, ``b1024_k1`` 0.138 / 0.133,
    ``b512_k4`` 0.150 / 0.156; OLMoE ``b512_k4`` 0.127 / 0.141) and
    ahead only at ``b1024_k4`` (0.839 / 0.354), which no cell's
    traffic forms (PERF.md §5, §6 PR 33; ROADMAP S11 a)."""
    seg_len = q.shape[1]
    ss = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    seg_mask = (
        jnp.arange(seg_len)[None, :] <= jnp.arange(seg_len)[:, None]
    )  # (L, L) causal within this segment
    ss = jnp.where(seg_mask[None, None], ss, jnp.finfo(ss.dtype).min)
    weights = jax.nn.softmax(
        ss.astype(jnp.float32), axis=-1).astype(mod.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

def _embed(lm, tokens, positions):
    tokens = tokens.astype(jnp.int32)
    rest = _rest(lm.spec, lm.dtype)
    x = nn.Embed(
        lm.vocab_size, lm.d_model, dtype=lm.dtype, param_dtype=rest,
        name="tok_embed",
    )(tokens)
    if lm.spec.residual_f32:
        x = x.astype(jnp.float32)  # and every ``x + ...`` after it
    if lm.spec.hc_mult:
        # a residual of several rows, stream-major (n, B, L, d):
        # every row starts as the token's embedding
        x = jnp.broadcast_to(x[None], (lm.spec.hc_mult, *x.shape))
    if lm.spec.rope:
        return x  # positions enter in every block, on q and k
    pos = nn.Embed(
        lm.max_len, lm.d_model, dtype=lm.dtype, param_dtype=rest,
        name="pos_embed",
    )(positions)
    return x + pos

def _unembed(lm, x, last=None):
    """Final norm and unembedding of the residual ``(B, L, d)``:
    float32 logits ``(B, L, vocab)``.  A residual of several rows
    ``(n, B, L, d)`` leaves as their sum.  With ``last`` — ``(B,)``
    int32, a row's one position to unembed — the position is
    gathered first, so the sum, the norm, the matmul and the cast
    run on ``(B, 1, d)`` and the logits are ``(B, 1, vocab)``: a
    prefill returns one row a prompt (PERF.md §6 PR 49)."""
    if last is not None:
        x = jnp.take_along_axis(
            x, last.reshape((1,) * (x.ndim - 3) + (-1, 1, 1)), axis=-2)
    if lm.spec.hc_mult:
        x = x.sum(axis=0)
    x = _norm(lm.spec, "final_norm")(x)
    if lm.spec.tied_head:
        # the head is the embedding's transpose: the ONE matrix
        # ``_embed`` declared, read where it rests (no second copy at
        # rest and none in a program: the contraction runs over its
        # minor dim)
        table = lm.variables["params"]["tok_embed"]["embedding"]
        return jnp.einsum(
            "bld,vd->blv", x.astype(lm.dtype), table.astype(lm.dtype)
        ).astype(jnp.float32)
    logits = _dense(lm.precision, lm.vocab_size, lm.dtype, "head",
                    lm.spec)(x)
    return logits.astype(jnp.float32)

def _head(lm, x, new_k, new_v, hists, last=None):
    """``(logits, K, V)`` stacked over layers, and a routed spec's
    ``int32[layers, E]`` assignment histogram as a fourth value."""
    out = (_unembed(lm, x, last), jnp.stack(new_k),
           None if new_v[0] is None else jnp.stack(new_v))  # one pool: no V
    return out + (jnp.stack(hists),) if hists else out

class HyperMix(nn.Module):
    """The mixing parameters of one sub-layer under a residual of
    several rows (ops/hyper.py), float32 at rest and in use: ``phi``
    ``(2n + n^2, n d)`` (a coefficient a row), ``bias`` and ``scale``
    (alpha_pre, alpha_post, alpha_res)."""

    @nn.compact
    def __call__(self, x):
        from seldon_core_tpu.ops import hyper

        n, d_model = x.shape[0], x.shape[-1]
        k = hyper.coefficients(n)
        init = nn.initializers.normal(0.02)
        return {"phi": self.param("phi", init, (k, n * d_model), jnp.float32),
                "bias": self.param("bias", init, (k,), jnp.float32),
                "scale": self.param("scale", init, (3,), jnp.float32)}

class DeltaBlock(nn.Module):
    """A linear-attention layer (Gated DeltaNet, ops/delta.py) and its
    FFN: the layer of a spec with ``"linear"`` layer kinds that keeps
    no pages.  With ``x`` the stream ``(B, L, d)``: q, k and v (one
    ``qkv`` projection, ``spec.lin_channels`` wide) pass a causal
    depthwise convolution of ``spec.lin_conv`` taps and SiLU; a head's
    q and k are normalised (q times ``d_k ** -0.5``); ``beta`` and the
    decay ``alpha`` come of the float32 ``ab`` projection, ``a_log``
    and ``dt_bias``; the recurrence's output is RMS-normed a head
    (``o_norm``), gated by ``silu(gate)`` and projected back.

    **The variant is the spec's** (``lin_gate``, ``lin_gate_floor``,
    ``lin_out_gate``: Kimi Delta Attention).  A decay a key CHANNEL
    comes of a full matrix ``a`` ``(d, H x d_k)`` that rests in the
    compute type, its product accumulated and kept in float32, under
    the bounded gate ``floor x sigmoid(exp(a_log) (. + dt_bias))`` with
    ``dt_bias`` a channel; ``beta`` of a float32 ``b`` ``(d, H)``; the
    output gate ``"sigmoid_head"`` is one sigmoid a head.  **The FFN is
    the one the layer's place calls for**: DeepSeek-V3's (a leading
    dense SwiGLU layer, or the routed experts held here beside a
    shared one, with the layer's routing histogram as a last value)
    where the spec's router is sigmoid, the dense SwiGLU of every
    layer elsewhere.

    Two calls.  **A prefill from position zero** (``state`` None):
    ``true_lens`` ``(B,)`` are the rows' real lengths; positions past
    them pass the pad rule, so the state ``(B, H / p, d_k, p x d_v)``
    and the convolution's tail ``(B, taps - 1, channels)`` that come
    back are those at each row's LAST REAL position.  **A decode
    step** (``L`` 1): ``state`` and ``tail`` as they rest, row ``b``
    its own lane's; a lane ``active`` leaves out keeps both.  Returns
    ``(x, state, tail)`` and a routed spec's histogram ``int32[E]``."""

    dtype: Any = jnp.bfloat16
    precision: str = "bf16"
    spec: Any = GPT2
    routed_layer: bool = True  # a spec with leading dense layers

    @nn.compact
    def __call__(self, x, state=None, tail=None, true_lens=None,
                 active=None, token_mask=None):
        from seldon_core_tpu.ops import delta

        spec = self.spec
        heads, dk, dv = spec.lin_heads, spec.lin_key_dim, spec.lin_value_dim
        batch, seg_len, d_model = x.shape
        pack = delta.pack_of(heads, dv)
        rest = _rest(spec, self.dtype)
        init = nn.initializers.normal(0.02)

        def proj(name, features, inp):
            return _dense(self.precision, features, self.dtype, name,
                          spec)(inp)

        y = x if spec.post_norm else _norm(spec, "attn_norm")(x)
        qkv = proj("qkv", spec.lin_channels, y)
        head_gate = spec.lin_out_gate == "sigmoid_head"
        gate = proj("gate", heads if head_gate else heads * dv, y)
        if spec.lin_gate == "channel":
            with jax.named_scope("seldon.delta.gate"):
                # the decay's projection: a full matrix in the compute
                # type, its product kept in float32 (alpha is an
                # exponential of it), and beta's float32 as a router's
                w_a = self.param("a", init, (d_model, heads * dk), rest)
                a = jnp.einsum(
                    "bld,dc->blc", y.astype(self.dtype),
                    w_a.astype(self.dtype),
                    preferred_element_type=jnp.float32)
                w_b = self.param("b", init, (d_model, heads), jnp.float32)
                b = jnp.einsum("bld,dh->blh", y.astype(jnp.float32), w_b,
                               precision=jax.lax.Precision.HIGHEST)
                log_alpha, beta = delta.gates(
                    a, b,
                    self.param("a_log", init, (heads,), jnp.float32),
                    self.param("dt_bias", init, (heads * dk,), jnp.float32),
                    spec.lin_neg_eigval, floor=spec.lin_gate_floor)
        else:
            # the two gates' projection: float32 at rest and in use, as a
            # router (alpha is an exponential of it)
            w_ab = self.param("ab", init, (d_model, 2 * heads), jnp.float32)
            ab = jnp.einsum("bld,dh->blh", y.astype(jnp.float32), w_ab,
                            precision=jax.lax.Precision.HIGHEST)
            log_alpha, beta = delta.gates(
                ab[..., :heads], ab[..., heads:],
                self.param("a_log", init, (heads,), jnp.float32),
                self.param("dt_bias", init, (heads,), jnp.float32),
                spec.lin_neg_eigval)
        taps = self.param("conv", init, (spec.lin_conv, spec.lin_channels),
                          rest)
        if state is None:
            mixed, tail = delta.conv(qkv, taps, true_lens)
        else:
            mixed, tail = delta.conv_step(tail, qkv[:, 0], taps, active)
            mixed = mixed[:, None]
        q = mixed[..., :heads * dk].reshape(batch, seg_len, heads, dk)
        k = mixed[..., heads * dk:2 * heads * dk].reshape(
            batch, seg_len, heads, dk)
        v = mixed[..., 2 * heads * dk:].reshape(batch, seg_len, heads, dv)
        q = delta.l2norm(q) * float(dk) ** -0.5
        k = delta.l2norm(k)
        if state is None:
            if true_lens is not None:  # the pad rule past a row's length
                real = (jnp.arange(seg_len)[None, :]
                        < true_lens[:, None])[..., None]
                log_alpha = jnp.where(
                    real[..., None] if log_alpha.ndim == 4 else real,
                    log_alpha, 0.0)
                beta = jnp.where(real, beta, 0.0)
            out, state = delta.chunked_scan(q, k, v, log_alpha, beta)
            state = delta.pack_state(state, pack)
        else:
            state, out = delta.step(
                state, q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0],
                beta[:, 0], pack=pack, active=active)
            out = out[:, None]
        out = nn.RMSNorm(epsilon=spec.norm_eps, dtype=jnp.float32,
                         name="o_norm")(out)
        if head_gate:
            out = out * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]
        else:
            out = out * jax.nn.silu(
                gate.astype(jnp.float32).reshape(batch, seg_len, heads, dv))
        out = proj("attn_proj", d_model,
                   out.reshape(batch, seg_len, heads * dv))
        if spec.post_norm:
            out = _norm(spec, "attn_post_norm")(out).astype(x.dtype)
        x = x + out
        if spec.score == "sigmoid":
            x, hist = _ffn_grouped(self, x, token_mask)
            return (x, state, tail, *hist)
        return _ffn_swiglu(self, x), state, tail

class SsmBlock(nn.Module):
    """A selective state-space layer (Mamba-1 as Jamba runs it,
    ops/ssm.py) and its FFN: the layer of a spec with ``"ssm"`` layer
    kinds, which keeps no pages.  With ``u`` the normed stream ``(B,
    L, d)``: ``[x~ ; z] = u W_in`` (``2 E`` wide, no bias); ``x~``
    passes a causal depthwise convolution of ``spec.ssm_conv`` taps,
    its bias and SiLU; step sizes ``Delta`` and the columns ``B``,
    ``C`` come of ``x`` (``ssm.select``: ``x_proj``, three inner
    RMSNorms, ``dt_proj`` and ``dt_bias``, softplus); the recurrence
    with ``A = -exp(a_log)`` and the skip ``d_skip``; ``out = (y .
    silu(z)) W_out``.  ``a_log`` rests ``(N, E)`` as the state does,
    float32 with ``d_skip``, ``dt_bias`` and the norms.

    The two calls, the pad rule and what comes back are
    :class:`DeltaBlock`'s: a prefill from position zero (``state``
    None; ``true_lens`` the rows' real lengths, ``Delta`` masked past
    them after its softplus) returns the state ``(B, N, E)`` and the
    tail ``(B, taps - 1, E)`` at each row's LAST REAL position; a
    decode step (``L`` 1) takes both as they rest, and a lane
    ``active`` leaves out keeps both."""

    dtype: Any = jnp.bfloat16
    precision: str = "bf16"
    spec: Any = GPT2

    @nn.compact
    def __call__(self, x, state=None, tail=None, true_lens=None,
                 active=None):
        from seldon_core_tpu.ops import delta, ssm

        spec = self.spec
        inner, cols, rank = spec.ssm_inner, spec.ssm_state, spec.ssm_dt_rank
        d_model = x.shape[-1]
        rest = _rest(spec, self.dtype)
        init = nn.initializers.normal(0.02)

        def proj(name, features, inp):
            return _dense(self.precision, features, self.dtype, name,
                          spec)(inp)

        def scale(name, width):  # an inner RMSNorm's learned scale
            return self.param(name, init, (width,), jnp.float32)

        xz = proj("in_proj", 2 * inner, _norm(spec, "attn_norm")(x))
        mixed, z = xz[..., :inner], xz[..., inner:]
        taps = self.param("conv", init, (spec.ssm_conv, inner), rest)
        bias = ({"bias": self.param("conv_bias", init, (inner,), jnp.float32)}
                if spec.ssm_conv_bias else {})
        if state is None:
            mixed, tail = delta.conv(mixed, taps, true_lens,
                                     scope="seldon.ssm.conv", **bias)
        else:
            mixed, tail = delta.conv_step(tail, mixed[:, 0], taps, active,
                                          scope="seldon.ssm.conv", **bias)
            mixed = mixed[:, None]
        dt, b, c = ssm.select(
            mixed, self.param("x_proj", init, (inner, rank + 2 * cols), rest),
            scale("dt_norm", rank), scale("b_norm", cols),
            scale("c_norm", cols),
            self.param("dt_proj", init, (rank, inner), rest),
            self.param("dt_bias", init, (inner,), jnp.float32),
            eps=spec.norm_eps, dtype=self.dtype)
        a = -jnp.exp(self.param("a_log", init, (cols, inner), jnp.float32))
        skip = self.param("d_skip", init, (inner,), jnp.float32)
        if state is None:
            y, state = ssm.scan(mixed, dt, b, c, a, skip,
                                true_lens=true_lens)
        else:
            state, y = ssm.step(state, mixed[:, 0], dt[:, 0], b[:, 0],
                                c[:, 0], a, skip, active=active)
            y = y[:, None]
        out = proj("attn_proj", d_model,
                   y * jax.nn.silu(z.astype(jnp.float32)))
        return _ffn_swiglu(self, x + out.astype(x.dtype)), state, tail

class PagedTransformerBlock(nn.Module):
    """TransformerBlock whose attention reads a paged K/V pool.

    Returns this call's K/V instead of mutating a flax collection —
    the caller owns the scatter (functional state, donate-friendly).
    """

    num_heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    precision: str = "bf16"  # "w8a8": int8×int8 projections
    spec: Any = GPT2
    routed_layer: bool = True  # a spec with leading dense layers
    kind: Any = None  # a spec with layer kinds: this layer's AttnKind

    @nn.compact
    def __call__(self, x, pk, pv, block_tables, lengths,
                 lora=None, adapter_idx=None, kv_scales=None,
                 layer=None, positions=None, token_mask=None,
                 window=None):
        # x: (B, L, d)
        # positions: (B, L) absolute token indices (a RoPE spec
        # reads them; GPT-2's enter at the LM's embedding)
        # token_mask: (B, L) rows the routing counters count
        # returns (x, k, v), and a routed spec's assignment
        # histogram int32[E] as a fourth value
        # pk/pv + layer: two forms, picked by the LM.  ``layer`` an
        # int — the kernel lane's: pk/pv are the WHOLE pools
        # (L, num_pages, ps, d); the decode kernel addresses
        # (layer, page) in them, the gather reads pk[layer, tables],
        # lora/kv_scales are the whole (L, ...) tables, and K/V come
        # back flat (B, L, d).  ``layer=None`` — every other lane's,
        # traced exactly as before PR 25: pk/pv are ONE layer
        # (num_pages, ps, d), which the gather below reshapes to
        # (B, cache_len, h, hd), and K/V come back (B, L, h, hd)
        # block_tables: (B, P) int32, or a TUPLE of per-bucket
        # tables ((B0, P0), (B1, P1), ...) with sum(Bb) == B — the
        # r6 length-bucketed gather: lanes arrive bucket-sorted and
        # each bucket gathers/attends at its own static page
        # horizon (dense projections stay full-batch)
        # lengths: (B,) tokens in cache
        # lora/adapter_idx (r16): slot-granular low-rank factor
        # pools + a TRACED per-lane slot id — every projection adds
        # the gathered grouped-matmul delta (ops/lora.py), so a
        # wave mixing K adapters is ONE program; lora=None is the
        # byte-identical adapter-off path (no new ops traced)
        # kv_scales (r18): ``(sk, sv)`` per-page f32 ``(num_pages,)``
        # scale vectors for an int8 pool — both attention lanes
        # dequantise through them (the kernel in-register, the
        # gather right after the page fetch); None means the pool
        # stores self.dtype natively and the trace is byte-identical
        # to r17
        tables = (
            tuple(block_tables)
            if isinstance(block_tables, (tuple, list))
            else (block_tables,)
        )
        if self.spec.latent:
            # one latent pool (pv is None), another attention
            return _latent_block(self, x, pk, tables, lengths, layer,
                                 positions, token_mask, window)
        if self.spec.kv_heads:
            # grouped-query heads, K/V pools of kinds, a router that
            # reads this block's normed input
            return _grouped_block(self, x, pk, pv, tables, lengths, layer,
                                  positions, token_mask, window)
        d_model = x.shape[-1]
        heads = self.num_heads
        head_dim = d_model // heads
        batch, seg_len = x.shape[:2]

        # since the r18 default flip ("auto") this is the PRODUCTION
        # decode lane on single-chip TPU backends — the r4 gather-
        # vs-kernel measurements that kept it opt-in predate the
        # streaming DMA rework; SELDON_TPU_PAGED_KERNEL=0 restores
        # the XLA gather lane byte-for-byte
        # the LM hands over the whole pool only where the kernel
        # lane serves (paged_kernel_static_eligible); what is left
        # is that this call is a decode step
        whole = layer is not None
        use_kernel = seg_len == 1 and whole
        # the kernel indexes the whole (L, ...) factor pools and
        # scale tables itself; everything else reads this layer's
        lora_pools, scale_tables = lora, kv_scales
        if whole and lora is not None:
            lora = {t: (ab[0][layer], ab[1][layer])
                    for t, ab in lora.items()}
        if whole and kv_scales is not None:
            kv_scales = (kv_scales[0][layer], kv_scales[1][layer])
        # r18: the per-lane qkv LoRA BGMV folds INTO the kernel
        # launch (the slot-index gather rides the scalar
        # prefetch next to the block tables) — one fused program
        # instead of kernel + two einsums.  Sound without further
        # care because this model applies no RoPE between the qkv
        # projection and attention (learned positional embeddings
        # add at the LM level), so the low-rank delta is linear in
        # the projection output.
        fold_qkv = use_kernel and lora is not None and "qkv" in lora

        spec = self.spec

        def _proj(name, features, inp):
            out = _dense(self.precision, features, self.dtype, name,
                         spec)(inp)
            if lora is not None and name in lora and not (
                fold_qkv and name == "qkv"
            ):
                from seldon_core_tpu.ops.lora import lora_delta

                a_f, b_f = lora[name]
                out = out + lora_delta(inp, a_f, b_f, adapter_idx).astype(
                    out.dtype
                )
            return out

        y = _norm(spec, "attn_norm")(x)
        qkv = _proj("qkv", 3 * d_model, y)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        # the whole pool takes its K/V as the projection left
        # them: (B, L, h, hd) -> (B, L, d) is a re-lay on the chip
        # ((20, 64) minor dims do not tile like 1280), so handing
        # the split form to write_kv cost a copy per page block
        k_flat, v_flat = k, v
        shape = (batch, seg_len, heads, head_dim)
        q, k, v = _heads(self, q, k, v, positions, shape)
        if spec.qk_norm or spec.rope:
            # K is cached as attention reads it: normed and rotated
            k_flat = k.reshape(batch, seg_len, d_model)

        scale = 1.0 / jnp.sqrt(head_dim).astype(q.dtype)
        if use_kernel:
            # pallas flash-decoding over the paged pool
            # (ops/kernels.py paged_attention_decode): pages stream
            # HBM->VMEM indexed by the block table; the
            # (B, P, ps, h, hd) gathered copy below never
            # materialises.  The current token merges via the flash
            # rule.  Under the bucketed gather each bucket is one
            # kernel call at its own table width.  Since PR 27 the
            # kernel's page loop runs each lane's
            # ceil(length / page_size) pages and an empty lane none
            # (before, it ran the table's width for every lane and
            # discarded the rest: 1.7 us a slot on the v5e, PERF.md
            # §6), so a bucket's width costs the kernel nothing.
            # NUMERIC REGIME: the kernel scores in f32 where the
            # gather path scores in bf16, so a kernel-decode engine
            # and a gather-path engine (e.g. a speculative verify
            # program) can break argmax ties differently — each lane
            # is deterministic, the f32 exactness lanes always use
            # the gather path, and SELDON_TPU_PAGED_KERNEL=0
            # restores one regime when cross-lane bit-equality
            # matters more than speed.
            from seldon_core_tpu.ops.kernels import paged_attention_decode

            if fold_qkv:
                a_f, b_fact = lora_pools["qkv"]
                # the kernel DMAs one lane's (r, D) factor rows of
                # this layer; the 128-aligned d minor wants A
                # TRANSPOSED (one transpose of the whole pool: the
                # layers' calls share it)
                a_T = jnp.swapaxes(a_f, -1, -2)   # (L, slots, r, d)
                q_scale_f = float(head_dim) ** -0.5
            outs = []
            deltas = []
            off = 0
            for tb in tables:
                nb = tb.shape[0]
                sl = slice(off, off + nb)
                q1 = (q[sl] * scale)[:, 0]  # (nb, h, hd)
                if fold_qkv:
                    acc, m, l, delta = paged_attention_decode(
                        q1, pk, pv, tb, lengths[sl], layer=layer,
                        page_size=pk.shape[2], kv_scales=scale_tables,
                        lora=(y[sl][:, 0], a_T, b_fact,
                              adapter_idx[sl], q_scale_f),
                    )
                    deltas.append(delta)
                    dq, dk, dv = jnp.split(delta, 3, axis=-1)
                    q_self = (
                        q1.astype(jnp.float32)
                        + q_scale_f * dq.reshape(nb, heads, head_dim)
                    )
                    k_self = (
                        k[sl][:, 0].astype(jnp.float32)
                        + dk.reshape(nb, heads, head_dim)
                    )
                    v_self = (
                        v[sl][:, 0].astype(jnp.float32)
                        + dv.reshape(nb, heads, head_dim)
                    )
                else:
                    acc, m, l = paged_attention_decode(
                        q1, pk, pv, tb, lengths[sl], layer=layer,
                        page_size=pk.shape[2], kv_scales=scale_tables,
                    )
                    q_self = q1.astype(jnp.float32)
                    k_self = k[sl][:, 0].astype(jnp.float32)
                    v_self = v[sl][:, 0].astype(jnp.float32)
                s_self = jnp.einsum("bhd,bhd->bh", q_self, k_self)
                m2 = jnp.maximum(m, s_self)
                alpha = jnp.exp(m - m2)
                w_self = jnp.exp(s_self - m2)
                l2 = l * alpha + w_self
                out_b = (
                    acc * alpha[..., None]
                    + v_self * w_self[..., None]
                ) / l2[..., None]
                outs.append(out_b[:, None].astype(self.dtype))
                off += nb
            attn = (
                outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
            )
            attn = attn.reshape(batch, seg_len, d_model)
            if fold_qkv:
                # fold the kernel's raw delta into the k/v this call
                # returns — the caller's pool write must store the
                # ADAPTED keys/values, same as the einsum path
                delta_all = (
                    deltas[0] if len(deltas) == 1
                    else jnp.concatenate(deltas, axis=0)
                )
                _, dk_all, dv_all = jnp.split(delta_all, 3, axis=-1)
                k = (
                    k.astype(jnp.float32)
                    + dk_all.reshape(batch, 1, heads, head_dim)
                ).astype(self.dtype)
                v = (
                    v.astype(jnp.float32)
                    + dv_all.reshape(batch, 1, heads, head_dim)
                ).astype(self.dtype)
                k_flat = k.reshape(batch, 1, d_model)
                v_flat = v.reshape(batch, 1, d_model)
        else:
            # gather path — same arithmetic as
            # TransformerBlock._cached_attention: bf16 scores
            # masked with finfo.min, f32 softmax; one gather +
            # attention per bucket, each at its own static width
            outs = []
            off = 0
            for tb in tables:
                nb = tb.shape[0]
                sl = slice(off, off + nb)
                if tb.shape[1] == 0:
                    # a table of no width: a prefill from position
                    # zero, whose segment has no cache to read and
                    # attends over itself alone
                    outs.append(_segment_attention(
                        self, q[sl], k[sl], v[sl], scale))
                    off += nb
                    continue
                # (nb, P, ps, d).  A whole pool is indexed (layer,
                # page) in ONE gather: pk[layer][tb] would cut the
                # layer out first, and XLA does not fuse that slice
                # into the gather
                gk = pk[layer, tb] if whole else pk[tb]
                gv = pv[layer, tb] if whole else pv[tb]
                pages_per, page_size = gk.shape[1], gk.shape[2]
                cache_len = pages_per * page_size
                if kv_scales is not None:
                    # int8 pool: dequantise right after the page
                    # fetch — one f32 scale per gathered page,
                    # broadcast over its (ps, ...) token block
                    sk_l, sv_l = kv_scales
                    bshape = (nb, pages_per, 1, 1)
                    gk = (
                        gk.astype(jnp.float32) * sk_l[tb].reshape(bshape)
                    ).astype(self.dtype)
                    gv = (
                        gv.astype(jnp.float32) * sv_l[tb].reshape(bshape)
                    ).astype(self.dtype)
                gk = gk.reshape(nb, cache_len, heads, head_dim)
                gv = gv.reshape(nb, cache_len, heads, head_dim)

                sc = jnp.einsum("bqhd,bkhd->bhqk", q[sl] * scale, gk)
                ss = jnp.einsum("bqhd,bkhd->bhqk", q[sl] * scale, k[sl])
                neg = jnp.finfo(sc.dtype).min
                cache_mask = (
                    jnp.arange(cache_len)[None, :] < lengths[sl][:, None]
                )  # (nb, cache_len)
                sc = jnp.where(cache_mask[:, None, None, :], sc, neg)
                seg_mask = (
                    jnp.arange(seg_len)[None, :]
                    <= jnp.arange(seg_len)[:, None]
                )  # (L, L) causal within this segment
                ss = jnp.where(seg_mask[None, None], ss, neg)
                scores = jnp.concatenate(
                    [sc, ss], axis=-1
                ).astype(jnp.float32)
                weights = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
                wc, ws = weights[..., :cache_len], weights[..., cache_len:]
                outs.append(
                    jnp.einsum("bhqk,bkhd->bqhd", wc, gv)
                    + jnp.einsum("bhqk,bkhd->bqhd", ws, v[sl])
                )
                off += nb
            attn = (
                outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
            )
            attn = attn.reshape(batch, seg_len, d_model)

        x = x + _proj("attn_proj", d_model, attn)
        x, hist = _ffn(self, x, _proj, token_mask)
        if whole:
            k, v = k_flat, v_flat
        return (x, k, v, *hist)

class ChunkTransformerBlock(nn.Module):
    """TransformerBlock reading a pre-gathered contiguous context
    plus a step-indexed in-chunk ring — the decode-chunk fast path.

    The r5 slot-scaling probe showed the per-STEP pool gather is
    the chunk's pathology: its cost scales superlinearly with
    total gathered bytes (measured 3.2 ms/step at 64 slots ->
    18.4 ms/step at 128, 13.7x the traffic floor), and the
    gather+DUS read/write hazard on the pool adds several more
    ms/step of scheduling overhead.  This block never touches the
    pool: the caller gathers each slot's context ONCE per chunk
    into ``ctx`` (amortised over steps) and accumulates the
    chunk's own K/V in ``ring`` (written at column ``step`` —
    uniform across slots, one DUS per step).  Attention is then
    three dense einsums (ctx, ring, self) — the same token set,
    masks, and dtypes as the pool gather path.
    """

    num_heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    precision: str = "bf16"  # "w8a8": int8×int8 projections
    spec: Any = GPT2

    @nn.compact
    def __call__(self, x, ctx_k, ctx_v, ring_k, ring_v, step, len0,
                 lora=None, adapter_idx=None, positions=None,
                 token_mask=None):
        # x: (B, 1, d)   ring_k/v: (B, S, h, hd)
        # ctx_k/v: (B, C, h, hd), or a TUPLE of per-bucket buffers
        # ((B0, C0, h, hd), (B1, C1, h, hd), ...) with sum(Bb) == B —
        # the r6 length-bucketed gather: lanes arrive bucket-sorted
        # (shortest contexts first), so each bucket's context einsums
        # run at ITS OWN static width instead of every lane paying
        # the longest stream's C.  Dense work (projections, MLP,
        # embed/head in the LM) stays full-batch — only the per-lane
        # context attention splits, so there is no extra weight
        # traffic and no extra dispatch.
        # — the engine materialises the working set SPLIT even over
        # a flat-at-rest pool ("flat at rest, split in flight"; the
        # split form is what the per-step dense reads want)
        # step: scalar — ring columns < step are live
        # len0: (B,) context lengths frozen at chunk start
        if not isinstance(ctx_k, (tuple, list)):
            ctx_k, ctx_v = (ctx_k,), (ctx_v,)
        d_model = x.shape[-1]
        heads = self.num_heads
        head_dim = d_model // heads
        batch, seg_len = x.shape[:2]

        # same grouped multi-LoRA hook as PagedTransformerBlock —
        # dense work (and therefore the delta) stays full-batch,
        # only the context attention splits by bucket
        spec = self.spec

        def _proj(name, features, inp):
            out = _dense(self.precision, features, self.dtype, name,
                         spec)(inp)
            if lora is not None and name in lora:
                from seldon_core_tpu.ops.lora import lora_delta

                a_f, b_f = lora[name]
                out = out + lora_delta(inp, a_f, b_f, adapter_idx).astype(
                    out.dtype
                )
            return out

        y = _norm(spec, "attn_norm")(x)
        qkv = _proj("qkv", 3 * d_model, y)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (batch, seg_len, heads, head_dim)
        q, k, v = _heads(self, q, k, v, positions, shape)
        scale = 1.0 / jnp.sqrt(head_dim).astype(q.dtype)

        S = ring_k.shape[1]
        ring_mask = jnp.arange(S) < step  # (S,) cols written so far
        neg = jnp.finfo(q.dtype).min
        outs = []
        off = 0
        for ck, cv in zip(ctx_k, ctx_v):
            nb, C = ck.shape[0], ck.shape[1]
            sl = slice(off, off + nb)
            q_b = q[sl] * scale
            sc = jnp.einsum("bqhd,bkhd->bhqk", q_b, ck)
            sr = jnp.einsum("bqhd,bkhd->bhqk", q_b, ring_k[sl])
            ss = jnp.einsum("bqhd,bkhd->bhqk", q_b, k[sl])
            ctx_mask = jnp.arange(C)[None, :] < len0[sl][:, None]  # (nb, C)
            sc = jnp.where(ctx_mask[:, None, None, :], sc, neg)
            sr = jnp.where(ring_mask[None, None, None, :], sr, neg)
            scores = jnp.concatenate(
                [sc, sr, ss], axis=-1
            ).astype(jnp.float32)
            weights = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
            wc = weights[..., :C]
            wr = weights[..., C:C + S]
            ws = weights[..., C + S:]
            outs.append(
                jnp.einsum("bhqk,bkhd->bqhd", wc, cv)
                + jnp.einsum("bhqk,bkhd->bqhd", wr, ring_v[sl])
                + jnp.einsum("bhqk,bkhd->bqhd", ws, v[sl])
            )
            off += nb
        attn = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
        attn = attn.reshape(batch, seg_len, d_model)
        x = x + _proj("attn_proj", d_model, attn)
        x, hist = _ffn(self, x, _proj, token_mask)
        return (x, k, v, *hist)

class ChunkTransformerLM(nn.Module):
    """PagedTransformerLM's decode-chunk twin: identical parameter
    tree (same module names per block), pool-free attention inputs.

    ``__call__(tokens, positions, ctx_k, ctx_v, ring_k, ring_v,
    step, len0)`` -> ``(logits, new_k, new_v)`` with ctx/ring
    shaped ``(layers, B, C|S, heads, head_dim)``; ``ctx_k``/
    ``ctx_v`` may instead be tuples of per-bucket buffers (the
    length-bucketed gather — see ChunkTransformerBlock).
    """

    vocab_size: int = 32_000
    d_model: int = 256
    num_layers: int = 4
    num_heads: int = 8
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    precision: str = "bf16"
    spec: Any = GPT2

    @nn.compact
    def __call__(self, tokens, positions, ctx_k, ctx_v, ring_k, ring_v,
                 step, len0, lora=None, adapter_idx=None,
                 token_mask=None):
        if self.spec.latent:
            raise ValueError(
                f"arch={self.spec.name!r} caches one latent row a "
                "token: the ring chunk's pre-gathered K and V context "
                "and ring are not built for it yet — it serves the "
                "pool chunk (SELDON_TPU_CHUNK_IMPL=pool or unset)")
        x = _embed(self, tokens, positions)
        bucketed = isinstance(ctx_k, (tuple, list))
        new_k, new_v, hists = [], [], []
        for i in range(self.num_layers):
            layer_ck = (
                tuple(c[i] for c in ctx_k) if bucketed else ctx_k[i]
            )
            layer_cv = (
                tuple(c[i] for c in ctx_v) if bucketed else ctx_v[i]
            )
            lora_i = (
                {t: (ab[0][i], ab[1][i]) for t, ab in lora.items()}
                if lora is not None else None
            )
            x, k, v, *hist = ChunkTransformerBlock(
                num_heads=self.num_heads, dtype=self.dtype,
                precision=self.precision, name=f"block_{i}",
                spec=self.spec,
            )(x, layer_ck, layer_cv, ring_k[i], ring_v[i], step, len0,
              lora=lora_i, adapter_idx=adapter_idx,
              positions=positions, token_mask=token_mask)
            new_k.append(k)
            new_v.append(v)
            hists += hist
        return _head(self, x, new_k, new_v, hists)

class PagedTransformerLM(nn.Module):
    """TransformerLM forward against a paged pool.

    ``__call__(tokens, positions, pages_k, pages_v, block_tables,
    lengths)`` -> ``(logits, new_k, new_v)`` where new_k/new_v are
    ``(layers, B, L, heads, head_dim)`` for the caller to scatter.
    """

    vocab_size: int = 32_000
    d_model: int = 256
    num_layers: int = 4
    num_heads: int = 8
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    precision: str = "bf16"
    # decode fast path (pallas flash-decoding) — the engine turns
    # this off under tensor-parallel meshes: GSPMD cannot partition
    # a pallas_call over the whole heads axis, so a heads-sharded
    # pool would all-gather per layer per step
    decode_kernel: bool = True
    spec: Any = GPT2

    @nn.compact
    def __call__(self, tokens, positions, pages_k, pages_v, block_tables,
                 lengths, lora=None, adapter_idx=None, kv_scales=None,
                 token_mask=None, window=None, delta=None, last=None):
        # last: (B,) int32 — the one position of each row to
        # unembed (a prefill's); None unembeds all L (_unembed)
        x = _embed(self, tokens, positions)
        # The kernel lane (no TP mesh — decode_kernel=False is how
        # the engine encodes one; env, dtype, backend: the shared
        # static predicate) hands every block the WHOLE pool and its
        # layer number: the decode kernel DMAs pool.at[layer, page],
        # so no layer (84 MB at GPT-2-large size) is ever cut out of
        # the pool, in any program of that engine.  Every other lane
        # slices here, as before PR 25, and lowers unchanged.
        whole = self.decode_kernel and paged_kernel_static_eligible(
            paged_kernel_mode(), True, self.dtype,
            *self.spec.head_sizes(self.num_heads, self.d_model),
            latent=self.spec.latent,
        )
        new_k, new_v, hists = [], [], []
        if self.spec.kinds:
            return self._kinds(x, positions, pages_k, pages_v,
                               block_tables, lengths, token_mask, window,
                               whole, last)
        if self.spec.recurrent:
            return self._hybrid(x, positions, pages_k, pages_v,
                                block_tables, lengths, whole, delta or {},
                                last, token_mask)
        for i in range(self.num_layers):
            if whole:
                pools = (pages_k, pages_v)
                per_layer = dict(lora=lora, kv_scales=kv_scales, layer=i)
            else:
                per_layer = dict(
                    lora=(
                        {t: (ab[0][i], ab[1][i]) for t, ab in lora.items()}
                        if lora is not None else None
                    ),
                    kv_scales=(
                        (kv_scales[0][i], kv_scales[1][i])
                        if kv_scales is not None else None
                    ),
                )
                # (a double layer's two attentions: its two rows)
                subs = self.spec.attn_sublayers
                pools = (pages_k[i] if subs == 1
                         else pages_k[subs * i:subs * (i + 1)],
                         None if pages_v is None else pages_v[i])
            kinds = ({"routed_layer": False}
                     if self.spec.routed and not self.spec.layer_routed(i)
                     else {})
            x, k, v, *hist = PagedTransformerBlock(
                num_heads=self.num_heads, dtype=self.dtype,
                precision=self.precision, name=f"block_{i}",
                spec=self.spec, **kinds,
            )(x, *pools, block_tables, lengths,
              adapter_idx=adapter_idx, **per_layer,
              positions=positions, token_mask=token_mask)
            # one cache row an attention: a double layer brings two
            new_k += k if isinstance(k, tuple) else [k]
            new_v.append(v)
            hists += hist
        return _head(self, x, new_k, new_v, hists, last)

    def _hybrid(self, x, positions, pages_k, pages_v, block_tables,
                lengths, whole, delta, last, token_mask=None):
        """The layers of a spec with layers that keep a state a lane: a
        ``"linear"`` layer is a :class:`DeltaBlock`, an ``"ssm"`` layer
        a :class:`SsmBlock`, each over its own state and keeping no
        pages (``delta`` below is either's side of the call); a
        ``"full"`` layer is the grouped-query
        block over the K/V pool — or, for a latent spec, the latent
        block over the ONE latent pool (``pages_v`` None) — whose
        leading axis counts the full layers alone
        (``spec.kind_index``).

        ``delta`` is the linear layers' side of the call.  A prefill
        from zero: ``{"true_lens": (B,)}``.  A decode step:
        ``{"state": (a layer's resting state, ...), "conv": (its
        tail, ...), "active": (slots,) bool}``, every one in SLOT
        order, and ``"order"`` = ``(to_slot, to_lane)`` where the
        call's lanes are a permutation of the slots (the bucketed
        chunk): the stream's rows are gathered to slot order round a
        linear layer, never the state.  Returns ``(logits, K, V,
        states, tails)``, the last two a tuple a linear layer, and a
        routed spec's ``int32[layers, E]`` assignment histogram over
        the rows ``token_mask`` keeps as a sixth value (a linear
        layer routes as a full one does; a dense layer's row is
        zeros)."""
        spec = self.spec
        new_k, new_v, states, tails, hists = [], [], [], [], []
        order = delta.get("order")
        # the rows a routed layer's histogram counts, in the lanes'
        # order and (round a linear layer of a decode step) the slots'
        mask = {"token_mask": token_mask} if spec.routed else {}
        slot_mask = ({"token_mask": token_mask[order[0]]}
                     if mask and order is not None else mask)
        for i in range(self.num_layers):
            at = spec.kind_index(i)
            place = ({"routed_layer": False}
                     if spec.routed and not spec.layer_routed(i) else {})
            if spec.layer_kind(i) in ("linear", "ssm"):
                block = (SsmBlock if spec.ssm else DeltaBlock)(
                    dtype=self.dtype, precision=self.precision,
                    spec=spec, name=f"block_{i}", **place)
                if "state" in delta:
                    rows = x if order is None else x[order[0]]
                    rows, state, tail, *hist = block(
                        rows, delta["state"][at], delta["conv"][at],
                        active=delta["active"], **slot_mask)
                    x = rows if order is None else rows[order[1]]
                else:
                    x, state, tail, *hist = block(
                        x, true_lens=delta.get("true_lens"), **mask)
                states.append(state)
                tails.append(tail)
                hists += hist
                continue
            if spec.latent:
                # one latent pool: a row a token a full layer, no V
                x, row, _none, hist = PagedTransformerBlock(
                    num_heads=self.num_heads, dtype=self.dtype,
                    precision=self.precision, name=f"block_{i}", spec=spec,
                    **place,
                )(x, pages_k if whole else pages_k[at], None, block_tables,
                  lengths, layer=at if whole else None,
                  positions=positions, token_mask=token_mask)
                new_k.append(row)
                hists.append(hist)
                continue
            pools = ((pages_k, pages_v) if whole
                     else (pages_k[at], pages_v[at]))
            x, (_name, k), v, *_read = PagedTransformerBlock(
                num_heads=self.num_heads, dtype=self.dtype,
                precision=self.precision, name=f"block_{i}", spec=spec,
                kind=spec.attn_kind(i, self.num_heads),
            )(x, *pools, block_tables, lengths,
              layer=at if whole else None, positions=positions)
            new_k.append(k)
            new_v.append(v)
        return (_unembed(self, x, last), jnp.stack(new_k),
                jnp.stack(new_v) if new_v else None,
                tuple(states), tuple(tails),
                *((jnp.stack(hists),) if hists else ()))

    def _kinds(self, x, positions, pools, pools_v, block_tables, lengths,
               token_mask, window, whole, last):
        """The layers of a spec whose attention differs by layer:
        ``pools`` is ``{"full", "index", "window"}`` (models/spec.py
        ``cache_kinds``), each ``(layers of the kind, pages,
        page_size, lanes)``; layer ``i`` reads its kind's pools at
        its place among that kind's layers, whole with the place as
        ``layer`` on the kernel lane and cut to its own rows
        elsewhere.  The new rows come back a dict of the same names,
        each stacked over its kind's layers.  A multi-head spec's
        kinds are ``{"full", "window"}`` twice, K in ``pools`` and V
        in ``pools_v`` (None for a latent spec), and V's rows come
        back a dict beside K's."""
        spec = self.spec
        rows = {name: [] for name in pools}
        rows_v = {name: [] for name in pools_v or ()}
        hists, reads = [], []
        for i in range(self.num_layers):
            kind = spec.attn_kind(i, self.num_heads)
            at = spec.kind_index(i)
            names = (("window",) if kind.window
                     else ("full", "index") if spec.latent else ("full",))
            mine = tuple(pools[n] if whole else pools[n][at]
                         for n in names)
            mine_v = (None if pools_v is None else pools_v[names[0]]
                      if whole else pools_v[names[0]][at])
            x, new, v, hist, *read = PagedTransformerBlock(
                num_heads=self.num_heads, dtype=self.dtype,
                precision=self.precision, name=f"block_{i}",
                spec=spec, routed_layer=spec.layer_routed(i), kind=kind,
            )(x, mine if kind.topk else mine[0], mine_v, block_tables,
              lengths, layer=at if whole else None, positions=positions,
              token_mask=token_mask, window=window)
            for name, row in zip(names, new[1:]):
                rows[name].append(row)
            if pools_v is not None:
                rows_v[names[0]].append(v)
            hists.append(hist)
            reads += read
        # (a decode step's fifth value: what each layer read,
        # int32[layers, 3] — _latent_attention)
        return (_unembed(self, x, last),
                {n: jnp.stack(r) for n, r in rows.items()},
                {n: jnp.stack(r) for n, r in rows_v.items()} or None,
                jnp.stack(hists), *((jnp.stack(reads),) if reads else ()))
