"""Latent attention (MLA) in plain XLA: the naive form a prefill runs
and the gather lane of the absorbed form a decode step runs.

A token's cache row is ``[c_kv ; k_r]``: ``rank`` values of normed
latent and the one rotary key every head shares.  **Naive**
(:func:`naive_attention`): per head ``k = [W_uk c_kv ; k_r]`` and ``v =
W_uv c_kv`` are made from the rows and attended causally — what a
segment of many queries wants, since the up-projection is paid once a
key.  **Absorbed** (:func:`ctx_state`): ``W_uk`` is folded into q
beforehand, so the score of a row is one ``(heads, W) x (W,)`` product
and the value read is the row's first ``rank`` values — what a decode
step wants, since it reads each cached row once for all heads.  The
Pallas kernel of the absorbed form is ``ops/kernels.py
latent_attention_decode``; it returns the same unnormalised flash state
as :func:`ctx_state`, and :func:`merge` joins states by the flash rule.
**Learned sparse attention** (:func:`index_scores`, :func:`kth_mask`,
:func:`step_mask`, :func:`indexed_attention`) chooses by a mask in both
forms: a prefill's blocks of queries hand their chosen rows to the fused
causal kernel (``causal_attention(chosen=)``) or mask their own scores
in XLA, a decode step hands the mask to the page loop
(``latent_attention_decode(chosen=)``) or to :func:`ctx_state` as
``valid``.
"""

from __future__ import annotations

# queries a block of naive_attention scores at once: (B, heads, 256,
# keys) float32 is 0.5 GB at 4 prompts of 2048 and 64 heads, where
# every query at once would be 4.3 GB
QUERY_BLOCK = 256


def ctx_state(q, rows, valid, rank: int):
    """Unnormalised absorbed attention of ``q`` ``(B, h, W)`` (scaled,
    ``W_uk`` folded in) over ``rows`` ``(B, C, W)`` where ``valid``
    ``(B, C)``: ``(acc (B, h, rank), m (B, h), l (B, h))`` float32, with
    ``m`` -inf and ``l`` 0 where no row is valid."""
    import jax.numpy as jnp

    s = jnp.einsum("bhw,bcw->bhc", q, rows,
                   preferred_element_type=jnp.float32)
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    m = s.max(axis=-1)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)[..., None])
    acc = jnp.einsum("bhc,bcr->bhr", p.astype(rows.dtype), rows[..., :rank],
                     preferred_element_type=jnp.float32)
    return acc, m, p.sum(axis=-1)


def merge(*states):
    """The attended latent ``(B, h, rank)`` float32 of flash states
    ``(acc, m, l)`` over disjoint key sets, at least one of them
    non-empty in every lane."""
    import jax.numpy as jnp

    m_all = states[0][1]
    for _acc, m, _l in states[1:]:
        m_all = jnp.maximum(m_all, m)
    acc_all = l_all = 0.0
    for acc, m, l in states:
        alpha = jnp.exp(m - m_all)        # exp(-inf) = 0: an empty set
        acc_all = acc_all + acc * alpha[..., None]
        l_all = l_all + l * alpha
    return acc_all / l_all[..., None]


def _kernel_operands(q_nope, q_rope, k_nope, k_rope):
    """``(q, k)`` as the fused causal kernel takes them, a head each:
    ``[nope ; rope]``, the one rotary key every head shares (``(B, L,
    r)``) repeated beside each head's ``k_nope``."""
    import jax.numpy as jnp

    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(
            k_rope[:, :, None, :], k_nope.shape[:3] + k_rope.shape[-1:])],
        axis=-1)
    return jnp.concatenate([q_nope, q_rope], axis=-1), k


def naive_attention(q_nope, q_rope, ctx, ctx_len, seg, w_uk, w_uv,
                    scale: float, dtype, fused: bool = False,
                    window: int = 0):
    """Causal attention of a segment over its cached prefix and itself,
    K and V made per head from the latent rows.

    ``q_nope`` ``(B, L, h, n)``, ``q_rope`` ``(B, L, h, r)`` (rotated);
    ``ctx`` ``(B, C, W)`` cached rows of which the first ``ctx_len``
    ``(B,)`` are the prefix, or None; ``seg`` ``(B, L, W)`` the
    segment's own rows; ``w_uk`` ``(h, rank, n)``, ``w_uv`` ``(h, rank,
    v)``.  Returns ``(B, L, h, v)`` in ``dtype``.  Scores and softmax in
    float32: in XLA :data:`QUERY_BLOCK` queries at a time, or — a
    segment with no prefix where the caller's rule says ``fused``
    (``ops/kernels.py prefill_attention_impl``) — in the fused causal
    kernel, on ``k = [k_nope ; k_rope]`` made a head as below.
    ``window`` (a segment with no prefix only): row ``i`` attends keys
    ``i - window + 1 .. i``, not every earlier one."""
    import jax
    import jax.numpy as jnp

    batch, seg_len = seg.shape[:2]
    rank = w_uk.shape[1]
    rows = seg if ctx is None else jnp.concatenate([ctx, seg], axis=1)
    cached = rows.shape[1] - seg_len
    # (the row may end in zero lanes that pad it to whole tiles)
    c_kv = rows[..., :rank]
    k_rope = rows[..., rank:rank + q_rope.shape[-1]]
    k_nope = jnp.einsum("bcr,hrn->bchn", c_kv, w_uk.astype(dtype),
                        preferred_element_type=jnp.float32).astype(dtype)
    v = jnp.einsum("bcr,hrv->bchv", c_kv, w_uv.astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)
    if ctx is None and fused:
        from seldon_core_tpu.ops.kernels import causal_attention

        q, k = _kernel_operands(q_nope, q_rope, k_nope, k_rope)
        return causal_attention(q, k, v, scale, window=window).astype(dtype)
    if window and ctx is not None:
        raise ValueError("naive_attention: a window over a cached prefix "
                         "is not built (a window layer prefills from zero)")
    key_at = jnp.arange(rows.shape[1])
    in_prefix = key_at[None, :] < ctx_len[:, None]             # (B, keys)

    def block(args):
        qn, qr, first = args               # (B, bq, h, ·), the block's offset
        s = (jnp.einsum("bqhn,bchn->bhqc", qn, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhr,bcr->bhqc", qr, k_rope,
                          preferred_element_type=jnp.float32)) * scale
        q_at = first + jnp.arange(qn.shape[1])
        own = (key_at[None, :] >= cached) & (
            key_at[None, :] - cached <= q_at[:, None])         # (bq, keys)
        if window:
            own &= key_at[None, :] > q_at[:, None] - window
        seen = in_prefix[:, None, :] | own[None]               # (B, bq, keys)
        s = jnp.where(seen[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(dtype)
        return jnp.einsum("bhqc,bchv->bqhv", p, v,
                          preferred_element_type=jnp.float32).astype(dtype)

    bq = QUERY_BLOCK
    if seg_len <= bq or seg_len % bq:
        return block((q_nope, q_rope, 0))
    blocks = seg_len // bq

    def cut(x):  # (B, L, h, ·) -> (blocks, B, bq, h, ·)
        return jnp.moveaxis(
            x.reshape(batch, blocks, bq, *x.shape[2:]), 1, 0)

    out = jax.lax.map(
        block, (cut(q_nope), cut(q_rope), jnp.arange(blocks) * bq))
    return jnp.moveaxis(out, 0, 1).reshape(batch, seg_len, *out.shape[3:])


# ---------------------------------------------------------------------------
# learned sparse attention: an indexer scores every earlier position and
# a row attends over the best ``topk`` (DeepSeek sparse attention's form).
# The best are told by a threshold — the ``topk``-th largest score, found
# by counting the row against candidates (:func:`_descend`), never by
# sorting it — and kept as a mask
# ---------------------------------------------------------------------------

# queries :func:`indexed_attention` selects at once: the indexer's (B,
# index heads, 128, keys) float32 is 0.13 GB at 64 heads and 4,096 keys
# (and, on the XLA lane, the attention's (B, heads, 128, keys) beside
# it 0.27 GB more at 128 heads)
INDEX_QUERY_BLOCK = 128


def index_scores(q_idx, w_idx, keys, scale: float):
    """The indexer's score of every key for every query: ``I[t, s] =
    scale * sum_j w[t, j] * relu(q[t, j] . k[s])``.  ``q_idx`` ``(B, Q,
    j, d)`` and ``keys`` ``(B, C, d)`` in the type the keys are cached in
    (one MXU pass, float32 accumulation), ``w_idx`` ``(B, Q, j)``
    float32: ``(B, Q, C)`` float32."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("bqjd,bcd->bjqc", q_idx, keys,
                   preferred_element_type=jnp.float32)
    s = jax.nn.relu(s) * jnp.swapaxes(w_idx, 1, 2)[..., None]
    return s.sum(axis=1) * scale


# bits of a threshold :func:`_descend` fixes in one pass over a row (a
# divisor of 32): a pass holds the row against 2**bits - 1 candidates at
# once inside one fused compare-and-count — the (3, lanes, span) compare
# never leaves its fusion — and a float32's 32 bits take 32 / bits
# passes.  On a v5e a pass costs ~1.3 us of launches and ~0.4 us a
# candidate at (64, 7168): a decode step's mask 71 us at 1 bit, 60 at
# 2, 108 at 4, 677 at 8, where the sort and the ties' running sum took
# 436 (PERF.md section 6, PR 55)
DESCENT_BITS = 2


def _descend(holds, bits: int, shape):
    """The largest ``t`` in ``[0, 2**bits)`` a row for which ``holds``,
    where ``holds`` is true at 0 and, once false, false at every larger
    ``t``: the bits of ``t`` fixed from the top, :data:`DESCENT_BITS` a
    pass.  ``holds(cands)`` takes the candidates ``(2**DESCENT_BITS - 1,
    *shape)`` int32 — bit patterns read as unsigned — and says which
    hold, in the same shape; ``t`` comes back ``shape`` int32."""
    import jax.numpy as jnp
    import numpy as np

    t = jnp.zeros(shape, jnp.int32)
    digits = np.arange(1, 2 ** DESCENT_BITS, dtype=np.uint32).reshape(
        (-1,) + (1,) * len(shape))
    top = (bits - 1) // DESCENT_BITS * DESCENT_BITS
    for shift in range(top, -1, -DESCENT_BITS):
        cands = t[None] | (digits << np.uint32(shift)).view(np.int32)
        # the candidates ascend, so those that hold are a pass's first
        # few: their count is the digit
        t = t | (holds(cands).sum(axis=0, dtype=jnp.int32) << shift)
    return t


def kth_mask(scores, allowed, k: int):
    """Which of each row's ``allowed`` entries are among its ``k``
    largest, ties to the lower index: bool like ``scores`` ``(..., C)``.
    A row with ``k`` allowed entries or fewer keeps them all.  The one
    selection rule: a prefill's rows (:func:`indexed_attention`) and a
    decode step's cached positions with its own (:func:`step_mask`)
    both take their chosen set from here, as a mask — no position list
    is sorted out and no row gathered by it.

    Nothing is sorted to find the ``k``-th largest score either: it is
    the largest ``t`` with ``count(score >= t) >= k``, found by a descent
    over the bits of the float32s' order-preserving integer image
    (:func:`_descend`: a count of the row against 3 candidates a pass,
    16 passes, where a sort orders the whole row to learn one of its
    values), and the last admitted tie is the largest index with no more
    than the room left of ties below it, by the same descent over the
    index's bits (no running sum over the row).  The threshold goes back
    to a float32 before anything is held against it: the scores are
    ReLU-weighted sums with zeros of both signs, which are one score and
    two bit patterns — compared as bits, a ``-0.0`` under a ``+0.0``
    threshold would fall out of a tie it belongs to."""
    import jax
    import jax.numpy as jnp

    width = scores.shape[-1]
    if width <= k:
        return allowed
    s = jnp.where(allowed, scores, -jnp.inf).astype(jnp.float32)
    rows = s.shape[:-1] + (1,)
    low = jnp.int32(-2 ** 31)

    def image(b):  # float32 bits <-> signed ints in the floats' order
        return b ^ ((b >> 31) & 0x7fffffff)

    def count(hit):  # along a row
        return hit.sum(axis=-1, keepdims=True, dtype=jnp.int32)

    key = image(jax.lax.bitcast_convert_type(s, jnp.int32))
    # an unsigned threshold t stands for the signed key t ^ low
    t = _descend(lambda cands: count(key[None] >= (cands ^ low)) >= k, 32, rows)
    # -inf where fewer than k are allowed
    kth = jax.lax.bitcast_convert_type(image(t ^ low), jnp.float32)
    above = s > kth
    tie = (s == kth) & allowed
    room = k - count(above)
    at = jnp.arange(width, dtype=jnp.int32)
    # ties below index t number `room` at the tie after the last admitted
    # one; with no such tie every bit sets and every tie is below t
    end = _descend(lambda cands: count(tie[None] & (at < cands)) <= room,
                   width.bit_length(), rows)
    return above | (tie & (at < end))


def any_over(lengths, topk: int):
    """Whether a decode step over lanes holding ``lengths`` cached
    positions selects: some lane's candidates (its cached positions and
    its own) outnumber ``topk``.  The block's rule and the engine's
    counters both read it here."""
    import jax.numpy as jnp

    return jnp.any(lengths >= topk)


def step_mask(scores, own_score, lengths, topk: int):
    """A decode step's chosen set, as a mask: of each lane's ``lengths``
    cached positions (``scores`` ``(B, C)``, the table's whole span) and
    its own (``own_score`` ``(B,)``, position ``lengths``) the ``topk``
    of largest score by :func:`kth_mask`'s rule (a count over the
    span a pass of its descent, no sort of it; the own score and the cut
    at the length are elementwise and fold into the one fusion that
    makes the scores' integer image).
    Returns ``(cached (B, C), own (B,))``: which cached rows are
    attended — what the page loop takes as ``chosen`` — and whether the
    step's own row is."""
    import jax.numpy as jnp

    at = jnp.arange(scores.shape[1])[None, :]
    here = lengths[:, None]
    # the step's own position stands in its own column
    is_own = at == here
    mask = kth_mask(
        jnp.where(is_own, own_score[:, None], scores), at <= here, topk)
    return mask & (at < here), (mask & is_own).any(axis=-1)


def indexed_attention(q_nope, q_rope, seg, w_uk, w_uv, scale: float, dtype,
                      q_idx, w_idx, k_idx, index_scale: float, topk: int,
                      fused: bool = False):
    """:func:`naive_attention` of a segment from position zero whose row
    ``t`` attends over the ``topk`` positions ``s <= t`` the indexer
    scores highest (:func:`index_scores`, :func:`kth_mask`; every one
    while ``t < topk``).  ``q_idx`` ``(B, L, j, d)``, ``w_idx`` ``(B, L,
    j)``, ``k_idx`` ``(B, L, d)`` beside ``naive_attention``'s operands.
    The selection runs :data:`INDEX_QUERY_BLOCK` queries at a time on
    either lane, so the indexer's ``(j, L, L)`` scores never exist whole
    (4.3 GB at 4,096 positions).  The attention under it: where the
    caller's rule says ``fused`` (``ops/kernels.py
    prefill_attention_impl`` at this layer's widths) the blocks hand back
    their chosen rows alone — a ``(B, L, L)`` mask — and the fused causal
    kernel weighs ``k = [k_nope ; k_rope]`` and ``v``, made a head as
    ``naive_attention(fused=True)`` makes them, under it
    (``causal_attention(chosen=)``: no score reaches HBM; while ``L <=
    topk`` no mask is built and the call is the plain causal one);
    elsewhere each block also scores, masks and weighs its 128 queries
    against every key in XLA (float32 operands, the CPU, a mesh,
    ``SELDON_TPU_PAGED_KERNEL=0``, a bucket under one query block), so
    the attention's ``(h, L, L)`` scores do not exist whole either (8.6
    GB).  ``(B, L, h, v)`` in ``dtype``."""
    import jax
    import jax.numpy as jnp

    batch, seg_len = seg.shape[:2]
    rank = w_uk.shape[1]
    c_kv = seg[..., :rank]
    k_rope = seg[..., rank:rank + q_rope.shape[-1]]
    k_nope = jnp.einsum("bcr,hrn->bchn", c_kv, w_uk.astype(dtype),
                        preferred_element_type=jnp.float32).astype(dtype)
    v = jnp.einsum("bcr,hrv->bchv", c_kv, w_uv.astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)
    key_at = jnp.arange(seg_len)

    def chosen(qi, wi, first):
        """The rows' chosen sets ``(B, bq, L)``: the one selection rule
        under the causal triangle."""
        q_at = first + jnp.arange(qi.shape[1])
        seen = jnp.broadcast_to(
            key_at[None, :] <= q_at[:, None], (batch, qi.shape[1], seg_len))
        if seg_len > topk:
            seen = kth_mask(index_scores(qi, wi, k_idx, index_scale), seen, topk)
        return seen

    def block(args):
        qn, qr, qi, wi, first = args
        seen = chosen(qi, wi, first)
        s = (jnp.einsum("bqhn,bchn->bhqc", qn, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhr,bcr->bhqc", qr, k_rope,
                          preferred_element_type=jnp.float32)) * scale
        s = jnp.where(seen[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(dtype)
        return jnp.einsum("bhqc,bchv->bqhv", p, v,
                          preferred_element_type=jnp.float32).astype(dtype)

    bq = INDEX_QUERY_BLOCK
    blocks = 0 if seg_len <= bq or seg_len % bq else seg_len // bq

    def cut(x):  # (B, L, ...) -> (blocks, B, bq, ...)
        return jnp.moveaxis(
            x.reshape(batch, blocks, bq, *x.shape[2:]), 1, 0)

    def whole(out):  # (blocks, B, bq, ...) -> (B, L, ...)
        return jnp.moveaxis(out, 0, 1).reshape(batch, seg_len, *out.shape[3:])

    firsts = jnp.arange(blocks) * bq
    if fused:
        from seldon_core_tpu.ops.kernels import causal_attention

        masked = {}
        if seg_len > topk and blocks:
            masked["chosen"] = whole(jax.lax.map(
                lambda args: chosen(*args), (cut(q_idx), cut(w_idx), firsts)))
        elif seg_len > topk:
            masked["chosen"] = chosen(q_idx, w_idx, 0)
        q, k = _kernel_operands(q_nope, q_rope, k_nope, k_rope)
        return causal_attention(q, k, v, scale, **masked).astype(dtype)
    if not blocks:
        return block((q_nope, q_rope, q_idx, w_idx, 0))
    return whole(jax.lax.map(
        block, (cut(q_nope), cut(q_rope), cut(q_idx), cut(w_idx), firsts)))
