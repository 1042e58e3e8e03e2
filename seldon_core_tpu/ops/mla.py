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


def naive_attention(q_nope, q_rope, ctx, ctx_len, seg, w_uk, w_uv,
                    scale: float, dtype, fused: bool = False):
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
    kernel, on ``k = [k_nope ; k_rope]`` made a head as below."""
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

        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(
                k_rope[:, :, None, :], k_nope.shape[:3] + k_rope.shape[-1:])],
            axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        return causal_attention(q, k, v, scale).astype(dtype)
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
