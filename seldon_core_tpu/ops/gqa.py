"""Grouped-query attention in plain XLA: ``h`` query heads over ``kv``
K/V heads, query head ``c`` reading K/V head ``c // (h / kv)``, with no
K or V repeated to ``h`` heads — the query heads of a group are an axis
of the einsums.

:func:`segment_attention` is a prefill from position zero (causal, or
over a window), a block of queries at a time where the fused kernel
(``ops/kernels.py causal_attention``, which maps heads by its block
index) is not the caller's rule; :func:`ctx_state` is the gather lane of
a decode step, the unnormalised flash state ``ops/kernels.py
paged_attention_decode`` returns for the same rows under the same mask,
joined with the step's own row by ``ops/mla.py merge``.
"""

from __future__ import annotations

# queries a block of segment_attention scores at once: (B, heads, 256,
# keys) float32 is 0.23 GB at one prompt of 8,192 and 28 heads, where
# every query at once would be 7.5 GB
QUERY_BLOCK = 256


def _grouped(q, kv_heads: int):
    """``(..., h, d)`` -> ``(..., kv, h / kv, d)``."""
    *lead, h, d = q.shape
    return q.reshape(*lead, kv_heads, h // kv_heads, d)


def segment_attention(q, k, v, scale: float, dtype, *, window: int = 0,
                      fused: bool = False):
    """Causal attention of a segment over itself alone: ``q`` ``(B, L,
    h, d)``, ``k`` / ``v`` ``(B, L, kv, d)`` -> ``(B, L, h, d)`` in
    ``dtype``.  Row ``i`` attends keys ``0 .. i``, or ``i - window + 1
    .. i`` under ``window``.  Scores and softmax in float32:
    :data:`QUERY_BLOCK` queries at a time in XLA, or in the fused causal
    kernel where the caller's rule says ``fused`` (``ops/kernels.py
    prefill_attention_impl``)."""
    import jax
    import jax.numpy as jnp

    if fused:
        from seldon_core_tpu.ops.kernels import causal_attention

        return causal_attention(q, k, v, scale, window=window).astype(dtype)
    batch, seg_len, heads, d = q.shape
    kv_heads = k.shape[2]
    key_at = jnp.arange(seg_len)

    def block(args):
        qb, first = args                       # (B, bq, h, d), its offset
        s = jnp.einsum("bqgsd,bkgd->bgsqk", _grouped(qb, kv_heads), k,
                       preferred_element_type=jnp.float32) * scale
        q_at = first + jnp.arange(qb.shape[1])
        seen = key_at[None, :] <= q_at[:, None]                # (bq, keys)
        if window:
            seen &= key_at[None, :] > q_at[:, None] - window
        s = jnp.where(seen[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(dtype)
        out = jnp.einsum("bgsqk,bkgd->bqgsd", p, v,
                         preferred_element_type=jnp.float32)
        return out.reshape(qb.shape).astype(dtype)

    bq = QUERY_BLOCK
    if seg_len <= bq or seg_len % bq:
        return block((q, 0))
    blocks = seg_len // bq
    cut = jnp.moveaxis(q.reshape(batch, blocks, bq, heads, d), 1, 0)
    out = jax.lax.map(block, (cut, jnp.arange(blocks) * bq))
    return jnp.moveaxis(out, 0, 1).reshape(batch, seg_len, heads, d)


def ctx_state(q, k_rows, v_rows, valid):
    """Unnormalised attention of a step's ``q`` ``(B, h, d)`` (scaled)
    over ``k_rows`` / ``v_rows`` ``(B, C, kv, d)`` where ``valid`` ``(B,
    C)``: ``(acc (B, h, d), m (B, h), l (B, h))`` float32, with ``m``
    -inf and ``l`` 0 where no row is valid."""
    import jax.numpy as jnp

    batch, heads, d = q.shape
    kv_heads = k_rows.shape[2]
    s = jnp.einsum("bgsd,bcgd->bgsc", _grouped(q, kv_heads), k_rows,
                   preferred_element_type=jnp.float32)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    m = s.max(axis=-1)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)[..., None])
    acc = jnp.einsum("bgsc,bcgd->bgsd", p.astype(v_rows.dtype), v_rows,
                     preferred_element_type=jnp.float32)
    return (acc.reshape(batch, heads, d), m.reshape(batch, heads),
            p.sum(axis=-1).reshape(batch, heads))
