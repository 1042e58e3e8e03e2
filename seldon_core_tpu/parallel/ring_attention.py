"""Ring attention — sequence-parallel attention over an ICI ring.

Long-sequence serving support (no reference analogue — the reference
scales payloads only via gRPC message-size knobs, reference: SURVEY
§5.7): activations are sharded along the sequence axis across devices,
and attention runs blockwise with K/V shards rotating around the mesh
ring via ``lax.ppermute`` while each device keeps a numerically-stable
online-softmax accumulator (flash-attention style m/l/acc carry).
Memory per device is O(S/n), so context length scales linearly with
the ring size; compute overlaps the neighbour exchange.

Written with ``shard_map`` so the collective schedule is explicit; the
single-device path (`plain_attention`) is the correctness oracle.

Role under the 2-D serving mesh (r19): the ``data`` axis that batch-
shards lanes and page-shards the paged KV pool doubles as a sequence
ring — ``ring_attention(..., seq_axis="data")`` runs this module's
online-softmax schedule over the SAME axis the serving engine spreads
a long stream's pages across, and ``plain_attention`` pins the
numerics of that layout in the long-context parity tests
(tests/test_paged_mesh.py).  The paged engine itself stays on
annotation-only GSPMD sharding; this module is the explicit-schedule
contrast and the oracle, not the serving data path.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np

NEG_INF = -1e30


def plain_attention(q, k, v, causal: bool = False):
    """Reference single-device attention. [batch, seq, heads, dim]."""
    import jax.numpy as jnp

    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        mask = jnp.arange(s_k)[None, :] > jnp.arange(s_q)[:, None]
        scores = jnp.where(mask[None, None], NEG_INF, scores)
    probs = jnp.asarray(
        __import__("jax").nn.softmax(scores.astype(jnp.float32), axis=-1), q.dtype
    )
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _ring_shard_body(q, k, v, axis_name: str, causal: bool):
    """Per-shard ring attention; q/k/v are the local sequence shards."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, s_local, h, d = q.shape
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    scale = 1.0 / np.sqrt(d)

    q32 = q.astype(jnp.float32)
    local_pos = jnp.arange(s_local)
    q_pos = my_idx * s_local + local_pos  # global positions of my queries

    def step(i, carry):
        k_blk, v_blk, m, l, acc = carry
        src = (my_idx - i) % n  # ring: block i hops old came from device src
        scores = jnp.einsum("bqhd,bkhd->bhqk", q32, k_blk.astype(jnp.float32)) * scale
        if causal:
            k_pos = src * s_local + local_pos
            mask = k_pos[None, :] > q_pos[:, None]  # [q, k]
            scores = jnp.where(mask[None, None], NEG_INF, scores)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
        )
        # rotate K/V to the next device; overlaps with the next block's math
        k_next = lax.ppermute(k_blk, axis_name, [(j, (j + 1) % n) for j in range(n)])
        v_next = lax.ppermute(v_blk, axis_name, [(j, (j + 1) % n) for j in range(n)])
        return k_next, v_next, m_new, l_new, acc_new

    m0 = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    acc0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    # loop carries must be typed as axis-varying
    m0, l0, acc0 = (
        lax.pcast(x, (axis_name,), to="varying") for x in (m0, l0, acc0)
    )
    _, _, m, l, acc = lax.fori_loop(0, n, step, (k, v, m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [b,h,q,d]
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def ring_attention(q, k, v, mesh, seq_axis: str = "seq", causal: bool = False):
    """Sequence-parallel attention over `mesh`'s `seq_axis` ring.

    q/k/v: [batch, seq, heads, dim] global arrays (or sharded jax
    Arrays); seq must divide by the ring size.
    """
    from jax.sharding import PartitionSpec as P

    spec = P(None, seq_axis, None, None)
    body = partial(_ring_shard_body, axis_name=seq_axis, causal=causal)
    from jax import shard_map

    return shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def sequence_sharding(mesh, seq_axis: str = "seq"):
    """NamedSharding placing [batch, seq, ...] arrays on the ring."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(None, seq_axis))
