"""The grouped-query page loop and prefill kernel (PR 41) in interpret
mode against the XLA lanes (``ops/gqa.py``) and a host oracle: the decode
kernel with and without a window's ``starts``, at 7 query heads a K/V
head and at 1 (the ungrouped kernel body with ``starts``), float32 and
bfloat16 pools; the prefill kernel mapping query head to K/V head by its
block index, causal and under a window; and that a call without either
traces the kernel it traced before this PR."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.ops import gqa, kernels, mla

RNG = np.random.default_rng(41)
LANES, PAGE, TABLE, LAYERS, PAGES = 5, 4, 6, 3, 40
LENGTHS = np.array([0, 3, 9, 24, 17], np.int32)
STARTS = np.array([0, 1, 4, 13, 10], np.int32)


def _case(heads, kv_heads, hd, dtype):
    q = jnp.asarray(RNG.standard_normal((LANES, heads, hd)), dtype)
    pk, pv = (jnp.asarray(RNG.standard_normal((LAYERS, PAGES, PAGE, kv_heads * hd)), dtype)
              for _ in "kv")
    tables = jnp.asarray(RNG.integers(1, PAGES, (LANES, TABLE)), jnp.int32)
    return q, pk, pv, tables


def _gather_lane(q, pk, pv, tables, layer, starts, kv_heads):
    hd = q.shape[-1]
    rows_k, rows_v = pk[layer][tables], pv[layer][tables]
    at = jnp.arange(TABLE * PAGE)[None, :]
    valid = at < jnp.asarray(LENGTHS)[:, None]
    if starts is not None:
        valid &= at >= jnp.asarray(starts)[:, None]
    return gqa.ctx_state(q, rows_k.reshape(LANES, -1, kv_heads, hd),
                         rows_v.reshape(LANES, -1, kv_heads, hd), valid)


def _attended(state):
    acc, _m, l = (np.asarray(a, np.float64) for a in state)
    return np.where(l[..., None] > 0, acc / np.where(l > 0, l, 1)[..., None], 0.0)


@pytest.mark.parametrize("heads, kv_heads", [(28, 4), (4, 4), (4, 2)])
@pytest.mark.parametrize("windowed", [False, True])
@pytest.mark.parametrize("dtype, atol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)])
def test_the_page_loop_against_the_gather_lane(heads, kv_heads, windowed, dtype, atol):
    q, pk, pv, tables = _case(heads, kv_heads, 16, dtype)
    starts = STARTS if windowed else None
    got = kernels.paged_attention_decode(
        q, pk, pv, tables, jnp.asarray(LENGTHS), layer=1, page_size=PAGE,
        **({"starts": jnp.asarray(starts)} if windowed else {}))
    want = _gather_lane(q, pk, pv, tables, 1, starts, kv_heads)
    assert got[0].shape == (LANES, heads, 16)
    np.testing.assert_allclose(_attended(got), _attended(want), atol=atol, rtol=0)
    # an empty lane leaves the neutral state, and every live lane's
    # statistics are the gather lane's
    live = np.asarray(want[2]) > 0
    assert np.array_equal(np.asarray(got[2]) > 0, live)
    assert np.all(np.asarray(got[1])[~live] == -np.inf)
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got[1])[live], np.asarray(want[1])[live],
                                   atol=1e-5)
        # ... so the step's own row joins by the flash rule
        own_k, own_v = (jnp.asarray(RNG.standard_normal((LANES, 1, kv_heads, 16)), dtype)
                        for _ in "kv")
        own = gqa.ctx_state(q, own_k, own_v, jnp.ones((LANES, 1), bool))
        np.testing.assert_allclose(np.asarray(mla.merge(got, own)),
                                   np.asarray(mla.merge(want, own)), atol=1e-5)


def test_grouped_heads_refuse_the_other_lanes():
    q, pk, pv, tables = _case(4, 2, 16, jnp.float32)
    with pytest.raises(ValueError, match="native pool only"):
        kernels.paged_attention_decode(
            q, pk, pv, tables, jnp.asarray(LENGTHS), layer=0, page_size=PAGE,
            kv_scales=(jnp.ones((LAYERS, PAGES)), jnp.ones((LAYERS, PAGES))))
    with pytest.raises(ValueError, match="K/V heads"):
        kernels.paged_attention_decode(
            q[:, :3], pk, pv, tables, jnp.asarray(LENGTHS), layer=0, page_size=PAGE)


@pytest.mark.parametrize("window", [0, 8])
@pytest.mark.parametrize("heads, kv_heads", [(4, 2), (14, 2), (4, 4)])
def test_the_prefill_kernel_maps_heads_by_index(heads, kv_heads, window):
    b, seg, hd = 2, 40, 16
    q = RNG.standard_normal((b, seg, heads, hd)).astype(np.float32)
    k, v = (RNG.standard_normal((b, seg, kv_heads, hd)).astype(np.float32) for _ in "kv")
    got = kernels.causal_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 0.25,
                                   block_q=16, block_k=8, window=window)
    xla = gqa.segment_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 0.25,
                                jnp.float32, window=window)
    share = heads // kv_heads
    kk, vv = np.repeat(k, share, axis=2), np.repeat(v, share, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q, kk) * 0.25
    at = np.arange(seg)
    seen = at[None, :] <= at[:, None]
    if window:
        seen &= at[None, :] > at[:, None] - window
    s = np.where(seen, s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", w / w.sum(-1, keepdims=True), vv)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(xla), want, atol=2e-6, rtol=0)


def test_the_xla_segment_runs_a_block_of_queries_at_a_time():
    b, seg, heads, kv_heads, hd = 1, 2 * gqa.QUERY_BLOCK, 4, 2, 8
    q = jnp.asarray(RNG.standard_normal((b, seg, heads, hd)), jnp.float32)
    k, v = (jnp.asarray(RNG.standard_normal((b, seg, kv_heads, hd)), jnp.float32)
            for _ in "kv")
    text = str(jax.make_jaxpr(
        lambda q, k, v: gqa.segment_attention(q, k, v, 0.3, jnp.float32, window=100))(q, k, v))
    # no (heads, S, S) array: the widest score block is QUERY_BLOCK rows
    assert f"{seg},{seg}" not in text.replace(" ", "")
    whole = gqa.segment_attention(q[:, :gqa.QUERY_BLOCK], k[:, :gqa.QUERY_BLOCK],
                                  v[:, :gqa.QUERY_BLOCK], 0.3, jnp.float32, window=100)
    blocked = gqa.segment_attention(q, k, v, 0.3, jnp.float32, window=100)
    np.testing.assert_allclose(np.asarray(blocked[:, :gqa.QUERY_BLOCK]), np.asarray(whole),
                               atol=1e-6)


# the traced kernel of an ungrouped call without ``starts``, as the tree
# before PR 41 traced it (sha256 of ``str(jax.make_jaxpr(...))``): GPT-2's
# and OLMoE's cells compile what they compiled.  A PR that changes the
# kernel for them on purpose measures their cells and replaces these.
UNGROUPED_JAXPRS = {
    "gpt2_large": ((32, 20, 64, 16, 36, 513), "a868e1d50c87b153"),
    "olmoe": ((32, 16, 128, 16, 8, 513), "f94c4116475735b4"),
}


def _traced(shape, **kw):
    lanes, heads, hd, table_w, layers, pages = shape

    def call(q, pk, pv, tables, lengths, starts):
        return kernels._stream_decode(
            q, pk, pv, tables, lengths, jnp.asarray(1, jnp.int32), None, None,
            **({"starts": starts} if kw.get("starts") else {}),
            quantized=False, fold=False, q_scale=1.0, interpret=False)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    width = kw.get("kv_heads", heads) * hd
    text = str(jax.make_jaxpr(call)(
        spec((lanes, heads, hd), jnp.bfloat16),
        spec((layers, pages, 64, width), jnp.bfloat16),
        spec((layers, pages, 64, width), jnp.bfloat16),
        spec((lanes, table_w), jnp.int32), spec((lanes,), jnp.int32),
        spec((lanes,), jnp.int32)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(UNGROUPED_JAXPRS))
def test_an_ungrouped_call_traces_the_kernel_it_traced(name):
    shape, digest = UNGROUPED_JAXPRS[name]
    assert _traced(shape) == digest
    # ... and a window's starts, or grouped heads, are structures of their own
    assert _traced(shape, starts=True) != digest
    assert _traced(shape, kv_heads=shape[1] // 4) != digest
