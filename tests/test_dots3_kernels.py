"""Parity of what PR 38 added under the latent block, in interpret mode
on the CPU (arithmetic only: nothing about Mosaic or speed), each against
``ops/mla.py``'s plain XLA form:

* the latent decode kernel at both of ``dots3_note``'s shapes — 128
  heads on a 640-lane row of rank 512, 64 heads on a 1,152-lane row of
  rank 1,024 — with a start offset (a window's trailing edge), lanes
  that are empty, short of one page, and offset past their first step;
* ``causal_attention`` with a window: key blocks wholly behind a query
  block's window skipped, both edges masked;
* the rows a decode step reads after selection (``sparse_select``, a
  gather, ``ctx_state``) against attention over every row under the
  reference's mask.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.ops import kernels, mla

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))
from reference import dots3_note as ref  # noqa: E402


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "force")


def _pool(rng, layers, pages, ps, width, values):
    pool = rng.normal(size=(layers, pages, ps, width)).astype(np.float32)
    pool[..., values:] = 0.0  # the lanes that pad a row to whole tiles
    return jnp.asarray(pool)


@pytest.mark.parametrize("heads,rank,width", [(128, 512, 640), (64, 1024, 1152)])
@pytest.mark.parametrize("step_tokens", [32, 1024])
def test_latent_kernel_with_a_start_offset(monkeypatch, heads, rank, width,
                                           step_tokens):
    """Lanes: a window in its table's second step | from position zero |
    empty | a start in the first page | start == length - 1 | a length
    below zero between live lanes (an idle slot under a stale base: it is
    an empty lane, and the hand-on of the next lane's first pages passes
    over it)."""
    monkeypatch.setattr(kernels, "LATENT_STEP_TOKENS", step_tokens)
    rng = np.random.default_rng(heads)
    ps, table_w = 8, 10
    pool = _pool(rng, 2, 64, ps, width, rank + 64)
    tables = jnp.asarray(rng.permutation(np.arange(1, 61)).reshape(6, table_w),
                         jnp.int32)
    lengths = jnp.asarray([77, 40, 0, 13, -3520, 60], jnp.int32)
    starts = jnp.asarray([41, 0, 0, 5, 0, 59], jnp.int32)
    q = jnp.asarray(rng.normal(size=(6, heads, width)).astype(np.float32)) * 0.05
    q = q.at[..., rank + 64:].set(0.0)
    got = kernels.latent_attention_decode(
        q, pool, tables, lengths, layer=1, page_size=ps, rank=rank,
        starts=starts)
    rows = pool[1, tables].reshape(6, table_w * ps, width)
    at = jnp.arange(table_w * ps)[None, :]
    valid = (at >= starts[:, None]) & (at < lengths[:, None])
    want = mla.ctx_state(q, rows, valid, rank)
    for g, w, name in zip(got, want, ("acc", "m", "l")):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5, err_msg=name)
    # ... and without an offset the call is the one it was
    lengths = jnp.maximum(lengths, 0)  # (its lanes are never negative)
    plain = kernels.latent_attention_decode(
        q, pool, tables, lengths, layer=1, page_size=ps, rank=rank)
    want = mla.ctx_state(q, rows, at < lengths[:, None], rank)
    for g, w in zip(plain, want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window,block_q,block_k", [
    (37, 64, 32), (9, 64, 64), (129, 128, 128), (65, 128, 32)])
def test_windowed_causal_attention(window, block_q, block_k):
    rng = np.random.default_rng(window)
    b, seg, h, d_qk, d_v = 2, 300, 2, 32, 16
    q, k = (jnp.asarray(rng.normal(size=(b, seg, h, d_qk)).astype(np.float32))
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(b, seg, h, d_v)).astype(np.float32))
    scale = d_qk ** -0.5
    got = kernels.causal_attention(q, k, v, scale, block_q=block_q,
                                   block_k=block_k, window=window)
    at = np.arange(seg)
    seen = (at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - window)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    # the mask is the whole difference: an off-by-one window is not this
    p1 = jax.nn.softmax(jnp.where(
        ((at[None, :] <= at[:, None])
         & (at[None, :] > at[:, None] - window + 1))[None, None], s, -jnp.inf), -1)
    off = jnp.einsum("bhqk,bkhd->bqhd", p1, v)
    assert float(jnp.abs(got - off).max()) > 0.1


def test_naive_attention_window_is_the_kernels():
    """``naive_attention(window=)`` in XLA (the CPU and float32 lanes')
    and fused are one function of their operands."""
    rng = np.random.default_rng(4)
    b, seg, h, n, r, vd, rank = 1, 160, 2, 16, 8, 8, 32
    q_nope = jnp.asarray(rng.normal(size=(b, seg, h, n)).astype(np.float32))
    q_rope = jnp.asarray(rng.normal(size=(b, seg, h, r)).astype(np.float32))
    rows = jnp.asarray(rng.normal(size=(b, seg, 128)).astype(np.float32))
    w_uk = jnp.asarray(rng.normal(size=(h, rank, n)).astype(np.float32)) * 0.2
    w_uv = jnp.asarray(rng.normal(size=(h, rank, vd)).astype(np.float32)) * 0.2
    args = (q_nope, q_rope, None, jnp.zeros((b,), jnp.int32), rows, w_uk, w_uv,
            0.2, jnp.float32)
    xla = mla.naive_attention(*args, window=21)
    fused = mla.naive_attention(*args, window=21, fused=True)
    np.testing.assert_allclose(xla, fused, rtol=2e-2, atol=2e-2)
    assert float(jnp.abs(xla - mla.naive_attention(*args)).max()) > 0.05


def test_chosen_rows_read_are_the_masked_attention():
    """A decode step over selected rows — ``sparse_select``, a gather
    through the block table, ``ctx_state`` on the chosen rows and on the
    step's own — is attention over EVERY cached row and the own under the
    reference's mask."""
    rng = np.random.default_rng(9)
    lanes, heads, rank, width, ps, pages, topk = 3, 4, 32, 128, 4, 12, 10
    pool = _pool(rng, 1, 64, ps, width, rank + 8)[0]
    table = jnp.asarray(rng.permutation(np.arange(1, 37)).reshape(lanes, pages),
                        jnp.int32)
    lengths = jnp.asarray([33, 9, 14], jnp.int32)       # over topk | under | over
    q = jnp.asarray(rng.normal(size=(lanes, heads, width)).astype(np.float32)) * 0.3
    own = jnp.asarray(rng.normal(size=(lanes, 1, width)).astype(np.float32))
    scores = jnp.asarray(rng.integers(0, 5, size=(lanes, pages * ps)), jnp.float32)
    own_score = jnp.asarray([4.0, 0.0, 0.0])
    at, is_cached, own_in = mla.sparse_select(scores, own_score, lengths, topk)
    rows = pool[jnp.take_along_axis(table, at // ps, axis=1), at % ps]
    got = mla.merge(mla.ctx_state(q, rows, is_cached, rank),
                    mla.ctx_state(q, own, own_in[:, None], rank))
    every = pool[table].reshape(lanes, pages * ps, width)
    for lane in range(lanes):
        t = int(lengths[lane])
        row = np.concatenate([np.asarray(scores[lane, :t]), [float(own_score[lane])]])
        grid = np.full((t + 1, t + 1), -1.0, np.float32)
        grid[t] = row
        kept = ref.select({"index_topk": topk}, grid)[t]          # (t + 1,)
        keys = jnp.concatenate([every[lane, :t], own[lane]], axis=0)
        want = mla.merge(mla.ctx_state(
            q[lane:lane + 1], keys[None], jnp.asarray(kept)[None], rank))
        np.testing.assert_allclose(got[lane], want[0], rtol=1e-5, atol=1e-5)
        assert kept.sum() == min(t + 1, topk)
