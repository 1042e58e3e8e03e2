"""Parity of what PR 38 added under the latent block, in interpret mode
on the CPU (arithmetic only: nothing about Mosaic or speed), each against
``ops/mla.py``'s plain XLA form:

* the latent decode kernel at both of ``dots3_note``'s shapes — 128
  heads on a 640-lane row of rank 512, 64 heads on a 1,152-lane row of
  rank 1,024 — with a start offset (a window's trailing edge), lanes
  that are empty, short of one page, and offset past their first step;
* ``causal_attention`` with a window: key blocks wholly behind a query
  block's window skipped, both edges masked;
* the rows a decode step weighs after selection (``step_mask``, then the
  same kernel under the mask as ``chosen``) against attention over every
  row under the reference's mask (PR 40: the page loop streams the
  lane's rows and the masked ones weigh exactly 0), and a call without
  a mask still traces the kernel it traced before there was one.
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


def _select(scores, own_score, length, topk):
    """The reference's chosen set of a step at position ``length``:
    ``(cached (length,), own)`` bool."""
    grid = np.full((length + 1, length + 1), -1.0, np.float32)
    grid[length] = np.concatenate([scores[:length], [own_score]])
    kept = ref.select({"index_topk": topk}, grid)[length]
    return kept[:length], bool(kept[length])


def test_chosen_rows_read_are_the_masked_attention():
    """A decode step over selected rows — ``step_mask``, the page loop
    through the block table under the mask, ``ctx_state`` on the step's
    own — is attention over EVERY cached row and the own under the
    reference's mask."""
    rng = np.random.default_rng(9)
    lanes, heads, rank, width, ps, pages, topk = 3, 4, 32, 128, 4, 12, 10
    pool = _pool(rng, 1, 64, ps, width, rank + 8)
    table = jnp.asarray(rng.permutation(np.arange(1, 37)).reshape(lanes, pages),
                        jnp.int32)
    lengths = jnp.asarray([33, 9, 14], jnp.int32)       # over topk | under | over
    q = jnp.asarray(rng.normal(size=(lanes, heads, width)).astype(np.float32)) * 0.3
    own = jnp.asarray(rng.normal(size=(lanes, 1, width)).astype(np.float32))
    scores = jnp.asarray(rng.integers(0, 5, size=(lanes, pages * ps)), jnp.float32)
    own_score = jnp.asarray([4.0, 0.0, 0.0])
    is_cached, own_in = mla.step_mask(scores, own_score, lengths, topk)
    got = mla.merge(
        kernels.latent_attention_decode(
            q, pool, table, lengths, layer=0, page_size=ps, rank=rank,
            chosen=is_cached),
        mla.ctx_state(q, own, own_in[:, None], rank))
    every = pool[0, table].reshape(lanes, pages * ps, width)
    for lane in range(lanes):
        t = int(lengths[lane])
        cached, own_kept = _select(
            np.asarray(scores[lane]), float(own_score[lane]), t, topk)
        kept = np.concatenate([cached, [own_kept]])
        keys = jnp.concatenate([every[lane, :t], own[lane]], axis=0)
        want = mla.merge(mla.ctx_state(
            q[lane:lane + 1], keys[None], jnp.asarray(kept)[None], rank))
        np.testing.assert_allclose(got[lane], want[0], rtol=1e-5, atol=1e-5)
        assert kept.sum() == min(t + 1, topk)
        np.testing.assert_array_equal(np.asarray(is_cached[lane, :t]), cached)
        assert not np.asarray(is_cached[lane, t:]).any()


# the lanes of ONE masked call, in the order the kernel's hand-on chain
# passes them: (name, cached length, what its scores look like)
MASKED_TOPK = 24
MASKED_LANES = (
    ("under_topk_keeps_every_row", 17),
    ("over_topk_own_chosen", 70),
    ("idle_lane", 0),
    ("negative_length_between_live_lanes", -3520),
    ("first_step_holds_no_chosen_row", 75),
    ("middle_step_holds_no_chosen_row", 78),
    ("ties_across_the_kth", 60),
    ("over_topk_own_not_chosen", 50),
)


@pytest.fixture(scope="module")
def masked_call():
    """``(heads, rank, width, step_tokens) -> (got, want, lanes)``: the
    kernel's state under a step's mask beside ``ctx_state``'s over the
    same mask, and what each lane's selection was (one call a shape and
    step, shared by the lanes' cases)."""
    memo = {}

    def call(heads, rank, width, step_tokens):
        key = (heads, rank, width, step_tokens)
        if key in memo:
            return memo[key]
        rng = np.random.default_rng(heads + step_tokens)
        ps, table_w, topk = 8, 10, MASKED_TOPK
        span = ps * table_w
        n = len(MASKED_LANES)
        pool = _pool(rng, 2, 96, ps, width, rank + 64)
        tables = jnp.asarray(
            rng.permutation(np.arange(1, 1 + n * table_w)).reshape(n, table_w),
            jnp.int32)
        lengths = np.asarray([length for _name, length in MASKED_LANES], np.int32)
        scores = rng.normal(size=(n, span)).astype(np.float32)
        scores[4, :32] -= 100.0          # a 32-token step's first holds none
        scores[5, 32:64] -= 100.0        # ... and its second
        scores[6] = rng.integers(0, 3, size=span)          # ties at the cut
        own_score = np.asarray([0, 50, 0, 0, 0, 0, 1, -50], np.float32)
        q = jnp.asarray(rng.normal(size=(n, heads, width)).astype(np.float32)) * 0.05
        q = q.at[..., rank + 64:].set(0.0)
        is_cached, own_in = mla.step_mask(
            jnp.asarray(scores), jnp.asarray(own_score), jnp.asarray(lengths), topk)
        old = kernels.LATENT_STEP_TOKENS
        kernels.LATENT_STEP_TOKENS = step_tokens
        try:
            got = kernels.latent_attention_decode(
                q, pool, tables, jnp.asarray(lengths), layer=1, page_size=ps,
                rank=rank, chosen=is_cached)
        finally:
            kernels.LATENT_STEP_TOKENS = old
        rows = pool[1, tables].reshape(n, span, width)
        want = mla.ctx_state(q, rows, is_cached, rank)
        memo[key] = (
            [np.asarray(g) for g in got], [np.asarray(w) for w in want],
            dict(scores=scores, own_score=own_score, lengths=lengths,
                 is_cached=np.asarray(is_cached), own_in=np.asarray(own_in)))
        return memo[key]

    return call


@pytest.mark.parametrize("lane", range(len(MASKED_LANES)),
                         ids=[name for name, _length in MASKED_LANES])
@pytest.mark.parametrize("heads,rank,width", [(128, 512, 640), (64, 1024, 1152)])
@pytest.mark.parametrize("step_tokens", [32, 1024])
def test_latent_kernel_under_a_selections_mask(masked_call, heads, rank, width,
                                               step_tokens, lane):
    """The page loop under ``chosen`` is ``ctx_state`` over the same
    mask, lane by lane — the flash state, not only its quotient: a
    masked row weighs exactly 0, a step of the loop that holds no chosen
    row leaves the state as it found it, an idle lane and one of
    negative length are empty lanes the hand-on passes over — and the
    mask is the reference's chosen set."""
    got, want, said = masked_call(heads, rank, width, step_tokens)
    name, length = MASKED_LANES[lane]
    for g, w, part in zip(got, want, ("acc", "m", "l")):
        np.testing.assert_allclose(g[lane], w[lane], rtol=2e-5, atol=2e-5,
                                   err_msg=f"{name}: {part}")
    is_cached, own_in = said["is_cached"][lane], said["own_in"][lane]
    if length <= 0:
        assert not is_cached.any()
        assert np.isneginf(got[1][lane]).all() and not got[2][lane].any()
        assert not got[0][lane].any()
        return
    cached, own_kept = _select(said["scores"][lane],
                               float(said["own_score"][lane]), length, MASKED_TOPK)
    np.testing.assert_array_equal(is_cached[:length], cached)
    assert not is_cached[length:].any() and bool(own_in) == own_kept
    assert is_cached.sum() + own_in == min(length + 1, MASKED_TOPK)
    assert np.isfinite(got[1][lane]).all() and (got[2][lane] > 0).all()
    if name.startswith("under_topk"):
        assert is_cached[:length].all() and own_in
    if name.startswith("first_step"):
        assert not is_cached[:32].any()
    if name.startswith("middle_step"):
        assert not is_cached[32:64].any() and is_cached[:32].any()
    if name.startswith("ties"):
        kth = np.sort(said["scores"][lane][:length])[::-1][MASKED_TOPK - 1]
        tied = said["scores"][lane][:length] == kth
        # some of the tied are in, some out: the lower positions
        assert 0 < is_cached[:length][tied].sum() < tied.sum()
        inside = np.nonzero(tied & is_cached[:length])[0]
        outside = np.nonzero(tied & ~is_cached[:length])[0]
        assert inside.max() < outside.min()
    if name.endswith("own_chosen"):
        assert own_in
    if name.endswith("own_not_chosen"):
        assert not own_in


# the traced kernel of a call WITHOUT a mask, as the tree before PR 40
# traced it (sha256 of ``str(jax.make_jaxpr(...))``, which names no
# source line): the configurations that never hand one over compile what
# they compiled.  A PR that changes the kernel for them on purpose
# measures their cells and replaces these.
UNMASKED_JAXPRS = {
    "gigachat": ((64, 64, 640, 512, 32, 6, 8193, False), "1509cbe316d45190"),
    "longcat": ((128, 64, 640, 512, 48, 8, 6145, False), "7e7d08bbee6bd068"),
    "dots3_window": ((128, 64, 1152, 1024, 9, 3, 1281, True), "36d50badd1dc7822"),
    "dots3_dense_branch": ((64, 128, 640, 512, 112, 3, 14337, False),
                           "7f62b58c41fd4897"),
}


def _traced(shape, chosen=False):
    import hashlib

    lanes, heads, width, rank, table_w, layers, pages, offset = shape

    def call(q, pool, tables, lengths, starts, mask):
        return kernels._latent_decode(
            q, pool, tables, lengths, jnp.asarray(1, jnp.int32),
            **({"starts": starts} if offset else {}),
            **({"chosen": mask} if chosen else {}),
            rank=rank, step_tokens=kernels.LATENT_STEP_TOKENS, interpret=False)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    text = str(jax.make_jaxpr(call)(
        spec((lanes, heads, width), jnp.bfloat16),
        spec((layers, pages, 64, width), jnp.bfloat16),
        spec((lanes, table_w), jnp.int32), spec((lanes,), jnp.int32),
        spec((lanes,), jnp.int32), spec((lanes, table_w * 64), jnp.int32)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(UNMASKED_JAXPRS))
def test_a_call_without_a_mask_traces_the_kernel_it_traced(name):
    shape, digest = UNMASKED_JAXPRS[name]
    assert _traced(shape) == digest
    # ... and the mask is a structure of its own
    assert _traced(shape, chosen=True) != digest
