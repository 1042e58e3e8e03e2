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
  a mask still traces the kernel it traced before there was one;
* ``causal_attention(chosen=)`` (PR 43: a prefill's rows attend their
  chosen sets in the fused causal kernel) against a float32 softmax
  under the reference's mask at the full layers' head widths, and
  ``causal_attention`` without a mask — plain, windowed, grouped —
  tracing what it traced before there was one;
* ``index_scores_decode`` (PR 51: a decode step's indexer scores its
  lane's cached keys in a page loop over the key pool) against
  ``index_scores`` over the keys gathered through the table, at the
  cell's widths and shapes, and a spec without an indexer tracing the
  chunk it traced.
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


# ---- a prefill's rows under their chosen sets (PR 43) -------------------

D_QK, D_V = 192, 128       # a full layer's head: 128 nope + 64 rope | values

# name -> (prompts, length, topk, query block, key block): what the
# selection looks like is made in ``_chosen_case``
CHOSEN_CASES = {
    "a_real_selection_over_more_than_topk": (1, 256, 96, 64, 64),
    "no_chosen_key_in_the_first_visited_block": (1, 256, 48, 64, 64),
    "ties_at_the_cut": (1, 192, 40, 64, 32),
    "a_length_that_is_no_multiple_of_the_query_block": (1, 200, 64, 64, 64),
    "two_prompts_two_masks": (2, 128, 32, 64, 64),
    "a_key_block_of_two_columns_of_lanes": (1, 512, 160, 256, 256),
}


def _chosen_case(name):
    """``(q, k, v, scores, mask, (bq, bk), topk)`` of a case: float32
    operands, the indexer's scores and ``kth_mask``'s chosen sets."""
    b, seg, topk, bq, bk = CHOSEN_CASES[name]
    rng = np.random.default_rng(len(name))
    q, k = (rng.normal(size=(b, seg, 2, D_QK)).astype(np.float32) for _ in range(2))
    v = rng.normal(size=(b, seg, 2, D_V)).astype(np.float32)
    scores = rng.normal(size=(b, seg, seg)).astype(np.float32)
    if name.startswith("no_chosen_key"):
        scores[:, :, :bk] -= 100.0   # the first block's keys: every row's last
    if name.startswith("ties"):
        scores = rng.integers(0, 3, size=(b, seg, seg)).astype(np.float32)
    at = np.arange(seg)
    causal = np.broadcast_to(at[None, :] <= at[:, None], (b, seg, seg))
    mask = mla.kth_mask(jnp.asarray(scores), jnp.asarray(causal), topk)
    return q, k, v, scores, np.asarray(mask), (bq, bk), topk


def _masked_softmax(q, k, v, mask, scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    return np.asarray(jnp.einsum("bhqk,bkhd->bqhd", p, v,
                                 precision=jax.lax.Precision.HIGHEST))


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 3e-5), (jnp.bfloat16, 0.03)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(CHOSEN_CASES))
def test_causal_attention_under_a_selections_mask(name, dtype, atol):
    """The fused causal kernel under ``chosen`` is the float32 softmax
    under the same mask — the operands as they are (bf16: scores and
    softmax float32, the weights rounded for ``p @ v``; seen 0.012) —
    and the mask is the reference's chosen set, row for row."""
    q, k, v, scores, mask, (bq, bk), topk = _chosen_case(name)
    b, seg = mask.shape[:2]
    scale = D_QK ** -0.5
    ops = [jnp.asarray(x, dtype) for x in (q, k, v)]
    got = kernels.causal_attention(*ops, scale, block_q=bq, block_k=bk,
                                   chosen=jnp.asarray(mask))
    assert got.shape == (b, seg, 2, D_V) and got.dtype == dtype
    want = _masked_softmax(*(x.astype(jnp.float32) for x in ops), mask, scale)
    assert np.abs(np.asarray(got, np.float32) - want).max() < atol
    # the chosen set: the reference's, and topk of a row past topk
    for row in range(b):
        np.testing.assert_array_equal(
            mask[row], ref.select({"index_topk": topk}, scores[row]))
    assert (mask.sum(-1) == np.minimum(np.arange(seg) + 1, topk)).all()
    # ... and it is the whole difference: the plain causal call is not this
    plain = kernels.causal_attention(*ops, scale, block_q=bq, block_k=bk)
    assert np.abs(np.asarray(plain, np.float32)[:, topk + 8:]
                  - want[:, topk + 8:]).max() > 0.05
    if name.startswith("no_chosen_key"):
        # rows past topk + a block hold no key of the loop's first block:
        # their running max is -inf after it, and the rescale is guarded
        late = slice(topk + bk, None)
        assert not mask[:, late, :bk].any() and mask[:, :bk, :bk].any()
        assert np.isfinite(np.asarray(got, np.float32)).all()
    if name.startswith("ties"):
        t = seg - 1
        kth = np.sort(scores[0, t])[::-1][topk - 1]
        tied = scores[0, t] == kth
        assert 0 < mask[0, t][tied].sum() < tied.sum()
        assert (np.nonzero(tied & mask[0, t])[0].max()
                < np.nonzero(tied & ~mask[0, t])[0].min())
    if name.startswith("two_prompts"):
        assert (mask[0] != mask[1]).any()
        swapped = kernels.causal_attention(
            *ops, scale, block_q=bq, block_k=bk, chosen=jnp.asarray(mask[::-1]))
        assert np.abs(np.asarray(swapped, np.float32) - want).max() > 0.05


def test_a_chosen_set_stands_alone_and_fits_its_blocks():
    q = jnp.zeros((1, 96, 2, 16), jnp.float32)
    mask = jnp.ones((1, 96, 96), bool)
    with pytest.raises(ValueError, match="chosen"):
        kernels.causal_attention(q, q, q, 1.0, block_q=32, block_k=32,
                                 chosen=mask, window=8)
    with pytest.raises(ValueError, match="chosen"):
        kernels.causal_attention(q, q, q, 1.0, block_q=32, block_k=32,
                                 chosen=mask[:, :64])
    # a key block over 128 keys holds whole columns of 128 and divides 4,096
    wide = jnp.zeros((1, 384, 1, 16), jnp.float32)
    with pytest.raises(ValueError, match="chosen"):
        kernels.causal_attention(wide, wide, wide, 1.0, block_q=384, block_k=384,
                                 chosen=jnp.ones((1, 384, 384), bool))


def _indexed_operands(seg, topk, dtype=jnp.float32, b=2, h=2, rank=32, seed=7):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32), dtype)

    n, r, vd, ih, idim = 16, 8, 8, 2, 16
    return dict(
        q_nope=draw(b, seg, h, n), q_rope=draw(b, seg, h, r), seg=draw(b, seg, 128),
        w_uk=draw(h, rank, n) * 0.2, w_uv=draw(h, rank, vd) * 0.2, scale=0.2,
        dtype=dtype, q_idx=draw(b, seg, ih, idim),
        w_idx=jnp.asarray(rng.normal(size=(b, seg, ih)), jnp.float32),
        k_idx=draw(b, seg, idim), index_scale=0.1, topk=topk)


def _kernel_names(fn, *args):
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"] if "name" in eqn.params
                              else eqn.params["name_and_src_info"].name,
                              len(eqn.invars)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("seg,topk,kernel", [
    (256, 64, ("prefill_chosen_attention", 4)),    # blocks of 128 queries select
    (200, 64, ("prefill_chosen_attention", 4)),    # no multiple of 128: at once
    (64, 64, ("prefill_causal_attention", 3)),     # L <= topk: no mask is built
    (48, 64, ("prefill_causal_attention", 3)),
], ids=["blocks_select", "one_block_selects", "at_topk_plain", "under_topk_plain"])
def test_indexed_attention_fused_is_its_xla_form(monkeypatch, seg, topk, kernel):
    """``indexed_attention(fused=True)`` — the selection as ever, the
    attention in the causal kernel under its mask — is the XLA lane's
    function of the same operands; while ``L <= topk`` the call is the
    plain causal one (three operands, no mask)."""
    monkeypatch.setattr(kernels, "CAUSAL_BLOCK_Q", 32)
    monkeypatch.setattr(kernels, "CAUSAL_BLOCK_K", 32)
    ops = _indexed_operands(seg, topk)
    xla = mla.indexed_attention(**ops)
    fused = mla.indexed_attention(**ops, fused=True)
    np.testing.assert_allclose(fused, xla, rtol=2e-4, atol=2e-4)
    assert _kernel_names(lambda: mla.indexed_attention(**ops, fused=True)) == [kernel]
    assert _kernel_names(lambda: mla.indexed_attention(**ops)) == []
    if seg > topk:
        # the selection matters: attention over every earlier row is not this
        dense = mla.indexed_attention(**dict(ops, topk=seg), fused=True)
        assert float(jnp.abs(dense - xla).max()) > 0.02


# the traced ``causal_attention`` of a call WITHOUT a chosen set, as the
# tree before PR 43 traced it (sha256 of ``str(jax.make_jaxpr(...))``):
# (prompts, length, heads, K/V heads, d_qk, d_v), window -> digest.  The
# configurations that hand no mask over compile what they compiled; a PR
# that changes the kernel for them on purpose measures their cells and
# replaces these.
UNCHOSEN_JAXPRS = {
    "gigachat": ((2, 2048, 64, 64, 192, 192), 0, "c2c9b0697b9f998b"),
    "longcat": ((4, 1024, 64, 64, 192, 128), 0, "43edf1551595801e"),
    "dots3_window": ((1, 4096, 64, 64, 256, 128), 513, "7bf7dd0d27f94337"),
    "smallthinker_grouped": ((1, 4096, 28, 4, 128, 128), 0, "5ea086b79502049f"),
    "smallthinker_grouped_window": ((1, 8192, 28, 4, 128, 128), 4096,
                                    "7b928f71d41cded1"),
}


def _traced_causal(monkeypatch, shape, window=0, chosen=False):
    import hashlib

    b, seg, h, kv_heads, d_qk, d_v = shape
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)

    def spec(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype)

    def call(q, k, v, *mask):
        return kernels.causal_attention(
            q, k, v, 0.07, **({"window": window} if window else {}),
            **({"chosen": mask[0]} if mask else {}))

    text = str(jax.make_jaxpr(call)(
        spec(b, seg, h, d_qk), spec(b, seg, kv_heads, d_qk),
        spec(b, seg, kv_heads, d_v),
        *([spec(b, seg, seg, dtype=jnp.bool_)] if chosen else [])))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(UNCHOSEN_JAXPRS))
def test_causal_attention_without_a_chosen_set_traces_what_it_traced(
        monkeypatch, name):
    shape, window, digest = UNCHOSEN_JAXPRS[name]
    assert _traced_causal(monkeypatch, shape, window) == digest
    if not window:
        # ... and the chosen set is a structure of its own
        assert _traced_causal(monkeypatch, shape, chosen=True) != digest


# ---- a decode step's indexer over the key pool (PR 51) -------------------

INDEX_HEADS, INDEX_DIM, INDEX_PS, INDEX_SCALE = 64, 128, 64, (64 * 128) ** -0.5
INDEX_TOPK = 2048


def _index_lengths(lanes, span, rng):
    """A length a lane, and which lanes hold each case: the edges first,
    every other lane ragged."""
    edges = {"empty": 0, "one_key": 1, "one_under_a_page_edge": 3 * INDEX_PS - 1,
             "at_a_page_edge": 3 * INDEX_PS, "one_over_a_page_edge": 3 * INDEX_PS + 1,
             "the_tables_whole_span": span}
    lengths = rng.integers(0, span + 1, size=lanes)
    where = {}
    for at, (name, n) in enumerate(edges.items()):
        # (between live lanes, and as the call's last lane: the hand-on
        # chain passes over an empty lane and ends on any)
        where[name] = [2 * at + 1, lanes - 1 - 2 * at]
        lengths[where[name]] = n
    where["ragged_across_lanes"] = [
        lane for lane in range(lanes) if not any(lane in v for v in where.values())]
    return lengths.astype(np.int32), where


INDEX_CASES = ("empty", "one_key", "one_under_a_page_edge", "at_a_page_edge",
               "one_over_a_page_edge", "the_tables_whole_span", "ragged_across_lanes")


@pytest.fixture(scope="module")
def index_call():
    """``(lanes, table pages) -> (got, want, lengths, where, masks)``: the
    kernel's scores beside ``index_scores`` over the gathered keys, and
    ``step_mask`` over both (one call a shape, shared by the cases)."""
    memo = {}

    def call(lanes, pages):
        if (lanes, pages) in memo:
            return memo[lanes, pages]
        rng = np.random.default_rng(lanes + pages)
        span = pages * INDEX_PS
        lengths, where = _index_lengths(lanes, span, rng)
        # (a key pool of 300 pages: a read shares pages across lanes freely)
        pool = jnp.asarray(rng.normal(size=(2, 300, INDEX_PS, INDEX_DIM)), jnp.bfloat16)
        tables = jnp.asarray(rng.integers(1, 300, size=(lanes, pages)), jnp.int32)
        q = jnp.asarray(rng.normal(size=(lanes, 1, INDEX_HEADS, INDEX_DIM)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(lanes, 1, INDEX_HEADS)), jnp.float32)
        got = kernels.index_scores_decode(
            q[:, 0], w[:, 0], pool, tables, jnp.asarray(lengths), layer=1,
            page_size=INDEX_PS, scale=INDEX_SCALE)
        assert got.shape == (lanes, pages, INDEX_PS) and got.dtype == jnp.float32
        got = got.reshape(lanes, span)
        want = jnp.concatenate([                 # (16 lanes' gathered keys at a time)
            mla.index_scores(
                q[at:at + 16], w[at:at + 16],
                pool[1, tables[at:at + 16]].reshape(16, span, INDEX_DIM),
                INDEX_SCALE)[:, 0]
            for at in range(0, lanes, 16)])
        own = jnp.asarray(rng.normal(size=lanes), jnp.float32)
        masks = [mla.step_mask(scores, own, jnp.asarray(lengths), INDEX_TOPK)
                 for scores in (got, want)]
        memo[lanes, pages] = (
            np.asarray(got), np.asarray(want), lengths, where,
            [[np.asarray(m) for m in pair] for pair in masks])
        return memo[lanes, pages]

    return call


@pytest.mark.parametrize("case", INDEX_CASES)
@pytest.mark.parametrize("lanes", [64, 128])
@pytest.mark.parametrize("pages", [64, 112])
def test_index_scores_over_the_key_pool(index_call, pages, lanes, case):
    """The page loop's scores are ``index_scores`` of the keys the table
    names, to the float32 rounding of a 64-term sum (same operands, same
    types; only the order of the sum over the heads may differ), 0.0 at
    and past a lane's length, and ``step_mask`` chooses the same set from
    either (seeded data: no ties)."""
    got, want, lengths, where, (mine, theirs) = index_call(lanes, pages)
    lanes_of = where[case]
    assert lanes_of
    for lane in lanes_of:
        n = int(lengths[lane])
        np.testing.assert_allclose(got[lane, :n], want[lane, :n], rtol=1e-5,
                                   atol=1e-5 * np.abs(want[lane]).max())
        assert not got[lane, n:].any()
        np.testing.assert_array_equal(mine[0][lane], theirs[0][lane])
        assert mine[1][lane] == theirs[1][lane]
        assert mine[0][lane].sum() + mine[1][lane] == min(n + 1, INDEX_TOPK)
    if case == "ragged_across_lanes":
        assert len({int(lengths[lane]) for lane in lanes_of}) > len(lanes_of) // 2
        assert any(lengths[lane] > INDEX_TOPK for lane in lanes_of)


def test_index_scores_refuses_a_pool_it_cannot_read():
    q = jnp.zeros((2, 4, 128), jnp.bfloat16)
    args = (jnp.zeros((2, 4), jnp.float32), jnp.zeros((3, 9, 8, 128), jnp.bfloat16),
            jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32))
    with pytest.raises(ValueError, match="page_size=4"):
        kernels.index_scores_decode(q, *args, layer=0, page_size=4, scale=1.0)
    with pytest.raises(ValueError, match="whole key pool"):
        kernels.index_scores_decode(q[..., :64], *args, layer=0, page_size=8, scale=1.0)


# a decode chunk of a spec WITHOUT an indexer (a tiny GigaChat on the
# kernel lane), as the tree before PR 51 lowered it (sha256 of the
# lowered text): only a kind with ``index_topk`` reaches the scoring, so
# every other configuration's chunk is the one it was.  A PR that changes
# that chunk on purpose measures those cells and replaces this.  (PR 57
# did: the router's group step by maxima and a count, ``ops/moe.py
# kept_groups``, the same gates and experts bit for bit.)
NO_INDEXER_CHUNK = "3261aa51d6465dbc"


def test_a_spec_without_an_indexer_traces_the_chunk_it_traced(monkeypatch):
    import hashlib

    import paged_harness as harness

    def chunk_text(model, lane="kernel"):
        spec, sizes = model[0].spec_and_config(model[1])
        eng, _params = harness.build(spec, sizes, lane, jnp.bfloat16, steps_per_call=2)
        try:
            with harness.tracing(eng):
                return eng.lower_chunk(2, ((2, 2), (2, 8))).as_text()
        finally:
            eng.close()

    text = chunk_text(harness.MODELS["gigachat"])
    assert "_index_decode" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == NO_INDEXER_CHUNK
    # ... and a spec with one scores in the kernel on this lane alone
    assert "_index_decode" in chunk_text(harness.MODELS["dots3"])
    assert "_index_decode" not in chunk_text(harness.MODELS["dots3"], "gather")
