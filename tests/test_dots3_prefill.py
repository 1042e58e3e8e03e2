"""``dots3_note``'s selection rule and its block off the engine, its
from-zero prefill under the selection's mask (PR 43), the reference's
tail, the share and the component's front door (PR 38; parts of
``test_dots3_paged.py`` until PR 44 split it, whose sizes, prompts and
tolerances these cases take).  Every engine here is a case's
own: the fused kernel's blocks are patched before it is built."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paged_harness as harness
from seldon_core_tpu.models import paged
from seldon_core_tpu.models.paged import StreamingLM
from seldon_core_tpu.models.spec import init_params
from seldon_core_tpu.ops import mla
from test_dots3_paged import (
    BF16_ATOL, BF16_SEED, MAX_LEN, MODEL, PAGE, PROMPTS, SIZES, SPEC, TOPK, ref,
    own_engine,  # noqa: F401  (the fixture: a case's own engines, closed after it)
)


def _sorted_kth_mask(scores, allowed, k):
    """``kth_mask`` as it stood until PR 55, the oracle of its rule: a
    ``top_k`` for its last value, ties to the lower index by a running
    sum."""
    if scores.shape[-1] <= k:
        return allowed
    s = jnp.where(allowed, scores, -jnp.inf)
    kth = jax.lax.top_k(s, k)[0][..., -1:]      # -inf: fewer than k allowed
    above = s > kth
    tie = (s == kth) & allowed
    room = k - above.sum(axis=-1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, axis=-1) <= room))


KTH_CASES = (
    "random", "a_third_tie", "zeros_of_both_signs", "few_none_exactly_k",
    "k_is_one", "k_is_all_but_one", "no_more_columns_than_k",
    "a_prefill_s_block", "extremes")


def _kth_case(case, seed):
    """``(scores, allowed, k)`` of one named case on one seed."""
    rng = np.random.default_rng(100 + seed)
    rows, width, k = 8, 200, 48
    if case == "a_prefill_s_block":
        # (B, 128 queries, L) under the causal triangle, few distinct values
        batch, bq, width, k = 2, 128, 256, 100
        scores = rng.integers(0, 9, size=(batch, bq, width)).astype(np.float32) / 4
        first = width - bq
        allowed = np.broadcast_to(
            np.arange(width)[None, :] <= first + np.arange(bq)[:, None],
            scores.shape)
        return jnp.asarray(scores), jnp.asarray(allowed), k
    scores = rng.standard_normal((rows, width)).astype(np.float32)
    allowed = rng.random((rows, width)) < 0.7
    if case == "a_third_tie":
        scores = np.round(scores * 1.5) / 1.5
    elif case == "zeros_of_both_signs":
        # a ReLU-weighted sum's row: most of it zero, of either sign
        scores = np.maximum(scores, 0.0) * rng.choice([1.0, -1.0], size=scores.shape)
        scores = np.where(rng.random(scores.shape) < 0.5, scores, 0.0).astype(np.float32)
        scores[:, ::3] = 0.0
        scores[:, 1::6] = -0.0
        allowed = rng.random((rows, width)) < 0.9
    elif case == "few_none_exactly_k":
        scores = np.round(scores * 2) / 2
        allowed[0] = False
        allowed[1] = False
        allowed[1, rng.permutation(width)[:k]] = True        # exactly k
        allowed[2] = False
        allowed[2, rng.permutation(width)[:k - 1]] = True    # one short
        allowed[3] = False
        allowed[3, rng.permutation(width)[:k + 1]] = True    # one over
        allowed[4] = True
        scores[5] = 1.25                                     # one score a row
    elif case == "k_is_one":
        k = 1
        scores[:4] = np.round(scores[:4])
    elif case == "k_is_all_but_one":
        k = width - 1
        scores[:4] = np.round(scores[:4])
    elif case == "no_more_columns_than_k":
        k = width + seed                                     # C == k, C < k
    elif case == "extremes":
        # infinities among the allowed, the largest and smallest floats,
        # subnormals of both signs
        scores = np.round(scores * 2) / 2
        pick = rng.integers(0, 8, size=scores.shape)
        for n, value in enumerate((np.inf, -np.inf, 3.4028235e38, -3.4028235e38,
                                   1e-45, -1e-45)):
            scores = np.where(pick == n, np.float32(value), scores)
        scores[0] = -np.inf
        scores[1] = np.inf
    return jnp.asarray(scores), jnp.asarray(allowed), k


class TestSelection:
    def test_chosen_set_is_the_references_at_every_step(self):
        """``step_mask`` (a decode step: cached scores and the own) and
        ``kth_mask`` (a prefill's rows) keep exactly the positions the
        reference's stable sort keeps, ties included, at every length
        from one to past ``topk``."""
        rng = np.random.default_rng(2)
        n, topk = 40, 12
        # few distinct values: ties at the cut are the rule
        scores = rng.integers(0, 6, size=(n, n)).astype(np.float32)
        want = ref.select({"index_topk": topk}, scores)
        causal = np.tril(np.ones((n, n), bool))
        got = np.asarray(mla.kth_mask(jnp.asarray(scores), jnp.asarray(causal), topk))
        np.testing.assert_array_equal(got, want)
        for t in range(n):
            cached = np.where(np.arange(n) < t, scores[t], 7.0)  # junk past the length
            is_cached, own = mla.step_mask(
                jnp.asarray(cached)[None], jnp.asarray(scores[t, t:t + 1]),
                jnp.asarray([t]), topk)
            chosen = set(np.nonzero(np.asarray(is_cached[0]))[0].tolist()) | (
                {t} if bool(own[0]) else set())
            assert chosen == set(np.nonzero(want[t])[0].tolist()), t

    @pytest.mark.parametrize("case", KTH_CASES)
    def test_the_descent_s_mask_is_the_sort_s_bit_for_bit(self, case):
        """``kth_mask`` finds its threshold by counting (PR 55) and hands
        back what the sort-and-``cumsum`` rule, kept here as the oracle,
        hands back — every entry of every row, on three seeds a case."""
        for seed in range(3):
            scores, allowed, k = _kth_case(case, seed)
            want = np.asarray(_sorted_kth_mask(scores, allowed, k))
            got = np.asarray(mla.kth_mask(scores, allowed, k))
            assert got.dtype == want.dtype and got.shape == want.shape
            np.testing.assert_array_equal(got, want, err_msg=f"{case} seed {seed}")

    def test_a_step_s_own_column_is_chosen_as_the_sort_chooses_it(self):
        """``step_mask`` puts the step's own score in column ``lengths``
        and allows the columns up to it: the same cached rows and the
        same verdict on the own as the oracle's mask of that very row,
        at lengths under, at and over ``topk``, junk past the length."""
        rng = np.random.default_rng(11)
        span, topk = 96, 24
        lengths = np.asarray([0, 5, topk - 1, topk, topk + 1, 60, span - 1], np.int32)
        cached = np.round(rng.standard_normal((len(lengths), span)) * 2).astype(
            np.float32) / 2
        own = cached[np.arange(len(lengths)), (lengths * 7) % span]  # ties with a cached row
        is_cached, own_in = mla.step_mask(
            jnp.asarray(cached), jnp.asarray(own), jnp.asarray(lengths), topk)
        at = np.arange(span)[None, :]
        row = np.where(at == lengths[:, None], own[:, None], cached)
        want = np.asarray(_sorted_kth_mask(
            jnp.asarray(row), jnp.asarray(at <= lengths[:, None]), topk))
        np.testing.assert_array_equal(
            np.asarray(is_cached), want & (at < lengths[:, None]))
        np.testing.assert_array_equal(
            np.asarray(own_in), want[np.arange(len(lengths)), lengths])

    def test_the_kernel_lane_and_the_one_layer_lane_are_one_block(self, monkeypatch):
        """A full layer's decode step that selects, called as the kernel
        lane calls it (the whole pools and the layer's place: the page
        loop under the mask) and as every other lane does (the layer's
        own pools: a gather, ``ctx_state`` under the same mask): the same
        stream out, the same rows to write, the same ``int32`` account —
        keys scored and rows moved the lengths, rows read the chosen
        set's cached members."""
        monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "force")
        from seldon_core_tpu.models.paged.blocks import PagedTransformerBlock

        block = PagedTransformerBlock(
            num_heads=SIZES["num_heads"], dtype=jnp.float32, spec=SPEC,
            routed_layer=False, kind=SPEC.attn_kind(0, SIZES["num_heads"]))
        rng = np.random.default_rng(5)
        d, pages, table_w = MODEL["hidden_size"], 64, 12
        kind = block.kind
        lengths = jnp.asarray([40, 20, 9, 31], jnp.int32)  # over | over | under | idle
        counted = jnp.asarray([[True], [True], [True], [False]])
        lanes = len(lengths)
        x = jnp.asarray(rng.normal(size=(lanes, 1, d)).astype(np.float32))
        pools = tuple(
            jnp.asarray(rng.normal(size=(2, pages, PAGE, w)).astype(np.float32))
            for w in (kind.lanes, 128))
        tables = (jnp.asarray(
            rng.permutation(np.arange(1, pages))[:lanes * table_w].reshape(
                lanes, table_w), jnp.int32),)
        assert table_w * PAGE > TOPK
        args = dict(positions=lengths[:, None], token_mask=counted, window=None)
        params = block.init(jax.random.key(1), x, pools, None, tables, lengths,
                            layer=1, **args)
        whole = block.apply(params, x, pools, None, tables, lengths, layer=1, **args)
        alone = block.apply(params, x, tuple(p[1] for p in pools), None, tables,
                            lengths, layer=None, **args)
        np.testing.assert_allclose(whole[0], alone[0], atol=2e-5, rtol=0)
        for a, b in zip(whole[1][1:], alone[1][1:]):
            np.testing.assert_array_equal(a, b)
        read, read_alone = np.asarray(whole[-1]), np.asarray(alone[-1])
        assert read.dtype == np.int32 and read.shape == (3,)
        np.testing.assert_array_equal(read, read_alone)
        live = int(lengths[:3].sum())
        assert read[0] == read[2] == live
        # each selecting lane reads topk rows, or one fewer with its own
        assert 2 * (TOPK - 1) + 9 <= read[1] <= 2 * TOPK + 9 < live


def _prefill_kernels(eng, bucket):
    """The names of the ``prefill_*`` kernels the from-zero program of
    ``bucket`` traces, in the layers' order."""
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    pages = eng._pages_pow2(-(-bucket // PAGE))
    program = eng._build_prefill(bucket, 1).__wrapped__
    calls = harness.pallas_calls(
        lambda *args: program(*args, window=(i32(1, eng.cache.window_pages), i32(1))),
        eng.params, *eng._kv_args(), i32(1, bucket), i32(1), i32(1, pages))
    return [name for name, _shapes in calls if name.startswith("prefill_")]


def _prefill_logits(eng, prompt):
    """The from-zero program of the prompt's bucket on ``prompt`` alone:
    its last position's logits (the rows it writes go to the trash
    page: a from-zero prefill attends its own segment)."""
    bucket = next(b for b in eng.prompt_buckets if b >= len(prompt))
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :len(prompt)] = prompt
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    last, pk, pv, *_hist = eng._build_prefill(bucket, 1)(
        eng.params, *eng._kv_args(), jnp.asarray(tokens),
        jnp.asarray([len(prompt)], jnp.int32),
        i32(1, eng._pages_pow2(-(-bucket // PAGE))),
        window=(i32(1, eng.cache.window_pages), i32(1)))
    eng.cache.store(pk, pv)
    return np.asarray(last)[0]


class TestIndexedPrefill:
    """PR 43: a from-zero prefill's indexed layers attend in the fused
    causal kernel under the selection's mask where the rule says so at
    their widths (layers: full, window, window, full; ``index_topk``
    16, buckets 16 / 32 / 64)."""

    def test_the_kernel_lanes_logits_are_the_xla_lanes(self, monkeypatch, own_engine):
        """The same weights on the kernel lane, the attention of every
        from-zero prefill in the kernel (blocks of 16) against XLA's (the
        buckets lie under the shipped query block): the prefill
        program's logits agree within the file's bf16 tolerance, and
        each holds the float32 reference to it."""
        prompts = PROMPTS[::2]             # the buckets past index_topk
        # (the XLA engine builds and traces under the shipped blocks)
        xla, params = own_engine("kernel", jnp.bfloat16, seed=BF16_SEED)
        assert set(xla.lane_report()["prefill_attention"].values()) == {"xla"}
        assert _prefill_kernels(xla, 64) == []
        want_xla = [_prefill_logits(xla, p) for p in prompts]
        harness.fused_here(monkeypatch)
        fused, _ = own_engine("kernel", jnp.bfloat16, seed=BF16_SEED)
        assert set(fused.lane_report()["prefill_attention"].values()) == {"fused"}
        assert _prefill_kernels(fused, 64).count("prefill_chosen_attention") == 2
        for prompt, xla_row in zip(prompts, want_xla):
            got = _prefill_logits(fused, prompt)
            np.testing.assert_allclose(got, xla_row, atol=BF16_ATOL, rtol=0)
            want = np.asarray(ref.logits(params, MODEL, prompt))[-1]
            np.testing.assert_allclose(got, want, atol=BF16_ATOL, rtol=0)

    @pytest.mark.parametrize("block", [16, 32])
    def test_the_report_is_what_each_program_traced(self, monkeypatch, own_engine, block):
        """``lane_report()["prefill_attention"]`` says a bucket each what
        the window layers (``b<bucket>``) and the indexed layers
        (``b<bucket>_indexed``) attend with, and the programs ask the
        same rule as they trace: under the mask past ``index_topk``
        positions, the plain causal call up to it, nothing of the
        kernel's under a query block."""
        harness.fused_here(monkeypatch, block)
        eng, _ = own_engine("kernel", jnp.bfloat16)
        report = eng.lane_report()["prefill_attention"]
        assert set(report) == {f"b{b}{tag}" for b in eng.prompt_buckets
                               for tag in ("", "_indexed")}
        for bucket in eng.prompt_buckets:
            want = "fused" if bucket >= block else "xla"
            assert report[f"b{bucket}"] == report[f"b{bucket}_indexed"] == want
            full = ("prefill_chosen_attention" if bucket > TOPK
                    else "prefill_causal_attention")
            assert _prefill_kernels(eng, bucket) == (
                [full, "prefill_window_attention", "prefill_window_attention", full]
                if want == "fused" else [])

    @pytest.mark.parametrize("lane,dtype", [
        ("kernel", jnp.float32), ("gather", jnp.float32), ("gather", jnp.bfloat16)])
    def test_float32_and_the_gather_lane_keep_the_xla_form(
            self, monkeypatch, own_engine, lane, dtype):
        """An exactness engine and ``SELDON_TPU_PAGED_KERNEL=0`` (one
        numeric regime; a mesh is refused for a latent pool altogether,
        and the rule answers a mesh's ``kernel_lane=False`` like the
        knob's)."""
        harness.fused_here(monkeypatch)
        eng, _ = own_engine(lane, dtype)
        assert set(eng.lane_report()["prefill_attention"].values()) == {"xla"}
        assert _prefill_kernels(eng, 64) == []
        stream = eng.submit(np.asarray(PROMPTS[0], np.int32), max_new_tokens=1)
        eng.run()
        assert stream.error is None
        stats = eng.engine_stats()
        assert stats["prefill_padded_tokens"] > 0
        assert stats["prefill_indexed_fused_positions"] == 0
        assert stats["prefill_fused_positions"] == 0

    def test_the_counter_is_what_the_annotation_says(self, monkeypatch, own_engine):
        """``prefill_indexed_fused_positions`` rises by the padded
        positions of the calls whose ``seldon.wave.prefill`` says
        ``indexed_fused``: every call of a bucket of a query block or
        more, none of the bucket under it."""
        harness.fused_here(monkeypatch, 32)
        eng, _ = own_engine("kernel", jnp.bfloat16)
        said = []
        begin = eng._seam.begin_prefill

        def spy(**stats):
            said.append(stats)
            return begin(**stats)

        monkeypatch.setattr(eng._seam, "begin_prefill", spy)
        streams = [eng.submit(np.asarray(p, np.int32), max_new_tokens=1)
                   for p in PROMPTS]
        eng.run()
        assert all(s.error is None for s in streams)
        assert sorted(c["bucket"] for c in said) == [16, 32, 64]
        for call in said:
            assert call["indexed_fused"] == call["fused"] == int(call["bucket"] >= 32)
        stats = eng.engine_stats()
        assert stats["prefill_padded_tokens"] == sum(c["padded"] for c in said)
        assert stats["prefill_indexed_fused_positions"] == sum(
            c["padded"] for c in said if c["indexed_fused"]) == 32 + 64
        assert stats["prefill_fused_positions"] == 32 + 64


class TestReferenceTail:
    @pytest.mark.parametrize("tail,variant", [(1, ""), (7, ""), (7, "window_short")])
    def test_the_last_layer_queried_at_the_tail_alone_gives_the_same_rows(
            self, tail, variant):
        """``logits(tail=)`` leaves the last layer's queries, attention
        and FFN to the last rows (the cell's reference pass: a sixth less
        work): the same logits there, past ``index_topk`` and the window."""
        params = init_params(SPEC, SIZES, 5, dtype=jnp.float32)
        seq = np.random.default_rng(7).integers(0, 64, size=40).tolist()
        whole = np.asarray(ref.logits(params, MODEL, seq, variant=variant))
        got = np.asarray(ref.logits(params, MODEL, seq, tail=tail, variant=variant))
        assert got.shape == (tail, 64)
        np.testing.assert_allclose(got, whole[-tail:], atol=2e-6, rtol=0)


class TestShares:
    def test_shares_add_up_to_the_uncut_layer(self):
        """The routed parts of the four replicas that share a layer and
        the shared expert counted once are the layer with every expert
        held."""
        whole = dict(MODEL, n_routed_experts=8, expert_offset=0)
        spec, sizes = ref.spec_and_config(whole)
        params = init_params(spec, sizes, 5, dtype=jnp.float32)
        x = jnp.asarray(np.random.default_rng(1).normal(size=(19, 32)), jnp.float32)
        pos = jnp.arange(19)
        with jax.default_matmul_precision("highest"):
            p = params["block_1"]
            full, parts = ref.layer(p, whole, 1, x, pos)
            total = 0.0
            for r in range(4):
                share = dict(p, **{k: p[k][2 * r:2 * r + 2] for k in (
                    "experts_gate", "experts_up", "experts_down")})
                _x, mine = ref.layer(share, whole, 1, x, pos, held=(2 * r, 2))
                total = total + mine["routed"]
                np.testing.assert_allclose(mine["shared"], parts["shared"], atol=1e-6)
        np.testing.assert_allclose(total, parts["routed"], atol=1e-5)
        assert float(jnp.abs(parts["routed"]).max()) > 1e-3


class TestServed:
    def test_streaming_lm_serves_the_arch_on_the_normal_path(self, monkeypatch):
        """``arch="dots3_note"`` through ``STREAMING_LM``'s own
        constructor, its JSON sizes and its prompt buckets."""
        monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "0")
        import json

        spec_sizes = {
            k: getattr(SPEC, k) for k in (
                "num_experts", "experts_per_tok", "expert_width", "dense_width",
                "experts_held", "expert_offset", "q_rank", "kv_rank", "nope_dim",
                "rope_dim", "v_dim", "window", "win_heads", "win_q_rank",
                "win_kv_rank", "win_nope_dim", "win_rope_dim", "win_v_dim",
                "index_heads", "index_dim", "index_topk")}
        spec_sizes["layer_kinds"] = list(SPEC.layer_kinds)
        lm = StreamingLM(
            arch="dots3_note", arch_sizes=json.dumps(spec_sizes), **SIZES,
            max_len=MAX_LEN, page_size=PAGE, max_slots=2, steps_per_call=4,
            max_new_tokens=6, seed=3, prompt_buckets="[8, 24, 64]")
        assert lm.spec == SPEC
        lm.load()
        try:
            assert lm.engine.prompt_buckets == [8, 24, 64]
            out = lm.predict(np.asarray([PROMPTS[0]], np.int32), None)
            assert np.asarray(out).shape == (1, 6)
            assert lm.engine.lane_report()["cache_kinds"][2]["name"] == "window"
        finally:
            lm.shutdown()
