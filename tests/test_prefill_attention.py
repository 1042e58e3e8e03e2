"""A prefill from position zero attends over its own segment alone
(PR 33): the fused causal kernel (``ops/kernels.py causal_attention``,
here under the Pallas interpreter) against a plain float32 softmax, the
rule that chooses it (``prefill_attention_impl``), and — for small
GPT-2, OLMoE, GigaChat-like and LongCat-like engines — the from-zero
prefill program against the form the parent commit traced (the slot's
table gathered, scored and masked out by ``lengths = 0``: what the
cached-suffix program computes at ``cached_lens = 0``) and against a
prompt resumed at a page boundary.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paged_harness as harness
from paged_harness import PAGE, PROMPT, fused_here
from seldon_core_tpu.ops import kernels


def plain_causal(q, k, v, scale):
    """Float32 causal softmax attention, ``(B, L, h, d)`` operands."""
    q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    s = np.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = np.where(np.tril(np.ones(s.shape[-2:], bool)), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bkhd->bqhd", w / w.sum(-1, keepdims=True), v)


def operands(seg, d_qk, d_v, dtype, batch=2, heads=2, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(batch, seg, heads, d)), dtype)
                 for d in (d_qk, d_qk, d_v))


WIDTHS = [(64, 64), (128, 128), (192, 192), (192, 128)]
# (length, query block, key block): one query block; whole blocks; a
# length that is no multiple of the block (padded inside the entry); a
# query block of two key blocks (the diagonal crosses both)
LAYOUTS = [(32, 32, 32), (96, 32, 32), (80, 32, 32), (128, 64, 32)]


class TestKernel:
    @pytest.mark.parametrize("seg,bq,bk", LAYOUTS,
                             ids=[f"L{s}_q{q}_k{k}" for s, q, k in LAYOUTS])
    @pytest.mark.parametrize("d_qk,d_v", WIDTHS, ids=[f"{a}x{b}" for a, b in WIDTHS])
    def test_bf16_operands_against_a_float32_softmax(self, d_qk, d_v, seg, bq, bk):
        q, k, v = operands(seg, d_qk, d_v, jnp.bfloat16)
        scale = d_qk ** -0.5
        got = kernels.causal_attention(q, k, v, scale, block_q=bq, block_k=bk)
        assert got.shape == (2, seg, 2, d_v) and got.dtype == jnp.bfloat16
        want = plain_causal(q, k, v, scale)
        # the weights are rounded to bf16 for p @ v and the output to
        # bf16: 2^-8 of values of unit size, summed; seen 0.012
        assert np.abs(np.asarray(got, np.float32) - want).max() < 0.03

    @pytest.mark.parametrize("seg,bq,bk", LAYOUTS,
                             ids=[f"L{s}_q{q}_k{k}" for s, q, k in LAYOUTS])
    def test_float32_operands_are_exact(self, seg, bq, bk):
        q, k, v = operands(seg, 24, 16, jnp.float32)
        got = kernels.causal_attention(q, k, v, 0.2, block_q=bq, block_k=bk)
        np.testing.assert_allclose(
            np.asarray(got), plain_causal(q, k, v, 0.2), atol=2e-5)

    def test_rows_past_the_true_length_do_not_reach_real_rows(self):
        """A bucket's pad rows hold whatever the pad token embeds to:
        the real rows' attention must not depend on them."""
        q, k, v = operands(64, 64, 64, jnp.bfloat16)
        junk = [x.at[:, 40:].set(jnp.asarray(7.0, x.dtype)) for x in (q, k, v)]
        a = kernels.causal_attention(q, k, v, 0.125, block_q=32, block_k=32)
        b = kernels.causal_attention(*junk, 0.125, block_q=32, block_k=32)
        np.testing.assert_array_equal(np.asarray(a[:, :40]), np.asarray(b[:, :40]))
        assert np.isfinite(np.asarray(b, np.float32)).all()

    def test_the_call_has_a_three_dim_output(self):
        """``benchmarks/layer_metrics/moe_work.py`` takes every
        ``pallas_kernel`` of two dims or fewer for a grouped matmul."""
        q, k, v = operands(32, 64, 64, jnp.bfloat16)
        calls = harness.pallas_calls(lambda q, k, v: kernels.causal_attention(
            q, k, v, 0.125, block_q=32, block_k=32), q, k, v)
        assert calls == [("prefill_causal_attention", [(4, 32, 64)])]

    def test_a_query_block_must_hold_whole_key_blocks(self):
        q, k, v = operands(96, 64, 64, jnp.bfloat16)
        with pytest.raises(ValueError, match="multiple"):
            kernels.causal_attention(q, k, v, 0.125, block_q=48, block_k=32)

    @pytest.mark.parametrize("seg", [100, 40])
    def test_a_query_block_cut_to_a_short_segment_keeps_whole_key_blocks(self, seg):
        """128 x 64 blocks on a segment under 128 that is no multiple of
        64: the cut query block takes one key block of its own length."""
        q, k, v = operands(seg, 64, 64, jnp.float32)
        got = kernels.flash_attention(q, k, v, causal=True, block_q=128, block_k=64)
        np.testing.assert_allclose(
            np.asarray(got), plain_causal(q, k, v, 0.125), atol=2e-5)

    def test_keys_that_do_not_fit_vmem_are_refused_and_flash_falls_back(self, monkeypatch):
        """The causal kernel keeps a head's K and V whole in VMEM: past
        its share the entry refuses, and ``flash_attention(causal=True)``
        answers with the einsum form."""
        monkeypatch.setattr(kernels, "_CAUSAL_KV_VMEM_BYTES", 1 << 14)
        q, k, v = operands(96, 64, 64, jnp.float32)
        with pytest.raises(ValueError, match="VMEM"):
            kernels.causal_attention(q, k, v, 0.125, block_q=32, block_k=32)
        assert harness.pallas_calls(
            lambda q, k, v: kernels.flash_attention(q, k, v, causal=True), q, k, v) == []
        np.testing.assert_allclose(
            np.asarray(kernels.flash_attention(q, k, v, causal=True)),
            plain_causal(q, k, v, 0.125), atol=2e-5)


RULE = [
    # seg, d_qk, d_v, table width, kernel lane, dtype -> impl
    (2048, 192, 192, 0, True, jnp.bfloat16, "fused"),     # GigaChat b2048
    (1024, 192, 192, 0, True, jnp.bfloat16, "fused"),     # GigaChat b1024
    (1024, 192, 128, 0, True, jnp.bfloat16, "fused"),     # LongCat b1024
    (512, 192, 128, 0, True, jnp.bfloat16, "fused"),      # LongCat b512
    (256, 192, 128, 0, True, jnp.bfloat16, "xla"),        # under a query block
    (1024, 192, 192, 2, True, jnp.bfloat16, "xla"),       # a cached suffix
    # a mesh, the CPU, SELDON_TPU_PAGED_KERNEL=0: no kernel lane
    (1024, 192, 192, 0, False, jnp.bfloat16, "xla"),
    (1024, 192, 192, 0, True, jnp.float32, "xla"),        # exactness engines
    (1024, 192, 192, 0, True, jnp.float16, "xla"),
    (512, 64, 64, 0, True, jnp.bfloat16, "fused"),        # any width that fits
    (1 << 17, 192, 192, 0, True, jnp.bfloat16, "xla"),    # K and V over VMEM
    # PR 43: dots3's indexed layers ask at their own widths (128 heads
    # of 192 / 128) and take the answer for the attention under their
    # selection; the window layers' 256 / 128 beside them
    (4096, 192, 128, 0, True, jnp.bfloat16, "fused"),
    (3072, 192, 128, 0, True, jnp.bfloat16, "fused"),
    (7168, 192, 128, 0, True, jnp.bfloat16, "fused"),
    (4096, 256, 128, 0, True, jnp.bfloat16, "fused"),
    (4096, 192, 128, 0, False, jnp.bfloat16, "xla"),      # a mesh, the CPU, =0
    (4096, 192, 128, 0, True, jnp.float32, "xla"),
    (256, 192, 128, 0, True, jnp.bfloat16, "xla"),        # under a query block
]


@pytest.mark.parametrize("seg,d_qk,d_v,width,kernel_lane,dtype,want", RULE)
def test_the_rule_is_a_function_of_what_a_trace_sees(
        seg, d_qk, d_v, width, kernel_lane, dtype, want):
    assert kernels.prefill_attention_impl(
        seg, d_qk, d_v, dtype, width, kernel_lane) == want


def test_the_multi_head_block_never_asks_the_rule(monkeypatch, own_engine):
    """GPT-2's and OLMoE's from-zero prefill is XLA over the segment on
    every lane: the rule is the latent block's alone."""
    def refuse(*a, **kw):
        raise AssertionError("the multi-head block asked the rule")
    monkeypatch.setattr(kernels, "prefill_attention_impl", refuse)
    fused_here(monkeypatch)
    eng = own_engine("gpt2", jnp.bfloat16, "kernel")
    assert set(eng.lane_report()["prefill_attention"].values()) == {"xla"}
    _from_zero(eng, [PROMPT])


# ---- the engines --------------------------------------------------------

OTHER = np.random.default_rng(6).integers(0, 97, size=21).tolist()

ARCHS = ["gpt2", "olmoe", "gigachat", "longcat"]
LATENT = ("gigachat", "longcat")


def _impl(arch, lane):
    """What a from-zero prefill of ``arch`` attends with on ``lane``."""
    return "fused" if arch in LATENT and lane == "kernel" else "xla"


def _impls(eng):
    return set(eng.lane_report()["prefill_attention"].values())


def _build(arch, dtype, lane="gather", seed=4):
    """An engine of ``arch`` on ``lane`` (the kernel asked for, or not,
    and the chunk left to follow: off the kernel lane the multi-head
    pools decode in the ring chunk, the latent ones in the pool chunk)."""
    if lane == "gather" and arch not in LATENT:
        lane = "ring"
    return harness.build(*harness.spec_and_sizes(arch), lane, dtype, seed=seed)


@pytest.fixture
def own_engine(monkeypatch):
    """``own_engine(arch, dtype, lane) -> engine``: no two cases here
    serve one (arch, type, lane) under the same blocks, so each builds
    its own, after whatever it patches; closed after the case."""
    for get in harness.own(monkeypatch, _build):
        yield lambda *key: get(*key)[0]


def _written(eng, prompts):
    """The pool rows of the pages ``prompts`` fill in their slot rows."""
    rows = harness.tables(eng, len(prompts))
    pages = np.concatenate([rows[i, :-(-len(p) // PAGE)]
                            for i, p in enumerate(prompts)])
    pools = [eng.cache.pages_k] + ([] if eng.cache.pages_v is None else [eng.cache.pages_v])
    return [np.asarray(p[:, pages], np.float32) for p in pools]


def _from_zero(eng, prompts):
    """The from-zero program on ``prompts`` (slot rows from page 1 and
    page 9): last-position logits and the pool rows it wrote."""
    last, _hist = harness.prefill_group(eng, prompts)
    return last, _written(eng, prompts)


def _through_the_table(eng, prompts, cached=0):
    """The cached-suffix program: at ``cached`` 0 with the slot's whole
    table it is what every from-zero prefill traced before PR 33 (every
    cached page gathered, scored and masked out); at a page boundary it
    resumes prompts whose first ``cached`` tokens are already written."""
    return harness.resume_group(eng, prompts, cached), _written(eng, prompts)


def _close(got, want, share, rows_share):
    """Last-position logits within ``share`` of their spread, written
    pool rows within ``rows_share`` of their largest value (a row's pad
    lanes are zeros: its spread says little)."""
    (g_last, g_rows), (w_last, w_rows) = got, want
    assert np.abs(g_last - w_last).max() <= share * w_last.std()
    for g, w in zip(g_rows, w_rows):
        assert np.abs(g - w).max() <= rows_share * np.abs(w).max()


@pytest.mark.parametrize("arch", ARCHS)
def test_the_segment_alone_is_the_parents_form_f32(own_engine, arch):
    """Float32, XLA on both sides: leaving the masked cache half out
    changes the order of no sum that matters (seen: 0 to 5e-7)."""
    eng = own_engine(arch, jnp.float32, "gather")
    assert _impls(eng) == {"xla"}
    want = _through_the_table(eng, [PROMPT, OTHER])
    got = _from_zero(eng, [PROMPT, OTHER])
    _close(got, want, 1e-5, 1e-6)


LANES = pytest.mark.parametrize("lane", ["gather", "kernel"])


@LANES
@pytest.mark.parametrize("arch", ARCHS)
def test_a_from_zero_prefill_is_the_parents_form_bf16(monkeypatch, own_engine, arch, lane):
    """The serving precision.  XLA over the segment alone rounds as the
    parent's form did (the multi-head engines on both lanes, every
    engine on the gather lane); a latent engine's kernel and its XLA
    form both score in float32 and differ by the weights' rounding to
    bf16 for ``p @ v``: seen 0.004-0.02 of the logits' spread, held to
    0.05 (the engines' own bf16 tolerance against their float32
    references), and a written row by one or two bf16 steps (2^-8 of
    its value each)."""
    fused_here(monkeypatch)
    eng = own_engine(arch, jnp.bfloat16, lane)
    assert _impls(eng) == {_impl(arch, lane)}
    want = _through_the_table(eng, [PROMPT, OTHER])
    got = _from_zero(eng, [PROMPT, OTHER])
    _close(got, want, 0.05, 2.0 ** -6)


@LANES
@pytest.mark.parametrize("arch", ARCHS)
def test_a_prompt_prefilled_whole_is_one_resumed_at_a_page_boundary(
        monkeypatch, own_engine, arch, lane):
    """The from-zero program against the cached-suffix program (XLA,
    over pages the from-zero program wrote) on the same prompt, on the
    gather lane (XLA on both sides: ``SELDON_TPU_PAGED_KERNEL=0`` keeps
    one numeric regime) and on the whole-pool kernel lane (the latent
    engines' from-zero program is the fused kernel there)."""
    fused_here(monkeypatch)
    eng = own_engine(arch, jnp.bfloat16, lane)
    assert _impls(eng) == {_impl(arch, lane)}
    whole, _rows_whole = _from_zero(eng, [PROMPT])
    _from_zero(eng, [PROMPT[:2 * PAGE]])
    resumed, _rows_resumed = _through_the_table(eng, [PROMPT], cached=2 * PAGE)
    assert np.abs(whole - resumed).max() <= 0.05 * resumed.std()


@LANES
@pytest.mark.parametrize("arch", ["gpt2", "gigachat"])
def test_the_engine_says_what_ran(monkeypatch, own_engine, arch, lane):
    """``prefill_fused_positions`` rises beside ``prefill_padded_tokens``
    by the padded positions of the calls the kernel served; the lane
    report names each bucket's implementation; served tokens are valid
    either way."""
    fused_here(monkeypatch, block=32)
    eng = own_engine(arch, jnp.bfloat16, lane)
    fused = _impl(arch, lane) == "fused"
    report = eng.lane_report()["prefill_attention"]
    assert set(report) == {f"b{b}" for b in eng.prompt_buckets}
    # the bucket of 16 is under the (patched) query block
    assert report["b16"] == "xla"
    assert report["b32"] == report["b64"] == _impl(arch, lane)
    streams = [eng.submit(np.asarray(p, np.int32), max_new_tokens=3)
               for p in (PROMPT, OTHER, PROMPT[:5])]
    eng.run()
    assert all(s.error is None and len(s.result) == 3 for s in streams)
    stats = eng.engine_stats()
    # a group of two in the bucket of 32 and one prompt in the bucket
    # of 16
    assert stats["prefill_padded_tokens"] == 2 * 32 + 16
    assert stats["prefill_fused_positions"] == (64 if fused else 0)
    # no indexer: nothing attends under a selection's mask (PR 43)
    assert stats["prefill_indexed_fused_positions"] == 0


@pytest.mark.parametrize("arch", ["olmoe", "longcat"])
def test_a_spec_without_an_indexer_counts_no_indexed_positions(monkeypatch, own_engine, arch):
    """``prefill_indexed_fused_positions`` and the report's
    ``b<bucket>_indexed`` entries are an indexed spec's alone (dots3:
    ``tests/test_dots3_paged.py TestIndexedPrefill``)."""
    fused_here(monkeypatch, block=32)
    eng = own_engine(arch, jnp.bfloat16, "kernel")
    said = []
    begin = eng._seam.begin_prefill
    monkeypatch.setattr(eng._seam, "begin_prefill",
                        lambda **stats: (said.append(stats), begin(**stats))[1])
    assert set(eng.lane_report()["prefill_attention"]) == {
        f"b{b}" for b in eng.prompt_buckets}
    stream = eng.submit(np.asarray(PROMPT, np.int32), max_new_tokens=1)
    eng.run()
    assert stream.error is None
    stats = eng.engine_stats()
    assert [c["indexed_fused"] for c in said] == [0]
    assert [c["fused"] for c in said] == [int(_impl(arch, "kernel") == "fused")]
    assert stats["prefill_padded_tokens"] == 32
    assert stats["prefill_indexed_fused_positions"] == 0


def _traced_kernels(eng, bucket):
    """The ``pallas_call`` s of the from-zero program of ``bucket``."""
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    pages = eng._pages_pow2(-(-bucket // PAGE))
    return harness.pallas_calls(
        eng._build_prefill(bucket, 1).__wrapped__, eng.params,
        *eng._kv_args(), i32(1, bucket), i32(1), i32(1, pages))


@pytest.mark.parametrize("arch", ["gpt2", "gigachat", "longcat"])
def test_the_report_is_what_each_program_traced(monkeypatch, own_engine, arch):
    """The engine derives its report from the rule on the host; the
    programs ask the rule as they trace.  Both agree, bucket by bucket:
    a ``prefill_causal_attention`` call an attention (three dims out)
    where the report says "fused", none where it says "xla"."""
    fused_here(monkeypatch, block=32)
    eng = own_engine(arch, jnp.bfloat16, "kernel")
    spec = eng.spec
    attentions = eng.module.num_layers * (2 if arch == "longcat" else 1)
    for bucket in eng.prompt_buckets:
        calls = [c for c in _traced_kernels(eng, bucket)
                 if c[0] == "prefill_causal_attention"]
        if eng.lane_report()["prefill_attention"][f"b{bucket}"] == "fused":
            assert calls == [("prefill_causal_attention", [
                (eng.module.num_heads, bucket, spec.v_dim)])] * attentions
        else:
            assert calls == []


def test_an_f32_engine_and_a_cached_suffix_never_take_the_kernel(monkeypatch, own_engine):
    fused_here(monkeypatch)
    eng = own_engine("gigachat", jnp.float32, "kernel")
    assert _impls(eng) == {"xla"}
    assert _traced_kernels(eng, 16) == []
    eng = own_engine("gigachat", jnp.bfloat16, "kernel")
    assert _impls(eng) == {"fused"}
    _from_zero(eng, [PROMPT[:2 * PAGE]])
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    cached = harness.pallas_calls(
        eng._build_prefill_cached(16, 1, 2).__wrapped__, eng.params,
        *eng._kv_args(), i32(1, 16), i32(1), i32(1), i32(1, 2), i32(1, 2))
    assert cached == []
