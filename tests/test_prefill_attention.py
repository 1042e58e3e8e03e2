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

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.paged import PagedEngine
from seldon_core_tpu.models.spec import GPT2, init_params
from seldon_core_tpu.ops import kernels

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))
from reference import deepseek_v3, longcat_flash, olmoe  # noqa: E402


def plain_causal(q, k, v, scale):
    """Float32 causal softmax attention, ``(B, L, h, d)`` operands."""
    q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    s = np.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = np.where(np.tril(np.ones(s.shape[-2:], bool)), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bkhd->bqhd", w / w.sum(-1, keepdims=True), v)


def pallas_calls(fn, *args):
    """``(name, output shapes)`` of every ``pallas_call`` ``fn`` traces."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"] if "name" in eqn.params
                              else eqn.params["name_and_src_info"].name,
                              [tuple(o.aval.shape) for o in eqn.outvars]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


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
        calls = pallas_calls(lambda q, k, v: kernels.causal_attention(
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
        assert pallas_calls(
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


def test_the_multi_head_block_never_asks_the_rule(monkeypatch):
    """GPT-2's and OLMoE's from-zero prefill is XLA over the segment on
    every lane: the rule is the latent block's alone."""
    def refuse(*a, **kw):
        raise AssertionError("the multi-head block asked the rule")
    monkeypatch.setattr(kernels, "prefill_attention_impl", refuse)
    _fused_here(monkeypatch)
    eng = _engine(monkeypatch, "gpt2", jnp.bfloat16, lane="force")
    try:
        assert set(eng.lane_report()["prefill_attention"].values()) == {"xla"}
        _from_zero(eng, [PROMPT])
    finally:
        eng.close()


# ---- the engines --------------------------------------------------------

PAGE, MAX_LEN, SLOTS = 8, 64, 4
PROMPT = np.random.default_rng(5).integers(0, 97, size=29).tolist()
OTHER = np.random.default_rng(6).integers(0, 97, size=21).tolist()

OLMOE_MODEL = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                   num_experts=8, num_experts_per_tok=2, intermediate_size=32,
                   rms_norm_eps=1e-5, rope_theta=10000, vocab_size=97)
GIGACHAT_MODEL = dict(
    hidden_size=64, num_hidden_layers=3, num_attention_heads=4, vocab_size=97,
    n_routed_experts=4, n_routed_experts_published=8, expert_offset=2,
    num_experts_per_tok=2, moe_intermediate_size=32,
    first_k_dense_replace=1, intermediate_size=96, n_shared_experts=1,
    n_group=4, topk_group=2, routed_scaling_factor=2.5, norm_topk_prob=True,
    q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=12, rope_theta=100000,
    rope_scaling=dict(factor=64, original_max_position_embeddings=16,
                      beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1),
    rms_norm_eps=1e-6)
LONGCAT_MODEL = dict(
    hidden_size=64, num_layers=2, num_attention_heads=4, vocab_size=97,
    n_routed_experts=4, n_routed_experts_published=8, expert_offset=2,
    zero_expert_num=4, moe_topk=4, expert_ffn_hidden_size=32, ffn_hidden_size=96,
    routed_scaling_factor=6, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, rope_theta=10000000,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, rms_norm_eps=1e-5)


def _spec_and_sizes(arch):
    if arch == "gpt2":
        return GPT2, dict(vocab_size=97, d_model=64, num_layers=2, num_heads=4)
    ref, model = {"olmoe": (olmoe, OLMOE_MODEL),
                  "gigachat": (deepseek_v3, GIGACHAT_MODEL),
                  "longcat": (longcat_flash, LONGCAT_MODEL)}[arch]
    return ref.spec_and_config(model)


ARCHS = ["gpt2", "olmoe", "gigachat", "longcat"]


LATENT = ("gigachat", "longcat")


def _fused_here(monkeypatch, block=16):
    """Toy sizes: on the kernel lane (``lane="force"``: the Pallas
    interpreter) the rule answers ``"fused"`` for a latent engine's bf16
    from-zero prefill of any bucket of at least ``block`` positions."""
    monkeypatch.setattr(kernels, "CAUSAL_BLOCK_Q", block)
    monkeypatch.setattr(kernels, "CAUSAL_BLOCK_K", block)


def _impl(arch, lane):
    """What a from-zero prefill of ``arch`` attends with on ``lane``."""
    return "fused" if arch in LATENT and lane == "force" else "xla"


def _impls(eng):
    return set(eng.lane_report()["prefill_attention"].values())


def _engine(monkeypatch, arch, dtype, lane="0", seed=4, **kw):
    monkeypatch.delenv("SELDON_TPU_CHUNK_IMPL", raising=False)
    monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", lane)
    spec, sizes = _spec_and_sizes(arch)
    params = init_params(spec, dict(sizes, max_len=MAX_LEN), seed, dtype=dtype)
    return PagedEngine(params, **sizes, max_len=MAX_LEN, page_size=PAGE,
                       max_slots=SLOTS, steps_per_call=1, dtype=dtype,
                       spec=spec, **kw)


def _rows(prompts, first_pages):
    full = np.zeros((len(prompts), MAX_LEN // PAGE), np.int32)
    for i, first in enumerate(first_pages):
        full[i] = np.arange(first, first + MAX_LEN // PAGE)
    return full


def _written(eng, pages):
    pools = [eng.pages_k] + ([] if eng.pages_v is None else [eng.pages_v])
    return [np.asarray(p[:, pages], np.float32) for p in pools]


def _from_zero(eng, prompts):
    """The from-zero program on ``prompts`` (slot rows from page 1 and
    page 9): last-position logits and the pool rows it wrote."""
    k = len(prompts)
    bucket = next(b for b in eng.prompt_buckets if b >= max(map(len, prompts)))
    tokens = np.zeros((k, bucket), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    pages_h = eng._pages_pow2(-(-bucket // PAGE))
    table = _rows(prompts, [1, 9][:k])[:, :pages_h]
    last, pk, pv, *_hist = eng._build_prefill(bucket, k)(
        eng.params, *eng._kv_args(), jnp.asarray(tokens),
        jnp.asarray([len(p) for p in prompts], jnp.int32), jnp.asarray(table))
    eng._store_kv(pk, pv)
    pages = np.concatenate([table[i, :-(-len(p) // PAGE)]
                            for i, p in enumerate(prompts)])
    return np.asarray(last), _written(eng, pages)


def _through_the_table(eng, prompts, cached=0):
    """The cached-suffix program: at ``cached`` 0 with the slot's whole
    table it is what every from-zero prefill traced before PR 33 (every
    cached page gathered, scored and masked out); at a page boundary it
    resumes prompts whose first ``cached`` tokens are already written."""
    k = len(prompts)
    suffixes = [p[cached:] for p in prompts]
    bucket = next(b for b in eng.prompt_buckets if b >= max(map(len, suffixes)))
    tokens = np.zeros((k, bucket), np.int32)
    for i, p in enumerate(suffixes):
        tokens[i, :len(p)] = p
    rp = eng._pages_pow2(max(1, cached // PAGE) if cached else -(-bucket // PAGE))
    wp = -(-bucket // PAGE)
    full = _rows(prompts, [1, 9][:k])
    first = cached // PAGE
    last, pk, pv, *_hist = eng._build_prefill_cached(bucket, k, rp)(
        eng.params, *eng._kv_args(), jnp.asarray(tokens),
        jnp.asarray([len(p) for p in suffixes], jnp.int32),
        jnp.full((k,), cached, jnp.int32), jnp.asarray(full[:, :rp]),
        jnp.asarray(full[:, first:first + wp]))
    eng._store_kv(pk, pv)
    pages = np.concatenate([full[i, :-(-len(p) // PAGE)]
                            for i, p in enumerate(prompts)])
    return np.asarray(last), _written(eng, pages)


def _close(got, want, share, rows_share):
    """Last-position logits within ``share`` of their spread, written
    pool rows within ``rows_share`` of their largest value (a row's pad
    lanes are zeros: its spread says little)."""
    (g_last, g_rows), (w_last, w_rows) = got, want
    assert np.abs(g_last - w_last).max() <= share * w_last.std()
    for g, w in zip(g_rows, w_rows):
        assert np.abs(g - w).max() <= rows_share * np.abs(w).max()


@pytest.mark.parametrize("arch", ARCHS)
def test_the_segment_alone_is_the_parents_form_f32(monkeypatch, arch):
    """Float32, XLA on both sides: leaving the masked cache half out
    changes the order of no sum that matters (seen: 0 to 5e-7)."""
    eng = _engine(monkeypatch, arch, jnp.float32)
    try:
        assert _impls(eng) == {"xla"}
        want = _through_the_table(eng, [PROMPT, OTHER])
        got = _from_zero(eng, [PROMPT, OTHER])
        _close(got, want, 1e-5, 1e-6)
    finally:
        eng.close()


LANES = pytest.mark.parametrize("lane", ["0", "force"], ids=["gather", "kernel"])


@LANES
@pytest.mark.parametrize("arch", ARCHS)
def test_a_from_zero_prefill_is_the_parents_form_bf16(monkeypatch, arch, lane):
    """The serving precision.  XLA over the segment alone rounds as the
    parent's form did (the multi-head engines on both lanes, every
    engine on the gather lane); a latent engine's kernel and its XLA
    form both score in float32 and differ by the weights' rounding to
    bf16 for ``p @ v``: seen 0.004-0.02 of the logits' spread, held to
    0.05 (the engines' own bf16 tolerance against their float32
    references), and a written row by one or two bf16 steps (2^-8 of
    its value each)."""
    _fused_here(monkeypatch)
    eng = _engine(monkeypatch, arch, jnp.bfloat16, lane=lane)
    try:
        assert _impls(eng) == {_impl(arch, lane)}
        want = _through_the_table(eng, [PROMPT, OTHER])
        got = _from_zero(eng, [PROMPT, OTHER])
        _close(got, want, 0.05, 2.0 ** -6)
    finally:
        eng.close()


@LANES
@pytest.mark.parametrize("arch", ARCHS)
def test_a_prompt_prefilled_whole_is_one_resumed_at_a_page_boundary(
        monkeypatch, arch, lane):
    """The from-zero program against the cached-suffix program (XLA,
    over pages the from-zero program wrote) on the same prompt, on the
    gather lane (XLA on both sides: ``SELDON_TPU_PAGED_KERNEL=0`` keeps
    one numeric regime) and on the whole-pool kernel lane (the latent
    engines' from-zero program is the fused kernel there)."""
    _fused_here(monkeypatch)
    eng = _engine(monkeypatch, arch, jnp.bfloat16, lane=lane)
    try:
        assert _impls(eng) == {_impl(arch, lane)}
        whole, _rows_whole = _from_zero(eng, [PROMPT])
        _from_zero(eng, [PROMPT[:2 * PAGE]])
        resumed, _rows_resumed = _through_the_table(eng, [PROMPT], cached=2 * PAGE)
        assert np.abs(whole - resumed).max() <= 0.05 * resumed.std()
    finally:
        eng.close()


@LANES
@pytest.mark.parametrize("arch", ["gpt2", "gigachat"])
def test_the_engine_says_what_ran(monkeypatch, arch, lane):
    """``prefill_fused_positions`` rises beside ``prefill_padded_tokens``
    by the padded positions of the calls the kernel served; the lane
    report names each bucket's implementation; served tokens are valid
    either way."""
    _fused_here(monkeypatch, block=32)
    eng = _engine(monkeypatch, arch, jnp.bfloat16, lane=lane)
    fused = _impl(arch, lane) == "fused"
    try:
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
    finally:
        eng.close()


@pytest.mark.parametrize("arch", ["olmoe", "longcat"])
def test_a_spec_without_an_indexer_counts_no_indexed_positions(monkeypatch, arch):
    """``prefill_indexed_fused_positions`` and the report's
    ``b<bucket>_indexed`` entries are an indexed spec's alone (dots3:
    ``tests/test_dots3_paged.py TestIndexedPrefill``)."""
    _fused_here(monkeypatch, block=32)
    eng = _engine(monkeypatch, arch, jnp.bfloat16, lane="force")
    said = []
    begin = eng._seam.begin_prefill
    monkeypatch.setattr(eng._seam, "begin_prefill",
                        lambda **stats: (said.append(stats), begin(**stats))[1])
    try:
        assert set(eng.lane_report()["prefill_attention"]) == {
            f"b{b}" for b in eng.prompt_buckets}
        stream = eng.submit(np.asarray(PROMPT, np.int32), max_new_tokens=1)
        eng.run()
        assert stream.error is None
        stats = eng.engine_stats()
        assert [c["indexed_fused"] for c in said] == [0]
        assert [c["fused"] for c in said] == [int(_impl(arch, "force") == "fused")]
        assert stats["prefill_padded_tokens"] == 32
        assert stats["prefill_indexed_fused_positions"] == 0
    finally:
        eng.close()


def _traced_kernels(eng, bucket):
    """The ``pallas_call`` s of the from-zero program of ``bucket``."""
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    pages = eng._pages_pow2(-(-bucket // PAGE))
    return pallas_calls(
        eng._build_prefill(bucket, 1).__wrapped__, eng.params,
        *eng._kv_args(), i32(1, bucket), i32(1), i32(1, pages))


@pytest.mark.parametrize("arch", ["gpt2", "gigachat", "longcat"])
def test_the_report_is_what_each_program_traced(monkeypatch, arch):
    """The engine derives its report from the rule on the host; the
    programs ask the rule as they trace.  Both agree, bucket by bucket:
    a ``prefill_causal_attention`` call an attention (three dims out)
    where the report says "fused", none where it says "xla"."""
    _fused_here(monkeypatch, block=32)
    eng = _engine(monkeypatch, arch, jnp.bfloat16, lane="force")
    try:
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
    finally:
        eng.close()


def test_an_f32_engine_and_a_cached_suffix_never_take_the_kernel(monkeypatch):
    _fused_here(monkeypatch)
    eng = _engine(monkeypatch, "gigachat", jnp.float32, lane="force")
    try:
        assert _impls(eng) == {"xla"}
        assert _traced_kernels(eng, 16) == []
    finally:
        eng.close()
    eng = _engine(monkeypatch, "gigachat", jnp.bfloat16, lane="force")
    try:
        assert _impls(eng) == {"fused"}
        _from_zero(eng, [PROMPT[:2 * PAGE]])
        i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
        cached = pallas_calls(
            eng._build_prefill_cached(16, 1, 2).__wrapped__, eng.params,
            *eng._kv_args(), i32(1, 16), i32(1), i32(1), i32(1, 2), i32(1, 2))
        assert cached == []
    finally:
        eng.close()
