"""Xing4.0 (``arch="xing4_0"``, PR 45) on the paged engine: DeepSeek-V3's
block — latent attention over the one-pool latent cache, sigmoid routing
beside a shared expert — under a residual of FOUR rows mixed round every
attention and every FFN (manifold-constrained hyper-connections,
``ops/hyper.py``), compared on **logits** with the benchmark's plain
float32 reference (``benchmarks/reference/xing4.py``).

Small size, CPU: d 64, 4 rows, 4 heads, ranks 24 / 16, heads of 8 nope +
4 rope against values of 12, 8 experts top-2 in one group, all held,
1 dense + 2 expert layers.  The engine's own compiled programs are
driven through the seams its other tests use, on the kernel lane and the
XLA gather lane; ``tests/test_hyper_ops.py`` has the mixing alone.
"""

from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paged_harness as harness
from paged_harness import PAGE, PROMPT, SLOTS, prefill, run_program
from seldon_core_tpu.models import paged
from seldon_core_tpu.models.paged import StreamingLM
from seldon_core_tpu.models.spec import XING4_0, init_params, model_spec
from seldon_core_tpu.ops import hyper

from reference.xing4_wrong import WRONG, wrong as wrong_reference  # noqa: E402

ref, MODEL = harness.MODELS["xing4"]
SPEC, SIZES = harness.spec_and_sizes("xing4")
LANES = ("gather", "kernel")  # the ring chunk refuses a latent pool

# float32 compute against a float32 reference: what is left is the order
# of sums (absorbed against naive attention, a grouped matmul, the
# projection of the rows before the norm's reciprocal instead of after).
# Logits have unit spread; the largest difference seen over the lane x
# program cases is 4.5e-6.  1e-4 is 20x that, and a tenth of what the
# mildest wrong program below moves them by.
F32_ATOL = 1e-4

# bfloat16 matmul operands (the rows and the coefficients stay float32)
# through 3 layers at d = 64: a row of logits lies 0.015 to 0.05 of their
# spread from the reference's over weight seeds 4-8 — unless a router
# near-tie falls the other way, which with 8 experts top-2, all held and
# weighing 2.0 between them, moves that row by 0.1 to 1.1 of the spread
# and happens at one or two of a seed's seven served rows (the float32
# cases hold the same rows to 1e-4).  So: the median row within 0.08, and
# at most a third of the rows past it.
BF16_ATOL, BF16_SEED = 0.08, 6

engines, own_engine = harness.fixtures(SPEC, SIZES)


def _reference(params, tokens, model=MODEL):
    return np.asarray(ref.logits(params, model, tokens))


@pytest.mark.parametrize("program", ["prefill", "decode", "cached"])
@pytest.mark.parametrize("lane", sorted(LANES))
def test_logits_match_the_reference_f32(engines, lane, program):
    eng, params = engines(lane)
    assert eng._kernel_active == (lane == "kernel")
    assert eng._chunk_impl == "pool" and eng.cache.pages_v is None
    assert eng.cache.pages_k.shape == (3, eng.num_pages, PAGE, 128)  # 20 values
    rows, tokens, at = run_program(eng, program)
    want = _reference(params, tokens)[at: at + len(rows)]
    assert np.abs(rows - want).max() < F32_ATOL


@pytest.mark.parametrize("program", ["prefill", "decode", "cached"])
@pytest.mark.parametrize("lane", sorted(LANES))
def test_logits_match_the_reference_bf16(engines, lane, program):
    """The serving precision: matrices at rest in bf16, a bf16 latent
    pool; router, norms, the rows and the mixing's parameters float32."""
    eng, params = engines(lane, jnp.bfloat16, BF16_SEED)
    block = params["block_1"]
    assert block["experts_gate"].dtype == block["kv_b_k"].dtype == jnp.bfloat16
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(
        [block["hc_attn"], block["hc_ffn"], block["router"]])} == {jnp.dtype("float32")}
    rows, tokens, at = run_program(eng, program)
    want = _reference(params, tokens)[at: at + len(rows)]
    off = np.abs(rows - want).max(axis=-1) / want.std()
    assert np.median(off) < BF16_ATOL
    assert (off > BF16_ATOL).mean() <= 1 / 3


def test_the_kernels_inside_the_programs(monkeypatch, own_engine):
    """What a TPU traces: ``hyper_pre_mix`` / ``hyper_post_mix`` round
    both sub-layers of every layer, here under the interpreter."""
    monkeypatch.setattr(hyper, "backend", lambda: "interpret")
    eng, params = own_engine("kernel")
    assert eng.lane_report()["hyper_mix"] == "pallas"
    for program in ("prefill", "decode"):
        rows, tokens, at = run_program(eng, program)
        want = _reference(params, tokens)[at: at + len(rows)]
        assert np.abs(rows - want).max() < F32_ATOL


# ---- the wrong programs the tolerance tells apart ----

@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_the_tolerance_fails_a_wrong_mixing(engines, wrong):
    """Each of these computes something else than the equations, and
    none stays inside the float32 tolerance — by a factor of ten at
    least.  The error is put into the reference
    (``benchmarks/reference/xing4_wrong.py``; the module's engine serves:
    a difference does not say whose it is), and the Sinkhorn stopped
    early is also built as a program below."""
    eng, params = engines("gather")
    rows, tokens, at = run_program(eng, "decode")
    right = _reference(params, tokens)[at: at + len(rows)]
    assert np.abs(rows - right).max() < F32_ATOL
    with wrong_reference(wrong, MODEL) as model:
        other = _reference(params, tokens, model)[at: at + len(rows)]
    assert np.abs(rows - other).max() > 10 * F32_ATOL, wrong


def test_a_mean_at_the_exit_is_no_other_program(engines):
    """The one listed departure no comparison of logits can tell: the
    rows leave as their sum into the final RMSNorm, which divides a
    constant factor out again (its epsilon, 1e-6 beside a mean square of
    tens, is all that is left of the 4).  Said here so that nobody takes
    the sum for tested."""
    eng, params = engines("gather")
    rows, tokens, at = run_program(eng, "decode")
    with wrong_reference("exit_a_mean", MODEL) as model:
        other = _reference(params, tokens, model)[at: at + len(rows)]
    assert np.abs(rows - other).max() < F32_ATOL


def test_the_tolerance_fails_a_program_that_stops_the_sinkhorn_early(own_engine):
    eng, params = own_engine("gather", spec=replace(SPEC, hc_sinkhorn_iters=1))
    rows, tokens, at = run_program(eng, "prefill")
    want = _reference(params, tokens)[at: at + len(rows)]
    assert np.abs(rows - want).max() > 10 * F32_ATOL


# ---- the share ----

def _share(held, offset):
    return dict(MODEL, n_routed_experts=held, n_routed_experts_published=8,
                expert_offset=offset)


def _share_block(block, held, offset):
    sl = slice(offset, offset + held)
    return {**block, **{n: block[n][sl] for n in
                        ("experts_gate", "experts_up", "experts_down")}}


def test_the_shares_layer_outputs_add_up_under_the_mixing():
    """A two-way split of the experts (4 of 8 each): what the two shares'
    expert layers write into the rows, the shared expert counted once,
    adds up to what the uncut layer writes — the write is linear in the
    FFN's output, the coefficients depend on the rows alone."""
    rng = np.random.default_rng(4)
    block = init_params(SPEC, SIZES, 9, dtype=jnp.float32)["block_1"]
    x = jnp.asarray(rng.normal(size=(50, 4, 64)), jnp.float32)     # (T, n, C)
    with jax.default_matmul_precision("highest"):
        h_pre, h_post, h_res = ref.coefficients(x, block["hc_ffn"], MODEL)
        h = ref.read(x, h_pre, block["ffn_norm"]["scale"], MODEL["rms_norm_eps"])
        whole = ref.ffn(h, block, MODEL, 1)
        parts = [ref.ffn(h, _share_block(block, 4, o), _share(4, o), 1)
                 for o in (0, 4)]
        shared = ref.ffn(h, _share_block(block, 0, 0), _share(0, 0), 1)
        assert all(float(jnp.abs(p - shared).max()) > 0 for p in parts)
        assert np.abs(np.asarray(sum(parts) - shared - whole)).max() < 1e-4
        uncut = ref.write(x, whole, h_post, h_res)
        # ... and through the program's own write, stream-major
        rows = jnp.moveaxis(x, 1, 0)
        written = [hyper.hyper_post(rows, y, h_post, h_res)
                   for y in (*parts, shared)]
        summed = written[0] + written[1] - written[2]
    assert np.abs(np.asarray(jnp.moveaxis(summed, 0, 1) - uncut)).max() < 1e-4


def test_a_share_serves_its_part(own_engine):
    """The engine with 4 of the 8 experts from the 4th on, against the
    reference given the same share."""
    spec = replace(SPEC, experts_held=4, expert_offset=4)
    params = init_params(spec, SIZES, 3, dtype=jnp.float32)
    eng, _params = own_engine("gather", spec=spec, params=params)
    assert eng.lane_report()["experts_held"] == 4
    last, _hist = prefill(eng, PROMPT)
    want = _reference(params, PROMPT, _share(4, 4))[-1]
    assert np.abs(last - want).max() < F32_ATOL


# ---- the cache, the pool, the cap, the sizes ----

def test_the_cache_row_and_the_pool_are_deepseek_v3_s(engines):
    """The four rows live inside a program: a token's cache row, the
    pool and its bytes are what DeepSeek-V3 keeps at the same sizes."""
    eng, _params = engines("gather")
    plain_spec, plain_sizes = harness.spec_and_sizes("gigachat")
    plain, _p = harness.build(plain_spec, plain_sizes, "gather", jnp.float32)
    try:
        assert SPEC.cache_width(64) == plain_spec.cache_width(64) == 128
        assert (SPEC.cache_pools, SPEC.cache_values) == (1, 20)
        assert eng.cache.pages_k.shape == plain.cache.pages_k.shape and eng.cache.pages_v is None
        a, b = eng.lane_report(), plain.lane_report()
        for key in ("pool_shard_bytes", "cache_width", "cache_layers", "attention",
                    "chunk_impl", "kv_dtype"):
            assert a[key] == b[key], key
        assert (a["hyper_streams"], a["hyper_mix"]) == (4, "xla")
        assert "hyper_streams" not in b
        # phi, bias and alpha of two sub-layers a layer, float32
        assert a["hyper_weight_bytes"] == 6 * 4 * (256 * 24 + 24 + 3)
    finally:
        plain.close()
    at_rest = dict(streams=4, ctx_len=64, d_model=128, num_layers=3, page_size=PAGE,
                   cache_pools=1, chunk_impl="pool")
    assert paged.paged_hbm_accounting(**at_rest)["pool_bytes"] == 4 * 64 * 128 * 3 * 2


def test_the_prefill_cap_counts_the_rows():
    """Arithmetic, no device: a position's temporaries grow by the rows
    read and the rows written, and at the configuration's widths a call
    takes 4,096 positions beside 7.96 GB of weights and 4.53 GB of
    pool."""
    spec = model_spec("xing4_0", experts_held=64, dense_layers=1)
    one_row = replace(spec, hc_mult=0)
    d, vocab, heads = 3584, 16384, 32
    per = paged.prefill_position_bytes(spec, d, vocab, heads)
    assert per - paged.prefill_position_bytes(one_row, d, vocab, heads) == 2 * 4 * 4 * d
    assert 330_000 < per < 350_000
    assert paged.prefill_positions_max(4 * 2**30, per) == 4096
    free = int(15.75 * 2**30 - 7.96e9 - 4.53e9)
    assert paged.prefill_positions_max(free, per) == 4096
    assert paged.prefill_group_max(3072, 4096) == paged.prefill_group_max(4096, 4096) == 1
    # a held pass of a 4,096-position call prices every assignment: 4 rows a token
    from seldon_core_tpu.ops import moe

    assert moe.held_rows_cap(4096, 4, 64, 64) == 4 * 4096
    assert moe.held_rows_cap(128, 4, 64, 64) == 2048


def test_the_published_sizes_are_the_defaults():
    spec = model_spec("xing4_0")
    assert spec is XING4_0
    assert (spec.num_experts, spec.experts_per_tok, spec.expert_width, spec.n_group,
            spec.topk_group, spec.dense_layers, spec.dense_width,
            spec.routed_scale) == (64, 4, 1024, 1, 1, 2, 9216, 2.0)
    assert (spec.q_rank, spec.kv_rank, spec.nope_dim, spec.rope_dim, spec.v_dim,
            spec.rope_theta, spec.rope_factor) == (768, 512, 128, 64, 128, 10_000.0, 64.0)
    assert (spec.hc_mult, spec.hc_sinkhorn_iters, spec.hc_eps, spec.hc_res_min,
            spec.hc_res_max) == (4, 20, 1e-6, -30.0, 30.0)
    assert spec.cache_values == 576 and spec.cache_width(3584) == 640
    lm = StreamingLM(arch="xing4_0",
                     arch_sizes='{"experts_held": 64, "dense_layers": 1}')
    assert (lm.spec.held, lm.spec.dense_layers, lm.spec.hc_mult) == (64, 1, 4)


@pytest.mark.parametrize("arch", ["gpt2", "olmoe", "deepseek_v3", "longcat_flash",
                                  "dots3_note", "smallthinker"])
@pytest.mark.parametrize("size", [{"hc_mult": 4}, {"hc_sinkhorn_iters": 5},
                                  {"hc_eps": 1e-5}, {"hc_res_max": 10}])
def test_no_other_arch_takes_the_residual_s_sizes(arch, size):
    with pytest.raises(ValueError, match="has no"):
        model_spec(arch, **size)


def test_the_residual_s_sizes_are_checked():
    assert model_spec("xing4_0", hc_mult=2, hc_sinkhorn_iters=3).hc_mult == 2
    with pytest.raises(ValueError, match="hc_mult 1"):
        model_spec("xing4_0", hc_mult=1)
    with pytest.raises(ValueError, match="clamp"):
        model_spec("xing4_0", hc_res_min=5, hc_res_max=-5)


def test_the_mixing_s_parameters_rest_float32_and_move_every_coefficient():
    """phi, bias and alpha are drawn so that the input-dependent term
    moves the coefficients by tenths (alpha in [0.5, 1.5), x~ phi of unit
    spread, bias within +-0.1): a program that kept the bias alone is
    the ``no_input_dependent_term`` case above."""
    block = init_params(SPEC, SIZES, 1)["block_1"]
    for name in ("hc_attn", "hc_ffn"):
        mix = block[name]
        assert mix["phi"].shape == (24, 256) and mix["phi"].dtype == jnp.float32
        assert 0.5 <= float(mix["scale"].min()) and float(mix["scale"].max()) < 1.5
        assert 0 < float(jnp.abs(mix["bias"]).max()) <= 0.1
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 4, 64)), jnp.float32)
    pre, post, res = ref.coefficients(x, block["hc_ffn"], MODEL)
    assert float(pre.std(axis=0).min()) > 0.05 and float(post.std(axis=0).min()) > 0.1
    assert float(res.std(axis=0).min()) > 0.03


# ---- the front door ----

def test_engine_serves_and_counts_the_mixed_positions(own_engine):
    """(An engine of its own: it counts from zero.)"""
    eng, params = own_engine("gather", steps_per_call=4)
    stream = eng.submit(np.asarray(PROMPT, np.int32), max_new_tokens=8)
    eng.run()
    toks = [int(t) for t in stream.result]
    want = _reference(params, PROMPT + toks[:-1])[len(PROMPT) - 1:]
    assert toks == want.argmax(axis=-1).tolist()
    stats = eng.engine_stats()
    assert (stats["hyper_streams"], stats["hyper_sinkhorn_iters"]) == (4, 20)
    # one prefill call of bucket 32, two mixed sub-layers in each of 3 layers
    assert stats["prefill_padded_tokens"] == 32
    assert stats["hyper_prefill_positions"] == 32 * 6
    # every launched step runs every lane's rows through them
    steps = stats["chunks"] * 4
    assert stats["hyper_decode_positions"] == steps * SLOTS * 6
    assert stats["latent_kv_tokens"] == 3 * stats["decode_kv_tokens"] > 0
    assert stats["moe_assignments"] > 0
