"""``arch="smallthinker"`` as a ``ModelSpec`` value (PR 41): what the
spec says, what ``model_spec`` takes and refuses, that the four shares
of one layer add up to the uncut reference's layer, that the four MoE
specs shipped before it lower byte-identically (GPT-2's hashes are in
tests/test_olmoe_paged.py), that the accounting counts both pools, and
that what a K/V cache of kinds cannot follow yet is refused by name."""

import hashlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models import paged
from seldon_core_tpu.models.paged import PagedEngine, StreamingLM
from seldon_core_tpu.models.spec import (
    SMALLTHINKER,
    init_params,
    model_spec,
)
from seldon_core_tpu.ops import moe

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))
from reference import smallthinker as ref  # noqa: E402

LAYOUT = [0, 1, 1, 1]
MODEL = dict(
    hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, vocab_size=64,
    sliding_window_layout=LAYOUT, rope_layout=LAYOUT, sliding_window_size=8,
    rope_theta=1500000, rms_norm_eps=1e-6, moe_ffn_hidden_size=32,
    moe_num_primary_experts=8, moe_num_primary_experts_published=8,
    expert_offset=0, moe_num_active_primary_experts=2, norm_topk_prob=True)


def test_the_published_value():
    s = SMALLTHINKER
    assert s == model_spec("smallthinker")
    assert (s.kv_heads, s.head_dim, s.window, s.experts_per_tok) == (4, 128, 4096, 6)
    assert len(s.layer_kinds) == 52 and s.layer_kinds[:5] == (
        "full", "window", "window", "window", "full")
    assert s.layer_kinds.count("window") == 39
    # the cache row follows the K/V heads, not d_model: two pools of 512
    assert s.cache_width(2560) == 512 and s.cache_pools == 2
    assert s.cache_kinds(12) == (("full", 3, 512), ("window", 9, 512))
    assert s.window_table_pages(64, 8) == 66
    # positions are a kind: the window layers rotate, the full layers have none
    assert s.attn_kind(0, 28).positions == "none" and s.attn_kind(0, 28).window == 0
    assert s.attn_kind(1, 28).positions == "rope" and s.attn_kind(1, 28).window == 4096
    assert (s.router_from, s.expert_act, s.norm_topk, s.score) == (
        "attn_input", "relu", True, "softmax")
    # the shipped specs keep what 0 means
    assert model_spec("olmoe").head_sizes(16, 2048) == (16, 128)
    assert model_spec("olmoe").cache_width(2048) == 2048


@pytest.mark.parametrize("sizes, match", [
    ({"kv_rank": 16}, "has no"), ({"dense_layers": 1}, "has no"),
    ({"zero_experts": 4}, "has no"),
    ({"layer_kinds": ("full", "local")}, "layer_kinds"),
    ({"experts_held": 48, "expert_offset": 32}, "experts_held"),
    ({"no_such": 1}, "unknown sizes")])
def test_sizes_are_the_arch_s_own(sizes, match):
    with pytest.raises(ValueError, match=match):
        model_spec("smallthinker", **sizes)


def test_a_share_and_kinds_are_not_olmoe_s():
    for sizes in ({"experts_held": 8}, {"layer_kinds": ("full",)}, {"kv_heads": 2}):
        with pytest.raises(ValueError, match="has no"):
            model_spec("olmoe", **sizes)
    # ... and a latent arch takes no K/V heads
    with pytest.raises(ValueError, match="has no"):
        model_spec("dots3_note", kv_heads=2)


def test_streaming_lm_takes_the_arch():
    lm = StreamingLM(arch="smallthinker", arch_sizes='{"experts_held": 16, "expert_offset": 16}')
    assert (lm.spec.held, lm.spec.expert_offset, lm.spec.num_experts) == (16, 16, 64)
    with pytest.raises(ValueError, match="serves"):
        StreamingLM(arch="smallthinker2")


def test_four_shares_add_up_to_the_uncut_layer():
    """One layer with every expert held against the sum of its four
    shares of two experts each: the residual and the attention are every
    share's, the experts' parts add (an absent expert adds nothing)."""
    spec, sizes = ref.spec_and_config(MODEL)
    params = init_params(spec, sizes, 5, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(5).standard_normal((19, 64)), jnp.float32)
    for index in (0, 1):  # a full layer, a window layer
        p = params[f"block_{index}"]
        with jax.default_matmul_precision("highest"):
            whole = np.asarray(ref.layer(p, MODEL, x, index))
            held0 = dict(MODEL, moe_num_primary_experts=0)
            attended = np.asarray(ref.layer(p, held0, x, index))  # no expert held
            parts = []
            for share in range(4):
                sub = dict(MODEL, moe_num_primary_experts=2, expert_offset=2 * share)
                cut = {k: (v[2 * share:2 * share + 2] if k.startswith("experts_") else v)
                       for k, v in p.items()}
                parts.append(np.asarray(ref.layer(cut, sub, x, index)) - attended)
        np.testing.assert_allclose(attended + sum(parts), whole, atol=2e-5, rtol=0)
        assert max(np.abs(part).max() for part in parts) > 0.01


# ---------------------------------------------------------------------------
# the specs shipped before this one lower as they did
# ---------------------------------------------------------------------------

SIZES = dict(vocab_size=64, d_model=32, num_layers=4, num_heads=4)
LATENT = dict(q_rank=24, kv_rank=16, nope_dim=8, rope_dim=8, v_dim=8,
              experts_held=2, expert_offset=2)
SHIPPED = {
    "olmoe": dict(num_experts=8, experts_per_tok=2, expert_width=32),
    "deepseek_v3": dict(num_experts=8, experts_per_tok=2, expert_width=16,
                        dense_layers=1, dense_width=48, n_group=2, topk_group=1,
                        rope_orig_len=16, **LATENT),
    "longcat_flash": dict(num_experts=8, experts_per_tok=2, expert_width=16,
                          dense_width=48, zero_experts=4, **LATENT),
    "dots3_note": dict(num_experts=8, experts_per_tok=2, expert_width=16,
                       dense_layers=1, dense_width=48,
                       layer_kinds=("full", "window", "window", "full"), window=9,
                       win_heads=2, win_q_rank=24, win_kv_rank=32, win_nope_dim=16,
                       win_rope_dim=8, win_v_dim=8, index_heads=4, index_dim=16,
                       index_topk=16, **LATENT),
}
# sha256 of each program's lowered text (prefill_b16_k2, chunk_s2_4x4,
# chunk_s2_2x2_2x8) on the tree before this PR (commit 236919b), by lane;
# the three specs that hold a share as PR 47 left them (commit 02ec28c +
# ``expert_ffn_held`` without its mask and zero-row copies: measured in
# their cells, PERF.md section 6).
# The first of each triple (the prefill) as PR 49 left it: the final norm
# and the head on the one row a prompt the program returns; every chunk
# hash is unedited.
# ``dots3_note/kernel``'s third (the one chunk whose table is wide enough
# to select) as PR 51 left it: the cached indexer keys are scored in the
# page loop over the key pool (``ops/kernels.py index_scores_decode``);
# its gather lane and every other line are unedited.
# Both ``dots3_note`` lanes' third as PR 55 left it: the selection's
# threshold by a descent over the scores' bits (``ops/mla.py kth_mask``),
# the same mask bit for bit; every other hash is unedited.
# ``deepseek_v3`` and ``dots3_note``, both lanes, all three, as PR 57 left
# them: ``ops/moe.py route_grouped``'s group step by maxima and a count
# (two groups of which one is kept) and not traced where every group is
# kept (``dots3_note``'s one), the same gates and experts bit for bit
# (``tests/test_deepseek_ops.py``); ``olmoe`` and ``longcat_flash``, which
# route by other functions, are unedited.
# A PR that changes what one of these specs traces on purpose measures
# its cell and replaces the line.
PARENT_SHA = {
    "olmoe/kernel": ("2b7346bf4ebf6f97", "cf4d3494fbabf487", "bd5e3f041abd3a30"),
    "olmoe/gather": ("af5c0d85895c1373", "d1d143ef68dd754f", "a564dbb262ecc1d5"),
    "deepseek_v3/kernel": ("6794a9292c3c4d04", "00ec546f7040f6f6", "2927130ce304ecee"),
    "deepseek_v3/gather": ("6794a9292c3c4d04", "ed3daeeb9375dce9", "6f599d5baec12e30"),
    "longcat_flash/kernel": ("2940c8bcf2928dc3", "35c56975942fe0dc", "e826ad7641948da1"),
    "longcat_flash/gather": ("2940c8bcf2928dc3", "516e14cd27e54d4d", "09a1306ca2b66391"),
    "dots3_note/kernel": ("1629dd5ae22fff87", "f489262fc226e2fd", "39c671543e739a48"),
    "dots3_note/gather": ("1629dd5ae22fff87", "74e3922e675415ba", "412879d5bdbb9b06"),
}


@pytest.mark.parametrize("case", sorted(PARENT_SHA))
def test_a_shipped_spec_lowers_as_on_the_parent(monkeypatch, case):
    arch, lane = case.split("/")
    monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", {"kernel": "force", "gather": "0"}[lane])
    monkeypatch.delenv("SELDON_TPU_CHUNK_IMPL", raising=False)
    spec = model_spec(arch, **SHIPPED[arch])
    params = init_params(spec, SIZES, 1, dtype=jnp.bfloat16)
    eng = PagedEngine(params, **SIZES, max_len=64, page_size=4, max_slots=4,
                      steps_per_call=2, dtype=jnp.bfloat16, spec=spec)
    try:
        pools = eng._kv_args()
        i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
        unwrap = lambda fn: fn if hasattr(fn, "lower") else fn.__wrapped__  # noqa: E731
        kinds = {"window": (i32(2, eng.cache.window_pages), i32(2))} if spec.kinds else {}
        texts = (
            unwrap(eng._build_prefill(16, 2)).lower(
                eng.params, *pools, i32(2, 16), i32(2), i32(2, 4), **kinds).as_text(),
            eng.lower_chunk(2, ((4, 4),)).as_text(),
            eng.lower_chunk(2, ((2, 2), (2, 8))).as_text(),
        )
    finally:
        eng.close()
    assert tuple(hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts) == PARENT_SHA[case]


# ---------------------------------------------------------------------------
# the engine: accounting of two pools, fences by name
# ---------------------------------------------------------------------------

TINY = dict(MODEL, num_hidden_layers=4, moe_num_primary_experts=4, expert_offset=2)


def _engine(**kw):
    spec, sizes = ref.spec_and_config(TINY)
    params = init_params(spec, sizes, 3, dtype=jnp.float32)
    return PagedEngine(params, **sizes, max_len=64, page_size=4, max_slots=4,
                       steps_per_call=2, dtype=jnp.float32, spec=spec, **kw), spec


def test_the_accounting_counts_both_pools():
    eng, spec = _engine()
    try:
        report = eng.lane_report()
        kinds = [(layers, lanes, spec.window if name == "window" else 0)
                 for name, layers, lanes in spec.cache_kinds(4)
                 for _pool in range(spec.cache_pools)]  # K and V
        said = paged.paged_hbm_accounting(
            streams=4, ctx_len=64, d_model=64, num_layers=4, page_size=4,
            steps_per_call=2, dtype_bytes=4, chunk_impl="pool", cache_kinds=kinds)
        # the pools hold every slot's tables full and a trash page each
        full = (4 * 16 + 1) * 4 * 32 * 4 * 2 * 1
        win = (4 * eng.cache.window_pages + 1) * 4 * 32 * 4 * 2 * 3
        assert report["pool_shard_bytes"] == full + win
        assert said["pool_bytes"] == (4 * 16 * 1 + 4 * eng.cache.window_pages * 3) * 4 * 32 * 4 * 2
        assert said["window_bytes"] == 4 * eng.cache.window_pages * 3 * 4 * 32 * 4 * 2
        # a window layer stops growing: capacity and the longest context
        # follow the full layers alone past the window
        budget = said["pool_bytes"]
        assert paged.paged_capacity_streams(
            budget, 64, d_model=64, num_layers=4, page_size=4, steps_per_call=2,
            dtype_bytes=4, chunk_impl="pool", cache_kinds=kinds) == 4
        assert paged.paged_max_context(
            budget, page_size=4, d_model=64, num_layers=4, steps_per_call=2,
            dtype_bytes=4, chunk_impl="pool", cache_kinds=kinds) > 64
    finally:
        eng.close()


def test_the_prefill_cap_counts_grouped_widths():
    s = SMALLTHINKER
    from dataclasses import replace
    held = replace(s, experts_held=16)
    got = paged.prefill_position_bytes(held, 2560, 37984, 28)
    # float32 logits and the residual stream, then the wider of the
    # attention's rows (q 3,584 and the attended values, k and v 512) and
    # the held experts' (6 assignments a token at the pass's headroom)
    attn = (8 * 28 + 4 * 4) * 128
    routed = 6 * (6 * 2560 + 10 * 768)
    assert got == 4 * 37984 + 6 * 2560 + max(attn, routed)
    assert attn == 30720
    # ... and where every expert is held elsewhere but a sliver, the
    # attention's rows are the widest
    sliver = replace(s, experts_held=1)
    assert paged.prefill_position_bytes(sliver, 2560, 37984, 28) == (
        4 * 37984 + 6 * 2560 + attn)


@pytest.mark.parametrize("kw, env, match", [
    ({"prefix_cache": True}, {}, "prefix cache"),
    ({"chunk_token_budget": 64}, {}, "chunked prefill"),
    ({"max_adapters": 2}, {}, "adapters"),
    ({"speculative": {"draft": "ngram"}}, {}, "speculative"),
    ({}, {"SELDON_TPU_KV_DTYPE": "int8"}, "int8"),
    ({}, {"SELDON_TPU_KV_OFFLOAD": "1"}, "host KV tier"),
    ({}, {"SELDON_TPU_CHUNK_IMPL": "ring"}, "ring chunk"),
    ({"tp": 2}, {}, "routes tokens to experts"),
])
def test_what_cannot_follow_is_refused_by_name(monkeypatch, kw, env, match):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    with pytest.raises(ValueError, match=match) as err:
        _engine(**kw)
    assert "smallthinker" in str(err.value)


def test_containers_are_refused_by_name():
    eng, _spec = _engine()
    try:
        for call in (lambda: eng.prefill_export([1, 2, 3]),
                     lambda: eng.submit_prefilled({}),
                     lambda: eng.migrate_import({})):
            with pytest.raises(ValueError, match="cache of row kinds"):
                call()
        assert eng.migrate_export() == []
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# the router and the activation, as ops
# ---------------------------------------------------------------------------

def test_route_renormalises_and_takes_logits():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((5, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    gates, experts = moe.route(h, w, 3)
    normed, same = moe.route(h, w, 3, norm=True)
    assert np.array_equal(experts, same)
    np.testing.assert_allclose(normed.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(normed, gates / gates.sum(-1, keepdims=True), atol=1e-7)
    # the same numbers as a softmax over the chosen logits
    logits = moe.router_logits(h, w)
    chosen = jnp.take_along_axis(logits, experts, axis=-1)
    np.testing.assert_allclose(normed, jax.nn.softmax(chosen, -1), atol=1e-6)
    # logits handed over: h and w are not read
    again, whose = moe.route(None, None, 3, logits=logits, norm=True)
    assert np.array_equal(whose, experts) and np.allclose(again, normed)


@pytest.mark.parametrize("impl", ["ragged_dot", "stream", "tiled"])
def test_relu_is_not_silu(monkeypatch, impl):
    """Both activations on every lane of ``grouped_swiglu``: 24 rows
    over three groups (under the ridge: the streaming kernel where the
    backend takes kernels), and 776 (over it: the tiled one)."""
    if impl != "ragged_dot":
        monkeypatch.setattr(moe, "matmul_backend", lambda: "interpret")
    rng = np.random.default_rng(1)
    dtype = jnp.float32 if impl == "ragged_dot" else jnp.bfloat16
    n, sizes = (776, [300, 0, 470]) if impl == "tiled" else (24, [8, 0, 16])
    rows = jnp.asarray(rng.standard_normal((n, 128)), dtype)
    gate, up = (jnp.asarray(rng.standard_normal((3, 128, 128)) * 0.1, dtype) for _ in "gu")
    down = jnp.asarray(rng.standard_normal((3, 128, 128)) * 0.1, dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    assert moe.expert_matmul_impl(n, 3, 128, 128, dtype, moe.matmul_backend()) == impl
    got = {act: np.asarray(moe.grouped_swiglu(rows, gate, up, down, sizes, act=act),
                           np.float32) for act in ("silu", "relu")}
    group = np.repeat(np.arange(3), np.asarray(sizes))
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    for act, fn in (("silu", jax.nn.silu), ("relu", jax.nn.relu)):
        want = np.stack([
            f32(fn(f32(rows)[i] @ f32(gate)[g]) * (f32(rows)[i] @ f32(up)[g])) @ f32(down)[g]
            for i, g in enumerate(group)])
        np.testing.assert_allclose(got[act][:len(group)], want,
                                   atol=1e-4 if impl == "ragged_dot" else 0.05)
    assert np.abs(got["silu"] - got["relu"])[:len(group)].max() > 0.05
    with pytest.raises(ValueError, match="activation"):
        moe.activation("gelu")
