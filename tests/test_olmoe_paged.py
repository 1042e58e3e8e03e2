"""OLMoE on the paged engine (PR 26): RoPE, RMSNorm, QK-norm and routed
SwiGLU experts through the same pool, kernel, prefix cache and programs
as GPT-2, compared on **logits** with the benchmark's plain float32
reference (``benchmarks/reference/olmoe.py``).

Small size, CPU: d 64, 4 heads of 16, 8 experts top-2 of width 32, 2
layers.  The engine's own compiled programs are driven through the
seams its other tests use (``_build_prefill``, ``_build_prefill_cached``,
``_get_chunk``): whole prefill, prefill then decode through the cache,
and a prefix-cached suffix, each on the kernel lane (Pallas in
interpret mode), the XLA gather lane and the ring chunk.
"""

import hashlib
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paged_harness as harness
from paged_harness import LANES, MAX_LEN, PAGE, PROMPT, SLOTS, cached_suffix, prefill, run_program
from seldon_core_tpu.models.generate import load_lm_params
from seldon_core_tpu.models.paged import PagedEngine, StreamingLM, paged_hbm_accounting
from seldon_core_tpu.models.spec import GPT2, OLMOE, init_params, model_spec
from seldon_core_tpu.ops import moe

ref, MODEL = harness.MODELS["olmoe"]
SPEC, SIZES = harness.spec_and_sizes("olmoe")

# float32 compute against a float32 reference: what is left is the order
# of sums (a grouped matmul, a paged softmax merged by the flash rule).
# Logits have unit spread; the largest difference seen over the nine
# lane x program cases is 1.1e-6.  1e-4 is ~100x that, and 1/43 of what
# the mildest of the four wrong programs below moves them by (a bf16
# router: 4.3e-3; no QK-norm 0.57, a dropped expert 0.70, renormalised
# gates 1.08).
F32_ATOL = 1e-4
# bfloat16 compute (8 bits of mantissa) through 2 layers at d = 64:
# rounding of every matmul output, of K and V in the pool and of the
# residual stream.  Largest difference seen: 0.0204, 0.022 of the
# logits' spread (0.93).  A little over twice that, as a share of the
# spread; a missing QK-norm, a renormalised gate or a dropped expert
# moves logits by 0.6-1.1 of it.
BF16_ATOL = 0.05


engines, own_engine = harness.fixtures(SPEC, SIZES)


def _reference(params, tokens):
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    return np.asarray(ref.logits(f32, MODEL, tokens))


@pytest.mark.parametrize("program", ["prefill", "decode", "cached"])
@pytest.mark.parametrize("lane", sorted(LANES))
def test_logits_match_the_reference_f32(engines, lane, program):
    eng, params = engines(lane)
    assert eng._kernel_active == (lane == "kernel")
    rows, tokens, at = run_program(eng, program)
    want = _reference(params, tokens)[at: at + len(rows)]
    assert np.abs(rows - want).max() < F32_ATOL


@pytest.mark.parametrize("experts", ["ragged_dot", "stream"])
@pytest.mark.parametrize("program", ["prefill", "decode", "cached"])
@pytest.mark.parametrize("lane", ["kernel", "gather"])
def test_logits_match_the_reference_bf16(monkeypatch, engines, own_engine, lane,
                                         program, experts):
    """The serving precision: weights at rest in bf16 (router and norm
    scales f32), bf16 pool and matmuls, f32 router; the experts through
    ``ragged_dot`` (what a CPU traces) and through the streaming kernel
    (what a TPU traces at these rows: here under the interpreter: an
    engine of its own, traced under the patch)."""
    if experts == "stream":
        monkeypatch.setattr(moe, "matmul_backend", lambda: "interpret")
        eng, params = own_engine(lane, jnp.bfloat16)
    else:
        eng, params = engines(lane, jnp.bfloat16)
    assert set(eng.lane_report()["expert_matmul"].values()) >= {experts}
    assert params["block_0"]["experts_gate"].dtype == jnp.bfloat16
    assert params["block_0"]["router"].dtype == jnp.float32
    rows, tokens, at = run_program(eng, program)
    want = _reference(params, tokens)[at: at + len(rows)]
    assert np.abs(rows - want).max() < BF16_ATOL * want.std()


def test_rope_and_the_prefix_cache(engines):
    """Positions are absolute, K is cached after RoPE: a suffix
    prefilled over cached pages (at any page-aligned cut) sees what a
    whole prefill sees, logit for logit."""
    eng, _params = engines("gather")
    whole, _hist = prefill(eng, PROMPT)
    for cached in (PAGE, 3 * PAGE):
        assert np.abs(cached_suffix(eng, PROMPT, cached) - whole).max() < 1e-5


def _bf16_router(h, w, k):
    return _ROUTE(h.astype(jnp.bfloat16), w.astype(jnp.bfloat16), k)


def _renormalised(h, w, k):
    gates, experts = _ROUTE(h, w, k)
    return gates / gates.sum(-1, keepdims=True), experts


def _dropped_last(h, w, k):
    gates, experts = _ROUTE(h, w, k)
    return gates.at[:, -1].set(0.0), experts


_ROUTE = moe.route


@pytest.mark.parametrize("wrong", ["bf16_router", "renormalised_gates",
                                   "no_qk_norm", "dropped_expert"])
def test_the_tolerance_fails_a_wrong_program(monkeypatch, own_engine, wrong):
    """What the f32 tolerance is for: each of these computes something
    else than the source defines, and none stays inside it (each traced
    anew, on an engine of its own)."""
    spec = SPEC
    if wrong == "no_qk_norm":
        spec = replace(SPEC, qk_norm=False)
    else:
        monkeypatch.setattr(moe, "route", {
            "bf16_router": _bf16_router, "renormalised_gates": _renormalised,
            "dropped_expert": _dropped_last}[wrong])
    eng, params = own_engine("gather", spec=spec)
    rows, tokens, at = run_program(eng, "decode")
    want = _reference(params, tokens)[at: at + len(rows)]
    assert np.abs(rows - want).max() > 10 * F32_ATOL


def test_engine_serves_and_counts_routing(own_engine):
    """Through submit/step: greedy tokens equal the reference's
    teacher-forced argmax (f32), a repeat is admitted on the prefix
    cache and answers the same, and the routing counters add up — from
    zero: an engine of its own (the only one of four steps a call)."""
    eng, params = own_engine("kernel", steps_per_call=4)
    first = eng.submit(np.asarray(PROMPT, np.int32), max_new_tokens=8)
    eng.run()
    toks = [int(t) for t in first.result]
    want = _reference(params, PROMPT + toks[:-1])[len(PROMPT) - 1:]
    assert toks == want.argmax(-1).tolist()
    stats = eng.engine_stats(detail=True)
    layers, k = MODEL["num_hidden_layers"], MODEL["num_experts_per_tok"]
    # every prompt token and every fed-back token, top-k each, per layer
    assert stats["moe_assignments"] == (len(PROMPT) + 8) * k * layers
    assert sum(stats["moe_expert_hits"]) == stats["moe_assignments"]
    # one lane decoding: exactly k experts hit per (layer, step)
    assert stats["moe_layer_steps"] == 8 * layers
    assert stats["moe_active_expert_steps"] == 8 * layers * k
    assert stats["moe_load_max"] >= stats["moe_load_mean"] > 0

    again = eng.submit(np.asarray(PROMPT, np.int32), max_new_tokens=8)
    eng.run()
    assert [int(t) for t in again.result] == toks
    assert eng.engine_stats()["prefix_hits"] == 1


def test_stream_survives_evict_and_restore(own_engine):
    """K/V pages are all a preemption moves: an OLMoE stream evicted
    mid-decode and re-admitted answers as one that never was.  (An
    engine of its own: it counts evictions from zero.)"""
    eng, _params = own_engine("gather", steps_per_call=2)
    calm = eng.submit(np.asarray(PROMPT, np.int32), max_new_tokens=10)
    eng.run()
    stream = eng.submit(np.asarray(PROMPT[::-1], np.int32), max_new_tokens=10)
    eng.step()
    eng.step()
    with eng._lock:
        eng._evict_locked(stream)
    eng.run()
    fresh = eng.submit(np.asarray(PROMPT[::-1], np.int32), max_new_tokens=10)
    eng.run()
    assert eng.engine_stats()["evictions"] == 1
    assert stream.result.tolist() == fresh.result.tolist()
    assert len(calm.result) == 10


class TestFences:
    def _params(self):
        return init_params(SPEC, SIZES, 0, dtype=jnp.float32)

    def _make(self, **kw):
        return PagedEngine(self._params(), **SIZES, max_len=MAX_LEN,
                           page_size=PAGE, max_slots=SLOTS, spec=SPEC, **kw)

    def test_adapters_with_a_rope_arch(self):
        with pytest.raises(ValueError, match="rotates q and k"):
            self._make(max_adapters=2)

    @pytest.mark.parametrize("kw", [{"quantize": "int8"}, {"precision": "w8a8"},
                                    {"precision": "int8w"}])
    def test_int8_lanes_with_an_expert_arch(self, kw):
        with pytest.raises(ValueError, match="expert matrices"):
            self._make(**kw)

    @pytest.mark.parametrize("kw", [{"tp": 2}, {"dp": 2}])
    def test_a_mesh_with_an_expert_arch(self, kw):
        with pytest.raises(ValueError, match="one chip"):
            self._make(**kw)

    def test_unknown_arch_fails_at_construction(self):
        with pytest.raises(ValueError, match="serves"):
            StreamingLM(arch="mixtral")
        with pytest.raises(ValueError, match="no experts"):
            model_spec("gpt2", num_experts=4)


def test_weights_rest_in_bf16_and_are_counted_as_they_are(own_engine):
    params = init_params(SPEC, SIZES, 1)
    kinds = {str(leaf.dtype) for leaf in jax.tree_util.tree_leaves(params)}
    assert kinds == {"bfloat16", "float32"}
    f32 = {k for k, v in params["block_0"].items()
           if jax.tree_util.tree_leaves(v)[0].dtype == jnp.float32}
    assert f32 == {"attn_norm", "ffn_norm", "q_norm", "k_norm", "router"}
    at_rest = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(params))
    eng, _ = own_engine("gather", jnp.bfloat16, params=params)  # seed 1's tree
    assert eng.lane_report()["weight_bytes"] == at_rest
    assert eng.lane_report()["arch"] == "olmoe"
    priced = paged_hbm_accounting(
        streams=2, ctx_len=64, d_model=64, num_layers=2, weight_bytes=at_rest)
    bare = paged_hbm_accounting(streams=2, ctx_len=64, d_model=64, num_layers=2)
    assert priced["peak_bytes"] - bare["peak_bytes"] == at_rest
    # the same seed makes the same tree; another seed another
    again, other = init_params(SPEC, SIZES, 1), init_params(SPEC, SIZES, 2)
    same = jax.tree_util.tree_map(lambda a, b: bool((a == b).all()), params, again)
    assert all(jax.tree_util.tree_leaves(same))
    assert not bool((params["head"]["kernel"] == other["head"]["kernel"]).all())


# the initialiser reads the tree off the module, so a block no arch
# names yet gets the tree that block applies: the two the spec's fields
# allow beside GPT-2 and OLMoE
THIRD_SPECS = {
    "rope_dense": replace(OLMOE, name="rope-dense", ffn="gelu", num_experts=0,
                          experts_per_tok=0, expert_width=0),
    "layernorm_experts": replace(GPT2, name="ln-moe", ffn="moe", num_experts=8,
                                 experts_per_tok=2, expert_width=32),
}


@pytest.mark.parametrize("which", sorted(THIRD_SPECS))
def test_the_initialiser_follows_the_spec_not_a_name(own_engine, which):
    spec = THIRD_SPECS[which]
    assert not spec.transformer_lm and GPT2.transformer_lm
    config = dict(SIZES, max_len=MAX_LEN)
    params = load_lm_params("", config, 4, spec=spec)
    block = set(params["block_0"])
    assert ("router" in block) == spec.routed
    assert ("mlp_in" in block) == (not spec.routed)
    assert ("q_norm" in block) == spec.qk_norm
    assert ("pos_embed" in params) == (not spec.rope)
    assert ("bias" in params["head"]) == spec.bias
    want = jnp.float32 if spec.weights_f32 else jnp.bfloat16
    assert params["head"]["kernel"].dtype == want
    assert params["tok_embed"]["embedding"].dtype == want
    # and the engine's own program applies it
    eng, _ = own_engine("gather", jnp.bfloat16, spec=spec, params=params)
    last, _hist = prefill(eng, PROMPT)
    assert np.isfinite(last).all() and last.std() > 0.1


def test_a_second_draw_compiles_nothing_and_is_the_same_tree():
    """``init_params`` draws through one jitted function a process (PR
    44: it made the function anew on every call, and with it the 18
    programs of a tree): a second draw at the same sizes finds every
    program compiled, and the seed's tree is the seed's tree."""
    from seldon_core_tpu.models.spec import _uniform

    first = init_params(SPEC, SIZES, 6)
    compiled = _uniform()._cache_size()
    second = init_params(SPEC, SIZES, 6)
    assert _uniform()._cache_size() == compiled > 0
    same = jax.tree_util.tree_map(lambda a, b: bool((a == b).all()), first, second)
    assert all(jax.tree_util.tree_leaves(same))


# ---------------------------------------------------------------------------
# GPT-2's programs did not change
# ---------------------------------------------------------------------------

# sha-256 of the lowered text of the programs the two gpt2-large cells
# run (prefill, cached prefill, chunk, bucketed chunk), at a small size
# on the CPU, per decode lane, taken on the parent commit (89e949e) with
# the function below.  A second model reads a spec; GPT-2's value of it
# must trace what was there.  A PR that changes GPT-2's programs on
# purpose (or the jax version) takes these again and says so.
# PR 27 changed the stream decode kernel on purpose: the ``kernel``
# lane's two ``chunk_*`` hashes were taken again on its tree; its two
# ``prefill_*`` hashes and all four of the ``gather`` lane are still
# 89e949e's — only the kernel moved.
# PR 33 changed the from-zero prefill on purpose (a read table of no
# width: the segment attends over itself alone, no gather of masked
# pages): the two ``prefill_b16_k2`` hashes, one a lane, were taken
# again on its tree.  The other six — the cached-suffix prefill and both
# chunk programs of both lanes — are what they were: those programs keep
# a table with width and lower byte-identically.
# PR 49 changed both prefill programs on purpose (the final norm and the
# head run on the one row a prompt the program returns, ``_unembed(last=)``):
# the four ``prefill_*`` hashes, two a lane, were taken again on its
# tree.  The four ``chunk_*`` hashes are unedited: a decode program
# passes no ``last`` and lowers byte-identically.
GPT2_CFG = dict(vocab_size=64, d_model=32, num_layers=2, num_heads=2, max_len=64)
PARENT_SHA = {
    "kernel": {
        "prefill_b16_k2": "e47b09db92521a6c595f91f778e6cf48aee62385c89bb679e088656b1a77f89a",
        "prefill_cached_b16_k2_r2": "e1dcf7ba0d31c7a77613b98bfb204cf3a3d63a67c1dba1ccab603aa18a732e36",
        "chunk_s2_4x4": "04ceedbf420cb4f47f3a4d64a7d82cd5741065e466be77fad9513d10476cd3b2",
        "chunk_s2_2x2_2x4": "1569aa08b7c74bbfb2bea2e50af781f9961c2c6b0923720ee05518d5abddef90",
    },
    "gather": {
        "prefill_b16_k2": "3ea14c754df2bb85c9567616e5b8b8aa2334c532a8589e3ab23dde5cb23151de",
        "prefill_cached_b16_k2_r2": "caa8c118238b1015656cc3d0e7db080a678a5679f94ddafc4de7323b8bd03492",
        "chunk_s2_4x4": "54dcd2ecee6971a385b2d6b0d32989a2d7d60133de79e20e9530e7d43e334079",
        "chunk_s2_2x2_2x4": "6ee6f04cc30b97e9a45f83aea471619782da1b0cc473b9a98abbd3d58d224a30",
    },
}


def gpt2_program_shas():
    from seldon_core_tpu.models.transformer import TransformerLM

    lm = TransformerLM(dtype=jnp.float32, **GPT2_CFG)
    params = lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = PagedEngine(params, **GPT2_CFG, page_size=8, max_slots=4,
                      steps_per_call=2, dtype=jnp.bfloat16)
    # PR 37: the engine holds the tree cast to bf16; the programs are
    # lowered here as the parent's were, handed the float32 tree, so the
    # hashes still say that the *programs* trace what they did
    # (tests/test_weights_at_rest.py holds what they are handed now)
    eng.params = jax.device_put(params)
    try:
        pools = eng._kv_args()
        i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
        unwrap = lambda fn: fn if hasattr(fn, "lower") else fn.__wrapped__  # noqa: E731
        texts = {
            "prefill_b16_k2": unwrap(eng._build_prefill(16, 2)).lower(
                eng.params, *pools, i32(2, 16), i32(2), i32(2, 2)).as_text(),
            "prefill_cached_b16_k2_r2": unwrap(
                eng._build_prefill_cached(16, 2, 2)).lower(
                eng.params, *pools, i32(2, 16), i32(2), i32(2), i32(2, 2),
                i32(2, 2)).as_text(),
            "chunk_s2_4x4": eng.lower_chunk(2, ((4, 4),)).as_text(),
            "chunk_s2_2x2_2x4": eng.lower_chunk(2, ((2, 2), (2, 4))).as_text(),
        }
        return {k: hashlib.sha256(v.encode()).hexdigest() for k, v in texts.items()}
    finally:
        eng.close()


@pytest.mark.parametrize("lane", ["kernel", "gather"])
def test_gpt2_programs_lower_as_on_the_parent(monkeypatch, lane):
    for k, v in LANES[lane].items():
        monkeypatch.setenv(k, v)
    assert GPT2 == model_spec("gpt2")
    assert gpt2_program_shas() == PARENT_SHA[lane]
