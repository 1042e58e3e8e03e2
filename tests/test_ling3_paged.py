"""Ling-3.0-flash on the paged engine (CPU, seeded weights, a tiny spec of
two periods — KDA x 2, MLA, twice — a dense first layer, 32 sigmoid-routed
experts of which the replica holds 8): the engine's own programs — prefill
in a padded bucket and in a group of different lengths, then decode
through the state a lane and the latent rows — against
``benchmarks/reference/ling3_flash.py``'s full forward pass (the
recurrence position by position, no state carried, latent attention over
up-projected keys and values) **on logits**.

Tolerances.  Float32 engine against the float32 reference: both compute
at the highest matmul precision and differ by the order of their sums;
read 4.3e-6 at logits of spread ~6.5 over 29 + 12 positions, held to
1e-4.  The six wrong programs — the decay averaged over a head's channels
(Olmo-Hybrid's rule), the softplus gate in the bounded one's place, beta
times 2, a state kept in bfloat16, the head-wise gate left out (the
reference's ``VARIANTS``) and q through a bottleneck (a wrong SPEC) — move
the same rows by 0.03 to 5 and each fails that tolerance a hundred times
over.  Bfloat16 engine: at d = 64 the precision itself reads high and a
router near-tie can flip a row; the bfloat16 case holds the MEDIAN row
under 0.8 of a deviation and is there for the types' plumbing (a bfloat16
tail and gate matrix, a float32 state); the published widths' precision
is read by ``tools/precision_readings.py`` and on the chip.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paged_harness as harness
from seldon_core_tpu.models import paged
from seldon_core_tpu.models.paged import PagedEngine
from seldon_core_tpu.models.spec import BAILING_HYBRID, init_params, model_spec

ref, TINY = harness.MODELS["ling3"]
SPEC, SIZES = ref.spec_and_config(TINY)
ENGINE = dict(max_len=128, prompt_buckets=[16, 32, 64])
TOL = 1e-4
RNG = np.random.default_rng(7)
PROMPTS = [RNG.integers(0, 97, size=n).tolist() for n in (29, 21, 9)]


@pytest.fixture(scope="module")
def engines():
    made = {}

    def get(lane, dtype=jnp.float32, ctx_buckets="", **kw):
        key = (lane, jnp.dtype(dtype).name, ctx_buckets, tuple(sorted(kw.items())))
        if key not in made:
            with harness.environment(SELDON_TPU_CTX_BUCKETS=ctx_buckets):
                made[key] = harness.build(SPEC, SIZES, lane, dtype, **ENGINE, **kw)
        return made[key]

    yield get
    for eng, _params in made.values():
        eng.close()


@pytest.fixture(scope="module")
def served(engines):
    """Three prompts served together for 12 tokens a lane: 29 and 21 in
    one padded call of the 32 bucket (a group of different lengths), 9
    alone in the 16 bucket; ``SELDON_TPU_CTX_BUCKETS=2``, so the chunk
    runs two length buckets and its lanes are a permutation of the slots
    the state rests by."""
    kept = {}

    def get(lane, dtype=jnp.float32):
        key = (lane, jnp.dtype(dtype).name)
        if key not in kept:
            eng, params = engines(lane, dtype, ctx_buckets="2")
            assert eng.lane_report()["ctx_buckets"] == 2
            with harness.tracing(eng):
                kept[key] = (harness.serve(eng, PROMPTS, 12), params)
            assert eng.engine_stats()["bucketed_chunks"] > 0
        return kept[key]

    return get


def reference_rows(params, prompt, tokens, variant=None, model=TINY):
    return np.asarray(ref.logits(params, model, prompt + tokens[:-1],
                                 tail=len(tokens), variant=variant))


@pytest.mark.parametrize("lane", ["gather", "kernel"])
def test_prefill_then_decode_agrees_with_the_reference_on_logits(served, lane):
    out, params = served(lane)
    for prompt, (tokens, rows) in zip(PROMPTS, out):
        want = reference_rows(params, prompt, tokens)
        np.testing.assert_allclose(rows, want, atol=TOL)
        assert tokens == want.argmax(-1).tolist()  # greedy, no near-tie at this seed


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_a_wrong_program_fails_the_tolerance(served, variant):
    """The controls: each of the reference's wrong programs is further
    from the served rows than the tolerance the sound one passes, on the
    prompt that was prefilled in a group and padded."""
    out, params = served("gather")
    tokens, rows = out[0]
    wrong = reference_rows(params, PROMPTS[0], tokens, variant=variant)
    assert np.abs(rows - wrong).max() > 100 * TOL


def test_q_through_a_bottleneck_fails_the_tolerance():
    """The sixth control, a wrong SPEC: DeepSeek-V3's q path (``q_a``, a
    norm, ``q_b``) where the source has ``q_lora_rank`` null.  Served with
    its own tree; the reference is handed the nearest plain projection,
    ``q = q_a q_b``, and everything else unchanged: the norm between the
    two alone moves the rows past the tolerance."""
    wrong = model_spec("bailing_hybrid", **{
        **{k: getattr(SPEC, k) for k in (
            "num_experts", "experts_per_tok", "expert_width", "dense_layers",
            "dense_width", "shared_experts", "n_group", "topk_group", "experts_held",
            "expert_offset", "kv_rank", "nope_dim", "rope_dim", "v_dim", "layer_kinds",
            "lin_heads", "lin_key_dim", "lin_value_dim")}, "q_rank": 24})
    assert wrong.q_rank == 24 and SPEC.q_rank == 0
    eng, params = harness.build(wrong, SIZES, "gather", jnp.float32, **ENGINE)
    try:
        with harness.tracing(eng):
            tokens, rows = harness.serve(eng, [PROMPTS[0]], 6)[0]
    finally:
        eng.close()
    plain = {k: dict(v) if k.startswith("block_") else v for k, v in params.items()}
    for i, kind in enumerate(SPEC.layer_kinds[:SIZES["num_layers"]]):
        block = plain[f"block_{i}"]
        assert ("q_a" in block) == (kind == "full") and "q" not in block
        if kind == "full":
            block["q"] = {"kernel": block["q_a"]["kernel"] @ block["q_b"]["kernel"]}
    want = reference_rows(plain, PROMPTS[0], tokens)
    assert np.abs(rows - want).max() > 100 * TOL
    # ... and the sound spec's tree has the one plain projection
    tree = init_params(SPEC, dict(SIZES, max_len=128), 3, dtype=jnp.float32)
    assert tree["block_2"]["q"]["kernel"].shape == (64, 4 * 12)
    assert "q_a" not in tree["block_2"] and "q_a_norm" not in tree["block_2"]


def test_the_scan_s_kernel_serves_the_same_rows_under_a_channel_decay(monkeypatch):
    """The prefill's scan as the kernel ``delta_chunk_scan`` (under the
    interpreter) inside the engine's prefill programs, the decay a key
    channel: the reference's rows, ``lane_report()`` says ``"pallas"`` and
    ``delta_scan_kernel_positions`` counts every padded position."""
    from seldon_core_tpu.ops import delta

    monkeypatch.setattr(delta, "backend", lambda: "interpret")
    eng, params = harness.build(SPEC, SIZES, "gather", jnp.float32, **ENGINE)
    try:
        harness.hold(monkeypatch, eng)
        assert eng.lane_report()["delta_scan"] == "pallas"
        out = harness.serve(eng, PROMPTS, 3)
        for prompt, (tokens, rows) in zip(PROMPTS, out):
            np.testing.assert_allclose(
                rows, reference_rows(params, prompt, tokens), atol=TOL)
        stats = eng.engine_stats()
        assert stats["delta_scan_kernel_positions"] == stats["delta_prefill_positions"] > 0
    finally:
        eng.close()


def test_sixty_four_tokens_across_two_chunk_calls(engines):
    """A chunk of 32 steps: the state is carried by the program's scan
    and stored back with the pool; after each call the lane's logits are
    the reference's row, and every token its argmax."""
    eng, params = engines("gather", steps_per_call=32)
    prompt = PROMPTS[1]
    with harness.tracing(eng):
        stream = eng.submit(np.asarray(prompt, np.int32), max_new_tokens=64)
        rows = []
        while not stream.event.is_set():
            slot = stream.slot
            eng.step()
            rows.append(np.asarray(eng._logits[slot if slot is not None else 0]))
    tokens = stream.result.tolist()
    assert len(tokens) == 64 and eng.engine_stats()["chunks"] == 2
    want = np.asarray(ref.logits(params, TINY, prompt + tokens, tail=65))
    assert tokens == want[:-1].argmax(-1).tolist()
    np.testing.assert_allclose(rows[0], want[32], atol=TOL)
    np.testing.assert_allclose(rows[1], want[64], atol=TOL)


def test_a_reused_slot_never_sees_the_old_stream_s_state(engines):
    eng, params = engines("gather", max_slots=1)
    with harness.tracing(eng):
        first = harness.serve(eng, [PROMPTS[0]], 6)[0]
        state_after = [np.asarray(s) for s in eng.cache.state]
        assert all(np.abs(s).max() > 0 for s in state_after)
        second = harness.serve(eng, [PROMPTS[2]], 6)[0]
    for prompt, (tokens, rows) in ((PROMPTS[0], first), (PROMPTS[2], second)):
        np.testing.assert_allclose(rows, reference_rows(params, prompt, tokens),
                                   atol=TOL)


def test_an_evicted_stream_restores_by_prefilling_again(engines):
    eng, _params = engines("gather", max_slots=1)
    prompt = np.asarray(PROMPTS[1], np.int32)
    with harness.tracing(eng):
        whole = eng.submit(prompt, max_new_tokens=8)
        while not whole.event.is_set():
            eng.step()
        cut = eng.submit(prompt, max_new_tokens=8)
        for _ in range(3):
            eng.step()
        with eng._lock:
            eng._evict_locked(cut)
            eng._queue.appendleft(cut)
            eng._queued.add(cut)
        while not cut.event.is_set():
            eng.step()
    assert cut.result.tolist() == whole.result.tolist()
    assert eng.engine_stats()["evictions"] >= 1


def test_bfloat16_serves_within_its_rounding(served, engines):
    out, params = served("gather", jnp.bfloat16)
    eng, _params = engines("gather", jnp.bfloat16, ctx_buckets="2")
    assert eng.cache.state[0].dtype == jnp.float32      # the state stays float32
    assert eng.cache.conv[0].dtype == jnp.bfloat16      # the tail rests as computed
    assert eng.params["block_0"]["a"].dtype == jnp.bfloat16     # the gate's full matrix
    assert eng.params["block_0"]["b"].dtype == jnp.float32      # beta's, as a router
    assert eng.params["block_0"]["dt_bias"].dtype == jnp.float32
    assert eng.cache.pages_v is None  # one latent pool beside the state
    for prompt, (tokens, rows) in zip(PROMPTS, out):
        want = reference_rows(params, prompt, tokens)
        gap = np.abs(rows - want).max(axis=-1) / want.std(axis=-1)
        assert np.median(gap) < 0.8, gap


def test_the_report_and_the_counters(engines, served):
    served("gather")
    eng, _params = engines("gather", ctx_buckets="2")
    assert engines("gather")[0].lane_report()["ctx_buckets"] == 1
    report = eng.lane_report()
    assert report["arch"] == "bailing_hybrid" and report["attention"] == "mla"
    assert report["delta_gate"] == "channel" and report["delta_gate_floor"] == -5.0
    assert report["state_kinds"] == {"linear": 4}
    assert report["layer_kinds"] == ["linear", "linear", "full"] * 2
    assert report["delta_state_dtype"] == "float32"
    assert report["delta_state_shape"] == [4, 4, 16, 16]  # pack_of 1
    assert report["delta_step"] in ("xla", "pallas")
    assert report["delta_scan"] in ("xla", "pallas")
    assert report["cache_layers"] == 2  # the pool's leading axis: the MLA layers
    assert report["cache_width"] == 128  # 16 + 4 values in one 128-lane tile
    assert report["experts_held"] == 8
    assert report["held_pass_rows"]["chunk"] > 0
    assert report["delta_state_bytes"] == 4 * SPEC.state_bytes(6)
    stats = eng.engine_stats()
    assert stats["delta_state_bytes"] == report["delta_state_bytes"]
    assert stats["delta_lane_steps"] == 4 * stats["decode_lane_steps"] > 0
    assert stats["latent_kv_tokens"] == 2 * stats["decode_kv_tokens"] > 0
    assert stats["delta_prefill_positions"] == 4 * stats["prefill_padded_tokens"]
    assert stats["delta_prefill_real_positions"] == 4 * stats["prefill_tokens"]
    assert stats["delta_scan_kernel_positions"] == 0 and report["delta_scan"] == "xla"
    assert stats["delta_slots_live"] == 0
    # every real token is routed to top-4 in each of the 5 routed layers,
    # a linear layer's as a full one's; a quarter of the router's outputs
    # are held here
    tokens = stats["prefill_tokens"] + stats["decode_lane_steps"]
    assert stats["moe_assignments"] == 4 * 5 * tokens
    assert 0.1 < stats["moe_local_assignments"] / stats["moe_assignments"] < 0.45
    assert stats["moe_held_active_expert_steps"] > 0


def test_the_programs_carry_the_scopes(engines):
    eng, _params = engines("gather")
    with harness.tracing(eng):
        text = eng.lower_chunk(1, ((4, 4),)).as_text(debug_info=True)
    for scope in ("seldon.delta.step", "seldon.delta.conv", "seldon.delta.gate"):
        assert scope in text, scope
    assert "seldon.delta.scan" not in text  # a decode step scans nothing
    unwrap = lambda fn: fn if hasattr(fn, "lower") else fn.__wrapped__  # noqa: E731
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    with harness.tracing(eng):
        text = unwrap(eng._build_prefill(16, 2)).lower(
            eng.params, *eng._kv_args(), i32(2, 16), i32(2), i32(2, 2),
            slots=i32(2)).as_text(debug_info=True)
    for scope in ("seldon.delta.scan", "seldon.delta.conv", "seldon.delta.gate"):
        assert scope in text, scope


def test_the_published_spec_and_its_bytes():
    spec = model_spec("bailing_hybrid")
    assert spec is BAILING_HYBRID and spec.linear and spec.latent and not spec.kinds
    assert spec.layer_kinds == (("linear",) * 5 + ("full",)) * 7
    assert (spec.cache_layers(12), spec.state_layers(12)) == (2, 10)
    assert (spec.cache_layers(42), spec.state_layers(42)) == (7, 35)
    assert spec.lin_channels == 12_288 and spec.q_rank == 0 and spec.cache_pools == 1
    assert spec.cache_width(2560) == 640
    assert (spec.lin_gate, spec.lin_gate_floor, spec.lin_out_gate) == (
        "channel", -5.0, "sigmoid_head")
    # a lane: 10 x (32 x 128 x 128 x 4 B + 3 x 12,288 x 2 B)
    assert spec.state_bytes(12) == 10 * (2_097_152 + 73_728) == 21_708_800
    kw = dict(ctx_len=6144, d_model=2560, num_layers=2, chunk_impl="pool")
    one = paged.paged_hbm_accounting(streams=1, state_bytes=spec.state_bytes(12), **kw)
    none = paged.paged_hbm_accounting(streams=1, **kw)
    assert one["peak_bytes"] - none["peak_bytes"] == one["state_bytes"] == 21_708_800
    # a layer routes whatever its kind: the first two are dense
    assert [spec.layer_routed(i) for i in range(4)] == [False, False, True, True]


@pytest.mark.parametrize("sizes, match", [
    ({"layer_kinds": ("linear", "window")}, "linear"),
    ({"lin_conv": 1}, "lin_conv"),
    ({"hc_mult": 4}, "has no"),
    ({"lin_gate": "head"}, "lin_gate"),
    ({"lin_gate_floor": 0.5}, "lin_gate"),
    ({"lin_out_gate": "tanh"}, "lin_gate"),
    ({"expert_swiglu_limits": [0, 0, 4]}, "expert_swiglu_limit_list"),
    ({"shared_swiglu_limits": [0, 5]}, "share_expert_swiglu_limit_list"),
])
def test_sizes_are_the_arch_s_own(sizes, match):
    with pytest.raises(ValueError, match=match):
        model_spec("bailing_hybrid", **sizes)


def test_the_variant_is_not_another_arch_s():
    for arch in ("olmo_hybrid", "deepseek_v3", "dots3_note"):
        with pytest.raises(ValueError, match="has no"):
            model_spec(arch, lin_gate="channel")
    with pytest.raises(ValueError, match="linear layers stand beside"):
        model_spec("dots3_note", layer_kinds=("linear", "full"))
    # all-zero limit lists are what the served layers have
    assert model_spec("bailing_hybrid", expert_swiglu_limits=[0] * 12,
                      shared_swiglu_limits=[0] * 12).expert_swiglu_limits == (0.0,) * 12


def _engine(**kw):
    params = init_params(SPEC, dict(SIZES, max_len=128), 3, dtype=jnp.float32)
    return PagedEngine(params, **SIZES, dtype=jnp.float32, spec=SPEC, max_len=128,
                       page_size=8, max_slots=2, **kw)


class TestFences:
    """Every lane a state a lane, a latent row or a routed layer is
    refused for stays refused by name for this spec: the first fence a
    request meets answers."""

    @pytest.mark.parametrize("kw, env, match, fence", [
        ({"prefix_cache": True}, {}, "prefix cache", "a state a lane"),
        ({"chunk_token_budget": 64}, {}, "chunked prefill", "a state a lane"),
        ({"max_adapters": 2}, {}, "adapters", "a state a lane"),
        ({"speculative": {"draft": "ngram"}}, {}, "speculative", "a latent pool"),
        ({"tp": 2}, {}, "one chip", "routes tokens to experts"),
        ({"quantize": "int8"}, {}, "precision 'bf16'", "routes tokens to experts"),
        ({"precision": "w8a8"}, {}, "precision 'bf16'", "routes tokens to experts"),
        ({}, {"SELDON_TPU_KV_DTYPE": "int8"}, "int8 KV pool", "a state a lane"),
        ({}, {"SELDON_TPU_KV_OFFLOAD": "1"}, "host KV tier", "a latent pool"),
        ({}, {"SELDON_TPU_CHUNK_IMPL": "ring"}, "ring chunk", "a state a lane"),
        ({}, {"SELDON_TPU_CHUNK_TOKEN_BUDGET": "64"}, "chunked prefill", "a state a lane"),
        ({}, {"SELDON_TPU_MAX_ADAPTERS": "2"}, "adapters", "a state a lane"),
    ])
    def test_what_assumes_pages_of_k_and_v_is_refused_by_name(
            self, monkeypatch, kw, env, match, fence):
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        with pytest.raises(ValueError, match=match) as err:
            _engine(**kw)
        assert "bailing_hybrid" in str(err.value) and fence in str(err.value)

    def test_containers_are_refused_by_name(self):
        eng = _engine()
        try:
            for call in (lambda: eng.prefill_export([1, 2, 3]),
                         lambda: eng.submit_prefilled({}),
                         lambda: eng.migrate_import({})):
                with pytest.raises(ValueError, match="cannot take a state a lane yet") as err:
                    call()
                assert "latent rows" in str(err.value)
            assert eng.migrate_export() == []
            assert not eng.cache.prefix_enabled
        finally:
            eng.close()


@pytest.mark.parametrize("index", [1, 2])
def test_the_shares_sum_to_the_uncut_layer(index):
    """The guide's share test on a routed KDA layer (1) and a routed MLA
    layer (2): the four shares' routed parts (8 of 32 experts each), the
    shared expert counted once, sum to the uncut layer's result."""
    import jax

    uncut = dict(TINY, num_experts=32, num_experts_published=32, expert_offset=0)
    spec, sizes = ref.spec_and_config(uncut)
    params = init_params(spec, dict(sizes, max_len=128), 5, dtype=jnp.float32)
    p = params[f"block_{index}"]
    x = jax.random.normal(jax.random.key(index), (19, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole_parts = {}
        whole = np.asarray(ref.layer(p, uncut, x, index, parts=whole_parts))
        routed = []
        for share in range(4):
            sub = dict(TINY, expert_offset=8 * share)
            cut = {k: (v[8 * share:8 * share + 8] if k.startswith("experts_") else v)
                   for k, v in p.items()}
            parts = {}
            ref.layer(cut, sub, x, index, parts=parts)
            routed.append(np.asarray(parts["routed"]))
            np.testing.assert_allclose(parts["shared"], whole_parts["shared"], atol=1e-6)
        attended = whole - np.asarray(whole_parts["routed"]) - np.asarray(whole_parts["shared"])
    np.testing.assert_allclose(
        attended + sum(routed) + np.asarray(whole_parts["shared"]), whole, atol=2e-5)
    assert max(np.abs(part).max() for part in routed) > 0.01
