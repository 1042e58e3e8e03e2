"""Olmo-Hybrid on the paged engine (CPU, seeded weights, a tiny spec of
two periods: linear x 3, full, twice): the engine's own programs —
prefill in a padded bucket and in a group of different lengths, then
decode through the state a lane and the K/V pages — against
``benchmarks/reference/olmo_hybrid.py``'s full forward pass (the
recurrence position by position, no state carried) **on logits**.

Tolerances.  Float32 engine against the float32 reference: both compute
at the highest matmul precision and differ by the order of their sums;
read 3.4e-5 at logits of spread ~3 over 29 + 12 positions, held to 3e-4.
The three wrong programs of the reference (a state kept in bfloat16, beta
without its factor 2, the decay left at 1) move the same rows by 4e-3 to
tenths and each fails that tolerance.  Bfloat16 engine: at d = 64 and sixteen post-normed
sub-layers the precision itself reads high (the largest difference of a
row over its spread: 0.2 to 0.7), the float32 reference with every
matmul's operands and results rounded to bfloat16 reads 0.18 in the
median, the engine — whose full layers also score in bfloat16, at heads
of 16 — 0.32): the bfloat16 case holds the MEDIAN row under 0.8 of a
deviation, where a wrong program reads several, and is there for the
types' plumbing (a bfloat16 tail, float32 state); the published widths'
precision is read by ``tools/precision_readings.py`` and on the chip.
"""


import numpy as np
import pytest

import jax.numpy as jnp

import paged_harness as harness
from seldon_core_tpu.models import paged
from seldon_core_tpu.models.paged import PagedEngine
from seldon_core_tpu.models.spec import OLMO_HYBRID, init_params, model_spec

ref, TINY = harness.MODELS["olmo_hybrid"]
SPEC, SIZES = ref.spec_and_config(TINY)
ENGINE = dict(max_len=128, prompt_buckets=[16, 32, 64])
TOL = 3e-4
RNG = np.random.default_rng(7)
PROMPTS = [RNG.integers(0, 97, size=n).tolist() for n in (29, 21, 9)]


@pytest.fixture(scope="module")
def engines():
    made = {}

    def get(lane, dtype=jnp.float32, ctx_buckets="", **kw):
        """(``ctx_buckets`` "2": the bucketed chunk, which a spec with
        linear layers runs only where the knob asks for it)"""
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
    alone in the 16 bucket; the engines are built with
    ``SELDON_TPU_CTX_BUCKETS=2``, so the chunk runs two length buckets and
    its lanes are a permutation of the slots the state rests by."""
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


def reference_rows(params, prompt, tokens, variant=None):
    return np.asarray(ref.logits(params, TINY, prompt + tokens[:-1],
                                 tail=len(tokens), variant=variant))


@pytest.mark.parametrize("lane", ["gather", "kernel"])
def test_prefill_then_decode_agrees_with_the_reference_on_logits(served, lane):
    out, params = served(lane)
    for prompt, (tokens, rows) in zip(PROMPTS, out):
        want = reference_rows(params, prompt, tokens)
        np.testing.assert_allclose(rows, want, atol=TOL)
        assert tokens == want.argmax(-1).tolist()  # greedy, no near-tie at this seed


@pytest.mark.parametrize("variant", ["state_bf16", "beta_one", "alpha_one",
                                     "rope_full", "pre_norm"])
def test_a_wrong_program_fails_the_tolerance(served, variant):
    """The controls: each of the reference's wrong programs is further
    from the served rows than the tolerance the sound one passes, on the
    prompt that was prefilled in a group and padded."""
    out, params = served("gather")
    tokens, rows = out[0]
    wrong = reference_rows(params, PROMPTS[0], tokens, variant=variant)
    assert np.abs(rows - wrong).max() > 10 * TOL


def test_the_step_kernel_serves_the_same_rows(monkeypatch):
    """The decode step's Pallas kernel (under the interpreter) inside the
    engine's chunk program, lanes permuted and one slot idle."""
    from seldon_core_tpu.ops import delta

    monkeypatch.setattr(delta, "backend", lambda: "interpret")
    eng, params = harness.build(SPEC, SIZES, "gather", jnp.float32, **ENGINE)
    try:
        harness.hold(monkeypatch, eng)
        assert eng.lane_report()["delta_step"] == "pallas"
        out = harness.serve(eng, PROMPTS, 6)
        for prompt, (tokens, rows) in zip(PROMPTS, out):
            np.testing.assert_allclose(
                rows, reference_rows(params, prompt, tokens), atol=TOL)
    finally:
        eng.close()


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_the_scan_s_kernel_counts_the_positions_it_served(monkeypatch, form):
    """``delta_scan_kernel_positions`` beside ``delta_prefill_positions``:
    equal where the prefill's scan is the kernel ``delta_chunk_scan``
    (under the interpreter here: the same rows served), 0 on XLA's form,
    and ``lane_report()["delta_scan"]`` says which."""
    from seldon_core_tpu.ops import delta

    if form == "pallas":
        monkeypatch.setattr(delta, "backend", lambda: "interpret")
    eng, params = harness.build(SPEC, SIZES, "gather", jnp.float32, **ENGINE)
    try:
        harness.hold(monkeypatch, eng)
        assert eng.lane_report()["delta_scan"] == form
        out = harness.serve(eng, PROMPTS, 3)
        for prompt, (tokens, rows) in zip(PROMPTS, out):
            np.testing.assert_allclose(
                rows, reference_rows(params, prompt, tokens), atol=TOL)
        stats = eng.engine_stats()
        assert stats["delta_prefill_positions"] == 6 * stats["prefill_padded_tokens"] > 0
        assert stats["delta_scan_kernel_positions"] == (
            stats["delta_prefill_positions"] if form == "pallas" else 0)
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
    # after the first call 32 tokens are out: the lane's logits choose the 33rd
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
    """Eviction discards a stream's tokens and re-prefills it from
    scratch on re-admission: the prompt's state is rebuilt by the same
    prefill, so the answer is the uninterrupted one."""
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

    for prompt, (tokens, rows) in zip(PROMPTS, out):
        want = reference_rows(params, prompt, tokens)
        gap = np.abs(rows - want).max(axis=-1) / want.std(axis=-1)
        assert np.median(gap) < 0.8, gap


def test_the_report_and_the_counters(engines, served):
    served("gather")
    eng, _params = engines("gather", ctx_buckets="2")
    # unasked, a spec with linear layers runs one length bucket a chunk
    assert engines("gather")[0].lane_report()["ctx_buckets"] == 1
    report = eng.lane_report()
    assert report["arch"] == "olmo_hybrid"
    assert report["state_kinds"] == {"linear": 6}
    assert report["layer_kinds"] == ["linear", "linear", "linear", "full"] * 2
    assert report["delta_state_dtype"] == "float32"
    assert report["delta_state_shape"] == [4, 2, 8, 128]  # two heads side by side
    assert report["delta_step"] in ("xla", "pallas")
    assert report["delta_scan"] in ("xla", "pallas")
    assert report["cache_layers"] == 2  # the pool's leading axis: the full layers
    # every slot's state as it rests: 6 layers x (4 x 8 x 64 x 4 B + 3 x 320 x 4 B)
    assert report["delta_state_bytes"] == 4 * SPEC.state_bytes(8)
    stats = eng.engine_stats()
    assert stats["delta_state_bytes"] == report["delta_state_bytes"]
    assert stats["delta_lane_steps"] == 6 * stats["decode_lane_steps"] > 0
    assert stats["delta_prefill_positions"] == 6 * stats["prefill_padded_tokens"]
    assert stats["delta_prefill_real_positions"] == 6 * stats["prefill_tokens"]
    assert stats["delta_slots_live"] == 0  # idle: no slot holds a stream
    # ... and the bridge exports each under a name and kind of its own
    from seldon_core_tpu.utils.metrics import ENGINE_STATS_METRICS

    kinds = {name: ENGINE_STATS_METRICS[name][0] for name in (
        "delta_lane_steps", "delta_prefill_positions",
        "delta_prefill_real_positions", "delta_scan_kernel_positions",
        "delta_state_bytes", "delta_slots_live")}
    assert list(kinds.values()) == ["counter"] * 4 + ["gauge"] * 2
    assert len({ENGINE_STATS_METRICS[name][1] for name in kinds}) == len(kinds)


def test_the_chunk_program_carries_the_scopes(engines):
    eng, _params = engines("gather")
    with harness.tracing(eng):
        text = eng.lower_chunk(1, ((4, 4),)).as_text(debug_info=True)
    assert "seldon.delta.step" in text and "seldon.delta.conv" in text
    assert "seldon.delta.scan" not in text  # a decode step scans nothing


def test_the_published_spec_and_its_bytes():
    spec = model_spec("olmo_hybrid")
    assert spec is OLMO_HYBRID and spec.linear and not spec.kinds
    assert spec.layer_kinds == ("linear", "linear", "linear", "full") * 8
    assert (spec.cache_layers(8), spec.state_layers(8)) == (2, 6)
    assert (spec.cache_layers(32), spec.state_layers(32)) == (8, 24)
    assert spec.lin_channels == 11_520
    # a lane: 6 x (30 x 96 x 192 x 4 B + 3 x 11,520 x 2 B)
    assert spec.state_bytes(8) == 6 * (2_211_840 + 69_120) == 13_685_760
    kw = dict(ctx_len=1536, d_model=3840, num_layers=2, chunk_impl="pool")
    one = paged.paged_hbm_accounting(streams=1, state_bytes=spec.state_bytes(8), **kw)
    none = paged.paged_hbm_accounting(streams=1, **kw)
    assert one["peak_bytes"] - none["peak_bytes"] == one["state_bytes"] == 13_685_760
    # a prefill position: float32 logits over the whole vocabulary lead
    got = paged.prefill_position_bytes(spec, 3840, 100_352, 30)
    assert got == 4 * 100_352 + 6 * 3840 + max(
        4 * 30 * (2 * 384 + 2 * 288 + 3 * 64), 10 * 11_008)
    assert 0.7e9 < 2048 * 4 * 100_352 < 0.9e9  # the logits of a b512_k4 call


@pytest.mark.parametrize("sizes, match", [
    ({"layer_kinds": ("linear", "window")}, "linear"),
    ({"lin_conv": 1}, "lin_conv"),
    ({"num_experts": 8}, "no experts"),
    ({"hc_mult": 4}, "has no"),
])
def test_sizes_are_the_arch_s_own(sizes, match):
    with pytest.raises(ValueError, match=match):
        model_spec("olmo_hybrid", **sizes)


def test_linear_layers_are_not_another_arch_s():
    for arch in ("olmoe", "smallthinker", "deepseek_v3"):
        with pytest.raises(ValueError, match="has no"):
            model_spec(arch, lin_heads=4)
    with pytest.raises(ValueError, match="linear layers stand beside"):
        model_spec("smallthinker", layer_kinds=("linear", "full"))


def _engine(**kw):
    params = init_params(SPEC, dict(SIZES, max_len=128), 3, dtype=jnp.float32)
    return PagedEngine(params, **SIZES, dtype=jnp.float32, spec=SPEC, max_len=128,
                       page_size=8, max_slots=2, **kw)


@pytest.mark.parametrize("kw, env, match", [
    ({"prefix_cache": True}, {}, "prefix cache"),
    ({"chunk_token_budget": 64}, {}, "chunked prefill"),
    ({"max_adapters": 2}, {}, "adapters"),
    ({"speculative": {"draft": "ngram"}}, {}, "speculative"),
    ({"tp": 2}, {}, "a mesh"),
    ({"quantize": "int8"}, {}, "int8 weights"),
    ({"precision": "w8a8"}, {}, "int8 weights"),
    ({}, {"SELDON_TPU_KV_DTYPE": "int8"}, "int8 KV pool"),
    ({}, {"SELDON_TPU_KV_OFFLOAD": "1"}, "host KV tier"),
    ({}, {"SELDON_TPU_CHUNK_IMPL": "ring"}, "ring chunk"),
    ({}, {"SELDON_TPU_CHUNK_TOKEN_BUDGET": "64"}, "chunked prefill"),
    ({}, {"SELDON_TPU_MAX_ADAPTERS": "2"}, "adapters"),
])
def test_what_assumes_state_is_pages_is_refused_by_name(monkeypatch, kw, env, match):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    with pytest.raises(ValueError, match=match) as err:
        _engine(**kw)
    assert "olmo_hybrid" in str(err.value)
    assert "cannot take a state a lane yet" in str(err.value)


def test_containers_are_refused_by_name():
    eng = _engine()
    try:
        for call in (lambda: eng.prefill_export([1, 2, 3]),
                     lambda: eng.submit_prefilled({}),
                     lambda: eng.migrate_import({})):
            with pytest.raises(ValueError, match="cannot take a state a lane yet"):
                call()
        assert eng.migrate_export() == []
        assert not eng.cache.prefix_enabled  # unset: off, whatever the env's default
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# a second variant of the linear layer came (PR 52): this one lowers as it did
# ---------------------------------------------------------------------------

# sha256 of each program's lowered text (prefill_b16_k2, chunk_s2_4x4,
# chunk_s2_2x2_2x8) on the tree before Ling-3.0-flash (commit ad8e2de), by
# lane: the decay a key channel, the bounded gate, the head-wise output gate,
# the routed FFN inside a linear layer and the latent pool beside the state
# are facts of a call's structure, and a spec that has none of them traces
# what it traced.  A PR that changes these programs on purpose measures the
# Olmo-Hybrid cell and replaces the lines.
PARENT_SHA = {
    "kernel": ("7872a205e5159aac", "626501788f86f93d", "5f3c82525baa2bb0"),
    "gather": ("7872a205e5159aac", "6c842a92ec4ec155", "d78e842ae58957de"),
}


@pytest.mark.parametrize("lane", sorted(PARENT_SHA))
def test_the_programs_lower_as_before_a_second_variant_came(monkeypatch, lane):
    import hashlib

    monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", {"kernel": "force", "gather": "0"}[lane])
    monkeypatch.delenv("SELDON_TPU_CHUNK_IMPL", raising=False)
    sizes = dict(vocab_size=64, d_model=32, num_layers=4, num_heads=4)
    spec = model_spec("olmo_hybrid", kv_heads=4, head_dim=8, dense_width=48,
                      layer_kinds=("linear", "linear", "linear", "full"), lin_heads=4,
                      lin_key_dim=8, lin_value_dim=64, lin_conv=4)
    params = init_params(spec, sizes, 1, dtype=jnp.bfloat16)
    eng = PagedEngine(params, **sizes, max_len=64, page_size=4, max_slots=4,
                      steps_per_call=2, dtype=jnp.bfloat16, spec=spec)
    try:
        i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
        unwrap = lambda fn: fn if hasattr(fn, "lower") else fn.__wrapped__  # noqa: E731
        texts = (
            unwrap(eng._build_prefill(16, 2)).lower(
                eng.params, *eng._kv_args(), i32(2, 16), i32(2), i32(2, 4),
                slots=i32(2)).as_text(),
            eng.lower_chunk(2, ((4, 4),)).as_text(),
            eng.lower_chunk(2, ((2, 2), (2, 8))).as_text(),
        )
    finally:
        eng.close()
    assert tuple(hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts) == PARENT_SHA[lane]
