"""Jamba on the paged engine (CPU, seeded weights, a tiny spec of eight
layers under period 4 / offset 1: Mamba x 1, attention, Mamba x 3,
attention, Mamba x 2; four query heads over ONE K/V head; a tied head):
the engine's own programs — prefill in a padded bucket and in a group of
different lengths, then decode through the state a lane, the
convolution's tail and the K/V pages — against
``benchmarks/reference/jamba.py``'s full forward pass (the recurrence
position by position, no state carried) **on logits**.

Tolerances.  Float32 engine against the float32 reference: both compute
at the highest matmul precision and differ by the order of their sums;
read 1.3e-5 at logits of spread ~1 over 29 + 12 positions, held to 1e-4.
Each wrong program of the reference (``reference/jamba.py VARIANTS``: the
three inner norms, the ``-exp`` of ``A_log``, the softplus, the
convolution's bias or ``D x`` left out, a bfloat16 state, the attention
layers rotated, an untied head, the one K/V head read as four) moves the
same rows by 1e-3 to whole deviations and fails it tenfold — a bfloat16
state included, which no count of served tokens can tell.  The tenth, pad
positions scanned into the state (``Delta`` masked before its softplus),
is a wrong ENGINE: a case of its own serves it.  Bfloat16 engine: held to
the MEDIAN row under 0.8 of a deviation (``tests/test_olmo_hybrid_paged.py``
says why), there for the types' plumbing; the published widths' precision
is read by ``tools/precision_readings.py`` and on the chip.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paged_harness as harness
from seldon_core_tpu.models import paged
from seldon_core_tpu.models.paged import PagedEngine
from seldon_core_tpu.models.spec import (
    JAMBA, init_params, jamba_layer_kinds, model_spec)
from seldon_core_tpu.ops import ssm

ref, TINY = harness.MODELS["jamba"]
SPEC, SIZES = ref.spec_and_config(TINY)
ENGINE = dict(max_len=128, prompt_buckets=[16, 32, 64])
TOL = 1e-4
RNG = np.random.default_rng(11)
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


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_a_wrong_program_fails_the_tolerance(served, variant):
    """The controls: each of the reference's wrong programs is further
    from the served rows than the tolerance the sound one passes, on the
    prompt that was prefilled in a group and padded."""
    out, params = served("gather")
    tokens, rows = out[0]
    wrong = reference_rows(params, PROMPTS[0], tokens, variant=variant)
    # (a raw step size or a positive A blows the state up: a NaN is far too)
    assert not (np.abs(rows - wrong) <= 10 * TOL).all()
    assert np.isfinite(rows).all()


def test_pad_positions_scanned_into_the_state_fail_the_tolerance(monkeypatch):
    """The tenth control is a wrong ENGINE: ``Delta`` masked BEFORE its
    softplus, so a bucket's pad positions move the state by ``softplus(0)``
    each.  The prefill's own row (read at the last real position) is still
    right; every decoded row after it is not."""
    scan = ssm.scan

    def masked_before(x, dt, b, c, a, d, *, true_lens=None):
        real = jnp.arange(x.shape[1])[None, :] < true_lens[:, None]
        return scan(x, jnp.where(real[..., None], dt, np.log(2.0)), b, c, a, d)

    monkeypatch.setattr(ssm, "scan", masked_before)
    eng, params = harness.build(SPEC, SIZES, "gather", jnp.float32, **ENGINE)
    try:
        harness.hold(monkeypatch, eng)
        tokens, rows = harness.serve(eng, [PROMPTS[1]], 4)[0]
    finally:
        eng.close()
    want = reference_rows(params, PROMPTS[1], tokens)
    np.testing.assert_allclose(rows[0], want[0], atol=TOL)
    assert np.abs(rows[1:] - want[1:]).max() > 10 * TOL


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_both_forms_of_the_step_and_the_scan_serve_the_same_rows(monkeypatch, form):
    """The kernels ``ssm_state_step`` and ``ssm_scan`` (under the
    interpreter) inside the engine's programs, lanes permuted and one slot
    idle; ``lane_report()`` says which form each takes, and the counters
    count the same work on both."""
    if form == "pallas":
        monkeypatch.setattr(ssm, "backend", lambda: "interpret")
    eng, params = harness.build(SPEC, SIZES, "gather", jnp.float32, **ENGINE)
    try:
        harness.hold(monkeypatch, eng)
        report = eng.lane_report()
        assert report["ssm_step"] == report["ssm_scan"] == form
        out = harness.serve(eng, PROMPTS, 6)
        for prompt, (tokens, rows) in zip(PROMPTS, out):
            np.testing.assert_allclose(
                rows, reference_rows(params, prompt, tokens), atol=TOL)
        stats = eng.engine_stats()
        assert stats["ssm_prefill_positions"] == 6 * stats["prefill_padded_tokens"] > 0
        assert stats["ssm_lane_steps"] == 6 * stats["decode_lane_steps"] > 0
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


def test_a_reused_slot_never_sees_the_old_stream_s_state_or_tail(engines):
    eng, params = engines("gather", max_slots=1)
    with harness.tracing(eng):
        first = harness.serve(eng, [PROMPTS[0]], 6)[0]
        assert all(np.abs(np.asarray(s)).max() > 0 for s in eng.cache.state)
        assert all(np.abs(np.asarray(t)).max() > 0 for t in eng.cache.conv)
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
    # the one tied matrix rests in the compute type, and there is no head
    assert eng.params["tok_embed"]["embedding"].dtype == jnp.bfloat16
    assert "head" not in eng.params
    for prompt, (tokens, rows) in zip(PROMPTS, out):
        want = reference_rows(params, prompt, tokens)
        gap = np.abs(rows - want).max(axis=-1) / want.std(axis=-1)
        assert np.median(gap) < 0.8, gap


def test_the_report_and_the_counters(engines, served):
    served("gather")
    eng, _params = engines("gather", ctx_buckets="2")
    # unasked, a spec with a state a lane runs one length bucket a chunk
    assert engines("gather")[0].lane_report()["ctx_buckets"] == 1
    report = eng.lane_report()
    assert report["arch"] == "jamba"
    assert report["state_kinds"] == {"ssm": 6}
    assert report["layer_kinds"] == ["ssm", "full", "ssm", "ssm", "ssm", "full", "ssm", "ssm"]
    assert report["ssm_state_dtype"] == "float32"
    assert report["ssm_state_shape"] == [4, 16, 128]
    assert report["ssm_step"] in ("xla", "pallas") and report["ssm_scan"] in ("xla", "pallas")
    assert (report["kv_heads"], report["head_dim"], report["cache_width"]) == (1, 16, 16)
    assert report["cache_layers"] == 2 and report["tied_head"] is True
    assert "delta_step" not in report and "delta_state_bytes" not in report
    # every slot's state as it rests: 6 layers x (16 x 128 x 4 B + 3 x 128 x 2 B)
    assert report["ssm_state_bytes"] == 4 * SPEC.state_bytes(8) == 4 * 6 * (8192 + 768)
    stats = eng.engine_stats()
    assert stats["ssm_state_bytes"] == report["ssm_state_bytes"]
    assert stats["ssm_lane_steps"] == 6 * stats["decode_lane_steps"] > 0
    assert stats["ssm_prefill_positions"] == 6 * stats["prefill_padded_tokens"]
    assert stats["ssm_prefill_real_positions"] == 6 * stats["prefill_tokens"]
    assert stats["ssm_slots_live"] == 0  # idle: no slot holds a stream
    # the delta rule's counters stay 0 for this spec: their readers read nothing
    assert not any(stats[name] for name in (
        "delta_lane_steps", "delta_prefill_positions", "delta_prefill_real_positions",
        "delta_scan_kernel_positions", "delta_state_bytes", "delta_slots_live"))
    from seldon_core_tpu.utils.metrics import ENGINE_STATS_METRICS

    kinds = {name: ENGINE_STATS_METRICS[name][0] for name in (
        "ssm_lane_steps", "ssm_prefill_positions", "ssm_prefill_real_positions",
        "ssm_state_bytes", "ssm_slots_live")}
    assert list(kinds.values()) == ["counter"] * 3 + ["gauge"] * 2
    assert len({ENGINE_STATS_METRICS[name][1] for name in kinds}) == len(kinds)


def test_the_programs_carry_the_scopes(engines):
    eng, _params = engines("gather")
    with harness.tracing(eng):
        text = eng.lower_chunk(1, ((4, 4),)).as_text(debug_info=True)
    for scope in ("seldon.ssm.step", "seldon.ssm.conv", "seldon.ssm.select"):
        assert scope in text, scope
    assert "seldon.ssm.scan" not in text  # a decode step scans nothing
    assert "seldon.delta" not in text     # ... and this is no delta rule
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    unwrap = lambda fn: fn if hasattr(fn, "lower") else fn.__wrapped__  # noqa: E731
    with harness.tracing(eng):
        text = unwrap(eng._build_prefill(16, 2)).lower(
            eng.params, *eng._kv_args(), i32(2, 16), i32(2), i32(2, 2),
            slots=i32(2)).as_text(debug_info=True)
    for scope in ("seldon.ssm.scan", "seldon.ssm.conv", "seldon.ssm.select"):
        assert scope in text, scope
    assert "seldon.ssm.step" not in text


def test_the_published_spec_its_layer_rule_and_its_bytes():
    spec = model_spec("jamba")
    assert spec is JAMBA and spec.ssm and spec.recurrent and not spec.linear
    assert not spec.kinds and spec.tied_head and spec.state_kind == "ssm"
    # the jamba family's rule: attention where i % 14 == 7, 26 : 2 of 28
    kinds = jamba_layer_kinds(28)
    assert spec.layer_kinds == kinds and len(kinds) == 28
    assert [i for i, k in enumerate(kinds) if k == "full"] == [7, 21]
    assert kinds.count("ssm") == 26
    assert jamba_layer_kinds(8, 4, 1) == SPEC.layer_kinds
    assert (spec.cache_layers(28), spec.state_layers(28)) == (2, 26)
    assert (spec.cache_layers(14), spec.state_layers(14)) == (1, 13)
    assert spec.cache_width(2560) == 128 and spec.head_sizes(20, 2560) == (1, 128)
    assert spec.attn_kind(7, 20).positions == "none"
    # a lane: 26 x (16 x 5,120 x 4 B + 3 x 5,120 x 2 B)
    assert spec.state_bytes(28) == 26 * (327_680 + 30_720) == 9_318_400
    assert spec.state_shape(256) == (256, 16, 5120)
    kw = dict(ctx_len=1536, d_model=128, num_layers=2, chunk_impl="pool")
    one = paged.paged_hbm_accounting(streams=1, state_bytes=spec.state_bytes(28), **kw)
    none = paged.paged_hbm_accounting(streams=1, **kw)
    assert one["peak_bytes"] - none["peak_bytes"] == one["state_bytes"] == 9_318_400
    # a prefill position: the kept float32 logits term, then the SwiGLU's
    # rows against the mixer's (2 x bf16 + 4 x f32 of 5,120 and the low rank)
    got = paged.prefill_position_bytes(spec, 2560, 65_536, 20)
    assert got == 4 * 65_536 + 6 * 2560 + max(
        (8 * 20 + 4 * 1) * 128, 20 * 5120 + 4 * (160 + 64), 10 * 8192)


@pytest.mark.parametrize("sizes, match", [
    ({"layer_kinds": ("ssm", "linear", "full")}, "two states a lane"),
    ({"layer_kinds": ("ssm", "window")}, "state-space layers stand beside"),
    ({"num_experts": 2}, "routed FFN inside a state-space stack"),
    ({"experts_per_tok": 2}, "routed FFN inside a state-space stack"),
    ({"ssm_conv": 1}, "ssm_conv"),
    ({"ssm_proj_bias": True}, "mamba_proj_bias"),
    ({"lin_heads": 4}, "has no"),
    ({"hc_mult": 4}, "has no"),
])
def test_sizes_are_the_arch_s_own(sizes, match):
    with pytest.raises(ValueError, match=match):
        model_spec("jamba", **sizes)


def test_one_expert_is_the_dense_ffn_and_state_space_layers_are_not_another_arch_s():
    assert model_spec("jamba", num_experts=1, experts_per_tok=1) is JAMBA
    for arch in ("olmoe", "smallthinker", "olmo_hybrid"):
        with pytest.raises(ValueError, match="has no"):
            model_spec(arch, ssm_inner=128)
    with pytest.raises(ValueError, match="state-space layers stand beside"):
        model_spec("olmo_hybrid", layer_kinds=("ssm", "full"))


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
    assert "jamba" in str(err.value) and "16 x 128 float32 a lane" in str(err.value)
    assert "state-space layers" in str(err.value)
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
