"""``smallthinker`` on the paged engine (PR 41): grouped-query heads (4
query heads on 2 K/V heads of 16) over K/V pools of kinds — full layers
WITHOUT positions whose pages grow with the stream beside window layers
with RoPE whose pages go back to the allocator behind the window — a
router that reads the attention's input, ReLU-gated experts of which the
replica holds a share behind a renormalised softmax top-k; compared on
**logits** with the benchmark's plain float32 reference
(``benchmarks/reference/smallthinker.py``: K and V repeated to the query
heads, full masks a block of queries at a time, a loop over the held
experts).

Small size, CPU: d 64, 8 layers of the published pattern (full, window,
window, window) x 2, window 8, pages of 4, 8 experts of 32 (top-2) of
which the replica holds 4 from the third on.  The engine is driven
through its own front door (``submit`` / ``step``), on the kernel lane
(Pallas in interpret mode) and the XLA gather lane; a prefill program's
logits are read where the engine calls it.
"""

from functools import partial

import numpy as np
import pytest

import jax.numpy as jnp

import paged_harness as harness

ref, MODEL = harness.MODELS["smallthinker"]
SPEC, SIZES = ref.spec_and_config(MODEL)
PAGE, MAX_LEN, SLOTS = 4, 64, 4
WINDOW = MODEL["sliding_window_size"]
RNG = np.random.default_rng(41)
# past the window at its first decode step | under it at first and past
# it by the end (the lane turns from growing to sliding) | several
# windows long, across page edges
PROMPTS = [RNG.integers(0, 64, size=n).tolist() for n in (13, 3, 30)]
NEW = 12

# float32 compute against a float32 reference: what is left is the order
# of sums (a paged softmax merged by the flash rule, grouped einsums, a
# grouped matmul).  Logits have unit spread; the largest difference seen
# over lanes, prompts and steps is 3e-6.  1e-4 is a thousandth of what
# the mildest wrong program below moves them by.
F32_ATOL = 1e-4
# bfloat16 compute through 8 layers at d = 64 (every matmul output, K and
# V in the pools, q and the softmax weights rounded).  Largest difference
# from the float32 reference over the 36 rows served, by weight seed
# 3-10: 1.38, 0.14, 0.49, 0.18, 0.032, 0.56, 0.36, 0.025 of the logits'
# spread — the large ones are seeds where rounding takes a router's
# second of 8 the other way, which at this size moves a whole expert; the
# test holds a seed where none does (a flip is not a fault: the
# benchmark's kind counts them, generation_share).  The wrong programs
# move the logits by 0.4 and more
BF16_ATOL, BF16_SEED = 0.1, 7


def _build(lane, dtype, seed=3, **kw):
    """``(engine, params)``, the allocator audited at every chunk
    boundary."""
    with harness.environment(SELDON_TPU_PAGED_DEBUG="1"):
        return harness.build(SPEC, SIZES, lane, dtype, seed=seed, max_len=MAX_LEN,
                             page_size=PAGE, max_slots=SLOTS, **kw)


@pytest.fixture(scope="module", params=["kernel", "gather"])
def module_f32(request):
    eng, params = _build(request.param, jnp.float32)
    yield eng, params
    eng.close()


# a case's view: the lane's environment held while it steps the engine
# (the lane's knob is read again at every trace: ``harness.tracing``)
@pytest.fixture
def f32_engine(module_f32, monkeypatch):
    harness.hold(monkeypatch, module_f32[0])
    return module_f32


_serve = partial(harness.serve, new=NEW)
_served_one = partial(harness.served_one, prompt=PROMPTS[0], new=NEW)


def _reference(params, prompt, tokens, **kw):
    """The reference's logits at the positions ``_serve`` reads."""
    rows = np.asarray(ref.logits(params, MODEL, prompt + tokens[:-1], **kw))
    return rows[len(prompt) - 1:]


_held_nothing = harness.held_nothing


GQA_COUNTERS = ("gqa_kv_rows_read", "gqa_kv_rows_cached", "window_rows_read",
                "window_pages_released", "decode_kv_tokens")


class TestLogits:
    def test_float32_prefill_and_decode(self, f32_engine):
        """Prefill then decode through both pools, three streams side by
        side (a group prefill, two length buckets), contexts on both
        sides of the window and across page edges: every logit row
        against the reference."""
        eng, params = f32_engine
        before = eng.engine_stats()
        served = _serve(eng, PROMPTS)
        after = eng.engine_stats()
        for prompt, (tokens, rows) in zip(PROMPTS, served):
            want = _reference(params, prompt, tokens)
            assert rows.shape == want.shape
            np.testing.assert_allclose(rows, want, atol=F32_ATOL, rtol=0)
        assert _held_nothing(eng)
        # the counters moved, and say what the windows spared
        d = {k: after[k] - before[k] for k in GQA_COUNTERS}
        assert d["gqa_kv_rows_cached"] == d["decode_kv_tokens"] * 8
        assert 0 < d["window_rows_read"] < d["gqa_kv_rows_read"] < d["gqa_kv_rows_cached"]
        # a full layer's every cached row, a window layer's live ones (a
        # lane runs a step a token, the last one's forward included)
        want_read = sum(
            2 * n + 6 * min(n, WINDOW - 1)
            for p in PROMPTS for n in range(len(p), len(p) + NEW))
        assert d["gqa_kv_rows_read"] == want_read
        assert d["window_pages_released"] > 0
        # the share's counters, under the names the latent cells export
        assert 0 < after["moe_local_assignments"] < after["moe_assignments"]
        assert after["moe_held_active_expert_steps"] > 0
        assert after["moe_held_pass_rows"] == 64


# what ``correct`` must be able to tell, held here on logits: each wrong
# program moves the float32 logits by far more than F32_ATOL
@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_a_wrong_program_is_told_from_the_served_one(f32_engine, variant):
    eng, params = f32_engine
    tokens, rows = _served_one(eng)
    wrong = _reference(params, PROMPTS[0], tokens, variant=variant)
    assert np.abs(rows - wrong).max() > 100 * F32_ATOL


class TestPages:
    def test_eviction_and_cancel_give_every_page_back(self, f32_engine):
        eng, _params = f32_engine
        streams = [eng.submit(np.asarray(p, np.int32), max_new_tokens=NEW)
                   for p in PROMPTS]
        for _ in range(3):
            eng.step()
        held = eng.engine_stats()
        assert held["window_pages_held"] > 0 and held["full_pages_held"] > 0
        # (window pages are held by a stream in a slot only: at most a
        # table's worth a lane)
        assert held["window_pages_held"] <= SLOTS * eng.cache.window_pages
        with eng._lock:
            eng._evict_locked(streams[0])
            eng._check_invariants_locked()
        eng.cancel(streams[1])
        while not all(s.event.is_set() for s in streams):
            eng.step()
        assert _held_nothing(eng)
        # the evicted stream ran again from its prompt: same tokens as a
        # stream served alone
        alone = eng.submit(np.asarray(PROMPTS[0], np.int32), max_new_tokens=NEW)
        while not alone.event.is_set():
            eng.step()
        assert streams[0].result.tolist() == alone.result.tolist()
        assert _held_nothing(eng)

    def test_lane_report_says_what_the_cache_holds(self, f32_engine):
        eng, _params = f32_engine
        report = eng.lane_report()
        assert (report["kv_heads"], report["head_dim"], report["cache_width"]) == (2, 16, 32)
        assert report["router_from"] == "attn_input" and report["experts_held"] == 4
        assert report["window"] == WINDOW and report["chunk_impl"] == "pool"
        kinds = {k["name"]: k for k in report["cache_kinds"]}
        assert (kinds["full"]["layers"], kinds["window"]["layers"]) == (2, 6)
        assert kinds["full"]["width"] == kinds["window"]["width"] == 32
        # ceil((8 - 1 + 1) / 4) + 1 columns a lane, every slot's backed
        assert report["window_table_pages"] == 3
        assert kinds["window"]["pages"] == SLOTS * 3 + 1
        # a softmax router over a share runs the held pass: the rule was
        # asked about a pass's rows, not every assignment's (PR 42)
        rows = report["held_pass_rows"]
        assert sorted(rows) == sorted(report["expert_matmul"])
        # no indexer: no layer attends under a selection's mask (PR 43)
        assert set(report["prefill_attention"]) == {
            f"b{b}" for b in eng.prompt_buckets}
        assert eng.engine_stats()["prefill_indexed_fused_positions"] == 0
        assert rows["chunk"] == eng.engine_stats()["moe_held_pass_rows"] == 64
