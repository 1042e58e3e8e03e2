"""``dots3_note`` on the paged engine (PR 38): layers of two attention
kinds in one model — full latent layers whose rows attend the positions
a learned indexer picks, window latent layers of another width whose
pages go back to the allocator behind the window — over a cache of three
row kinds, compared on **logits** with the benchmark's plain float32
reference (``benchmarks/reference/dots3_note.py``: naive attention under
full masks, the selection by a stable sort, a loop over the held
experts).

Small size, CPU: d 32, 4 layers (full, window, window, full; the first
dense), full layers of 4 heads (ranks 24 / 16, 8 nope + 8 rope against
values of 8) with an indexer of 4 heads x 16 that keeps 16 positions,
window layers of 2 heads (ranks 24 / 32, 16 + 8 against 8) over 9
positions, 8 experts of 16 (top-2) of which the replica holds 2 from the
third on, beside a shared one; pages of 4 tokens.  The engine is driven
through its own front door (``submit`` / ``step``: its allocator, its
tables, its compiled programs), on the kernel lane (Pallas in interpret
mode) and the XLA gather lane; a prefill program's logits are read where
the engine calls it.

``test_dots3_prefill.py`` has the indexed prefill under the fused
kernel, the reference's tail, the share and the component's front door
(PR 44 split one file of 821 s along its classes; this one holds the
module's engines).
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paged_harness as harness
from seldon_core_tpu.models import paged
from seldon_core_tpu.models.paged import PagedEngine
from seldon_core_tpu.models.spec import init_params, model_spec

ref, MODEL = harness.MODELS["dots3"]
SPEC, SIZES = ref.spec_and_config(MODEL)
PAGE, MAX_LEN, SLOTS = 4, 64, 4
TOPK, WINDOW = MODEL["index_topk"], MODEL["sliding_window_size"]
RNG = np.random.default_rng(11)
# past index_topk and past the window at its first decode step | under
# both at first, over both by the end | a window's worth
PROMPTS = [RNG.integers(0, 64, size=n).tolist() for n in (21, 5, 33)]
NEW = 14
# what a decode step's indexer scores its cached keys with, by lane
INDEX_IMPL = {"kernel": "kernel", "gather": "xla"}

# float32 compute against a float32 reference: what is left is the order
# of sums (absorbed against naive attention, a paged softmax merged by
# the flash rule, a grouped matmul).  Logits have unit spread; the
# largest difference seen over lanes, prompts and steps is 4e-6.  1e-4
# is ~25x that, and a fortieth of what the mildest wrong program
# below (a window one position short) moves them by.
F32_ATOL = 1e-4
# bfloat16 compute through 4 layers at d = 32 (the stated precision:
# every matmul output, the rows and indexer keys in the pools, q with
# W_uk folded in and the softmax weights are rounded).  Largest
# difference from the float32 reference over 15 rows, by weight seed 3-8:
# 0.022, 0.35, 0.017, 0.066, 0.20, 0.026 of the logits' spread — the two
# large ones are seeds where rounding takes a discrete choice the other
# way (a router's second of 8, an indexer's 16th place), which at this
# size moves a whole expert or row; the test holds a seed where none
# does.  The wrong programs below move the logits by 0.48 to 2.8 on
# every seed (the mildest: 8-bit operands on seed 7, a window one
# position wide on seed 6).
BF16_ATOL, BF16_SEED = 0.12, 3

def _build(lane, dtype, seed=3, **kw):
    """``(engine, params)``, the allocator audited at every chunk
    boundary."""
    with harness.environment(SELDON_TPU_PAGED_DEBUG="1"):
        return harness.build(SPEC, SIZES, lane, dtype, seed=seed, max_len=MAX_LEN,
                             page_size=PAGE, max_slots=SLOTS, **kw)


@pytest.fixture(scope="module")
def both_f32():
    """``{lane: (engine, params)}``: one tree on both lanes."""
    made = {lane: _build(lane, jnp.float32) for lane in ("kernel", "gather")}
    yield made
    for eng, _params in made.values():
        eng.close()


@pytest.fixture(scope="module", params=["kernel", "gather"])
def module_f32(request, both_f32):
    return both_f32[request.param]


# a case's view: the lane's environment held while it steps the engine
# (the lane's knob is read again at every trace: ``harness.tracing``)
@pytest.fixture
def f32_engine(module_f32, monkeypatch):
    harness.hold(monkeypatch, module_f32[0])
    return module_f32


@pytest.fixture
def own_engine(monkeypatch):
    """``own_engine(lane, dtype, **build's) -> (engine, params)`` for a
    case that patches what a trace reads or counts from zero: the lane's
    environment held to the case's end, closed after it."""
    yield from harness.own(monkeypatch, _build)


_serve = partial(harness.serve, new=NEW)
_served_one = partial(harness.served_one, prompt=PROMPTS[0], new=NEW)


def _reference(params, prompt, tokens, **kw):
    """The reference's logits at the positions ``_serve`` reads."""
    rows = np.asarray(ref.logits(params, MODEL, prompt + tokens[:-1], **kw))
    return rows[len(prompt) - 1:]


_held_nothing = harness.held_nothing


class TestLogits:
    def test_float32_prefill_and_decode(self, f32_engine):
        """Prefill then decode through the pools, three streams side by
        side (two length buckets), contexts over ``index_topk`` and over
        the window: every logit row against the reference."""
        eng, params = f32_engine
        before = eng.engine_stats()
        served = _serve(eng, PROMPTS)
        after = eng.engine_stats()
        for prompt, (tokens, rows) in zip(PROMPTS, served):
            want = _reference(params, prompt, tokens)
            assert rows.shape == want.shape
            np.testing.assert_allclose(rows, want, atol=F32_ATOL, rtol=0)
        assert _held_nothing(eng)
        # the counters moved, and say what the selection saved
        d = {k: after[k] - before[k] for k in PagedEngine.SPARSE_COUNTERS}
        assert 0 < d["sparse_rows_read"] < d["sparse_rows_cached"]
        assert d["index_keys_scored"] > 0 and d["window_rows_read"] > 0
        assert d["sparse_lane_steps"] > 0
        # what the page loop streamed under a mask: more than was needed
        assert d["sparse_rows_read"] < d["sparse_rows_moved"] <= d["sparse_rows_cached"]
        assert after["window_pages_released"] > before["window_pages_released"]

    def test_the_read_counters_are_the_blocks_own_account(self, f32_engine):
        """A stream alone, every decode step over ``index_topk``: the
        rows the full layers read are the cached members of the
        reference's chosen sets (the step's own position, where chosen,
        is no cached row), the keys scored every cached position, a
        window's rows the ``window - 1`` before the token."""
        eng, params = f32_engine
        before = eng.engine_stats()
        (tokens, _rows), = _serve(eng, PROMPTS[:1])
        after = eng.engine_stats()
        d = {k: after[k] - before[k] for k in (
            *PagedEngine.SPARSE_COUNTERS, "decode_lane_steps")}
        n0, steps = len(PROMPTS[0]), d["decode_lane_steps"]
        chosen = []
        ref.logits(params, MODEL, PROMPTS[0] + tokens, chosen=chosen)
        at = range(n0, n0 + steps)   # each step attends from its token's position
        assert steps == NEW and len(chosen) == 2
        assert d["sparse_rows_read"] == sum(
            int(s[t, :t].sum()) for s in chosen for t in at)
        assert d["sparse_rows_read"] < 2 * TOPK * steps  # the own row was chosen somewhere
        assert d["index_keys_scored"] == d["sparse_rows_cached"] == 2 * sum(at)
        # every step selected: the page loop streamed each lane's rows
        assert d["sparse_rows_moved"] == 2 * sum(at)
        assert d["sparse_lane_steps"] == steps
        assert d["window_rows_read"] == 2 * (WINDOW - 1) * steps

    def test_stated_precision(self, own_engine):
        eng, params = own_engine("kernel", jnp.bfloat16, seed=BF16_SEED)
        (tokens, rows), = _serve(eng, PROMPTS[:1])
        want = _reference(params, PROMPTS[0], tokens)
        np.testing.assert_allclose(rows, want, atol=BF16_ATOL, rtol=0)

    @pytest.mark.parametrize("wrong", [
        "no_selection", "window_short", "window_wide", "no_gate", "no_rescale",
        "8bit"])
    def test_a_wrong_program_fails_both_tolerances(self, f32_engine, wrong):
        """What the tolerances tell apart: the reference with the
        selection dropped (every row read), the window one position
        short or wide, the gate or the rescale left out, or every matrix
        rounded to 8 bits (e4m3, the precision below the stated one)
        lies further from the served logits than either allows."""
        eng, params = f32_engine
        tokens, rows = _served_one(eng)
        if wrong == "8bit":
            rounded = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
                if a.ndim >= 2 else a, params)
            other = _reference(rounded, PROMPTS[0], tokens)
        else:
            other = _reference(params, PROMPTS[0], tokens, variant=wrong)
        assert np.abs(rows - other).max() > BF16_ATOL


class TestSelection:
    def test_both_lanes_score_the_same_keys_and_serve_the_same_tokens(self, both_f32):
        """The indexer's cached keys through the page loop over the key
        pool (the kernel lane) or gathered through the table
        (``SELDON_TPU_PAGED_KERNEL=0``): the same tokens past
        ``index_topk`` positions, the same count of keys scored, and the
        engine says which."""
        said = {}
        for lane, (eng, _params) in both_f32.items():
            with harness.tracing(eng):
                before = eng.engine_stats()["index_keys_scored"]
                served = _serve(eng, PROMPTS)
                scored = eng.engine_stats()["index_keys_scored"] - before
            said[lane] = ([tokens for tokens, _rows in served], scored)
            assert eng.lane_report()["index_score_impl"] == INDEX_IMPL[lane]
        assert said["kernel"] == said["gather"]
        assert said["kernel"][1] > 0
        assert all(len(p) + NEW > TOPK for p in PROMPTS)

    def test_reference_chosen_sets(self, f32_engine):
        """The reference's own sets: all of a row's positions while it
        has ``topk`` or fewer, exactly ``topk`` after."""
        _eng, params = f32_engine
        chosen = []
        ref.logits(params, MODEL, PROMPTS[2], chosen=chosen)
        assert len(chosen) == 2  # the two full layers
        for kept in chosen:
            counts = kept.sum(-1)
            np.testing.assert_array_equal(
                counts, np.minimum(np.arange(len(PROMPTS[2])) + 1, TOPK))


def _fake_stream(slot=0):
    s = paged._Stream(0, np.zeros((1,), np.int32), 1, 0.0, 0, -1, 0)
    s.slot = slot
    return s


class TestWindowAllocator:
    @pytest.mark.parametrize("seed", range(6))
    def test_a_page_is_held_while_a_window_reads_it_and_no_longer(
            self, f32_engine, seed):
        """Over random prompt lengths and chunk sizes: at every launch
        the lane's table names a page for every position a step of the
        chunk reads or writes, the pages wholly behind the window at the
        chunk's first position are back with the allocator (freed the
        launch after their last position left every window), and no page
        is lost."""
        eng, _ = f32_engine
        rng = np.random.default_rng(seed)
        total = eng.cache.num_window_pages - 1
        assert len(eng.cache.free_wpages) == total
        s = _fake_stream()
        length = int(rng.integers(1, 40))
        s.wpages, s.wfirst = [], eng.cache.window_first(length)
        eng.cache.window_ensure(s, length, length)
        held_before = {}
        while length < MAX_LEN - 1:
            steps = int(rng.integers(1, eng.max_steps + 1))
            horizon = min(length + steps, MAX_LEN)
            eng.cache.window_ensure(s, length, horizon)
            first = max(0, length - (WINDOW - 1))
            base = int(eng.cache.wbase[0])
            assert base % PAGE == 0 and base <= first
            row = eng.cache.wtables[0]
            for at in range(first, horizon):        # read or written this chunk
                page = row[(at - base) // PAGE]
                assert page != 0 and page not in eng.cache.free_wpages
                # ... and the same page it was when the position was written
                assert held_before.setdefault(at // PAGE, page) == page
            # nothing behind the window is kept: the table starts at the
            # page that holds the window's first position
            assert base == first // PAGE * PAGE
            assert len(s.wpages) <= eng.cache.window_pages
            assert len(set(s.wpages)) == len(s.wpages)
            assert len(eng.cache.free_wpages) + len(s.wpages) == total
            length = horizon
        eng.cache.free_window(s, eng._slots)
        assert len(eng.cache.free_wpages) == total
        eng.cache.wtables[0] = 0

    def test_pages_of_both_kinds_return_at_finish_eviction_and_abort(
            self, f32_engine):
        eng, _ = f32_engine
        # finish (the streams TestLogits served: nothing new to compile)
        _serve(eng, PROMPTS)
        assert _held_nothing(eng)
        # eviction: back to the queue with no page of either kind
        stream = eng.submit(np.asarray(PROMPTS[0], np.int32), max_new_tokens=8)
        eng.step()
        stats = eng.engine_stats()
        assert stats["full_pages_held"] > 0 and stats["window_pages_held"] > 0
        with eng._lock:
            eng._evict_locked(stream)
        assert _held_nothing(eng)
        # ... re-admitted, then aborted by its consumer
        eng.step()
        assert eng.engine_stats()["window_pages_held"] > 0
        eng.cancel(stream)
        while eng.has_work():
            eng.step()
        assert _held_nothing(eng)

    def test_a_predicted_finisher_gives_its_window_pages_up_with_its_slot(self, own_engine):
        """The serving loop's order (a wave launched before the one in
        flight is read): a stream whose budget the launched chunk
        exhausts gives up its slot and its window pages at once, a
        queued stream takes both under the wave in flight, and every
        stream still decodes the reference's greedy tokens.  Only a
        stream in a slot ever holds window pages, so the pool — every
        slot's table full — is never short."""
        eng, params = own_engine("gather", jnp.float32)  # (its audit is turned off)
        eng._debug_invariants = False      # plan from predicted state
        news = (3, 9, 9, 9, 6, 6)          # two more streams than slots
        prompts = [PROMPTS[0]] * len(news)  # (one prefill bucket: few programs)
        streams = [eng.submit(np.asarray(p, np.int32), max_new_tokens=n)
                   for p, n in zip(prompts, news)]
        total, prev, shared = eng.cache.num_window_pages - 1, None, False
        while eng.has_work():
            nxt = eng.launch()
            with eng._lock:
                holders = [s for s in streams if s.wpages]
                assert all(eng._slots[s.slot] is s for s in holders)
                assert sum(len(s.wpages) for s in holders) + len(
                    eng.cache.free_wpages) == total
                # a finisher not yet read, its slot already a joiner's
                shared |= any(
                    not s.event.is_set() and not s.wpages and s.slot is not None
                    and eng._slots[s.slot] not in (s, None) for s in streams)
            eng.harvest(prev)
            prev = nxt
        eng.harvest(prev)
        assert shared
        assert _held_nothing(eng)
        # the same prompt: every stream decodes the longest one's tokens,
        # which are the reference's greedy ones
        longest = streams[1].result.tolist()
        assert _reference(params, PROMPTS[0], longest).argmax(-1).tolist() == longest
        for s, n in zip(streams, news):
            assert s.result.tolist() == longest[:n]


class TestAccounting:
    def test_hbm_accounting_counts_the_kinds(self, f32_engine):
        eng, _ = f32_engine
        kinds = [(layers, lanes, WINDOW if name == "window" else 0)
                 for name, layers, lanes in eng.cache.kinds]
        kw = dict(d_model=32, num_layers=4, page_size=PAGE, steps_per_call=1,
                  chunk_impl="pool", dtype_bytes=4, cache_pools=1,
                  cache_kinds=kinds)
        one = paged.paged_hbm_accounting(streams=1, ctx_len=MAX_LEN, **kw)
        full = MAX_LEN * (2 * 128 + 2 * 128) * 4
        window = eng.cache.window_pages * PAGE * 2 * 128 * 4
        assert eng.cache.window_pages == 4  # 8 + 1 positions across page edges, a step
        assert (one["pool_bytes"], one["window_bytes"]) == (full + window, window)
        # a stream's bytes stop growing in the window layers
        short = paged.paged_hbm_accounting(streams=1, ctx_len=8, **kw)
        assert short["window_bytes"] == 2 * PAGE * 2 * 128 * 4
        assert paged.paged_capacity_streams(10 * (full + window), MAX_LEN, **kw) == 10
        assert paged.paged_max_context(full + window, max_len_cap=1 << 12, **kw) == MAX_LEN
        # ... and the engine's pools are what the accounting prices
        assert eng.cache.pool_shard_bytes == (
            (eng.num_pages * 2 * 2 + eng.cache.num_window_pages * 2) * PAGE * 128 * 4)

    def test_lane_report_names_the_kinds(self, f32_engine):
        eng, _ = f32_engine
        report = eng.lane_report()
        assert [(k["name"], k["layers"], k["width"], k["pages"])
                for k in report["cache_kinds"]] == [
            ("full", 2, 128, eng.num_pages), ("index", 2, 128, eng.num_pages),
            ("window", 2, 128, eng.cache.num_window_pages)]
        assert (report["index_topk"], report["window"]) == (TOPK, WINDOW)
        assert report["index_score_impl"] == INDEX_IMPL[eng.lane]
        assert report["window_table_pages"] == 4

    def test_a_prefill_call_of_4096_positions_fits_the_cell(self):
        """``prefill_position_bytes`` at the published widths leaves the
        configuration's 4,096-position call under half of what a v5e
        holds beside its weights and pools (benchmarks/configs/
        dots3-note-prev.json: 4.37 GB + 4.23 GB + 0.57 GB of 15.75 GiB)."""
        spec = model_spec("dots3_note", experts_held=8)
        per_position = paged.prefill_position_bytes(spec, 5120, 19_008, 128)
        free = 15.75 * 2 ** 30 - 4.374e9 - 4.228e9 - 0.567e9
        assert paged.prefill_positions_max(int(free), per_position) in (4096, 8192)
        # the indexed layer's blocks are counted: more than the window's rows
        assert per_position > paged.prefill_position_bytes(
            model_spec("deepseek_v3", experts_held=8), 5120, 19_008, 128)


FENCES = {
    "prefix cache": (dict(prefix_cache=True), {}, "prefix cache"),
    "chunked prefill": (dict(chunk_token_budget=64), {}, "chunked prefill"),
    "adapters": (dict(max_adapters=2), {}, "adapters"),
    "speculative lane": (dict(speculative={"draft": "ngram", "draft_k": 2}), {},
                         "speculative"),
    "host tier": ({}, {"SELDON_TPU_KV_OFFLOAD": "1"}, "host KV tier"),
    "ring chunk": ({}, {"SELDON_TPU_CHUNK_IMPL": "ring"}, "ring chunk"),
    "int8 rows": ({}, {"SELDON_TPU_KV_DTYPE": "int8"}, "int8 KV pool"),
    "a mesh": (dict(tp=2), {}, "mesh"),
}


class TestFences:
    @pytest.mark.parametrize("lane", sorted(FENCES))
    def test_a_lane_the_cache_of_kinds_cannot_take_is_refused_by_name(
            self, monkeypatch, lane):
        kw, env, named = FENCES[lane]
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        params = init_params(SPEC, SIZES, 3, dtype=jnp.float32)
        with pytest.raises(ValueError, match=named) as err:
            PagedEngine(params, **SIZES, max_len=MAX_LEN, page_size=PAGE,
                        max_slots=SLOTS, dtype=jnp.float32, spec=SPEC, **kw)
        assert "dots3_note" in str(err.value)

    @pytest.mark.parametrize("call", ["prefill_export", "migrate_import"])
    def test_disaggregation_and_migration_are_refused(self, f32_engine, call):
        eng, _ = f32_engine
        with pytest.raises(ValueError, match="dots3_note"):
            if call == "prefill_export":
                eng.prefill_export(np.asarray(PROMPTS[1], np.int32))
            else:
                eng.migrate_import({})
        # (a stream is never exported: the drain journal re-derives it)
        assert eng.migrate_export() == []

    def test_the_prefix_cache_stays_off_unasked(self, f32_engine):
        eng, _ = f32_engine
        assert eng.cache.prefix_enabled is False


