"""Page-granular automatic prefix caching (r9): refcounted page reuse,
cached-prefill skip, LRU reclamation.

Correctness bar: greedy decode is bit-exact cache-on vs cache-off —
shared pages are read-only bit-identical KV — across both chunk impls
(ring | pool) × w8a8 × speculative (including the draft-hint lane).
Exactness is asserted in the f32 regime, the same single-numeric-regime
discipline every cross-program parity suite here uses (bf16 carries the
documented one-ulp cross-program caveat — see tools/profile_prefix_cache).

Fast tier: one tiny engine pays the only compiles; the allocator,
index, capacity and collision tests are host-side.  The full parity
matrix and the churn test are @slow.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models import paged as paged_mod
from seldon_core_tpu.models.paged import PagedEngine, StreamingLM
from seldon_core_tpu.models.transformer import TransformerLM
from seldon_core_tpu.runtime.component import MicroserviceError

CFG = dict(vocab_size=64, d_model=32, num_layers=1, num_heads=2, max_len=128)


@pytest.fixture(scope="module")
def params():
    lm = TransformerLM(dtype=jnp.float32, **CFG)
    return lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(params, **kw):
    base = dict(dtype=jnp.float32, page_size=8, max_slots=2, steps_per_call=4)
    base.update(kw)
    return PagedEngine(params, **CFG, **base)


def _shared_prompts(n=3, shared_tokens=16, seed=0):
    """n prompts sharing a ``shared_tokens`` system prefix (page-aligned
    at page_size 8) with distinct suffixes."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, CFG["vocab_size"], size=(shared_tokens,)).astype(
        np.int32
    )
    return [
        np.concatenate(
            [shared, rng.integers(0, CFG["vocab_size"], size=(3 + i,)).astype(np.int32)]
        )
        for i in range(n)
    ]


class TestPrefixReuse:
    def test_sequential_shared_prefix_bit_exact_with_hits(self, params):
        """First request misses and publishes the prefix pages; every
        follower maps them and prefills only its suffix — emitting
        exactly the tokens the cache-off engine emits."""
        on = _engine(params)
        off = _engine(params, prefix_cache=False)
        prompts = _shared_prompts()
        for p in prompts:
            a = on.generate(p, max_new_tokens=6)
            b = off.generate(p, max_new_tokens=6)
            np.testing.assert_array_equal(a, b)
        s = on.engine_stats()
        assert s["prefix_misses"] == 1 and s["prefix_hits"] == 2
        # 16 shared tokens = 2 pages skipped per follower
        assert s["prefix_tokens_saved"] == 2 * 16
        assert s["prefix_pages_cached"] > 0
        # cached pages are NOT "used": they are reclaimable capacity
        assert s["pool_pages_used"] == 0
        so = off.engine_stats()
        assert so["prefix_hits"] == so["prefix_misses"] == 0
        assert so["prefix_pages_cached"] == 0

    def test_concurrent_streams_share_pages_by_refcount(self, params):
        """A follower admitted while the publisher still decodes maps
        the same physical pages (refcount 2, identical block-table
        prefix) — sharing is block-table indirection, not a copy."""
        on = _engine(params, max_slots=2)
        prompts = _shared_prompts(n=2)
        a = on.submit(prompts[0], max_new_tokens=20)
        on.step()  # admit + prefill + first chunk; registers the prefix
        assert a.slot is not None and a.result is None
        b = on.submit(prompts[1], max_new_tokens=12)
        on.step()  # admits b mid-flight
        assert b.slot is not None and b.result is None
        assert b.cached_len == 16
        shared_pages = a.pages[:2]
        assert b.pages[:2] == shared_pages
        for p in shared_pages:
            assert int(on.cache.page_ref[p]) == 2
        on.run()
        off = _engine(params, prefix_cache=False)
        np.testing.assert_array_equal(
            a.result, off.generate(prompts[0], max_new_tokens=20)
        )
        np.testing.assert_array_equal(
            b.result, off.generate(prompts[1], max_new_tokens=12)
        )
        # both finished: shared pages sit on the LRU exactly once
        for p in shared_pages:
            assert int(on.cache.page_ref[p]) == 0
            assert p in on.cache.lru

    def test_env_knob_disables(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_PREFIX_CACHE", "0")
        eng = _engine(params)
        for p in _shared_prompts():
            eng.generate(p, max_new_tokens=4)
        s = eng.engine_stats()
        assert s["prefix_hits"] == s["prefix_misses"] == 0
        assert s["prefix_pages_cached"] == 0
        assert len(eng.cache.free_pages) == eng.num_pages - 1  # all freed eagerly

    def test_constructor_arg_wins_over_env(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_PREFIX_CACHE", "0")
        eng = _engine(params, prefix_cache=True)
        assert eng.cache.prefix_enabled
        eng.generate(_shared_prompts()[0], max_new_tokens=4)
        assert eng.engine_stats()["prefix_pages_cached"] > 0

    def test_last_prompt_page_stays_private(self, params):
        """Even an exactly page-aligned prompt keeps its final page out
        of the index: the suffix prefill always has >= 1 token to
        produce next-token logits from."""
        eng = _engine(params)
        prompt = np.arange(16, dtype=np.int32) % CFG["vocab_size"]  # 2 pages
        eng.generate(prompt, max_new_tokens=4)
        eng.generate(prompt.copy(), max_new_tokens=4)
        s = eng.engine_stats()
        assert s["prefix_hits"] == 1
        # only page 0 is shareable: (16 - 1) // 8 = 1 full page
        assert s["prefix_tokens_saved"] == 8


class TestAllocator:
    def test_alloc_free_refcount_discipline(self, params):
        eng = _engine(params)
        with eng._lock:
            total = eng.num_pages - 1
            got = eng.cache.alloc(3)
            assert len(got) == 3 and len(eng.cache.free_pages) == total - 3
            assert all(int(eng.cache.page_ref[p]) == 1 for p in got)
            assert eng.cache.alloc(total) is None  # over capacity: refused
            eng.cache.free(got)
            assert len(eng.cache.free_pages) == total
            assert all(int(eng.cache.page_ref[p]) == 0 for p in got)

    def test_alloc_reclaims_lru_cached_pages(self, params):
        eng = _engine(params)
        eng.generate(_shared_prompts()[0], max_new_tokens=4)
        s = eng.engine_stats()
        assert s["prefix_pages_cached"] > 0
        with eng._lock:
            total = eng.num_pages - 1
            got = eng.cache.alloc(total)  # must evict every cached page
            assert got is not None and len(got) == total
        s = eng.engine_stats()
        assert s["prefix_pages_cached"] == 0
        assert s["prefix_evictions"] > 0

    def test_debug_invariants_clean_under_workload(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_PAGED_DEBUG", "1")
        eng = _engine(params)
        assert eng._debug_invariants
        for p in _shared_prompts():
            eng.generate(p, max_new_tokens=6)  # raises on any violation

    def test_registration_noop_after_fail_all_race(self, params):
        """fail_all from another thread between admission and prefix
        registration clears the stream's pages but leaves its slot id:
        registration must detect the lost slot and publish nothing
        (regression: it indexed the emptied pages list)."""
        eng = _engine(params)
        stream = eng.submit(_shared_prompts(n=1)[0], max_new_tokens=4)
        with eng._lock:
            admitted = eng._admit_locked()
        assert admitted and admitted[0][0] is stream
        eng.fail_all(RuntimeError("injected"))
        assert stream.pages == [] and stream.slot is not None
        with eng._lock:
            eng._publish_prefix_locked(stream)  # must not raise
        assert not eng.cache.prefix_index

    def test_invariant_checker_catches_corruption(self, params):
        eng = _engine(params)
        stream = eng.submit(_shared_prompts()[0], max_new_tokens=20)
        eng.step()
        assert stream.slot is not None
        with eng._lock:
            eng.cache.free_pages.append(stream.pages[0])  # free AND mapped
            with pytest.raises(RuntimeError, match="invariant"):
                eng._check_invariants_locked()
            eng.cache.free_pages.pop()
            eng._check_invariants_locked()  # restored: clean
        eng.run()


class TestAdmissionCapacity:
    def test_admitted_after_evicting_cached_pages(self, params):
        """A request is admitted when only LRU-cached pages stand in
        its way: allocation reclaims them instead of stalling."""
        # 6 usable pages; a finished 2-page-prompt stream caches 1 page
        eng = _engine(params, num_pages=7, max_slots=1)
        first = _shared_prompts(n=1)[0][:15]
        out_a = eng.generate(first, max_new_tokens=4)
        assert eng.engine_stats()["prefix_pages_cached"] == 1
        # 40 tokens prompt + 8 new = 6 pages: needs the cached one back
        big = (np.arange(40, dtype=np.int32) * 3) % CFG["vocab_size"]
        out_b = eng.generate(big, max_new_tokens=8)
        s = eng.engine_stats()
        assert s["prefix_evictions"] >= 1
        assert s["completed"] == 2 and s["evictions"] == 0  # no stream evicted
        off = _engine(params, num_pages=7, max_slots=1, prefix_cache=False)
        np.testing.assert_array_equal(out_a, off.generate(first, max_new_tokens=4))
        np.testing.assert_array_equal(out_b, off.generate(big, max_new_tokens=8))

    def test_submit_guard_prices_full_pool_not_free_list(self, params):
        """The SEQUENCE_TOO_LONG ceiling is the whole non-trash pool —
        a warm cache must never shrink the admissible request size."""
        eng = _engine(params, num_pages=7, max_slots=1)
        eng.generate(_shared_prompts(n=1)[0][:15], max_new_tokens=4)
        assert eng.engine_stats()["prefix_pages_cached"] > 0
        # exactly fills the pool: admissible despite the cached pages
        ok = eng.submit(np.arange(40, dtype=np.int32) % 64, max_new_tokens=8)
        eng.run()
        assert ok.error is None and ok.result is not None
        # one page over the pool: rejected regardless of cache state
        with pytest.raises(MicroserviceError, match="needs 7 pages") as exc:
            eng.submit(np.arange(48, dtype=np.int32) % 64, max_new_tokens=8)
        assert exc.value.reason == "SEQUENCE_TOO_LONG"


class TestCollisionHardening:
    def test_colliding_keys_verify_tokens_before_sharing(self, params, monkeypatch):
        """With every chain key colliding, token-equality verification
        must keep foreign KV out of the match — different prompts stay
        private (and correct); identical prompts still share."""
        monkeypatch.setattr(paged_mod.cache, "prefix_chain_key", lambda p, t: 7)
        eng = _engine(params)
        off = _engine(params, prefix_cache=False)
        p1 = (np.arange(20, dtype=np.int32) * 5) % CFG["vocab_size"]
        p2 = (np.arange(20, dtype=np.int32) * 11 + 3) % CFG["vocab_size"]
        np.testing.assert_array_equal(
            eng.generate(p1, max_new_tokens=6), off.generate(p1, max_new_tokens=6)
        )
        np.testing.assert_array_equal(
            eng.generate(p2, max_new_tokens=6), off.generate(p2, max_new_tokens=6)
        )
        s = eng.engine_stats()
        assert s["prefix_hits"] == 0 and s["prefix_misses"] == 2
        # identical tokens DO match under the colliding key
        np.testing.assert_array_equal(
            eng.generate(p1.copy(), max_new_tokens=6),
            off.generate(p1.copy(), max_new_tokens=6),
        )
        assert eng.engine_stats()["prefix_hits"] == 1


class TestObservabilitySurface:
    def test_engine_stats_carries_prefix_keys(self, params):
        s = _engine(params).engine_stats()
        for key in ("prefix_hits", "prefix_misses", "prefix_evictions",
                    "prefix_tokens_saved", "prefix_pages_cached"):
            assert key in s

    def test_flight_recorder_records_carry_prefix_fields(
        self, params, monkeypatch
    ):
        monkeypatch.setenv("SELDON_TPU_FLIGHT_RECORDER", "64")
        eng = _engine(params)
        for p in _shared_prompts(n=2):
            eng.generate(p, max_new_tokens=4)
        recs = eng.engine_stats(detail=True)["recorder"]
        assert recs
        for rec in recs:
            for key in ("prefix_hits", "prefix_tokens_saved",
                        "prefix_pages_cached"):
                assert key in rec
        # one admission wave hit (the second request)
        assert sum(r["prefix_hits"] for r in recs) == 1
        assert sum(r["prefix_tokens_saved"] for r in recs) == 16

    def test_streaminglm_exports_prefix_gauges(self):
        comp = StreamingLM(max_slots=2, steps_per_call=2, **CFG)
        comp.load()
        try:
            keys = {m["key"] for m in comp.metrics()}
            assert {"paged_prefix_hit_rate", "paged_prefix_pages_cached",
                    "paged_prefix_tokens_saved"} <= keys
        finally:
            comp.shutdown()


@pytest.mark.slow
class TestParityMatrix:
    """The tentpole correctness bar: greedy bit-exactness cache-on vs
    cache-off across chunk impls × w8a8 × speculative (incl. the
    draft-hint oracle lane), in the f32 exactness regime."""

    MCFG = dict(vocab_size=64, d_model=32, num_layers=2, num_heads=4,
                max_len=64)

    @pytest.fixture(scope="class")
    def mparams(self):
        lm = TransformerLM(dtype=jnp.float32, **self.MCFG)
        return lm.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))["params"]

    def _prompts(self):
        rng = np.random.default_rng(3)
        shared = rng.integers(0, 64, size=(17,)).astype(np.int32)
        return [
            np.concatenate(
                [shared, rng.integers(0, 64, size=(2 + i,)).astype(np.int32)]
            )
            for i in range(3)
        ]

    def _run(self, params, monkeypatch, *, impl, precision, speculative,
             prefix_cache, hints=None):
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", impl)
        eng = PagedEngine(
            params, dtype=jnp.float32, page_size=8, max_slots=2,
            steps_per_call=4, precision=precision,
            speculative=speculative, prefix_cache=prefix_cache, **self.MCFG,
        )
        outs = []
        for i, p in enumerate(self._prompts()):
            stream = eng.submit(
                p, max_new_tokens=8,
                draft_hint=None if hints is None else hints[i],
            )
            eng.run()
            outs.append(stream.result)
        return outs, eng.engine_stats()

    @pytest.mark.parametrize("impl", ["ring", "pool"])
    @pytest.mark.parametrize("precision", ["", "w8a8"])
    def test_plain_decode_parity(self, mparams, monkeypatch, impl, precision):
        on, s_on = self._run(mparams, monkeypatch, impl=impl,
                             precision=precision, speculative=None,
                             prefix_cache=True)
        off, _ = self._run(mparams, monkeypatch, impl=impl,
                           precision=precision, speculative=None,
                           prefix_cache=False)
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a, b)
        assert s_on["prefix_hits"] == 2  # the cache actually engaged

    @pytest.mark.parametrize("precision", ["", "w8a8"])
    @pytest.mark.parametrize("draft", ["ngram", "oracle"])
    def test_speculative_parity_including_draft_hint(
        self, mparams, monkeypatch, precision, draft
    ):
        plain, _ = self._run(mparams, monkeypatch, impl="ring",
                             precision=precision, speculative=None,
                             prefix_cache=False)
        spec_cfg = {"draft": draft, "draft_k": 3}
        hints = list(plain) if draft == "oracle" else None
        on, s_on = self._run(mparams, monkeypatch, impl="ring",
                             precision=precision, speculative=spec_cfg,
                             prefix_cache=True, hints=hints)
        off, _ = self._run(mparams, monkeypatch, impl="ring",
                           precision=precision, speculative=spec_cfg,
                           prefix_cache=False, hints=hints)
        for a, b, c in zip(on, off, plain):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)
        assert s_on["prefix_hits"] == 2


@pytest.mark.slow
class TestEvictionChurn:
    def test_competing_prefixes_churn_with_invariants(self, params, monkeypatch):
        """Two system prompts through a pool sized for one: sustained
        LRU reclamation (the PrefixCacheThrash traffic shape) with the
        debug audit on, outputs exact throughout."""
        monkeypatch.setenv("SELDON_TPU_PAGED_DEBUG", "1")
        rng = np.random.default_rng(9)
        shareds = [
            rng.integers(0, 64, size=(24,)).astype(np.int32) for _ in range(2)
        ]
        prompts = [
            np.concatenate(
                [shareds[i % 2],
                 rng.integers(0, 64, size=(3 + i,)).astype(np.int32)]
            )
            for i in range(6)
        ]
        eng = _engine(params, num_pages=8, max_slots=1)
        off = _engine(params, num_pages=8, max_slots=1, prefix_cache=False)
        for p in prompts:
            np.testing.assert_array_equal(
                eng.generate(p, max_new_tokens=6),
                off.generate(p, max_new_tokens=6),
            )
        s = eng.engine_stats()
        assert s["prefix_evictions"] > 0
        assert s["completed"] == 6
