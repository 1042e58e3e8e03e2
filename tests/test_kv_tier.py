"""Hierarchical KV tier (r22): host-RAM/disk demotion of evicted
prefix and session pages with promote-on-hit.

Correctness bar: a chain that was demoted and promoted back decodes
greedy bit-exact against one that was never evicted — the tier stores
pages exactly as resident (bf16/f32, or int8+scales) and the promote
scatter is the disaggregation import, so no numeric path changes.
Exactness asserts in f32, the single-numeric-regime discipline every
cross-program suite here uses; the slow matrix covers ring|pool ×
int8-KV × tp=2 on top.

The off lane must be free: ``SELDON_TPU_KV_OFFLOAD=0`` (default)
lowers byte-identically, sheds exactly the tier keys from
engine_stats, and discards reclaimed pages exactly as before.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.codec.bufview import pack_kv_handoff
from seldon_core_tpu.codec.tensor import PayloadError
from seldon_core_tpu.models.kvtier import HostKvTier
from seldon_core_tpu.models.paged import PagedEngine, paged_hbm_accounting
from seldon_core_tpu.models.transformer import TransformerLM

CFG = dict(vocab_size=64, d_model=32, num_layers=1, num_heads=2, max_len=128)


@pytest.fixture(scope="module")
def params():
    lm = TransformerLM(dtype=jnp.float32, **CFG)
    return lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(params, **kw):
    base = dict(dtype=jnp.float32, page_size=8, max_slots=1, steps_per_call=4)
    base.update(kw)
    return PagedEngine(params, **CFG, **base)


def _sessions(n=2, tokens=40, seed=7):
    """n distinct session prompts, each spanning several full pages."""
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, CFG["vocab_size"], size=(tokens,)).astype(np.int32)
        for _ in range(n)
    ]


# ---------------------------------------------------------------------------
# tier unit level (no engine)
# ---------------------------------------------------------------------------

def _container(tokens, seed=0):
    """One valid single-page handoff container for unit tests."""
    tokens = np.asarray(tokens, np.int32)
    rng = np.random.default_rng(seed)
    kv_shape = (1, 1, len(tokens), 16)  # rank-4 flat: 1 layer, 1 page
    return pack_kv_handoff({
        "prompt": tokens,
        "last_logits": np.zeros((1,), np.float32),
        "k": rng.normal(size=kv_shape).astype(np.float32),
        "v": rng.normal(size=kv_shape).astype(np.float32),
    })


class TestHostKvTierUnit:
    def test_put_pop_roundtrip_host_level(self):
        toks = tuple(range(8))
        blob = _container(toks)
        tier = HostKvTier(budget_bytes=1 << 20)
        assert tier.put(11, 3, toks, blob) == 0
        s = tier.stats()
        assert s["host_entries"] == 1 and s["host_bytes"] == len(blob)
        payload, got_blob, level = tier.pop(11, 3, toks)
        assert level == "host" and got_blob == blob
        np.testing.assert_array_equal(payload["prompt"], np.asarray(toks))
        assert tier.pop(11, 3, toks) is None  # pop consumes
        assert tier.stats()["host_bytes"] == 0

    def test_identity_mismatch_degrades_to_miss(self):
        toks = tuple(range(8))
        tier = HostKvTier(budget_bytes=1 << 20)
        tier.put(11, 3, toks, _container(toks))
        assert tier.pop(11, 4, toks) is None          # wrong parent
        assert tier.pop(11, 3, tuple(range(1, 9))) is None  # wrong tokens
        assert tier.pop(11, 3, toks) is not None      # entry survived misses

    def test_budget_evicts_oldest_and_counts(self):
        toks = tuple(range(8))
        blob = _container(toks)
        tier = HostKvTier(budget_bytes=int(len(blob) * 1.5))
        tier.put(1, 0, toks, blob)
        evicted = tier.put(2, 0, toks, blob)
        assert evicted == 1
        assert tier.pop(1, 0, toks) is None           # oldest fell off
        assert tier.pop(2, 0, toks) is not None
        assert tier.stats()["evictions"] == 1

    def test_spill_level_roundtrip(self, tmp_path):
        toks = tuple(range(8))
        blob = _container(toks)
        tier = HostKvTier(budget_bytes=0, spill_dir=str(tmp_path),
                          spill_budget_bytes=1 << 20)
        tier.put(5, 2, toks, blob)
        s = tier.stats()
        assert s["host_entries"] == 0 and s["disk_entries"] == 1
        assert s["disk_bytes"] == len(blob)
        assert len(list(tmp_path.glob("kv_*.srt1"))) == 1
        payload, got, level = tier.pop(5, 2, toks)
        assert level == "disk" and got == blob
        assert not list(tmp_path.glob("kv_*.srt1"))   # consumed file removed

    def test_disk_crc_corruption_rejects_naming_offset(self, tmp_path):
        toks = tuple(range(8))
        blob = _container(toks)
        tier = HostKvTier(budget_bytes=0, spill_dir=str(tmp_path),
                          spill_budget_bytes=1 << 20)
        tier.put(5, 2, toks, blob)
        path = next(tmp_path.glob("kv_*.srt1"))
        raw = bytearray(path.read_bytes())
        raw[len(raw) - 9] ^= 0xFF  # last body byte, before the trailer
        path.write_bytes(bytes(raw))
        with pytest.raises(PayloadError, match=rf"offset {len(raw) - 8}"):
            tier.pop(5, 2, toks)
        # the poisoned entry is gone — it cannot be re-served
        assert tier.pop(5, 2, toks) is None
        assert tier.stats()["disk_entries"] == 0

    def test_rescan_survives_restart_and_verifies_tokens(self, tmp_path):
        toks = tuple(range(8))
        blob = _container(toks)
        first = HostKvTier(budget_bytes=0, spill_dir=str(tmp_path),
                           spill_budget_bytes=1 << 20)
        first.put(5, 2, toks, blob)
        reborn = HostKvTier(budget_bytes=1 << 20, spill_dir=str(tmp_path),
                            spill_budget_bytes=1 << 20)
        assert reborn.stats()["disk_entries"] == 1
        # rescanned entries complete identity from the prompt frame:
        # asking for different tokens under the same key is a miss
        assert reborn.pop(5, 2, tuple(range(1, 9))) is None
        got = reborn.pop(5, 2, toks)
        assert got is not None and got[2] == "disk"

    def test_audit_catches_corruption(self):
        toks = tuple(range(8))
        blob = _container(toks)
        tier = HostKvTier(budget_bytes=1 << 20)
        tier.put(1, 0, toks, blob)
        assert tier.audit() == []
        # orphaned host entry: index key disagrees with the entry's key
        entry = tier._host.pop(1)
        tier._host[99] = entry
        problems = tier.audit()
        assert any("orphaned host entry" in p for p in problems)
        tier._host.pop(99)
        tier._host[1] = entry
        # double residency: same key at both levels
        tier._disk[1] = type(
            "E", (), {"key": 1, "parent": 0, "tokens": toks,
                      "path": "/nonexistent", "nbytes": 0}
        )()
        problems = tier.audit()
        assert any("BOTH tier levels" in p for p in problems)
        del tier._disk[1]
        # byte-ledger drift is a corruption, not a rounding error
        tier._host_bytes += 1
        assert any("drifted" in p for p in tier.audit())


# ---------------------------------------------------------------------------
# engine level: demote on reclaim, promote on hit
# ---------------------------------------------------------------------------

class TestTierDemotePromote:
    def test_churn_demotes_promotes_bit_exact(self, params, monkeypatch):
        """Two sessions through a one-session pool: each admission
        reclaims the other's parked chain (demotion), each revisit
        promotes it back — greedy outputs bit-exact against a tier-off
        engine AND against a big-pool engine whose chains were never
        evicted, with the debug audit on throughout."""
        monkeypatch.setenv("SELDON_TPU_KV_OFFLOAD", "1")
        monkeypatch.setenv("SELDON_TPU_PAGED_DEBUG", "1")
        eng = _engine(params, num_pages=8)
        assert eng._kv_tier is not None
        monkeypatch.setenv("SELDON_TPU_KV_OFFLOAD", "0")
        off = _engine(params, num_pages=8)
        never = _engine(params)  # big pool: nothing ever evicted
        assert off._kv_tier is None and never._kv_tier is None

        a, b = _sessions()
        for _round in range(2):
            for p in (a, b):
                out = eng.generate(p, max_new_tokens=6)
                np.testing.assert_array_equal(
                    out, off.generate(p, max_new_tokens=6)
                )
                np.testing.assert_array_equal(
                    out, never.generate(p, max_new_tokens=6)
                )
        s = eng.engine_stats()
        assert s["kv_tier_demotions"] > 0
        assert s["kv_tier_promotions"] > 0
        assert s["kv_tier_host_hits"] > 0
        assert s["kv_tier_bytes_demoted"] > 0
        assert s["kv_tier_bytes_promoted"] > 0
        assert s["kv_tier_host_bytes"] > 0  # loser of the last round
        # promoted pages skipped their prefill: the revisit's cached
        # cursor covered the promoted chain
        assert s["completed"] == 4
        # tier-off engine re-paid prefill and shows no tier keys
        so = off.engine_stats()
        assert not any(k.startswith("kv_tier_") for k in so)

    def test_promotion_re_registers_chain_in_prefix_index(
        self, params, monkeypatch
    ):
        """After a promote + finish, the chain is HBM-registered again
        and the tier no longer holds those keys (one residency per
        key) — the next revisit is a plain HBM prefix hit."""
        monkeypatch.setenv("SELDON_TPU_KV_OFFLOAD", "1")
        monkeypatch.setenv("SELDON_TPU_PAGED_DEBUG", "1")
        eng = _engine(params, num_pages=8)
        a, b = _sessions()
        eng.generate(a, max_new_tokens=6)
        eng.generate(b, max_new_tokens=6)   # reclaims A's chain -> tier
        eng.generate(a, max_new_tokens=6)   # promotes A back
        with eng._lock:
            hbm_keys = set(eng.cache.prefix_index)
        assert not (eng._kv_tier.keys() & hbm_keys)
        s = eng.engine_stats()
        assert s["kv_tier_promotions"] >= 1

    def test_off_knob_lowers_byte_identically(self, params, monkeypatch):
        """The tier adds no program: chunk lowering is byte-identical
        default vs OFFLOAD=0 vs OFFLOAD=1 (promotion reuses the
        disaggregation import program, demotion is host-side)."""
        def text(eng):
            return eng.lower_chunk(2, ((eng.max_slots, 4),)).as_text()

        base = text(_engine(params))
        monkeypatch.setenv("SELDON_TPU_KV_OFFLOAD", "0")
        assert text(_engine(params)) == base
        monkeypatch.setenv("SELDON_TPU_KV_OFFLOAD", "1")
        assert text(_engine(params)) == base

    def test_engine_stats_carries_tier_keys_only_when_on(
        self, params, monkeypatch
    ):
        for k, on in (("0", False), ("1", True)):
            monkeypatch.setenv("SELDON_TPU_KV_OFFLOAD", k)
            s = _engine(params).engine_stats()
            tier_keys = {k for k in s if k.startswith("kv_tier_")}
            if on:
                assert {
                    "kv_tier_demotions", "kv_tier_promotions",
                    "kv_tier_host_hits", "kv_tier_disk_hits",
                    "kv_tier_misses", "kv_tier_evictions",
                    "kv_tier_bytes_demoted", "kv_tier_bytes_promoted",
                    "kv_tier_host_bytes", "kv_tier_disk_bytes",
                } <= tier_keys
            else:
                assert tier_keys == set()

    def test_audit_catches_double_resident_key(self, params, monkeypatch):
        """A key registered in the HBM prefix index AND parked in the
        tier is a partition violation the debug audit must name."""
        monkeypatch.setenv("SELDON_TPU_KV_OFFLOAD", "1")
        eng = _engine(params, num_pages=8)
        a = _sessions()[0]
        eng.generate(a, max_new_tokens=6)
        with eng._lock:
            key = next(iter(eng.cache.prefix_index))
        toks = tuple(range(8))
        eng._kv_tier.put(key, 0, toks, _container(toks))
        with eng._lock:
            with pytest.raises(RuntimeError, match="invariant"):
                eng._check_invariants_locked()
        eng._kv_tier.discard(key)
        with eng._lock:
            eng._check_invariants_locked()  # restored: clean

    def test_hbm_accounting_prices_host_tier_off_peak(self):
        base = paged_hbm_accounting(
            streams=2, ctx_len=128, d_model=32, num_layers=1
        )
        tiered = paged_hbm_accounting(
            streams=2, ctx_len=128, d_model=32, num_layers=1,
            host_tier_gib=2.0,
        )
        assert base["host_tier_bytes"] == 0  # always present
        assert tiered["host_tier_bytes"] == 2 << 30
        assert tiered["host_reclaimable_bytes"] == 2 << 30
        # host bytes are HOST memory: HBM peak must not move
        assert tiered["peak_bytes"] == base["peak_bytes"]

    def test_telemetry_snapshot_sheds_with_engine_stats(
        self, params, monkeypatch
    ):
        from seldon_core_tpu.utils.telemetry import TelemetryRing

        monkeypatch.setenv("SELDON_TPU_KV_OFFLOAD", "1")
        on = _engine(params, num_pages=8)
        monkeypatch.setenv("SELDON_TPU_KV_OFFLOAD", "0")
        off = _engine(params, num_pages=8)
        ring = TelemetryRing(replica_id="r0")
        p_on = ring.sample_engine(on)
        assert "kv_tier_host_bytes" in p_on
        assert 0.0 <= p_on["kv_tier_hit_rate"] <= 1.0
        p_off = ring.sample_engine(off)
        assert "kv_tier_host_bytes" not in p_off
        assert "kv_tier_hit_rate" not in p_off


# ---------------------------------------------------------------------------
# slow parity matrix: ring|pool x int8-KV x tp=2
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestTierParityMatrix:
    """Promote-vs-never-evicted greedy bit-exactness in f32 across
    chunk impls × the int8 KV pool (pool-impl-only) × tp=2 — the tier
    round-trips pages exactly as resident, so no combination may move
    a token."""

    def _run(self, params, monkeypatch, *, impl, kv, tp, offload):
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", impl)
        if kv:
            monkeypatch.setenv("SELDON_TPU_KV_DTYPE", kv)
        else:
            monkeypatch.delenv("SELDON_TPU_KV_DTYPE", raising=False)
        monkeypatch.setenv("SELDON_TPU_KV_OFFLOAD", "1" if offload else "0")
        kw = dict(num_pages=8)
        if tp > 1:
            kw.update(tp=tp, shard_min_weight_size=0)
        eng = _engine(params, **kw)
        outs = []
        a, b = _sessions()
        for _round in range(2):
            for p in (a, b):
                outs.append(eng.generate(p, max_new_tokens=6))
        stats = eng.engine_stats()
        eng.close()
        return outs, stats

    @pytest.mark.parametrize("impl,kv", [
        ("ring", ""), ("pool", ""), ("pool", "int8"),
    ])
    @pytest.mark.parametrize("tp", [1, 2])
    def test_promote_parity(self, params, monkeypatch, impl, kv, tp):
        on, s_on = self._run(params, monkeypatch, impl=impl, kv=kv, tp=tp,
                             offload=True)
        off, _ = self._run(params, monkeypatch, impl=impl, kv=kv, tp=tp,
                           offload=False)
        for x, y in zip(on, off):
            np.testing.assert_array_equal(x, y)
        assert s_on["kv_tier_promotions"] > 0  # the tier actually engaged
        assert s_on["kv_tier_host_hits"] > 0
