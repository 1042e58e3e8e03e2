"""Live KV-state stream migration + failover (r17).

Covers the SRT1 migration container and its CRC32C integrity trailer,
`PagedEngine.migrate_export` / `migrate_import` (mid-decode resume at
the exact next token, greedy AND sampled bit-exact), the in-process
waiter-adoption lane (zero token loss for streaming consumers), the
evacuation coordinator (health-gated, priority-ordered, cost-priced,
journal fallback), the StreamingLM migration ingress + SIGTERM
evacuation plumbing, the r12 drain-journal edge cases PR 8 left
untested, and the supervisor's evacuation-chained replica specs.

Exactness bar: a migrated stream's continuation is bit-identical to the
uninterrupted run, in the f32 regime, across the standing parity matrix
(ring|pool × prefix-cache × w8a8 × tp × adapter — the slow tier).
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.codec import bufview
from seldon_core_tpu.codec.bufview import (
    crc32c,
    pack_kv_handoff,
    pack_kv_migration,
    unpack_kv_handoff,
    unpack_kv_migration,
)
from seldon_core_tpu.codec.tensor import PayloadError
from seldon_core_tpu.models.disagg import (
    evacuate_streams,
    migration_journal_entry,
)
from seldon_core_tpu.models.paged import PagedEngine, StreamingLM
from seldon_core_tpu.models.transformer import TransformerLM
from seldon_core_tpu.runtime.component import MicroserviceError
from seldon_core_tpu.utils import faults

CFG = dict(vocab_size=64, d_model=32, num_layers=1, num_heads=2, max_len=256)


@pytest.fixture(scope="module")
def params():
    lm = TransformerLM(dtype=jnp.float32, **CFG)
    return lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture(autouse=True)
def _disarm():
    faults.clear()
    yield
    faults.clear()


def _engine(params, **kw):
    base = dict(dtype=jnp.float32, page_size=8, max_slots=4, steps_per_call=4)
    base.update(kw)
    return PagedEngine(params, **CFG, **base)


def _prompt(n=40, seed=5):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], size=(n,)
    ).astype(np.int32)


def _mid_decode(eng, *submits, waves=2):
    """Submit streams and run a few waves so they are mid-decode."""
    streams = [eng.submit(*a, **k) for a, k in submits]
    for _ in range(waves):
        eng.step()
    return streams


# ---------------------------------------------------------------------------
# CRC32C integrity trailer
# ---------------------------------------------------------------------------


class TestChecksum:
    def test_crc32c_known_vector(self):
        # iSCSI check value: crc32c("123456789") == 0xE3069283
        assert crc32c(b"123456789") == 0xE3069283
        assert bufview._crc32c_py(b"123456789") == 0xE3069283

    def test_native_crc_agrees_with_python(self):
        from seldon_core_tpu.native import get_lib

        lib = get_lib()
        if lib is None or not hasattr(lib, "srt1_crc32c"):
            pytest.skip("native library without the v4 CRC surface")
        data = bytes(range(256)) * 3
        # bytes pass by pointer (c_char_p argtypes — the copy-free lane
        # crc32c() itself uses); embedded NULs are covered by length
        assert lib.srt1_crc32c(data, len(data), 0) == bufview._crc32c_py(data)
        assert lib.srt1_crc_magic() == bufview.SRT1_CRC_MAGIC

    def test_handoff_trailer_rejects_flipped_payload_byte(self, params):
        eng = _engine(params)
        payload = eng.prefill_export(_prompt(20), seed=3)
        buf = pack_kv_handoff(payload)
        # flip one byte mid-payload: without the trailer this decoded
        # as garbage KV; with it, a NAMED rejection carrying the offset
        bad = bytearray(buf)
        bad[len(buf) // 2] ^= 0x01
        with pytest.raises(PayloadError, match="CRC32C mismatch at trailer"):
            unpack_kv_handoff(bytes(bad))
        out = unpack_kv_handoff(buf)  # pristine container still decodes
        np.testing.assert_array_equal(out["prompt"], payload["prompt"])

    def test_checksum_knob_off_skips_trailer_both_ways(
        self, params, monkeypatch
    ):
        eng = _engine(params)
        payload = eng.prefill_export(_prompt(20), seed=3)
        with_trailer = pack_kv_handoff(payload)
        monkeypatch.setenv("SELDON_TPU_KV_CHECKSUM", "0")
        without = pack_kv_handoff(payload)
        assert len(without) < len(with_trailer)
        # knob-off consumer accepts BOTH forms (mixed-fleet rollouts):
        # the trailer strips unverified, its absence is fine
        unpack_kv_handoff(without)
        unpack_kv_handoff(with_trailer)

    def test_trailerless_container_accepted_with_knob_on(
        self, params, monkeypatch
    ):
        eng = _engine(params)
        payload = eng.prefill_export(_prompt(20), seed=3)
        monkeypatch.setenv("SELDON_TPU_KV_CHECKSUM", "0")
        without = pack_kv_handoff(payload)
        monkeypatch.delenv("SELDON_TPU_KV_CHECKSUM")
        unpack_kv_handoff(without)  # old producer, new consumer: OK

    def test_migration_container_trailer_rejects_corruption(self, params):
        a, b = _engine(params), _engine(params)
        _mid_decode(a, ((_prompt(),), dict(max_new_tokens=12, seed=0)))
        (payload, _stream), = a.migrate_export()
        buf = pack_kv_migration(payload)
        bad = bytearray(buf)
        bad[len(buf) // 3] ^= 0xFF
        with pytest.raises(PayloadError):
            unpack_kv_migration(bytes(bad))
        del b


# ---------------------------------------------------------------------------
# migration container
# ---------------------------------------------------------------------------


class TestContainer:
    def _payload(self, params):
        a = _engine(params)
        _mid_decode(a, ((_prompt(),), dict(
            max_new_tokens=12, seed=0, priority=2, stream_tokens=True,
        )))
        (payload, _stream), = a.migrate_export()
        return payload

    def test_round_trip_preserves_state(self, params):
        payload = self._payload(params)
        out = unpack_kv_migration(pack_kv_migration(payload))
        np.testing.assert_array_equal(out["prompt"], payload["prompt"])
        np.testing.assert_array_equal(out["tokens"], payload["tokens"])
        np.testing.assert_array_equal(out["key_data"], payload["key_data"])
        np.testing.assert_array_equal(out["k"], payload["k"])
        assert out["page_size"] == payload["page_size"]
        assert out["seed"] == payload["seed"]
        assert out["priority"] == 2
        assert out["stream_tokens"] is True
        assert out["streamed"] == payload["streamed"]
        assert out["max_new_tokens"] == 12

    def test_geometry_mismatch_rejected(self, params):
        payload = dict(self._payload(params))
        payload["tokens"] = np.asarray(
            list(payload["tokens"]) + [1] * 32, np.int32
        )  # tokens no longer fit the page count
        with pytest.raises(PayloadError, match="geometry mismatch"):
            unpack_kv_migration(pack_kv_migration(payload))

    def test_missing_entry_named(self):
        with pytest.raises(PayloadError, match="missing the 'k'"):
            pack_kv_migration({"prompt": np.arange(4), "last_logits": [],
                               "v": np.zeros((1, 1, 8, 32), np.float32)})

    def test_wrong_frame_count_rejected(self):
        buf = bufview.pack_frames([np.arange(4, dtype=np.int32)])
        with pytest.raises(PayloadError, match="frames"):
            unpack_kv_migration(buf)


# ---------------------------------------------------------------------------
# engine: migrate_export / migrate_import
# ---------------------------------------------------------------------------


class TestEngineMigration:
    def test_mid_decode_greedy_bit_exact(self, params):
        ref = _engine(params)
        expect = ref.generate(_prompt(), max_new_tokens=16, seed=7)
        a, b = _engine(params), _engine(params)
        (s,) = _mid_decode(a, ((_prompt(),), dict(max_new_tokens=16, seed=7)))
        assert 0 < len(s.tokens) < 16  # genuinely mid-decode
        (payload, stream), = a.migrate_export()
        assert a.engine_stats()["migrated_out"] == 1
        s2 = b.migrate_import(payload, stream=stream)
        assert s2 is s  # adoption: the same waiter object
        b.run()
        assert s.error is None
        np.testing.assert_array_equal(s.result, expect)
        assert b.engine_stats()["migrated_in"] == 1
        # the peer never re-paid the prompt's prefill FLOPs
        assert b.engine_stats()["prefill_tokens"] == 0

    def test_sampled_stream_resumes_same_path(self, params):
        """RNG key data travels: a temperature>0 stream's continuation
        after migration is bit-identical to the uninterrupted sampled
        run — a re-derived key would fork the sample path here."""
        ref = _engine(params)
        expect = ref.generate(
            _prompt(), max_new_tokens=16, seed=3, temperature=0.9, top_k=8
        )
        a, b = _engine(params), _engine(params)
        (s,) = _mid_decode(a, ((_prompt(),), dict(
            max_new_tokens=16, seed=3, temperature=0.9, top_k=8,
        )))
        (payload, stream), = a.migrate_export()
        b.migrate_import(payload, stream=stream)
        b.run()
        np.testing.assert_array_equal(s.result, expect)

    def test_streaming_consumer_sees_exact_continuation(self, params):
        """Zero token loss: one token queue across the migration, no
        repeats, no gaps — the tentpole invariant."""
        ref = _engine(params)
        expect = ref.generate(_prompt(), max_new_tokens=16, seed=7)
        a, b = _engine(params), _engine(params)
        (s,) = _mid_decode(a, ((_prompt(),), dict(
            max_new_tokens=16, seed=7, stream_tokens=True,
        )))
        got = []
        while s.token_queue.qsize():
            item = s.token_queue.get()
            if item:
                got.extend(item)
        assert 0 < len(got) < 16
        (payload, stream), = a.migrate_export()
        b.migrate_import(payload, stream=stream)
        b.run()
        while True:
            item = s.token_queue.get()
            if item is None:
                break
            got.extend(item)
        np.testing.assert_array_equal(np.asarray(got, np.int32), expect)

    def test_dcn_form_builds_fresh_stream(self, params):
        ref = _engine(params)
        expect = ref.generate(_prompt(), max_new_tokens=16, seed=7)
        a, b = _engine(params), _engine(params)
        _mid_decode(a, ((_prompt(),), dict(max_new_tokens=16, seed=7)))
        (payload, _stream), = a.migrate_export()
        s2 = b.migrate_import(unpack_kv_migration(pack_kv_migration(payload)))
        b.run()
        np.testing.assert_array_equal(s2.result, expect)

    def test_priority_and_deadline_carry(self, params):
        import time as _time

        a, b = _engine(params), _engine(params)
        deadline = _time.monotonic() + 30.0
        _mid_decode(a, ((_prompt(),), dict(
            max_new_tokens=16, seed=7, priority=2, deadline=deadline,
        )))
        (payload, stream), = a.migrate_export()
        assert payload["priority"] == 2
        assert 0 < payload["deadline_remaining_ms"] <= 30_000
        s2 = b.migrate_import(payload, stream=stream)
        assert s2.priority == 2
        assert s2.deadline is not None
        assert 0 < s2.deadline - _time.monotonic() <= 30.0
        b.run()
        assert s2.error is None

    def test_page_size_mismatch_is_clean_400(self, params):
        a = _engine(params)
        b = _engine(params, page_size=16)
        _mid_decode(a, ((_prompt(),), dict(max_new_tokens=12, seed=0)))
        (payload, stream), = a.migrate_export()
        with pytest.raises(MicroserviceError) as e:
            b.migrate_import(payload, stream=stream)
        assert e.value.status_code == 400
        assert e.value.reason == "KV_LAYOUT_MISMATCH"

    def test_wrong_kv_shape_is_clean_400(self, params):
        a, b = _engine(params), _engine(params)
        _mid_decode(a, ((_prompt(),), dict(max_new_tokens=12, seed=0)))
        (payload, _stream), = a.migrate_export()
        payload = dict(payload, k=payload["k"][:, :-1])
        with pytest.raises(MicroserviceError) as e:
            b.migrate_import(payload)
        assert e.value.reason == "KV_LAYOUT_MISMATCH"

    def test_mid_prefill_streams_not_exportable(self, params):
        """A stream still chunking its prefill has incomplete KV: it
        falls back to the drain journal, never a partial snapshot."""
        eng = _engine(params, chunk_token_budget=12, steps_per_call=4)
        s = eng.submit(_prompt(64), max_new_tokens=8)
        eng.step()  # one budgeted wave: a slice, not the whole prompt
        assert 0 < s.prefilled < 64
        assert eng.migrate_export() == []
        entries = eng.drain()
        assert len(entries) == 1  # the journal still covers it

    def test_queued_streams_not_exportable(self, params):
        eng = _engine(params, max_slots=1)
        s1 = eng.submit(_prompt(seed=1), max_new_tokens=16, seed=1)
        s2 = eng.submit(_prompt(seed=2), max_new_tokens=16, seed=2)
        eng.step()
        exported = eng.migrate_export()
        assert [st for _p, st in exported] == [s1]
        assert s2 in list(eng._queue)

    def test_speculative_engine_falls_back_to_journal(self, params):
        eng = _engine(params, speculative={"draft": "ngram", "draft_k": 2})
        eng.submit(_prompt(), max_new_tokens=12, seed=0)
        eng.step()
        eng.step()
        assert eng.migrate_export() == []
        assert len(eng.drain()) == 1

    def test_migrated_in_stream_excluded_from_drain_journal(self, params):
        """The r15 journal exclusion follows the stream: once imported,
        its KV came through the migration lane, and the coordinating
        layer (not the journal) owns its recovery."""
        a, b = _engine(params), _engine(params)
        _mid_decode(a, ((_prompt(),), dict(max_new_tokens=32, seed=0)))
        (payload, stream), = a.migrate_export()
        b.migrate_import(payload, stream=stream)
        b.step()  # consume the import; stream decodes mid-flight now
        assert stream.kv_imported
        assert b.drain() == []

    def test_adopted_stream_rolls_back_on_closed_peer(self, params):
        a, b = _engine(params), _engine(params)
        _mid_decode(a, ((_prompt(),), dict(max_new_tokens=12, seed=0)))
        (payload, stream), = a.migrate_export()
        b.close()
        with pytest.raises(MicroserviceError) as e:
            b.migrate_import(payload, stream=stream)
        assert e.value.status_code == 503


# ---------------------------------------------------------------------------
# evacuation coordinator
# ---------------------------------------------------------------------------


class TestEvacuation:
    def test_health_gated_and_bit_exact(self, params):
        ref = _engine(params)
        prompts = [_prompt(seed=i) for i in range(3)]
        expect = [
            ref.generate(p, max_new_tokens=12, seed=i)
            for i, p in enumerate(prompts)
        ]
        src = _engine(params)
        good, bad = _engine(params), _engine(params)
        bad._watchdog.state = "degraded"
        streams = _mid_decode(src, *[
            ((p,), dict(max_new_tokens=12, seed=i))
            for i, p in enumerate(prompts)
        ])
        summary = evacuate_streams(src, [bad, good])
        assert summary["migrated"] == 3
        assert summary["failed"] == 0
        assert bad.engine_stats()["migrated_in"] == 0
        good.run()
        for i, s in enumerate(streams):
            np.testing.assert_array_equal(s.result, expect[i])

    def test_priority_ordered_placement(self, params):
        src = _engine(params)
        peer = _engine(params)
        lo = src.submit(_prompt(seed=1), max_new_tokens=12, seed=1, priority=0)
        hi = src.submit(_prompt(seed=2), max_new_tokens=12, seed=2, priority=5)
        for _ in range(2):
            src.step()
        order = []
        real_import = peer.migrate_import

        def spy(payload, **kw):
            order.append(payload["priority"])
            return real_import(payload, **kw)

        peer.migrate_import = spy
        evacuate_streams(src, [peer])
        assert order == [5, 0]
        peer.run()
        assert hi.error is None and lo.error is None

    def test_refusing_peers_fall_back_to_journal(self, params):
        src = _engine(params)
        tiny = _engine(params, page_size=16)  # geometry mismatch: refuses
        (s,) = _mid_decode(src, ((_prompt(),), dict(max_new_tokens=12, seed=0)))
        summary = evacuate_streams(src, [tiny])
        assert summary["migrated"] == 0
        assert summary["failed"] == 1
        assert len(summary["journal"]) == 1
        entry = summary["journal"][0]
        assert entry["prompt"] == [int(t) for t in _prompt()]
        # the waiter resolved with the MIGRATING 503, not a hang
        assert s.event.is_set()
        assert s.error is not None and s.error.reason == "MIGRATING"

    def test_journal_entry_from_payload_replays(self, params):
        src = _engine(params)
        ref = _engine(params)
        expect = ref.generate(_prompt(), max_new_tokens=12, seed=9)
        _mid_decode(src, ((_prompt(),), dict(max_new_tokens=12, seed=9)))
        (payload, stream), = src.migrate_export()
        entry = migration_journal_entry(payload)
        fresh = _engine(params)
        (replayed,) = fresh.replay([entry])
        fresh.run()
        np.testing.assert_array_equal(replayed.result, expect)
        src.fail_stream(stream, MicroserviceError("x", status_code=503))

    def test_streaminglm_evacuate_end_to_end(self, params, tmp_path):
        lm_a = StreamingLM(max_new_tokens=16, seed=0, page_size=8,
                           max_slots=4, steps_per_call=4, **CFG)
        lm_b = StreamingLM(max_new_tokens=16, seed=0, page_size=8,
                           max_slots=4, steps_per_call=4, **CFG)
        import threading

        lm_a.load()
        lm_b.load()
        try:
            got = []
            done = threading.Event()

            def consume():
                for chunk in lm_a.predict_stream(
                    np.atleast_2d(_prompt()), None,
                    {"tags": {"max_new_tokens": 24, "seed": 11}},
                ):
                    got.extend(int(t) for t in chunk)
                done.set()

            # throttle A's waves so the evacuation window is deterministic
            # (a 24-token request on this tiny model would otherwise
            # finish before evacuate() quiesces the loop)
            import time as _time

            # (the serving loop chains the wave's two halves, PR 29:
            # launch is the one it calls once a wave)
            orig_launch = lm_a.engine.launch

            def slow_launch():
                _time.sleep(0.05)
                return orig_launch()

            lm_a.engine.launch = slow_launch
            t = threading.Thread(target=consume)
            t.start()
            # wait until genuinely mid-decode, then evacuate A -> B
            # (generous ceiling: under a loaded tier-1 run the first
            # chunk can take well over the uncontended couple seconds)
            for _ in range(900):
                if got:
                    break
                _time.sleep(0.02)
            assert got, "stream never started"
            summary = lm_a.evacuate([lm_b], journal_path=str(
                tmp_path / "evac.jsonl"
            ))
            assert summary["migrated"] == 1
            done.wait(timeout=30)
            assert done.is_set()
            assert len(got) == 24  # zero token loss through one queue
            # bit-identical to an uninterrupted run of the same request
            ref = StreamingLM(max_new_tokens=16, seed=0, page_size=8,
                              max_slots=4, steps_per_call=4, **CFG)
            ref.load()
            try:
                expect = ref.predict(
                    np.atleast_2d(_prompt()), None,
                    {"tags": {"max_new_tokens": 24, "seed": 11}},
                )[0]
                np.testing.assert_array_equal(np.asarray(got), expect)
            finally:
                ref.shutdown()
            t.join(timeout=10)
        finally:
            lm_a.shutdown()
            lm_b.shutdown()

    def test_streaminglm_migration_ingress(self, params):
        lm = StreamingLM(max_new_tokens=16, seed=0, page_size=8,
                         max_slots=4, steps_per_call=4, **CFG)
        lm.load()
        try:
            # StreamingLM engines run bf16: the source must match the
            # peer's pool dtype (a mismatch is the clean 400 tested above)
            a = _engine(params, dtype=jnp.bfloat16)
            _mid_decode(a, ((_prompt(),), dict(max_new_tokens=12, seed=4)))
            (payload, _stream), = a.migrate_export()
            buf = pack_kv_migration(payload)
            ack = lm.predict(
                np.frombuffer(buf, np.uint8)[None, :], None,
                {"tags": {"kv_migration": 1}},
            )
            assert ack.shape == (1, 1)
            # the import is consumed (and counted) by the decode loop's
            # next wave; the resumed stream then finishes
            import time as _time

            for _ in range(300):
                if lm.engine.engine_stats()["completed"] >= 1:
                    break
                _time.sleep(0.02)
            stats = lm.engine.engine_stats()
            assert stats["migrated_in"] == 1
            assert stats["completed"] >= 1
        finally:
            lm.shutdown()

    def test_ingress_rejects_malformed_container(self):
        lm = StreamingLM(max_new_tokens=8, seed=0, page_size=8,
                         max_slots=2, steps_per_call=4, **CFG)
        lm.load()
        try:
            with pytest.raises(MicroserviceError) as e:
                lm.predict(
                    np.zeros((1, 64), np.uint8), None,
                    {"tags": {"kv_migration": 1}},
                )
            assert e.value.status_code == 400
            assert e.value.reason == "BAD_MIGRATION_PAYLOAD"
        finally:
            lm.shutdown()


# ---------------------------------------------------------------------------
# drain-journal edge cases (the r12 gaps this PR closes)
# ---------------------------------------------------------------------------


class TestJournalEdgeCases:
    def test_entry_expiring_between_write_and_replay_skipped_with_count(
        self, params
    ):
        eng = _engine(params)
        entry = {
            "req_id": 7, "prompt": [1, 2, 3], "max_new_tokens": 4,
            "seed": 0, "deadline_remaining_ms": 0.0,
        }
        before = eng.engine_stats()["expired"]
        out = eng.replay([entry])
        assert out == []
        assert eng.engine_stats()["expired"] == before + 1
        assert eng.engine_stats()["replayed"] == 0

    def test_live_entry_with_budget_still_replays(self, params):
        eng = _engine(params)
        entry = {
            "req_id": 8, "prompt": [1, 2, 3], "max_new_tokens": 4,
            "seed": 0, "deadline_remaining_ms": 60_000.0,
        }
        (s,) = eng.replay([entry])
        eng.run()
        assert s.result is not None
        assert eng.engine_stats()["replayed"] == 1

    def test_adapterless_journal_replays_on_adapter_enabled_engine(
        self, params
    ):
        src = _engine(params, max_adapters=0)
        src.submit(_prompt(), max_new_tokens=8, seed=0)
        entries = src.drain()
        assert entries and entries[0]["adapter"] is None
        dst = _engine(params, max_adapters=2, lora_rank=4)
        out = dst.replay(entries)
        assert len(out) == 1
        dst.run()
        assert out[0].result is not None

    def test_adapter_journal_on_adapterless_engine_is_clean_skip(
        self, params
    ):
        """The vice-versa direction: an adapter-carrying entry replayed
        on a max_adapters=0 engine hits the clean 400
        ADAPTERS_DISABLED and is skipped — never a crash, never a
        half-admitted stream."""
        entry = {
            "req_id": 9, "prompt": [1, 2, 3], "max_new_tokens": 4,
            "seed": 0, "adapter": "tenant-a",
        }
        dst = _engine(params, max_adapters=0)
        out = dst.replay([entry])
        assert out == []
        assert dst.engine_stats()["replayed"] == 0
        # the engine is untouched and keeps serving
        s = dst.submit(_prompt(), max_new_tokens=4)
        dst.run()
        assert s.result is not None

    def test_adapter_submit_on_adapterless_engine_is_400(self, params):
        eng = _engine(params, max_adapters=0)
        with pytest.raises(MicroserviceError) as e:
            eng.submit(_prompt(), max_new_tokens=4, adapter="tenant-a")
        assert e.value.status_code == 400
        assert e.value.reason == "ADAPTERS_DISABLED"


# ---------------------------------------------------------------------------
# supervisor wiring
# ---------------------------------------------------------------------------


class TestReplicaSpecs:
    def test_evacuation_chain_env(self):
        from seldon_core_tpu.controlplane.supervisor import (
            replica_worker_specs,
        )

        specs = replica_worker_specs("lm", replicas=3, base_grpc=9800)
        assert [s.name for s in specs] == ["lm-0", "lm-1", "lm-2"]
        assert specs[0].env["SELDON_TPU_EVACUATE_TO"] == "grpc://127.0.0.1:9801"
        assert specs[1].env["SELDON_TPU_EVACUATE_TO"] == "grpc://127.0.0.1:9802"
        assert specs[2].env["SELDON_TPU_EVACUATE_TO"] == "grpc://127.0.0.1:9800"

    def test_chain_off_or_single_replica_has_no_peer(self):
        from seldon_core_tpu.controlplane.supervisor import (
            replica_worker_specs,
        )

        for specs in (
            replica_worker_specs("lm", replicas=2, evacuate_chain=False),
            replica_worker_specs("lm", replicas=1),
        ):
            for s in specs:
                assert "SELDON_TPU_EVACUATE_TO" not in s.env


# ---------------------------------------------------------------------------
# the standing parity matrix (slow tier): ring|pool × prefix × w8a8
# × tp × adapter — mid-decode migration must be greedy bit-exact with
# the uninterrupted run in every engine variant
# ---------------------------------------------------------------------------


def _migrate_and_compare(make_engine, submit_kw, waves=3):
    ref = make_engine()
    sref = ref.submit(_prompt(), **submit_kw)
    ref.run()
    expect = sref.result
    a, b = make_engine(), make_engine()
    s = a.submit(_prompt(), **submit_kw)
    for _ in range(waves):
        a.step()
    assert 0 < len(s.tokens) < submit_kw["max_new_tokens"]
    exported = a.migrate_export()
    assert len(exported) == 1
    payload, stream = exported[0]
    b.migrate_import(payload, stream=stream)
    b.run()
    assert s.error is None, s.error
    np.testing.assert_array_equal(s.result, expect)
    for e in (ref, a, b):
        e.close()


@pytest.mark.slow
class TestParityMatrix:
    @pytest.mark.parametrize("impl", ["ring", "pool"])
    @pytest.mark.parametrize("precision", ["", "w8a8"])
    @pytest.mark.parametrize("prefix", [True, False])
    def test_mid_decode_migration_matrix(
        self, params, monkeypatch, impl, precision, prefix
    ):
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", impl)
        _migrate_and_compare(
            lambda: _engine(params, precision=precision, prefix_cache=prefix),
            dict(max_new_tokens=16, seed=7),
        )

    def test_mid_decode_migration_tp2(self, params):
        _migrate_and_compare(
            lambda: _engine(params, tp=2),
            dict(max_new_tokens=16, seed=7),
        )

    def test_mid_decode_migration_with_adapter(self, params):
        from seldon_core_tpu.ops.lora import make_lora_params

        lora = make_lora_params(
            3, num_layers=CFG["num_layers"], d_model=CFG["d_model"], rank=4
        )

        def make():
            eng = _engine(params, max_adapters=2, lora_rank=4)
            eng.load_adapter("tenant-a", lora)
            return eng

        _migrate_and_compare(make, dict(max_new_tokens=16, seed=7,
                                        adapter="tenant-a"))


# ---------------------------------------------------------------------------
# slow e2e: SIGTERM-with-evacuation across real processes
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sigterm_evacuates_streams_to_peer_worker():
    """The full r17 failover loop across real processes: worker A
    (SELDON_TPU_EVACUATE_TO -> worker B) is SIGTERMed MID-REQUEST; the
    dying process live-migrates its in-flight stream to B as an SRT1
    migration container over gRPC (method="migrate" hops), and B's
    engine resumes decoding it — `migrated_in_total` moves and the
    stream completes on B without A's journal ever being needed."""
    import asyncio
    import socket
    import time as _time
    import urllib.request

    from seldon_core_tpu.controlplane.supervisor import (
        ProcessSpec,
        Supervisor,
    )
    from seldon_core_tpu.engine.graph import Endpoint, UnitSpec
    from seldon_core_tpu.engine.transport import GrpcClient
    from seldon_core_tpu.runtime.message import InternalMessage

    def _free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    worker_params = json.dumps([
        {"name": "vocab_size", "value": "2048", "type": "INT"},
        {"name": "d_model", "value": "64", "type": "INT"},
        {"name": "num_layers", "value": "2", "type": "INT"},
        {"name": "num_heads", "value": "4", "type": "INT"},
        {"name": "max_len", "value": "256", "type": "INT"},
        {"name": "max_new_tokens", "value": "240", "type": "INT"},
        {"name": "page_size", "value": "8", "type": "INT"},
        {"name": "max_slots", "value": "2", "type": "INT"},
        # one compiled chunk per token: the SIGTERM lands mid-stream
        {"name": "steps_per_call", "value": "1", "type": "INT"},
        {"name": "seed", "value": "0", "type": "INT"},
    ])
    a_http, a_grpc = _free_port(), _free_port()
    b_http, b_grpc = _free_port(), _free_port()
    base_env = {"JAX_PLATFORMS": "cpu", "SELDON_TPU_PLATFORM": "cpu"}
    sup = Supervisor()
    prompt = (np.arange(6, dtype=np.int32) % 64)[None, :]

    def peer_metric(name: str) -> float:
        metrics = urllib.request.urlopen(
            f"http://127.0.0.1:{b_http}/metrics", timeout=10
        ).read().decode()
        return sum(
            float(line.rsplit(" ", 1)[1])
            for line in metrics.splitlines()
            if line.startswith(name) and not line.startswith("#")
        )

    async def scenario():
        await asyncio.to_thread(
            sup.add,
            ProcessSpec(
                name="evac-b",
                component="seldon_core_tpu.models.paged.StreamingLM",
                http_port=b_http, grpc_port=b_grpc,
                parameters_json=worker_params, env=dict(base_env),
            ),
            240.0,
        )
        await asyncio.to_thread(
            sup.add,
            ProcessSpec(
                name="evac-a",
                component="seldon_core_tpu.models.paged.StreamingLM",
                http_port=a_http, grpc_port=a_grpc,
                parameters_json=worker_params,
                env={**base_env,
                     "SELDON_TPU_EVACUATE_TO": f"grpc://127.0.0.1:{b_grpc}"},
            ),
            240.0,
        )
        worker_a = sup.processes["evac-a"]
        worker_a._stop.set()  # no respawn: B inherits the stream, not A
        unit = UnitSpec(name="lm", type="MODEL")
        unit.endpoint = Endpoint(host="127.0.0.1", port=a_grpc,
                                 transport="GRPC")
        client = GrpcClient(unit, deadline_s=180.0, retries=1, breaker=False)
        try:
            # warm B's compiled programs so the resumed stream decodes
            # promptly (and pin the baseline answer from A)
            unit_b = UnitSpec(name="lm", type="MODEL")
            unit_b.endpoint = Endpoint(host="127.0.0.1", port=b_grpc,
                                       transport="GRPC")
            client_b = GrpcClient(unit_b, deadline_s=180.0, retries=1,
                                  breaker=False)
            out = await client_b.transform_input(
                InternalMessage(payload=prompt, kind="ndarray")
            )
            assert np.asarray(out.array()).shape[-1] == 240
            completed_before = peer_metric(
                "seldon_tpu_engine_streams_completed_total"
            )
            await client_b.close()

            inflight = asyncio.ensure_future(client.transform_input(
                InternalMessage(payload=prompt, kind="ndarray")
            ))
            await asyncio.sleep(1.0)
            assert not inflight.done(), "decode too fast for the chaos"
            worker_a.proc.terminate()
            # the dying worker's drain ships the stream to B; the local
            # waiter fails cleanly (MIGRATING/DRAINING in-band, or a
            # transport error when the connection dies first)
            try:
                res = await asyncio.wait_for(inflight, timeout=120.0)
                status = res.status or {}
                assert status.get("status") == "FAILURE", status
            except (MicroserviceError, asyncio.TimeoutError):
                pass

            # B imported and RESUMED the stream: migrated_in moves, and
            # the stream completes on B (bridge exports on the decode
            # loop's cadence — poll)
            deadline = _time.monotonic() + 240.0
            migrated = completed_after = 0.0
            while _time.monotonic() < deadline:
                migrated = peer_metric("seldon_tpu_engine_migrated_in_total")
                completed_after = peer_metric(
                    "seldon_tpu_engine_streams_completed_total"
                )
                if migrated >= 1 and completed_after > completed_before:
                    break
                await asyncio.sleep(0.5)
            assert migrated >= 1, "peer never imported the migrated stream"
            assert completed_after > completed_before, (
                "migrated stream never completed on the peer"
            )
        finally:
            await client.close()

    try:
        asyncio.run(scenario())
    finally:
        sup.stop_all()
