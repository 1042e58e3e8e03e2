"""r21 per-request black-box capture + deterministic replay forensics.

The capture plane's whole loop, round-tripped: the SRT1 capture
container (CRC trailer, redaction filter), the bounded LRU store, the
trigger matrix (head sampling / always-on-error / p99-breach linkage),
the engine-side assembly (five-phase latency split, per-wave recorder
slice with puids, cost totals, knob snapshot), the gateway's
``GET /debug/request/<puid>`` stitched timeline, and
``tools/seldon_replay.py`` bit-exact greedy replay — including a w8a8
capture and an adapter-tagged capture, each replayed through the full
ingress path.

The off-lane contract mirrors the telemetry plane's:
``SELDON_TPU_CAPTURE=0`` (the default) is bit-exact and grows NO new
``engine_stats()`` keys.

Fast tier: tiny f32 engines (the test_paged_smoke config) pay the only
compiles; replay tests pay one extra tiny compile each by design — the
replay BUILDS a second engine from the captured model config.
"""

import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from seldon_core_tpu.codec import bufview
from seldon_core_tpu.utils import capture
from seldon_core_tpu.utils.flightrec import FlightRecorder

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


CFG = dict(vocab_size=64, d_model=32, num_layers=1, num_heads=2, max_len=128)


@pytest.fixture(autouse=True)
def _isolated_store(monkeypatch, tmp_path):
    """Every test gets its own store dir and a fresh singleton — the
    default store caches SELDON_TPU_CAPTURE_DIR at first touch."""
    monkeypatch.setenv("SELDON_TPU_CAPTURE_DIR", str(tmp_path / "store"))
    capture.reset_default_store()
    yield
    capture.reset_default_store()


def _tiny_engine(**kw):
    import jax

    from seldon_core_tpu.models.paged import PagedEngine
    from seldon_core_tpu.models.transformer import TransformerLM

    lm = TransformerLM(dtype=jnp.float32, **CFG)
    params = lm.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    base = dict(dtype=jnp.float32, page_size=8, max_slots=2, steps_per_call=4)
    base.update(kw)
    return PagedEngine(params, **CFG, **base)


def _cap(puid="p-1", **kw):
    base = dict(
        trace_id="t-1", trigger="sample", seed=7, max_new_tokens=4,
        temperature=0.0, top_k=0, eos_id=-1, adapter=None, priority=1,
        rows=1, phases={"total_ms": 12.5}, waves=[{"kind": "decode"}],
        cost={"page_seconds": 0.5}, knobs=[{"name": "X", "value": "1"}],
        model={"vocab_size": 64}, tags={"tenant": "a"}, time=123.0,
        prompt=np.arange(5, dtype=np.int32),
        tokens=np.arange(4, dtype=np.int32) + 10,
    )
    base.update(kw)
    return capture.RequestCapture(puid=puid, **base)


# ---------------------------------------------------------------------------
# container codec + redaction
# ---------------------------------------------------------------------------


class TestContainer:
    def test_pack_unpack_round_trip(self):
        cap = _cap()
        blob = bufview.pack_capture(cap.to_payload())
        back = capture.RequestCapture.from_payload(
            bufview.unpack_capture(blob)
        )
        assert back.puid == "p-1" and back.trigger == "sample"
        assert back.seed == 7 and back.temperature == 0.0
        assert back.phases == {"total_ms": 12.5}
        assert back.waves == [{"kind": "decode"}]
        assert back.cost == {"page_seconds": 0.5}
        assert back.knobs == [{"name": "X", "value": "1"}]
        assert back.model == {"vocab_size": 64}
        np.testing.assert_array_equal(back.prompt, cap.prompt)
        np.testing.assert_array_equal(back.tokens, cap.tokens)

    def test_crc_trailer_detects_corruption(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_KV_CHECKSUM", "1")
        blob = bytearray(bufview.pack_capture(_cap().to_payload()))
        blob[len(blob) // 2] ^= 0xFF
        with pytest.raises(bufview.PayloadError):
            bufview.unpack_capture(bytes(blob))

    def test_unpack_rejects_wrong_frame_count(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_KV_CHECKSUM", "0")
        two = bufview.pack_frames([np.arange(3, dtype=np.int32),
                                   np.arange(2, dtype=np.int32)])
        with pytest.raises(bufview.PayloadError, match="frames"):
            bufview.unpack_capture(two)

    def test_redact_stamps_lengths_and_keeps_payloads_by_default(self):
        out = capture.redact(_cap().to_payload())
        assert out["meta"]["prompt_len"] == 5
        assert out["meta"]["tokens_len"] == 4
        assert out["meta"]["payloads_redacted"] is False
        assert out["prompt"].size == 5 and out["tokens"].size == 4

    def test_redact_drops_frames_when_payloads_off(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_CAPTURE_PAYLOADS", "0")
        out = capture.redact(_cap().to_payload())
        assert out["prompt"].size == 0 and out["tokens"].size == 0
        # lengths survive: the forensics story keeps its shape even
        # when the raw ids must never reach disk
        assert out["meta"]["prompt_len"] == 5
        assert out["meta"]["tokens_len"] == 4
        assert out["meta"]["payloads_redacted"] is True


# ---------------------------------------------------------------------------
# bounded on-disk store
# ---------------------------------------------------------------------------


class TestCaptureStore:
    def test_put_get_round_trip(self, tmp_path):
        store = capture.CaptureStore(root=str(tmp_path))
        path = store.put(_cap("req/weird puid"))
        assert path is not None and os.path.isfile(path)
        back = store.get("req/weird puid")
        assert back is not None and back.puid == "req/weird puid"
        assert store.stats()["writes"] == 1
        assert store.total_bytes() > 0

    def test_unsafe_puids_do_not_alias(self, tmp_path):
        store = capture.CaptureStore(root=str(tmp_path))
        # the sanitized stems collide; the crc32 suffix must not
        assert store.path_for("a/b") != store.path_for("a.b")

    def test_lru_eviction_drops_oldest_by_mtime(self, tmp_path):
        store = capture.CaptureStore(root=str(tmp_path), max_bytes=1 << 30)
        paths = [store.put(_cap(f"p-{i}")) for i in range(4)]
        for i, p in enumerate(paths):  # deterministic age order
            os.utime(p, (1000.0 + i, 1000.0 + i))
        keep = sum(os.path.getsize(p) for p in paths[2:])
        store.max_bytes = keep
        store._evict_over_budget()
        assert [os.path.exists(p) for p in paths] == [
            False, False, True, True,
        ]
        assert store.evictions == 2
        assert store.get("p-0") is None and store.get("p-3") is not None

    def test_just_written_container_survives_tiny_budget(self, tmp_path):
        store = capture.CaptureStore(root=str(tmp_path), max_bytes=1)
        path = store.put(_cap("only"))
        assert path is not None and os.path.isfile(path)
        assert store.get("only") is not None

    def test_write_failure_is_counted_not_raised(self, tmp_path):
        store = capture.CaptureStore(root=str(tmp_path))
        bad = _cap("bad", tags={"x": object()})  # not JSON-serializable
        assert store.put(bad) is None
        assert store.errors == 1 and store.writes == 0

    def test_default_store_resolves_env_dir(self, monkeypatch, tmp_path):
        monkeypatch.setenv("SELDON_TPU_CAPTURE_DIR", str(tmp_path / "d"))
        capture.reset_default_store()
        store = capture.default_store()
        assert store is capture.default_store()  # singleton
        store.put(_cap("env-routed"))
        assert (tmp_path / "d").is_dir()


# ---------------------------------------------------------------------------
# phase decomposition + knob snapshot helpers
# ---------------------------------------------------------------------------


class TestHelpers:
    def test_phase_terms_decompose_the_five_stamps(self):
        terms = capture.phase_terms(10.0, 10.1, 10.3, 10.35, 10.5)
        assert terms["queued_ms"] == pytest.approx(100.0)
        assert terms["prefill_ms"] == pytest.approx(200.0)
        assert terms["decode_ms"] == pytest.approx(200.0)
        assert terms["ttft_ms"] == pytest.approx(350.0)
        assert terms["total_ms"] == pytest.approx(500.0)
        assert terms["stamps"]["t_submit"] == 10.0

    def test_phase_terms_tolerate_missing_stamps(self):
        # an error capture may die before decode ever started
        terms = capture.phase_terms(10.0, 10.1, 0.0, 0.0, 10.2)
        assert terms["queued_ms"] == pytest.approx(100.0)
        assert terms["decode_ms"] is None and terms["ttft_ms"] is None

    def test_knob_snapshot_carries_only_set_knobs(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_CAPTURE_SAMPLE", "3")
        snap = capture.knob_snapshot()
        names = {k["name"] for k in snap}
        assert "SELDON_TPU_CAPTURE_SAMPLE" in names
        assert all(k["value"] is not None for k in snap)
        by = {k["name"]: k["value"] for k in snap}
        assert by["SELDON_TPU_CAPTURE_SAMPLE"] == "3"


# ---------------------------------------------------------------------------
# trigger matrix + breach linkage (engine level)
# ---------------------------------------------------------------------------


class TestTriggerMatrix:
    def test_error_beats_everything(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_CAPTURE", "1")
        eng = _tiny_engine()
        try:
            assert eng.capture_trigger("p", RuntimeError("x")) == "error"
        finally:
            eng.close()

    def test_head_sampling_fires_every_nth(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_CAPTURE", "1")
        monkeypatch.setenv("SELDON_TPU_CAPTURE_SAMPLE", "3")
        eng = _tiny_engine()
        try:
            fired = [eng.capture_trigger(f"p{i}", None) for i in range(6)]
            assert fired == [None, None, "sample", None, None, "sample"]
        finally:
            eng.close()

    def test_breach_membership_fires_once(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_CAPTURE", "1")
        eng = _tiny_engine()
        try:
            eng._note_breach_puids(
                [{"puids": ["p-a", "p-b"]}, {"puids": ["p-a"]}], "dump.jsonl"
            )
            assert eng.capture_trigger("p-a", None) == "breach"
            # popped: a second termination of the same puid is ordinary
            assert eng.capture_trigger("p-a", None) is None
            assert eng.capture_trigger("p-b", None) == "breach"
        finally:
            eng.close()

    def test_capture_off_trigger_never_fires(self):
        eng = _tiny_engine()
        try:
            assert eng.capture_trigger("p", RuntimeError("x")) is None
        finally:
            eng.close()

    def test_breach_index_is_bounded(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_CAPTURE", "1")
        eng = _tiny_engine()
        try:
            eng._note_breach_puids(
                [{"puids": [f"p{i}" for i in range(1500)]}], "d"
            )
            assert len(eng._breach_puids) <= 1024
        finally:
            eng.close()


class TestBreachPuidLinkage:
    """Satellite 2: wave records carry their active puids whenever the
    recorder records — dump files are joinable to requests even with
    the capture plane off."""

    def test_wave_records_carry_stream_puids(self):
        eng = _tiny_engine()  # capture OFF: the linkage is unconditional
        try:
            s = eng.submit(np.arange(5, dtype=np.int32) % 64,
                           max_new_tokens=4, puid="wave-puid-1")
            eng.run()
            assert s.error is None
            waves = [r for r in eng.recorder.snapshot()
                     if "wave-puid-1" in r.get("puids", ())]
            assert waves, "no wave record carried the stream's puid"
            phases = {r.get("phase") for r in waves}
            assert "decode" in phases
        finally:
            eng.close()

    def test_dump_hook_receives_records_and_path(self, tmp_path):
        rec = FlightRecorder(capacity=8, dump_p99_ms=5.0,
                             dump_dir=str(tmp_path), dump_cooldown_s=0.0)
        got = []
        rec.on_dump = lambda records, path: got.append((records, path))
        for _ in range(4):
            rec.record({"wall_ms": 1.0, "puids": ["fast"]})
        assert got == []  # quiet traffic never dumps
        for _ in range(4):
            rec.record({"wall_ms": 50.0, "puids": ["slow-1"]})
        assert got, "breach never reached the hook"
        records, path = got[0]
        assert os.path.isfile(path)
        assert any("slow-1" in r.get("puids", ()) for r in records)

    def test_dump_hook_failure_is_contained(self, tmp_path):
        rec = FlightRecorder(capacity=4, dump_p99_ms=5.0,
                             dump_dir=str(tmp_path), dump_cooldown_s=0.0)

        def boom(records, path):
            raise RuntimeError("hook died")

        rec.on_dump = boom
        for _ in range(4):
            rec.record({"wall_ms": 50.0})  # must not raise
        assert rec.dumps >= 1

    def test_engine_wires_hook_only_when_capture_on(self, monkeypatch):
        eng_off = _tiny_engine()
        try:
            assert eng_off.recorder.on_dump is None
        finally:
            eng_off.close()
        monkeypatch.setenv("SELDON_TPU_CAPTURE", "1")
        eng_on = _tiny_engine()
        try:
            assert eng_on.recorder.on_dump == eng_on._note_breach_puids
        finally:
            eng_on.close()


# ---------------------------------------------------------------------------
# StreamingLM end-to-end capture + stats + off-lane contract
# ---------------------------------------------------------------------------


def _tiny_lm(**kw):
    from seldon_core_tpu.models.paged import StreamingLM

    base = dict(max_new_tokens=4, page_size=8, max_slots=2,
                steps_per_call=4, **CFG)
    base.update(kw)
    lm = StreamingLM(**base)
    lm.load()
    return lm


class TestEndToEndCapture:
    def test_sampled_capture_carries_the_whole_black_box(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_CAPTURE", "1")
        monkeypatch.setenv("SELDON_TPU_CAPTURE_SAMPLE", "1")
        capture.reset_default_store()
        lm = _tiny_lm()
        try:
            X = (np.arange(5, dtype=np.int32) % 64)[None, :]
            out = lm.predict(X, [], meta={"puid": "e2e-ok-1",
                                          "tags": {"tenant": "acme"}})
            cap = capture.default_store().get("e2e-ok-1")
            assert cap is not None
            assert cap.status == "ok" and cap.trigger == "sample"
            assert cap.seed is not None
            np.testing.assert_array_equal(cap.prompt, X[0])
            np.testing.assert_array_equal(cap.tokens, out[0])
            # five-phase decomposition, all terms live for an ok request
            for term in ("queued_ms", "prefill_ms", "decode_ms",
                         "ttft_ms", "total_ms"):
                assert cap.phases[term] is not None, term
            # the recorder slice: every wave carried this puid
            assert cap.waves
            assert all("e2e-ok-1" in w.get("puids", ()) for w in cap.waves)
            # cost totals match the ledger's exact counts
            assert cap.cost["prefill_tokens"] == 5
            assert cap.cost["decode_tokens"] == 4
            # the knob snapshot is the replay recipe: SET knobs only
            names = {k["name"] for k in cap.knobs}
            assert "SELDON_TPU_CAPTURE" in names
            # the model config rebuilds THIS engine
            assert cap.model["vocab_size"] == 64
            assert cap.model["max_slots"] == 2
            assert cap.tags == {"tenant": "acme"}
            # and the engine counted the write + exposes store size
            stats = lm.engine.engine_stats()
            assert stats["captures"] == 1
            assert stats["capture_store_bytes"] > 0
        finally:
            lm.shutdown()

    def test_error_capture_via_failed_stream(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_CAPTURE", "1")
        capture.reset_default_store()
        lm = _tiny_lm()
        try:
            eng = lm.engine
            s = eng.submit(np.arange(5, dtype=np.int32) % 64,
                           max_new_tokens=4, puid="e2e-err-1")
            # the serving loop is the engine's one stepper: a step() from
            # here races it for the donated pool once a compile outlasts
            # the loop's 0.5 s poll ("Buffer has been deleted or donated")
            lm._wake.set()
            assert s.event.wait(timeout=300)
            eng.fail_stream(s, RuntimeError("boom"))
            lm._maybe_capture(
                [s], tags={}, meta={"puid": "e2e-err-1"}, request_seed=9,
                status="error", reason="RuntimeError('boom')",
            )
            cap = capture.default_store().get("e2e-err-1")
            assert cap is not None
            assert cap.status == "error" and cap.trigger == "error"
            assert "boom" in cap.reason
            # sampling rate 0: ONLY the error trigger wrote this
            assert capture.sample_every() == 0
        finally:
            lm.shutdown()

    def test_off_lane_is_bit_exact_and_sheds_every_new_stats_key(
        self, monkeypatch
    ):
        """SELDON_TPU_CAPTURE=0 contract (the r21 acceptance gate):
        greedy decode is bit-exact vs the capture-on lane and
        engine_stats grows NO new keys."""
        prompt = (np.arange(6, dtype=np.int32) % 64)[None, :]

        def run_lane():
            lm = _tiny_lm()
            try:
                out = lm.predict(prompt.copy(), [],
                                 meta={"puid": "lane-req"})
                return out, lm.engine.engine_stats()
            finally:
                lm.shutdown()

        monkeypatch.setenv("SELDON_TPU_CAPTURE", "1")
        monkeypatch.setenv("SELDON_TPU_CAPTURE_SAMPLE", "1")
        capture.reset_default_store()
        on_out, on_stats = run_lane()
        monkeypatch.setenv("SELDON_TPU_CAPTURE", "0")
        capture.reset_default_store()
        off_out, off_stats = run_lane()
        np.testing.assert_array_equal(on_out, off_out)
        assert set(on_stats) - set(off_stats) == {
            "captures", "capture_store_bytes",
        }


# ---------------------------------------------------------------------------
# gateway GET /debug/request/<puid>
# ---------------------------------------------------------------------------


class TestDebugRequestEndpoint:
    def _app(self, lm):
        from seldon_core_tpu.engine import PredictorService, UnitSpec
        from seldon_core_tpu.engine.server import Gateway, build_gateway_app

        svc = PredictorService(
            UnitSpec(name="lm", type="MODEL", component=lm), name="main",
        )
        return build_gateway_app(Gateway([(svc, 1.0)]))

    def _get(self, app, path):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        async def scenario():
            client = TestClient(TestServer(app))
            await client.start_server()
            resp = await client.get(path)
            doc = await resp.json()
            await client.close()
            return resp.status, doc

        return asyncio.run(scenario())

    def test_stitched_timeline_serves_capture_and_phases(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_CAPTURE", "1")
        monkeypatch.setenv("SELDON_TPU_CAPTURE_SAMPLE", "1")
        capture.reset_default_store()
        lm = _tiny_lm()
        try:
            X = (np.arange(5, dtype=np.int32) % 64)[None, :]
            lm.predict(X, [], meta={"puid": "dbg-1"})
            status, doc = self._get(self._app(lm), "/debug/request/dbg-1")
            assert status == 200 and doc["found"] is True
            cap_doc = doc["capture"]
            for term in ("queued_ms", "prefill_ms", "decode_ms",
                         "ttft_ms", "total_ms"):
                assert cap_doc["phases"][term] is not None, term
            assert cap_doc["cost"]["prefill_tokens"] == 5
            assert cap_doc["cost"]["decode_tokens"] == 4
            assert cap_doc["prompt"] == X[0].tolist()
            assert len(cap_doc["tokens"]) == 4
            # the timeline merges the stream stamps, time-sorted
            events = [e["event"] for e in doc["timeline"]]
            assert "t_submit" in events and "t_finish" in events
            ts = [e["t"] for e in doc["timeline"]]
            assert ts == sorted(ts)
        finally:
            lm.shutdown()

    def test_unknown_puid_is_404(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_CAPTURE", "1")
        capture.reset_default_store()
        lm = _tiny_lm()
        try:
            status, doc = self._get(self._app(lm), "/debug/request/nope")
            assert status == 404 and doc["found"] is False
        finally:
            lm.shutdown()


# ---------------------------------------------------------------------------
# deterministic replay (tools/seldon_replay.py)
# ---------------------------------------------------------------------------


def _capture_one(monkeypatch, *, puid, lm_kwargs=None, tags=None):
    """Serve one request with capture on; return its stored capture."""
    monkeypatch.setenv("SELDON_TPU_CAPTURE", "1")
    monkeypatch.setenv("SELDON_TPU_CAPTURE_SAMPLE", "1")
    capture.reset_default_store()
    lm = _tiny_lm(**(lm_kwargs or {}))
    try:
        X = (np.arange(3, 11, dtype=np.int32) % 64)[None, :]
        meta = {"puid": puid}
        if tags:
            meta["tags"] = dict(tags)
        out = lm.predict(X, [], meta=meta)
    finally:
        lm.shutdown()
    cap = capture.default_store().get(puid)
    assert cap is not None
    return cap, out


class TestReplay:
    def test_first_divergence(self):
        from tools.seldon_replay import _first_divergence

        assert _first_divergence([1, 2, 3], [1, 2, 3]) is None
        assert _first_divergence([1, 2, 3], [1, 9, 3]) == 1
        assert _first_divergence([1, 2], [1, 2, 3]) == 2

    def test_greedy_replay_is_bit_exact(self, monkeypatch):
        from tools.seldon_replay import replay_capture

        cap, out = _capture_one(monkeypatch, puid="rep-greedy")
        report = replay_capture(cap)  # strict: greedy must not diverge
        assert report["replayable"] and report["greedy"]
        assert report["bit_exact"] is True
        assert report["first_divergence"] is None
        assert report["replayed_tokens"] == out[0].tolist()
        # the latency diff came from the replayed request's OWN capture
        for term in ("queued_ms", "prefill_ms", "decode_ms",
                     "ttft_ms", "total_ms"):
            assert report["latency"][term]["replayed"] is not None, term
        # and the replay restored this process's capture env
        assert capture.sample_every() == 1

    def test_w8a8_capture_replays_bit_exact(self, monkeypatch):
        """One-numeric-regime bit-exactness: a capture taken under the
        w8a8 precision lane replays under w8a8 — the captured model
        config carries the regime, so the replay rebuilds it."""
        from tools.seldon_replay import replay_capture

        cap, out = _capture_one(
            monkeypatch, puid="rep-w8a8",
            lm_kwargs=dict(precision="w8a8"),
        )
        assert cap.model["precision"] == "w8a8"
        report = replay_capture(cap)
        assert report["bit_exact"] is True
        assert report["replayed_tokens"] == out[0].tolist()

    def test_adapter_tagged_capture_replays_bit_exact(self, monkeypatch):
        from tools.seldon_replay import replay_capture

        adapters = {"u1": {"seed": 21}}
        cap, out = _capture_one(
            monkeypatch, puid="rep-lora",
            lm_kwargs=dict(max_adapters=2, lora_rank=2, adapters=adapters),
            tags={"adapter": "u1"},
        )
        assert cap.adapter == "u1"
        assert cap.model["adapters"] == adapters
        report = replay_capture(cap)
        assert report["adapter"] == "u1"
        assert report["bit_exact"] is True
        assert report["replayed_tokens"] == out[0].tolist()

    def test_redacted_capture_is_not_replayable(self, monkeypatch):
        from tools.seldon_replay import replay_capture

        monkeypatch.setenv("SELDON_TPU_CAPTURE_PAYLOADS", "0")
        cap, _ = _capture_one(monkeypatch, puid="rep-redacted")
        assert cap.prompt.size == 0  # frames never reached disk
        report = replay_capture(cap)
        assert report["replayable"] is False
        assert "PAYLOADS" in report["info"]

    def test_load_capture_by_path_and_by_puid(self, monkeypatch, tmp_path):
        from tools.seldon_replay import load_capture

        store = capture.CaptureStore(root=str(tmp_path))
        path = store.put(_cap("lookup-1"))
        assert load_capture(path).puid == "lookup-1"
        assert load_capture(
            "lookup-1", store_dir=str(tmp_path)
        ).puid == "lookup-1"
        with pytest.raises(SystemExit):
            load_capture("missing", store_dir=str(tmp_path))
