"""Generation-engine observability (r7): per-request lifecycle spans
linked into the request trace, the per-chunk flight recorder, and the
Prometheus bridge's complete-by-contract mapping of engine_stats().

Fast tier: one tiny engine (the test_paged_smoke config) pays the only
compiles; everything else is host-side.
"""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from seldon_core_tpu.utils import tracing
from seldon_core_tpu.utils.flightrec import FlightRecorder


CFG = dict(vocab_size=64, d_model=32, num_layers=1, num_heads=2, max_len=128)


def _tiny_engine(**kw):
    import jax

    from seldon_core_tpu.models.paged import PagedEngine
    from seldon_core_tpu.models.transformer import TransformerLM

    lm = TransformerLM(dtype=jnp.float32, **CFG)
    params = lm.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    base = dict(dtype=jnp.float32, page_size=8, max_slots=2, steps_per_call=4)
    base.update(kw)
    return PagedEngine(params, **CFG, **base)


class TestLifecycleSpans:
    """The r7 acceptance criterion: ONE trace for one generation
    request carries the engine-level request span AND the gen.*
    lifecycle spans, linked via puid (trace_id) + parent_span_id."""

    def test_gen_spans_link_to_request_span_by_puid_and_parent(self):
        tracer = tracing.setup_tracing("gen-obs-test")
        eng = _tiny_engine()
        try:
            with tracer.span("microservice.predict", trace_id="puid-7") as root:
                stream = eng.submit(
                    np.arange(5, dtype=np.int32) % 64, max_new_tokens=6
                )
            eng.run()
            assert stream.error is None
            spans = {s.name: s for s in tracer.find("puid-7")}
            # the engine-level request span plus the full lifecycle
            for name in ("microservice.predict", "gen.queued",
                         "gen.prefill", "gen.decode", "gen.finish"):
                assert name in spans, f"missing {name} in trace"
            for name in ("gen.queued", "gen.prefill", "gen.decode",
                         "gen.finish"):
                s = spans[name]
                assert s.trace_id == "puid-7"  # puid linkage
                assert s.parent_span_id == root.span_id  # span linkage
                assert s.tags["puid"] == "puid-7"
                assert s.duration_s >= 0.0
            assert spans["gen.prefill"].tags["prompt_len"] == 5
            assert spans["gen.finish"].tags["tokens"] == 6
            assert spans["gen.queued"].tags["queue_depth"] == 0
        finally:
            eng.close()
            tracing._tracer = None

    def test_no_tracer_no_spans_no_cost(self):
        eng = _tiny_engine()
        try:
            stream = eng.submit(np.ones(3, np.int32), max_new_tokens=4)
            assert stream.trace_id == ""  # linkage never captured
            eng.run()
            assert stream.error is None
        finally:
            eng.close()

    def test_explicit_trace_id_wins_over_context(self):
        tracer = tracing.setup_tracing("gen-obs-test2")
        eng = _tiny_engine()
        try:
            stream = eng.submit(
                np.ones(3, np.int32), max_new_tokens=4, trace_id="req-x",
            )
            eng.run()
            assert stream.error is None
            names = {s.name for s in tracer.find("req-x")}
            assert {"gen.queued", "gen.prefill", "gen.decode",
                    "gen.finish"} <= names
        finally:
            eng.close()
            tracing._tracer = None


class TestFlightRecorder:
    def test_ring_is_bounded_and_seq_monotonic(self):
        rec = FlightRecorder(capacity=4)
        for i in range(10):
            rec.record({"wall_ms": float(i), "queue_depth": i})
        snap = rec.snapshot()
        assert len(snap) == 4
        assert [r["seq"] for r in snap] == [7, 8, 9, 10]
        assert rec.stats()["records"] == 4
        assert rec.stats()["last_queue_depth"] == 9

    def test_since_consumes_incrementally(self):
        rec = FlightRecorder(capacity=8)
        for i in range(3):
            rec.record({"wall_ms": 1.0})
        assert len(rec.since(0)) == 3
        assert len(rec.since(3)) == 0
        rec.record({"wall_ms": 2.0})
        got = rec.since(3)
        assert len(got) == 1 and got[0]["seq"] == 4

    def test_dump_on_breach_writes_jsonl_with_cooldown(self, tmp_path):
        clock = [1000.0]
        rec = FlightRecorder(
            capacity=16, dump_p99_ms=50.0, dump_dir=str(tmp_path),
            dump_cooldown_s=30.0, clock=lambda: clock[0],
        )
        for _ in range(10):
            rec.record({"wall_ms": 1.0})
        assert rec.dumps == 0  # fast chunks: no breach check even runs
        rec.record({"wall_ms": 99.0})  # p99 of the window now breaches
        assert rec.dumps == 1
        lines = [json.loads(l) for l in open(rec.last_dump_path)]
        assert len(lines) == 11
        assert lines[-1]["wall_ms"] == 99.0
        # cooldown: a sustained breach produces one dump per window
        rec.record({"wall_ms": 120.0})
        assert rec.dumps == 1
        clock[0] += 31.0
        rec.record({"wall_ms": 120.0})
        assert rec.dumps == 2

    def test_quantile_and_manual_dump(self, tmp_path):
        rec = FlightRecorder(capacity=128)
        for i in range(100):
            rec.record({"wall_ms": float(i + 1)})
        assert rec.quantile_ms(0.5) == pytest.approx(51.0, abs=2)
        assert rec.quantile_ms(0.99) == pytest.approx(99.0, abs=2)
        path = rec.dump_jsonl(str(tmp_path / "ring.jsonl"))
        assert sum(1 for _ in open(path)) == 100


class TestEngineRecorder:
    def test_engine_stats_detail_carries_chunk_records(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_FLIGHT_RECORDER", "64")
        eng = _tiny_engine()
        try:
            eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=6)
            eng.run()
            base = eng.engine_stats()
            assert "recorder" not in base  # default surface unchanged
            stats = eng.engine_stats(detail=True)
            recs = stats["recorder"]
            assert recs and stats["recorder_stats"]["records"] == len(recs)
            for rec in recs:
                assert rec["phase"] == "decode"
                assert rec["wall_ms"] > 0
                assert rec["steps"] == 4
                assert rec["occupancy"] >= 1
                assert isinstance(rec["buckets"], list)
                for key in ("admissions", "stalls", "queue_depth", "tokens",
                            "prefill_tokens", "decode_tokens"):
                    assert key in rec
                # r15 contract: "tokens" is the wave's TOTAL work and
                # the prefill/decode split decomposes it exactly
                assert rec["tokens"] == (
                    rec["prefill_tokens"] + rec["decode_tokens"]
                )
            assert sum(r["decode_tokens"] for r in recs) == base["tokens"]
            assert (
                sum(r["prefill_tokens"] for r in recs)
                == base["prefill_tokens"]
            )
        finally:
            eng.close()

    def test_recorder_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_FLIGHT_RECORDER", "0")
        eng = _tiny_engine()
        try:
            assert eng.recorder is None
            stats = eng.engine_stats(detail=True)
            assert stats["recorder"] == []
        finally:
            eng.close()


class TestPrometheusBridgeContract:
    """CI contract: every engine_stats() key is either mapped to a
    canonical metric or explicitly excluded — new counters cannot
    silently skip Prometheus export."""

    def test_every_engine_stats_key_mapped_or_excluded(
        self, monkeypatch, tmp_path
    ):
        from seldon_core_tpu.utils import capture
        from seldon_core_tpu.utils.metrics import (
            ENGINE_STATS_EXCLUDED,
            ENGINE_STATS_METRICS,
        )

        # capture on: the r21 keys are mapped, but the plane defaults
        # OFF and engine_stats sheds them on the off lane — the
        # phantom check below needs the full key set emitted
        monkeypatch.setenv("SELDON_TPU_CAPTURE", "1")
        monkeypatch.setenv("SELDON_TPU_CAPTURE_DIR", str(tmp_path))
        # KV tier on for the same reason: the r22 kv_tier_* keys are
        # mapped but default OFF, and the off lane sheds them
        monkeypatch.setenv("SELDON_TPU_KV_OFFLOAD", "1")
        capture.reset_default_store()
        eng = _tiny_engine()
        try:
            stats = eng.engine_stats()
            unmapped = [
                k for k in stats
                if k not in ENGINE_STATS_METRICS
                and k not in ENGINE_STATS_EXCLUDED
            ]
            assert not unmapped, (
                f"engine_stats keys with no GenerationPrometheusBridge "
                f"mapping and no exclusion entry: {unmapped}"
            )
            # and the inverse: the mapping doesn't name phantom keys
            phantom = [k for k in ENGINE_STATS_METRICS if k not in stats]
            assert not phantom, f"mapped keys engine_stats never emits: {phantom}"
            for key in ENGINE_STATS_EXCLUDED:
                assert key in stats
        finally:
            eng.close()
            capture.reset_default_store()

    def test_mapping_uses_canonical_names_and_kinds(self):
        from seldon_core_tpu.utils.metrics import ENGINE_STATS_METRICS

        for key, (kind, name, doc) in ENGINE_STATS_METRICS.items():
            assert name.startswith("seldon_tpu_engine_"), name
            assert kind in ("counter", "gauge")
            if kind == "counter":
                assert name.endswith("_total"), name
            assert doc
        # the ISSUE-named canonical set is present
        names = {n for _, n, _ in ENGINE_STATS_METRICS.values()}
        assert {"seldon_tpu_engine_slot_occupancy",
                "seldon_tpu_engine_queue_depth",
                "seldon_tpu_engine_tokens_total",
                "seldon_tpu_engine_evictions_total"} <= names


class TestPrometheusBridgeExport:
    def test_counters_gauges_and_histogram_land_in_registry(self, monkeypatch):
        import prometheus_client as prom

        from seldon_core_tpu.utils.metrics import GenerationPrometheusBridge

        monkeypatch.setenv("SELDON_TPU_FLIGHT_RECORDER", "64")
        registry = prom.CollectorRegistry()
        eng = _tiny_engine()
        try:
            bridge = GenerationPrometheusBridge(
                eng, deployment_name="dep", predictor_name="main",
                model_name="lm", registry=registry,
            )
            eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=6)
            eng.run()
            bridge.collect()
            labels = {"deployment_name": "dep", "predictor_name": "main",
                      "model_name": "lm"}
            stats = eng.engine_stats()

            def val(name):
                return registry.get_sample_value(name, labels)

            assert val("seldon_tpu_engine_tokens_total") == stats["tokens"]
            assert val("seldon_tpu_engine_chunks_total") == stats["chunks"]
            assert val("seldon_tpu_engine_slot_occupancy") == 0.0
            assert val("seldon_tpu_engine_queue_depth") == 0.0
            assert (
                val("seldon_tpu_engine_chunk_duration_seconds_count")
                == stats["chunks"]
            )
            assert val("seldon_tpu_engine_chunk_p99_ms") > 0.0
            # second collect with no new work: counters must NOT re-add
            bridge.collect()
            assert val("seldon_tpu_engine_tokens_total") == stats["tokens"]
            assert (
                val("seldon_tpu_engine_chunk_duration_seconds_count")
                == stats["chunks"]  # each chunk observed exactly once
            )
        finally:
            eng.close()

    def test_counter_reset_rebases_instead_of_incing_garbage(self):
        import prometheus_client as prom

        from seldon_core_tpu.utils.metrics import GenerationPrometheusBridge

        class FakeEngine:
            def __init__(self):
                self.stats = {"tokens": 100, "queued_streams": 0}
                self.recorder = None

            def engine_stats(self, detail=False):
                return dict(self.stats)

        registry = prom.CollectorRegistry()
        fake = FakeEngine()
        bridge = GenerationPrometheusBridge(fake, registry=registry)
        bridge.collect()
        labels = {"deployment_name": "", "predictor_name": "", "model_name": ""}
        assert registry.get_sample_value(
            "seldon_tpu_engine_tokens_total", labels) == 100.0
        fake.stats["tokens"] = 30  # engine replaced: cumulative went DOWN
        bridge.collect()
        # rebased on the new engine's count, not inc'd by a negative
        assert registry.get_sample_value(
            "seldon_tpu_engine_tokens_total", labels) == 130.0

    def test_collect_never_raises(self):
        from seldon_core_tpu.utils.metrics import GenerationPrometheusBridge

        class Exploding:
            recorder = None

            def engine_stats(self, detail=False):
                raise RuntimeError("engine gone")

        GenerationPrometheusBridge(Exploding()).collect()  # must not raise


class TestDebugEndpoints:
    """The gateway's /debug surface: engine stats (with the recorder
    ring under ?detail=1) and the tracer's span ring."""

    def _gateway(self):
        from seldon_core_tpu.engine import PredictorService, UnitSpec
        from seldon_core_tpu.engine.server import Gateway
        from seldon_core_tpu.runtime import TPUComponent

        class FakeEngine:
            def engine_stats(self, detail=False):
                out = {"chunks": 3, "tokens": 42, "queued_streams": 1,
                       "active_slots": 2}
                if detail:
                    out["recorder"] = [
                        {"seq": 1, "phase": "decode", "wall_ms": 1.5,
                         "queue_depth": 1}
                    ]
                return out

        class GenModel(TPUComponent):
            def __init__(self):
                super().__init__()
                self.engine = FakeEngine()

            def predict(self, X, names, meta=None):
                return np.asarray(X)

        svc = PredictorService(
            UnitSpec(name="lm", type="MODEL", component=GenModel()),
            name="main",
        )
        return Gateway([(svc, 1.0)])

    def test_debug_engine_reports_stats_and_detail(self):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.server import build_gateway_app

        async def scenario():
            client = TestClient(TestServer(build_gateway_app(self._gateway())))
            await client.start_server()
            plain = await (await client.get("/debug/engine")).json()
            detail = await (
                await client.get("/debug/engine", params={"detail": "1"})
            ).json()
            await client.close()
            return plain, detail

        plain, detail = asyncio.run(scenario())
        assert plain["main"]["lm"]["tokens"] == 42
        assert "recorder" not in plain["main"]["lm"]
        assert detail["main"]["lm"]["recorder"][0]["wall_ms"] == 1.5

    def test_debug_traces_serves_span_ring(self):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.server import build_gateway_app

        app = build_gateway_app(self._gateway())
        tracer = tracing.setup_tracing("debug-ep-test")
        try:
            with tracer.span("predictor.predict", trace_id="p-1"):
                pass
            with tracer.span("other", trace_id="p-2"):
                pass

            async def scenario():
                client = TestClient(TestServer(app))
                await client.start_server()
                allsp = await (await client.get("/debug/traces")).json()
                one = await (
                    await client.get("/debug/traces",
                                     params={"trace_id": "p-1"})
                ).json()
                await client.close()
                return allsp, one

            allsp, one = asyncio.run(scenario())
            assert allsp["enabled"] and len(allsp["spans"]) == 2
            assert [s["traceId"] for s in one["spans"]] == ["p-1"]
            assert one["spans"][0]["spanId"]
        finally:
            tracing._tracer = None

    def test_debug_traces_without_tracer_says_disabled(self):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.server import build_gateway_app

        assert tracing.get_tracer() is None

        async def scenario():
            client = TestClient(TestServer(build_gateway_app(self._gateway())))
            await client.start_server()
            out = await (await client.get("/debug/traces")).json()
            await client.close()
            return out

        out = asyncio.run(scenario())
        assert out["enabled"] is False and out["spans"] == []


class TestProfileEngineTraceTool:
    def test_tool_importable_and_argparse_defaults(self):
        import importlib.util

        path = os.path.join(
            os.path.dirname(__file__), os.pardir, "tools",
            "profile_engine_trace.py",
        )
        spec = importlib.util.spec_from_file_location("pet", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert callable(mod.main)


@pytest.mark.parametrize("key", [
    "clock_s", "host_work_s", "host_wait_s", "ttft_s", "ttfts",
    "first_token_s", "first_tokens", "decode_stream_s",
    "decode_stream_tokens", "deliver_lag_s", "deliveries",
    "deliveries_behind",
])
def test_the_host_halfs_default_keys_are_mapped_or_excluded(key):
    """PR 35: each is in the DEFAULT ``engine_stats()`` (no
    ``?detail=1``), a number, and exported by the bridge — all but the
    snapshot's own clock, which is what a reader divides by."""
    from seldon_core_tpu.utils.metrics import (
        ENGINE_STATS_EXCLUDED,
        ENGINE_STATS_METRICS,
    )

    eng = _tiny_engine()
    try:
        eng.submit(np.arange(5, dtype=np.int32) % 64, max_new_tokens=6)
        eng.run()
        stats = eng.engine_stats()
        detail = eng.engine_stats(detail=True)
    finally:
        eng.close()
    assert isinstance(stats[key], (int, float)) and stats[key] >= 0
    if key == "clock_s":
        assert key in ENGINE_STATS_EXCLUDED and key not in ENGINE_STATS_METRICS
        assert detail[key] >= stats[key] > 0
    else:
        kind, name, _doc = ENGINE_STATS_METRICS[key]
        assert kind == "counter" and name.endswith("_total")
    # what went with the enqueue's clock stays gone
    assert "prefill_wall_s" not in stats
    assert "phase_wall_s" not in stats and "phase_s" not in detail
    assert set(detail["phase_wall_s"]) == {
        "admit", "prefill", "launch", "wait", "harvest", "record", "between"}
    # the device's idle by where the engine thread was: in the default
    # view, every where a key from the start, summing to the idle
    assert set(stats["device_idle_by_s"]) >= {"no_work", "prefill.tail", "launch.call"}
    assert sum(stats["device_idle_by_s"].values()) == pytest.approx(
        stats["device_idle_s"], abs=1e-9)
    assert all("prefill_wall_ms" not in rec for rec in detail["recorder"])


@pytest.mark.parametrize("key", [
    "device_busy_s", "device_idle_s", "device_programs", "device_idle_by_s",
    "xla_compiles", "xla_compile_s",
])
def test_the_device_clocks_default_keys_are_mapped_or_excluded(key):
    """PR 50: each is in the DEFAULT ``engine_stats()`` (what
    ``/debug/engine`` answers without ``?detail=1``); the idle goes out
    as ONE labelled counter by where the engine thread was, so the dict
    and its sum are excluded from the flat mapping, and the rest are
    counters."""
    from prometheus_client import CollectorRegistry

    from seldon_core_tpu.utils.metrics import (
        DEVICE_IDLE_METRIC,
        ENGINE_STATS_EXCLUDED,
        ENGINE_STATS_METRICS,
        GenerationPrometheusBridge,
    )

    eng = _tiny_engine()
    try:
        for first in (1, 2):  # step by step: the device idles between waves
            eng.submit((np.arange(5, dtype=np.int32) + first) % 64, max_new_tokens=6)
            eng.run()
        eng._seam.device.stop()  # everything dispatched is settled
        stats = eng.engine_stats()
        registry = CollectorRegistry()
        GenerationPrometheusBridge(eng, model_name="m", registry=registry).collect()
    finally:
        eng.close()
    if key in ("device_idle_by_s", "device_idle_s"):
        assert key in ENGINE_STATS_EXCLUDED and key not in ENGINE_STATS_METRICS
        exported = {
            s.labels["where"]: s.value for m in registry.collect()
            for s in m.samples if s.name == DEVICE_IDLE_METRIC}
        assert exported == pytest.approx(
            {k: v for k, v in stats["device_idle_by_s"].items() if v > 0})
        assert sum(exported.values()) == pytest.approx(stats["device_idle_s"])
        assert stats["device_idle_s"] > 0.0
    else:
        kind, name, _doc = ENGINE_STATS_METRICS[key]
        assert kind == "counter" and name.endswith("_total")
        assert isinstance(stats[key], (int, float)) and stats[key] > 0
        (value,) = [s.value for m in registry.collect() for s in m.samples
                    if s.name == name]
        assert value == pytest.approx(stats[key])
