"""Observability tests: prometheus metric names/tags, tracing spans,
request-pair logging (reference: analytics.md:9-16 metric contract,
PredictionService.java:169-202 pair format)."""

import asyncio
import os
import json
import struct
import threading

import numpy as np
import pytest
from prometheus_client import CollectorRegistry

from seldon_core_tpu.engine import PredictorService, UnitSpec
from seldon_core_tpu.runtime import InternalFeedback, InternalMessage, TPUComponent
from seldon_core_tpu.utils.metrics import PrometheusObserver
from seldon_core_tpu.utils.reqlogger import JsonlPairLogger
from seldon_core_tpu.utils import tracing


def run(coro):
    return asyncio.run(coro)


def msg(arr):
    return InternalMessage(payload=np.asarray(arr, dtype=np.float64), kind="tensor")


class MetricModel(TPUComponent):
    def predict(self, X, names, meta=None):
        return np.asarray(X) * 2

    def metrics(self):
        return [
            {"key": "my_counter", "type": "COUNTER", "value": 2.0},
            {"key": "my_gauge", "type": "GAUGE", "value": 7.5, "tags": {"stage": "test"}},
            {"key": "my_timer", "type": "TIMER", "value": 120.0},
        ]

    def send_feedback(self, features, names, reward, truth, routing=None):
        return None


def sample(registry, name, labels):
    return registry.get_sample_value(name, labels)


class TestPrometheus:
    def test_reference_metric_names_and_tags(self):
        registry = CollectorRegistry()
        obs = PrometheusObserver("dep1", "pred1", registry=registry)
        svc = PredictorService(
            UnitSpec(name="m", type="MODEL", component=MetricModel()),
            name="pred1",
            observer=obs,
        )
        out = run(svc.predict(msg([[1.0]])))
        assert out.status["status"] == "SUCCESS"

        base = {"deployment_name": "dep1", "predictor_name": "pred1", "model_name": "m"}
        # custom metrics with the reference's deployment/predictor/model tags
        assert sample(registry, "my_counter_total", base) == 2.0
        assert sample(registry, "my_gauge", dict(base, stage="test")) == 7.5
        assert sample(registry, "my_timer_count", base) == 1.0
        # engine server histogram
        assert (
            sample(
                registry,
                "seldon_api_engine_server_requests_duration_seconds_count",
                {"deployment_name": "dep1", "predictor_name": "pred1", "method": "predictions", "code": "200"},
            )
            == 1.0
        )
        # engine->node client histogram
        assert (
            sample(
                registry,
                "seldon_api_engine_client_requests_duration_seconds_count",
                dict(base, method="transform_input"),
            )
            == 1.0
        )

    def test_feedback_counters(self):
        registry = CollectorRegistry()
        obs = PrometheusObserver("dep1", "pred1", registry=registry)
        svc = PredictorService(
            UnitSpec(name="m", type="MODEL", component=MetricModel()),
            observer=obs,
        )
        resp = run(svc.predict(msg([[1.0]])))
        fb = InternalFeedback(request=msg([[1.0]]), response=resp, reward=0.8)
        run(svc.send_feedback(fb))
        base = {"deployment_name": "dep1", "predictor_name": "pred1", "model_name": "m"}
        assert sample(registry, "seldon_api_model_feedback_total", base) == 1.0
        assert sample(registry, "seldon_api_model_feedback_reward_total", base) == pytest.approx(0.8)

    def test_observer_errors_never_break_data_plane(self):
        def exploding_observer(event, unit, payload):
            raise RuntimeError("observer bug")

        svc = PredictorService(
            UnitSpec(name="m", type="MODEL", component=MetricModel()),
            observer=exploding_observer,
        )
        out = run(svc.predict(msg([[1.0]])))
        assert out.status["status"] == "SUCCESS"


class TestTracing:
    def test_spans_per_request_and_node(self):
        tracer = tracing.setup_tracing("test-svc")
        try:
            svc = PredictorService(UnitSpec(name="m", type="MODEL", component=MetricModel()))
            out = run(svc.predict(msg([[1.0]])))
            puid = out.meta.puid
            spans = tracer.find(puid)
            names = {s.name for s in spans}
            assert "predictor.predict" in names
            assert "node.m.transform_input" in names
            for s in spans:
                assert s.duration_s >= 0
        finally:
            tracing._tracer = None

    def test_jsonl_export(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        tracer = tracing.setup_tracing("test-svc", export_path=path)
        try:
            with tracer.span("op", trace_id="t1", foo="bar"):
                pass
            lines = [json.loads(l) for l in open(path)]
            assert lines[0]["traceId"] == "t1"
            assert lines[0]["tags"]["foo"] == "bar"
        finally:
            tracer.close()
            tracing._tracer = None

    def test_jsonl_export_keeps_parent_linkage(self, tmp_path):
        """Span.to_dict carries spanId/parentSpanId, so a trace
        reassembled from the JSONL file keeps the same tree the OTLP
        exporter ships — the file lane must not lose linkage."""
        path = str(tmp_path / "spans.jsonl")
        tracer = tracing.setup_tracing("test-svc", export_path=path)
        try:
            with tracer.span("parent", trace_id="t1") as parent:
                with tracer.span("child"):
                    pass
            by_name = {
                line["name"]: line
                for line in (json.loads(l) for l in open(path))
            }
            assert by_name["parent"]["spanId"] == parent.span_id
            assert by_name["parent"]["parentSpanId"] is None  # root
            # round-trip linkage: the child's parentSpanId resolves to
            # the parent's spanId within the same trace
            assert by_name["child"]["parentSpanId"] == by_name["parent"]["spanId"]
            assert by_name["child"]["traceId"] == by_name["parent"]["traceId"]
            assert by_name["child"]["spanId"] != by_name["parent"]["spanId"]
        finally:
            tracer.close()
            tracing._tracer = None


class TestRequestLogger:
    def test_pair_logged(self, tmp_path):
        path = str(tmp_path / "pairs.jsonl")
        svc = PredictorService(
            UnitSpec(name="m", type="MODEL", component=MetricModel()),
            request_logger=JsonlPairLogger(path),
        )
        run(svc.predict(msg([[3.0]])))
        pairs = [json.loads(l) for l in open(path)]
        assert len(pairs) == 1
        assert pairs[0]["request"]["data"]["tensor"]["values"] == [3.0]
        assert pairs[0]["response"]["data"]["tensor"]["values"] == [6.0]
        assert pairs[0]["puid"]


class TestRequestLogConsumer:
    """The consumer side of the pair stream (VERDICT r2 missing #3;
    reference: seldon-request-logger/app/app.py:15-60 indexes pairs
    into ES — here SQLite + the same CloudEvents ingestion surface)."""

    def test_predict_log_ingest_query_by_puid(self, tmp_path):
        """The full loop: predict -> pair logged -> indexed -> queryable."""
        from seldon_core_tpu.utils.reqconsumer import PairIndex

        path = str(tmp_path / "pairs.jsonl")
        svc = PredictorService(
            UnitSpec(name="m", type="MODEL", component=MetricModel()),
            request_logger=JsonlPairLogger(path),
        )
        out = run(svc.predict(msg([[3.0]])))
        puid = out.meta.puid
        index = PairIndex(str(tmp_path / "pairs.sqlite"))
        assert index.ingest_jsonl(path) == 1
        pair = index.get(puid)
        assert pair is not None
        assert pair["request"]["data"]["tensor"]["values"] == [3.0]
        assert pair["response"]["data"]["tensor"]["values"] == [6.0]
        assert index.get("no-such-puid") is None

    def test_http_pair_logger_to_consumer_e2e(self, tmp_path):
        """HttpPairLogger -> CloudEvents POST -> consumer app -> query:
        the reference's engine->logger wire, end to end over sockets."""
        import asyncio
        import time as _time

        from seldon_core_tpu.utils.reqconsumer import PairIndex, build_consumer_app
        from seldon_core_tpu.utils.reqlogger import HttpPairLogger

        async def scenario():
            from aiohttp.test_utils import TestClient, TestServer

            index = PairIndex()
            client = TestClient(TestServer(build_consumer_app(index)))
            await client.start_server()
            url = f"http://127.0.0.1:{client.port}/"

            svc = PredictorService(
                UnitSpec(name="m", type="MODEL", component=MetricModel()),
                request_logger=HttpPairLogger(url),
            )
            out = await svc.predict(msg([[4.0]]))
            # the logger posts from a background thread
            deadline = _time.time() + 10.0
            while index.count() < 1 and _time.time() < deadline:
                await asyncio.sleep(0.05)
            svc.request_logger.close()

            got = await client.get(f"/pairs/{out.meta.puid}")
            body = await got.json()
            listed = await client.get("/pairs", params={"limit": "10"})
            listing = await listed.json()
            stats = await (await client.get("/stats")).json()
            await client.close()
            return got.status, body, listing, stats

        status, body, listing, stats = run(scenario())
        assert status == 200
        assert body["response"]["data"]["tensor"]["values"] == [8.0]
        assert listing["count"] == 1
        assert stats["pairs"] == 1

    def test_deployment_annotation_wires_pair_logging(self, tmp_path):
        """`seldon.io/request-log-jsonl` on a deployment spec turns on
        pair logging declaratively (the reference's
        message.logging.service env wiring)."""
        import asyncio

        from seldon_core_tpu.controlplane import Deployer, TpuDeployment
        from seldon_core_tpu.utils.reqconsumer import PairIndex

        path = str(tmp_path / "pairs.jsonl")
        spec = TpuDeployment.from_dict({
            "name": "logged-dep",
            "annotations": {"seldon.io/request-log-jsonl": path},
            "predictors": [{
                "name": "main", "traffic": 100,
                "graph": {"name": "stub", "type": "MODEL",
                          "implementation": "SIMPLE_MODEL"},
            }],
        })

        async def scenario():
            deployer = Deployer(device_ids=[0])
            managed = await deployer.apply(spec)
            out = await managed.gateway.predict(msg([[1.0]]))
            await deployer.delete("logged-dep")
            return out.meta.puid

        puid = asyncio.run(scenario())
        index = PairIndex()
        assert index.ingest_jsonl(path) >= 1
        assert index.get(puid) is not None

    def test_query_filters_and_upsert(self):
        from seldon_core_tpu.utils.reqconsumer import PairIndex

        index = PairIndex()
        for i, (puid, predictor) in enumerate(
            [("p1", "main"), ("p2", "main"), ("p3", "canary")]
        ):
            index.ingest({
                "puid": puid, "time": 100.0 + i,
                "request": {"data": {"ndarray": [[i]]}},
                "response": {"meta": {"puid": puid, "tags": {"predictor": predictor}}},
            })
        assert index.count() == 3
        assert len(index.query(predictor="main", limit=10)) == 2
        assert len(index.query(since=101.5, limit=10)) == 1
        # re-ingesting the same puid upserts, never duplicates
        index.ingest({"puid": "p1", "time": 200.0,
                      "request": {}, "response": {"meta": {"puid": "p1"}}})
        assert index.count() == 3
        assert index.get("p1")["time"] == 200.0
        # a pair without any puid is rejected loudly
        import pytest as _pytest

        with _pytest.raises(ValueError):
            index.ingest({"request": {}, "response": {}})


class TestPairStamping:
    """r21 pair enrichment: every logged pair carries a W3C traceparent
    and the response's cost-ledger totals, so an indexer can pivot
    pair -> trace -> capture -> bill without a join table."""

    _TRACEPARENT = r"^00-[0-9a-f]{32}-[0-9a-f]{16}-01$"

    def _pair_msgs(self, puid="puid-abc", cost=None):
        import re  # noqa: F401 — used by callers via the class regex

        req = msg([[1.0]])
        resp = msg([[2.0]])
        resp.meta.puid = puid
        if cost is not None:
            resp.meta.tags["cost"] = cost
        return req, resp

    def test_traceparent_is_puid_derived_without_a_live_span(self):
        import re

        from seldon_core_tpu.utils.reqlogger import build_pair

        req, resp = self._pair_msgs()
        pair = build_pair(req, resp)
        assert re.match(self._TRACEPARENT, pair["traceparent"])
        # deterministic: the same puid always yields the same ids (the
        # OTLP exporter mints the same trace id, so the pivot holds)
        again = build_pair(*self._pair_msgs())
        assert again["traceparent"] == pair["traceparent"]
        other = build_pair(*self._pair_msgs(puid="puid-xyz"))
        assert other["traceparent"] != pair["traceparent"]

    def test_traceparent_uses_the_live_span_when_one_is_active(self):
        import re

        from seldon_core_tpu.utils.reqlogger import build_pair
        from seldon_core_tpu.utils.tracing import w3c_trace_id

        tracer = tracing.setup_tracing("pair-test")
        try:
            with tracer.span("op", trace_id="t-live") as span:
                pair = build_pair(*self._pair_msgs())
            assert re.match(self._TRACEPARENT, pair["traceparent"])
            assert pair["traceparent"] == \
                f"00-{w3c_trace_id('t-live')}-{span.span_id}-01"
        finally:
            tracing._tracer = None

    def test_cost_totals_ride_the_pair(self):
        from seldon_core_tpu.utils.reqlogger import build_pair

        cost = {"page_seconds": 0.25, "decode_tokens": 8, "adapter": "base"}
        pair = build_pair(*self._pair_msgs(cost=cost))
        assert pair["cost"] == cost
        # and a costless response (telemetry off) simply omits the key
        assert "cost" not in build_pair(*self._pair_msgs())


class TestHttpPairLoggerDrainClose:
    """Satellite 3: the buffered sink's failure modes — a full queue
    drops (counted, data plane never blocks), a dead collector loses
    pairs without raising, close() drains then joins."""

    def test_full_queue_drops_and_counts(self):
        from seldon_core_tpu.utils.reqlogger import HttpPairLogger

        lg = HttpPairLogger("http://127.0.0.1:9/", capacity=2)
        # wedge the drain thread by filling faster than a dead-URL POST
        # can fail: stop the thread first so the queue genuinely fills
        lg._queue.put(None)
        lg._thread.join(timeout=5.0)
        req, resp = msg([[1.0]]), msg([[2.0]])
        resp.meta.puid = "p"
        for _ in range(4):
            lg(req, resp)
        assert lg.dropped == 2  # capacity 2, four offered

    def test_dead_collector_never_raises_and_close_is_bounded(self):
        import time as _time

        from seldon_core_tpu.utils.reqlogger import HttpPairLogger

        # port 9 (discard) refuses immediately: the POST fails fast,
        # the drain loop logs and keeps going
        lg = HttpPairLogger("http://127.0.0.1:9/", capacity=8,
                            timeout_s=0.2)
        req, resp = msg([[1.0]]), msg([[2.0]])
        resp.meta.puid = "p"
        for _ in range(3):
            lg(req, resp)  # must not raise
        t0 = _time.monotonic()
        lg.close()
        assert _time.monotonic() - t0 < 5.0
        assert not lg._thread.is_alive()
        assert lg.dropped == 0  # failures are lost downstream, not drops


class TestGatewayRequestLogger:
    """Satellite 1: the gateway-level pair sink — one logger sees every
    FINALIZED pair (predictor tag already stamped) regardless of which
    predictor served, and a sink failure never loses a request."""

    def _gateway(self, request_logger):
        from seldon_core_tpu.engine.server import Gateway

        svc = PredictorService(
            UnitSpec(name="m", type="MODEL", component=MetricModel()),
            name="main",
        )
        return Gateway([(svc, 1.0)], request_logger=request_logger)

    def test_pairs_logged_after_finalize(self, tmp_path):
        import re

        path = str(tmp_path / "gw-pairs.jsonl")
        gw = self._gateway(JsonlPairLogger(path))
        out = run(gw.predict(msg([[3.0]])))
        pairs = [json.loads(l) for l in open(path)]
        assert len(pairs) == 1
        assert pairs[0]["puid"] == out.meta.puid
        # finalize ran first: the pair records WHO served it
        assert pairs[0]["response"]["meta"]["tags"]["predictor"] == "main"
        assert re.match(TestPairStamping._TRACEPARENT,
                        pairs[0]["traceparent"])

    def test_sink_failure_loses_the_pair_never_the_request(self):
        calls = []

        def broken_logger(request, response):
            calls.append(1)
            raise RuntimeError("sink down")

        gw = self._gateway(broken_logger)
        out = run(gw.predict(msg([[3.0]])))
        assert calls == [1]
        assert out.payload is not None  # the request still served

    def test_close_closes_the_sink(self):
        class ClosableSink:
            closed = False

            def __call__(self, request, response):
                pass

            def close(self):
                self.closed = True

        sink = ClosableSink()
        gw = self._gateway(sink)
        run(gw.close())
        assert sink.closed is True


class TestGatewayLoggerAnnotation:
    """`seldon.io/request-logger` resolves to a sink by spec shape:
    http(s) URL, kafka:brokers/topic, else a JSONL path."""

    def _resolve(self, spec):
        from seldon_core_tpu.controlplane.deployer import (
            _gateway_logger_from_annotations,
        )

        return _gateway_logger_from_annotations(
            {} if spec is None else {"seldon.io/request-logger": spec}
        )

    def test_unset_is_none(self):
        assert self._resolve(None) is None
        assert self._resolve("") is None

    def test_http_url_builds_http_sink(self):
        from seldon_core_tpu.utils.reqlogger import HttpPairLogger

        lg = self._resolve("http://collector:8080/")
        try:
            assert isinstance(lg, HttpPairLogger)
            assert lg.url == "http://collector:8080/"
        finally:
            lg.close()

    def test_kafka_spec_builds_kafka_sink(self):
        from seldon_core_tpu.utils.reqlogger import KafkaPairLogger

        lg = self._resolve("kafka:b1:9092,b2:9092/pairs")
        try:
            assert isinstance(lg, KafkaPairLogger)
            assert lg.topic == "pairs"
        finally:
            lg.close(timeout_s=1.0)

    def test_malformed_kafka_spec_fails_loudly(self):
        from seldon_core_tpu.controlplane.deployer import DeploymentSpecError

        with pytest.raises(DeploymentSpecError, match="kafka"):
            self._resolve("kafka:no-topic-here")

    def test_anything_else_is_a_jsonl_path(self, tmp_path):
        from seldon_core_tpu.utils.reqlogger import JsonlPairLogger as JPL

        lg = self._resolve(str(tmp_path / "x.jsonl"))
        assert isinstance(lg, JPL)

    def test_deployment_annotation_wires_gateway_logger(self, tmp_path):
        """End to end through the deployer: the annotation lands on the
        GATEWAY (not the per-predictor graph lane) and every served
        request leaves a stamped pair."""
        import re

        from seldon_core_tpu.controlplane import Deployer, TpuDeployment

        path = str(tmp_path / "gw.jsonl")
        spec = TpuDeployment.from_dict({
            "name": "gw-logged-dep",
            "annotations": {"seldon.io/request-logger": path},
            "predictors": [{
                "name": "main", "traffic": 100,
                "graph": {"name": "stub", "type": "MODEL",
                          "implementation": "SIMPLE_MODEL"},
            }],
        })

        async def scenario():
            deployer = Deployer(device_ids=[0])
            managed = await deployer.apply(spec)
            assert isinstance(managed.gateway.request_logger,
                              JsonlPairLogger)
            out = await managed.gateway.predict(msg([[1.0]]))
            await deployer.delete("gw-logged-dep")
            return out.meta.puid

        puid = asyncio.run(scenario())
        pairs = [json.loads(l) for l in open(path)]
        assert [p["puid"] for p in pairs] == [puid]
        assert re.match(TestPairStamping._TRACEPARENT,
                        pairs[0]["traceparent"])
        assert pairs[0]["response"]["meta"]["tags"]["predictor"] == "main"


class TestMonitoringAssets:
    """The shipped prometheus/alertmanager/grafana configs stay coherent
    with the metric names the code emits (reference analogue: the
    seldon-core-analytics chart's rules + dashboards)."""

    MONITORING = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "monitoring")

    def _load(self, name):
        import yaml

        with open(os.path.join(self.MONITORING, name)) as f:
            return yaml.safe_load(f)

    def test_alert_rules_parse_and_reference_emitted_metrics(self):
        rules = self._load("alert-rules.yml")
        exprs = " ".join(
            r["expr"] for g in rules["groups"] for r in g["rules"]
        )
        # metric families that PrometheusObserver and the detectors emit
        for metric in (
            "seldon_api_engine_server_requests_duration_seconds",
            "seldon_api_engine_client_requests_duration_seconds",
            "seldon_api_model_feedback",
            "outliers_total",
            # generation lane (StreamingLM/SpeculativeLM metrics())
            "paged_pool_utilization",
            "paged_evictions",
            "speculative_acceptance_rate",
            # per-hop transport telemetry (engine -> node clients, r8)
            "seldon_tpu_transport_errors_total",
            "seldon_tpu_transport_requests_total",
            "seldon_tpu_transport_retries_total",
            # the recompile sentinel (utils/jitwatch.py)
            "seldon_tpu_jit_compiles_total",
        ):
            assert metric in exprs, f"alert rules no longer cover {metric}"
        names = {r["alert"] for g in rules["groups"] for r in g["rules"]}
        assert "TransportErrorBudgetBurn" in names
        for g in rules["groups"]:
            for r in g["rules"]:
                assert r["labels"]["severity"] in ("info", "warning", "critical")
                assert "summary" in r["annotations"]

    def test_prometheus_config_wires_rules_and_alertmanager(self):
        prom = self._load("prometheus.yml")
        assert "alert-rules.yml" in prom["rule_files"]
        targets = prom["alerting"]["alertmanagers"][0]["static_configs"][0]["targets"]
        assert targets == ["localhost:9093"]

    def test_alertmanager_routes_and_inhibition(self):
        am = self._load("alertmanager.yml")
        names = {r["name"] for r in am["receivers"]}
        assert am["route"]["receiver"] in names
        for route in am["route"].get("routes", []):
            assert route["receiver"] in names
        assert am["inhibit_rules"]

    def test_dashboards_parse_and_use_emitted_metrics(self):
        import json

        gdir = os.path.join(self.MONITORING, "grafana")
        dashboards = [f for f in os.listdir(gdir) if f.endswith(".json")]
        # predictions + outliers + generation (reference ships several)
        assert len(dashboards) >= 3
        emitted_families = (
            "seldon_api",
            "outliers_total",
            "paged_",
            "speculative_",
            "seldon_tpu_fleet_",
        )
        for name in dashboards:
            with open(os.path.join(gdir, name)) as f:
                dash = json.load(f)
            assert dash["panels"], name
            exprs = " ".join(
                t["expr"] for p in dash["panels"] for t in p.get("targets", [])
            )
            assert any(fam in exprs for fam in emitted_families), name

    def test_predictions_dashboard_covers_transport_telemetry(self):
        import json

        with open(os.path.join(self.MONITORING, "grafana", "predictions-dashboard.json")) as f:
            dash = json.load(f)
        exprs = " ".join(
            t["expr"] for p in dash["panels"] for t in p.get("targets", [])
        )
        for metric in (
            "seldon_tpu_transport_requests_total",
            "seldon_tpu_transport_errors_total",
            "seldon_tpu_transport_network_seconds",
            "seldon_tpu_transport_serialize_seconds",
            "seldon_tpu_transport_request_bytes_total",
            "seldon_tpu_transport_inflight",
            "seldon_tpu_transport_retries_total",
            "seldon_tpu_jit_compiles_total",
        ):
            assert metric in exprs, f"predictions dashboard lost {metric}"

    def test_generation_dashboard_covers_engine_stats(self):
        import json

        with open(os.path.join(self.MONITORING, "grafana", "generation-dashboard.json")) as f:
            dash = json.load(f)
        exprs = " ".join(
            t["expr"] for p in dash["panels"] for t in p.get("targets", [])
        )
        for metric in ("paged_pool_utilization", "paged_tokens_emitted",
                       "paged_stall_events", "speculative_acceptance_rate"):
            assert metric in exprs, metric


class TestOtlpExporter:
    """OTLP/HTTP JSON export (Jaeger >=1.35 / otel-collector :4318
    ingest) emitted with the stdlib — no opentelemetry-sdk."""

    def _collector(self):
        import http.server
        import threading

        received = []

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                body = self.rfile.read(int(self.headers["Content-Length"]))
                received.append((self.path, json.loads(body)))
                self.send_response(200)
                self.end_headers()
                self.wfile.write(b"{}")

            def log_message(self, *a):
                pass

        srv = http.server.HTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, received

    def test_spans_ship_in_otlp_shape(self):
        from seldon_core_tpu.utils.tracing import OtlpHttpExporter, Tracer

        srv, received = self._collector()
        try:
            exporter = OtlpHttpExporter(
                endpoint=f"http://127.0.0.1:{srv.server_port}/v1/traces",
                service_name="svc-x",
                batch_size=2,
            )
            tracer = Tracer(exporter=exporter)
            with tracer.span("predictor.predict", trace_id="puid-1", model="m1"):
                # nested span: parent linkage comes from the contextvar
                # stack, the way the engine's node spans nest in practice
                with tracer.span("node.transform_input"):
                    pass
            # batch_size=2 -> one POST fired (on the export worker)
            exporter.flush()
            assert len(received) == 1
            path, body = received[0]
            assert path == "/v1/traces"
            rs = body["resourceSpans"][0]
            svc_attr = rs["resource"]["attributes"][0]
            assert svc_attr == {"key": "service.name", "value": {"stringValue": "svc-x"}}
            spans = rs["scopeSpans"][0]["spans"]
            # child closes (and records) first
            spans.sort(key=lambda x: x["name"])
            assert [s["name"] for s in spans] == ["node.transform_input", "predictor.predict"]
            spans.reverse()  # [parent, child]
            # same puid -> same 32-hex traceId; child links its parent
            assert spans[0]["traceId"] == spans[1]["traceId"]
            assert len(spans[0]["traceId"]) == 32 and len(spans[0]["spanId"]) == 16
            # the child inherited the trace and links the parent's real id
            assert spans[1]["parentSpanId"] == spans[0]["spanId"]
            assert spans[1]["spanId"] != spans[0]["spanId"]
            assert int(spans[0]["endTimeUnixNano"]) >= int(spans[0]["startTimeUnixNano"])
            assert exporter.exported == 2
        finally:
            srv.shutdown()

    def test_collector_down_never_raises(self):
        from seldon_core_tpu.utils.tracing import OtlpHttpExporter, Span

        exporter = OtlpHttpExporter(endpoint="http://127.0.0.1:1/v1/traces", timeout_s=0.2)
        assert exporter.export([Span(trace_id="t", name="n", start_s=0.0)]) is False
        assert exporter.failures == 1
        exporter.close()

    def test_full_queue_drops_oldest_and_counts(self):
        """A blackholed collector must not grow memory without limit:
        the export queue is bounded, overflow sheds the OLDEST batch,
        and the loss lands in the `dropped` counter."""
        import threading

        from seldon_core_tpu.utils.tracing import OtlpHttpExporter, Span

        release = threading.Event()
        exporter = OtlpHttpExporter(
            endpoint="http://127.0.0.1:1/v1/traces",
            batch_size=1, max_queue_batches=2, timeout_s=0.2,
        )
        # wedge the worker inside its current batch: every batch after
        # the in-flight one piles into the bounded queue
        orig_export = exporter.export
        first = threading.Event()

        def blocked_export(spans):
            first.set()
            release.wait(timeout=10)
            return orig_export(spans)

        exporter.export = blocked_export
        try:
            exporter(Span(trace_id="t", name="s0", start_s=0.0))
            assert first.wait(timeout=5)  # worker is now wedged
            for i in range(1, 8):  # 7 more batches into a queue of 2
                exporter(Span(trace_id="t", name=f"s{i}", start_s=0.0))
            assert exporter._queue.qsize() <= 2  # bounded under load
            assert exporter.dropped == 5  # 7 offered - 2 retained
        finally:
            release.set()
            exporter.close()

    def test_unwedged_exporter_drops_nothing(self):
        from seldon_core_tpu.utils.tracing import OtlpHttpExporter, Span

        srv, received = self._collector()
        try:
            exporter = OtlpHttpExporter(
                endpoint=f"http://127.0.0.1:{srv.server_port}/v1/traces",
                batch_size=1,  # default queue bound: 8 batches fit easily
            )
            for i in range(8):
                exporter(Span(trace_id="t", name=f"s{i}", start_s=0.0))
            exporter.flush()
            assert exporter.dropped == 0
            assert exporter.exported == 8
        finally:
            srv.shutdown()

    def test_setup_tracing_env_wiring(self, monkeypatch):
        from seldon_core_tpu.utils import tracing

        srv, received = self._collector()
        try:
            monkeypatch.setenv(
                "OTEL_EXPORTER_OTLP_ENDPOINT", f"http://127.0.0.1:{srv.server_port}"
            )
            tracer = tracing.setup_tracing(service_name="env-svc")
            assert tracer.exporter is not None
            assert tracer.exporter.endpoint.endswith("/v1/traces")
            with tracer.span("op", trace_id="p"):
                pass
            tracer.close()  # flushes the partial batch
            assert len(received) == 1
        finally:
            srv.shutdown()
            tracing._tracer = None


class FakeKafkaBroker:
    """In-repo Kafka broker speaking Metadata v0 + Produce v0 over a
    real socket (the reference ships a runnable cluster, kafka/
    kafka.json:1-30; this is the no-egress stand-in).  Decoding here is
    written INDEPENDENTLY of utils/kafka.py's encoder — struct-level,
    CRC re-verified — so the contract test catches a wrong frame on
    either side rather than a shared bug cancelling out."""

    def __init__(self, partitions: int = 2):
        import socket

        self.partitions = partitions
        self.records = []  # (topic, partition, key, value)
        self.produce_frames = []  # raw produce request payloads
        self._srv = socket.socket()
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(4)
        self.port = self._srv.getsockname()[1]
        self._running = True
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    # ---- wire helpers (independent decode) --------------------------------

    @staticmethod
    def _rd_str(buf, off):
        (n,) = struct.unpack_from(">h", buf, off)
        off += 2
        if n < 0:
            return None, off
        return buf[off:off + n].decode(), off + n

    @staticmethod
    def _wr_str(s):
        b = s.encode()
        return struct.pack(">h", len(b)) + b

    def _serve(self):
        while self._running:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            threading.Thread(target=self._handle, args=(conn,), daemon=True).start()

    def _recv_exact(self, conn, n):
        out = b""
        while len(out) < n:
            chunk = conn.recv(n - len(out))
            if not chunk:
                return None
            out += chunk
        return out

    def _handle(self, conn):
        try:
            while True:
                head = self._recv_exact(conn, 4)
                if head is None:
                    return
                (size,) = struct.unpack(">i", head)
                payload = self._recv_exact(conn, size)
                if payload is None:
                    return
                api_key, api_version, corr = struct.unpack_from(">hhi", payload, 0)
                _client, off = self._rd_str(payload, 8)
                assert api_version == 0, f"broker only speaks v0, got {api_version}"
                if api_key == 3:
                    resp = self._metadata_response(payload, off)
                elif api_key == 0:
                    resp = self._produce_response(payload, off)
                else:
                    return
                frame = struct.pack(">i", corr) + resp
                conn.sendall(struct.pack(">i", len(frame)) + frame)
        except (ConnectionError, OSError):
            return
        finally:
            conn.close()

    def _metadata_response(self, buf, off):
        (n_topics,) = struct.unpack_from(">i", buf, off)
        off += 4
        names = []
        for _ in range(n_topics):
            name, off = self._rd_str(buf, off)
            names.append(name)
        out = struct.pack(">i", 1)  # one broker
        out += struct.pack(">i", 0) + self._wr_str("127.0.0.1") + struct.pack(">i", self.port)
        out += struct.pack(">i", len(names))
        for name in names:
            parts = b""
            for p in range(self.partitions):
                parts += struct.pack(">hii", 0, p, 0)  # err, id, leader=node 0
                parts += struct.pack(">ii", 1, 0)      # replicas [0]
                parts += struct.pack(">ii", 1, 0)      # isr [0]
            out += struct.pack(">h", 0) + self._wr_str(name)
            out += struct.pack(">i", self.partitions) + parts
        return out

    def _produce_response(self, buf, off):
        import zlib

        self.produce_frames.append(buf)
        _acks, _timeout = struct.unpack_from(">hi", buf, off)
        off += 6
        (n_topics,) = struct.unpack_from(">i", buf, off)
        off += 4
        resp_topics = b""
        for _ in range(n_topics):
            topic, off = self._rd_str(buf, off)
            (n_parts,) = struct.unpack_from(">i", buf, off)
            off += 4
            parts_resp = b""
            for _ in range(n_parts):
                partition, mset_size = struct.unpack_from(">ii", buf, off)
                off += 8
                end = off + mset_size
                base = len(self.records)
                while off + 12 <= end:
                    _offset, msize = struct.unpack_from(">qi", buf, off)
                    off += 12
                    (crc,) = struct.unpack_from(">I", buf, off)
                    body = buf[off + 4:off + msize]
                    off += msize
                    assert zlib.crc32(body) & 0xFFFFFFFF == crc, "CRC mismatch"
                    magic, _attrs = struct.unpack_from(">bb", body, 0)
                    assert magic == 0
                    (klen,) = struct.unpack_from(">i", body, 2)
                    p = 6
                    key = None
                    if klen >= 0:
                        key = body[p:p + klen]
                        p += klen
                    (vlen,) = struct.unpack_from(">i", body, p)
                    p += 4
                    value = body[p:p + vlen]
                    self.records.append((topic, partition, key, value))
                parts_resp += struct.pack(">ihq", partition, 0, base)
            resp_topics += self._wr_str(topic) + struct.pack(">i", n_parts) + parts_resp
        return struct.pack(">i", n_topics) + resp_topics

    def close(self):
        self._running = False
        self._srv.close()


class TestKafkaPairLogger:
    """The Kafka lane produced to a (fake) broker over a real socket:
    wire frames byte-verified broker-side (VERDICT r4 missing #3 —
    the lane had never produced to anything)."""

    def test_pairs_stream_to_topic_over_the_wire(self):
        from seldon_core_tpu.runtime.message import InternalMessage
        from seldon_core_tpu.utils.reqlogger import KafkaPairLogger

        broker = FakeKafkaBroker(partitions=2)
        try:
            logger = KafkaPairLogger(f"127.0.0.1:{broker.port}", topic="pairs")
            req = InternalMessage(payload=np.asarray([[1.0, 2.0]]), kind="ndarray")
            req.meta.puid = "p-1"
            logger(req, req.with_payload(np.asarray([[0.9]])))
            logger.close()  # drains the queue, so the send has landed
            assert logger.sent == 1 and logger.dropped == 0
            assert len(broker.records) == 1
            topic, partition, key, value = broker.records[0]
            assert topic == "pairs"
            assert 0 <= partition < 2
            assert key == b"p-1"  # puid-keyed -> stable partition
            pair = json.loads(value)
            assert pair["request"]["data"]["ndarray"] == [[1.0, 2.0]]
            assert pair["response"]["data"]["ndarray"] == [[0.9]]
            assert pair["puid"] == "p-1"
            # byte-level: the produce frame carries v0 framing
            assert any(b"pairs" in f for f in broker.produce_frames)
        finally:
            broker.close()

    def test_puid_keys_pin_partition(self):
        from seldon_core_tpu.runtime.message import InternalMessage
        from seldon_core_tpu.utils.reqlogger import KafkaPairLogger

        broker = FakeKafkaBroker(partitions=4)
        try:
            logger = KafkaPairLogger(f"127.0.0.1:{broker.port}", topic="t")
            req = InternalMessage(payload=np.asarray([[1.0]]), kind="ndarray")
            req.meta.puid = "same-puid"
            for _ in range(3):
                logger(req, req.with_payload(np.asarray([[2.0]])))
            logger.close()
            parts = {p for (_, p, _, _) in broker.records}
            assert len(broker.records) == 3 and len(parts) == 1
        finally:
            broker.close()

    def test_multi_broker_bootstrap_falls_through_dead_entries(self):
        """Standard 'b1:9092,b2:9092' bootstrap lists parse, and an
        unreachable first broker falls through to a live one."""
        from seldon_core_tpu.utils.kafka import MiniKafkaProducer

        broker = FakeKafkaBroker(partitions=1)
        try:
            p = MiniKafkaProducer(
                f"127.0.0.1:1,127.0.0.1:{broker.port}", timeout_s=1.0
            )
            assert p.send("t", b"v") == 0
            p.close()
        finally:
            broker.close()

    def test_producer_reconnects_after_connection_drop(self):
        """A dead connection is dropped (with the metadata cache) and
        the next send reconnects — one broker hiccup must not kill the
        logging lane for the process lifetime."""
        from seldon_core_tpu.utils.kafka import MiniKafkaProducer

        broker = FakeKafkaBroker(partitions=1)
        try:
            p = MiniKafkaProducer(f"127.0.0.1:{broker.port}", timeout_s=1.0)
            assert p.send("t", b"one") == 0
            # sever every live connection under the producer
            for sock in list(p._conns.values()):
                sock.close()
            p._conns.clear()  # simulate the post-error _drop state
            assert p.send("t", b"two") >= 0
            assert [v for (_, _, _, v) in broker.records] == [b"one", b"two"]
            p.close()
        finally:
            broker.close()

    def test_broker_outage_is_counted_not_silent(self):
        """Pairs lost to a dead broker must show in the counters, not
        only in a warning log line.  (The outage is a never-listening
        port: closing a FakeKafkaBroker mid-accept leaves CPython's
        deferred-fd-close serving one more connection.)"""
        from seldon_core_tpu.runtime.message import InternalMessage
        from seldon_core_tpu.utils.reqlogger import KafkaPairLogger

        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()  # nothing ever listens here
        logger = KafkaPairLogger(f"127.0.0.1:{port}", topic="t", timeout_s=0.5)
        req = InternalMessage(payload=np.asarray([[1.0]]), kind="ndarray")
        req.meta.puid = "p"
        logger(req, req.with_payload(np.asarray([[2.0]])))
        logger.close()
        assert logger.failed == 1 and logger.sent == 0

    def test_close_is_bounded_with_full_queue_and_stuck_broker(self, monkeypatch):
        """Shutdown must not hang when the queue is full AND the broker
        is wedged mid-send: the old blocking put(None) waited for queue
        room that a stuck drain thread would never free.  close() now
        signals a stop flag with a deadline and returns."""
        import time as _time

        from seldon_core_tpu.runtime.message import InternalMessage
        from seldon_core_tpu.utils.reqlogger import KafkaPairLogger

        broker = FakeKafkaBroker(partitions=1)
        try:
            logger = KafkaPairLogger(
                f"127.0.0.1:{broker.port}", topic="t", capacity=1
            )
            # wedge the producer: every send blocks far past the test
            monkeypatch.setattr(
                logger._producer, "send",
                lambda *a, **k: _time.sleep(30),
            )
            req = InternalMessage(payload=np.asarray([[1.0]]), kind="ndarray")
            req.meta.puid = "p"
            # first pair occupies the drain thread inside the stuck
            # send; the second fills the capacity-1 queue
            logger(req, req.with_payload(np.asarray([[2.0]])))
            deadline = _time.monotonic() + 2.0
            while logger._queue.qsize() > 0 and _time.monotonic() < deadline:
                _time.sleep(0.01)  # wait for the drain thread to pick up #1
            logger(req, req.with_payload(np.asarray([[2.0]])))
            assert logger._queue.full()
            t0 = _time.monotonic()
            logger.close(timeout_s=0.5)
            assert _time.monotonic() - t0 < 5.0  # bounded, not wedged
        finally:
            broker.close()

    def test_close_still_flushes_pending_pairs(self):
        """The bounded close keeps the old flush semantics when the
        broker is healthy: pairs enqueued before close() land."""
        from seldon_core_tpu.runtime.message import InternalMessage
        from seldon_core_tpu.utils.reqlogger import KafkaPairLogger

        broker = FakeKafkaBroker(partitions=1)
        try:
            logger = KafkaPairLogger(f"127.0.0.1:{broker.port}", topic="t")
            req = InternalMessage(payload=np.asarray([[1.0]]), kind="ndarray")
            req.meta.puid = "p"
            for _ in range(5):
                logger(req, req.with_payload(np.asarray([[2.0]])))
            logger.close()
            assert logger.sent == 5 and len(broker.records) == 5
        finally:
            broker.close()

    def test_producer_roundtrip_primitives(self):
        """encode/decode of the v0 message set are inverses and CRC'd
        (the recorded-bytes half of the contract)."""
        from seldon_core_tpu.utils.kafka import decode_message_set, encode_message_set

        mset = encode_message_set(b"k", b"v" * 100)
        assert decode_message_set(mset) == [(b"k", b"v" * 100)]
        corrupted = mset[:-1] + bytes([mset[-1] ^ 0xFF])
        with pytest.raises(ValueError, match="CRC"):
            decode_message_set(corrupted)


class TestHistogramQuantileSamplerEdges:
    """Edge cases of the windowed-quantile estimate the autoscaler
    consumes: a counter reset must not interpolate garbage from
    negative deltas, and all-traffic-in-+Inf must return the last
    finite bound rather than inf/nonsense."""

    def _sampler(self, quantile=0.95):
        import prometheus_client as prom

        from seldon_core_tpu.utils.metrics import HistogramQuantileSampler

        registry = prom.CollectorRegistry()
        hist = prom.Histogram(
            "edge_hist", "t", registry=registry,
            buckets=(0.1, 1.0, 10.0),
        )
        return hist, HistogramQuantileSampler(hist, quantile=quantile)

    def test_counter_reset_returns_zero_then_recovers(self):
        hist, sampler = self._sampler()
        for _ in range(20):
            hist.observe(0.05)
        sampler()  # prime the window
        for _ in range(10):
            hist.observe(0.05)
        assert sampler() > 0.0
        # counter reset: the previous sample claims MORE cumulative
        # traffic than the live histogram now shows (process restart /
        # histogram re-registration) -> negative deltas
        sampler._last = [c + 1000.0 for c in sampler._last]
        got = sampler()
        assert got == 0.0  # no garbage (pre-guard this interpolated junk)
        # and the very next window is healthy again
        for _ in range(10):
            hist.observe(0.05)
        recovered = sampler()
        assert 0.0 < recovered <= 0.1

    def test_all_traffic_in_inf_bucket_returns_last_finite_bound(self):
        hist, sampler = self._sampler()
        sampler()  # prime
        for _ in range(50):
            hist.observe(99.0)  # beyond every finite bucket bound
        got = sampler()
        assert got == 10.0  # the last finite bound, never inf or 0

    def test_empty_window_stays_zero(self):
        _hist, sampler = self._sampler()
        assert sampler() == 0.0
        assert sampler() == 0.0


class TestJitSentinel:
    """utils/jitwatch.py: the first call per distinct argument-shape
    signature is a compile event — counted and WARNed; repeat shapes
    are free of both."""

    def test_counts_once_per_signature_and_warns(self, caplog):
        import logging

        import prometheus_client as prom

        from seldon_core_tpu.utils.jitwatch import JitSentinel

        import jax
        import jax.numpy as jnp

        sentinel = JitSentinel("test_prog_sig")
        fn = sentinel.wrap(jax.jit(lambda x: x * 2), static="variant=a")
        before = prom.REGISTRY.get_sample_value(
            "seldon_tpu_jit_compiles_total", {"program": "test_prog_sig"}
        ) or 0.0
        with caplog.at_level(logging.WARNING, logger="seldon_core_tpu.utils.jitwatch"):
            fn(jnp.zeros((2, 2)))
            fn(jnp.ones((2, 2)))   # same signature: no new compile
            fn(jnp.zeros((4, 4)))  # new shape: compile event
        assert sentinel.compiles == 2
        after = prom.REGISTRY.get_sample_value(
            "seldon_tpu_jit_compiles_total", {"program": "test_prog_sig"}
        )
        assert after - before == 2.0
        warns = [r for r in caplog.records if "jit compile" in r.getMessage()]
        assert len(warns) == 2
        # the WARN names the program AND the triggering signature
        assert "test_prog_sig" in warns[0].getMessage()
        assert "(2, 2)" in warns[0].getMessage()
        assert "(4, 4)" in warns[1].getMessage()

    def test_static_key_separates_variants(self):
        from seldon_core_tpu.utils.jitwatch import JitSentinel

        import jax
        import jax.numpy as jnp

        sentinel = JitSentinel("test_prog_static")
        a = sentinel.wrap(jax.jit(lambda x: x + 1), static="steps=8")
        b = sentinel.wrap(jax.jit(lambda x: x + 2), static="steps=16")
        a(jnp.zeros((2,)))
        b(jnp.zeros((2,)))  # same array shape, distinct static key
        assert sentinel.compiles == 2

    def test_a_leafs_dtype_is_named_once(self):
        """The walk names a leaf ``(shape, str(dtype))``; the name of a
        dtype is computed once, not per leaf per call (a dozen python
        calls each, over a whole parameter tree)."""
        import numpy as np

        from seldon_core_tpu.utils import jitwatch

        import jax.numpy as jnp

        for leaf in (np.zeros((2, 3), np.float32), jnp.zeros((4,), jnp.bfloat16),
                     np.zeros((), np.int32)):
            assert jitwatch._leaf_sig(leaf) == (tuple(leaf.shape), str(leaf.dtype))
            assert jitwatch._DTYPE_NAMES[leaf.dtype] == str(leaf.dtype)
        assert jitwatch._leaf_sig(3) == "int" and jitwatch._leaf_sig(None) == "NoneType"

    def test_kill_switch_returns_fn_unwrapped(self, monkeypatch):
        from seldon_core_tpu.utils.jitwatch import JitSentinel

        monkeypatch.setenv("SELDON_TPU_JIT_SENTINEL", "0")
        sentinel = JitSentinel("test_prog_off")
        fn = lambda x: x  # noqa: E731
        assert sentinel.wrap(fn) is fn

    def test_engine_stats_exposes_summed_compiles(self):
        """PagedEngine wires sentinels on its chunk/prefill programs and
        engine_stats carries the sum (bridge-excluded: jitwatch exports
        the per-program split itself)."""
        import numpy as np

        import jax
        import jax.numpy as jnp

        from seldon_core_tpu.models.paged import PagedEngine
        from seldon_core_tpu.models.transformer import TransformerLM

        lm = TransformerLM(vocab_size=256, d_model=64, num_layers=1,
                           num_heads=4, max_len=128, dtype=jnp.float32)
        params = lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
        eng = PagedEngine(
            params, vocab_size=256, d_model=64, num_layers=1, num_heads=4,
            max_len=128, page_size=16, max_slots=2, steps_per_call=4,
            dtype=jnp.float32,
        )
        try:
            assert eng.engine_stats()["jit_compiles"] == 0
            eng.submit(np.arange(8, dtype=np.int32), max_new_tokens=4)
            while eng.has_work():
                eng.step()
            # at least the prefill + one chunk program compiled
            assert eng.engine_stats()["jit_compiles"] >= 2
        finally:
            eng.close()


class TestSharedRegistryObservers:
    def test_two_observers_one_registry_no_duplicate_timeseries(self):
        """Two predictors of one deployment (or a rolling re-apply)
        share the process registry; metric objects must be shared, with
        only label values differing."""
        import prometheus_client as prom

        from seldon_core_tpu.utils.metrics import PrometheusObserver, api_latency_sampler

        registry = prom.CollectorRegistry()
        a = PrometheusObserver("dep", "main", registry=registry)
        b = PrometheusObserver("dep", "canary", registry=registry)
        # both paths that register metrics must not collide
        a("predict_done", "m", 0.01)
        b("predict_done", "m", 0.02)
        sampler_a = api_latency_sampler(a)
        sampler_b = api_latency_sampler(b)
        sampler_a(), sampler_b()  # prime both without raising
        for _ in range(10):
            a("predict_done", "m", 0.2)
        assert sampler_a() > 0.0
        assert sampler_b() == 0.0  # canary saw no traffic
