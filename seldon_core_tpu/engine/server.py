"""Orchestrator front server: external REST + gRPC around predictors.

The ingress-facing shell of the data plane, equivalent to the reference
engine's controllers (reference: RestClientController.java:127-268,
SeldonGrpcServer.java:30-60, SeldonService.java:30-67):

    POST /api/v0.1/predictions   POST /api/v0.1/feedback
    GET  /ping /ready /live      PUT/POST /pause /unpause
    GET  /metrics
    gRPC seldon.protos.Seldon/Predict, /SendFeedback

A ``Gateway`` fronts one *deployment* = several predictors with traffic
weights (canary / A-B across predictors, the reference's Istio
VirtualService weight semantics,
reference: seldondeployment_controller.go:171-239) plus optional shadow
traffic (reference: ambassador.go:50-133).
"""

from __future__ import annotations

import asyncio
import logging
import random
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import grpc
from aiohttp import web

from seldon_core_tpu.engine.service import PredictorService, failure_message
from seldon_core_tpu.proto import pb, services
from seldon_core_tpu.runtime.component import MicroserviceError
from seldon_core_tpu.runtime.executor_pool import dispatch_pool
from seldon_core_tpu.runtime.message import InternalFeedback, InternalMessage
from seldon_core_tpu.runtime.rest import _error_response, _request_body

logger = logging.getLogger(__name__)


class Gateway:
    """Weighted traffic split across predictors of one deployment."""

    def __init__(
        self,
        predictors: Sequence[Tuple[PredictorService, float]],
        shadows: Sequence[PredictorService] = (),
        seed: Optional[int] = None,
        supervisor=None,
        request_logger=None,
    ):
        if not predictors:
            raise ValueError("gateway needs at least one predictor")
        # gateway-level request/response pair sink (r21): the
        # `seldon.io/request-logger` annotation lands here — one logger
        # sees every FINALIZED pair regardless of which predictor
        # served it (the per-predictor loggers inside PredictorService
        # see pre-routing graph traffic instead).  Pairs are stamped
        # with puid + traceparent + cost by utils/reqlogger.build_pair.
        self.request_logger = request_logger
        # the Supervisor owning this deployment's remote workers (None
        # when every node is in-process): /debug/workers reads through
        # it so the breaker/alert layer can see a restart-exhausted
        # (silently dead) worker instead of inferring it from absence
        self.supervisor = supervisor
        self.entries: List[Tuple[PredictorService, float]] = list(predictors)
        total = sum(w for _, w in self.entries)
        if total <= 0:  # all-zero weights -> uniform
            self.entries = [(p, 1.0) for p, _ in self.entries]
            total = float(len(self.entries))
        self._weights = [w / total for _, w in self.entries]
        self.shadows = list(shadows)
        self._rng = random.Random(seed)
        # puid -> serving predictor name, so feedback can be routed to
        # the predictor that actually served the request (reference
        # semantics: PredictiveUnitBean.java:206-246 follows the
        # recorded routing; broadcasting would teach every predictor's
        # MAB from traffic it never saw).  Bounded FIFO eviction.
        self._served: "OrderedDict[str, str]" = OrderedDict()
        self._served_cap = 65536
        self._served_lock = threading.Lock()

    def _record_served(self, puid: str, predictor: str) -> None:
        if not puid:
            return
        with self._served_lock:
            self._served[puid] = predictor
            while len(self._served) > self._served_cap:
                self._served.popitem(last=False)

    def finalize_response(self, response: InternalMessage, request: InternalMessage,
                          svc: PredictorService) -> InternalMessage:
        """Stamp the serving predictor on the response and record the
        puid→predictor mapping — single helper shared by the async and
        sync ingress paths so they cannot drift.  The tag is assigned
        unconditionally: a request may arrive with a stale client-echoed
        `predictor` tag that would otherwise misroute feedback."""
        response.meta.tags["predictor"] = svc.name
        self._record_served(response.meta.puid or request.meta.puid, svc.name)
        return response

    def _feedback_target(self, feedback: InternalFeedback) -> Optional[PredictorService]:
        """The predictor that served the request, if identifiable: by
        the `predictor` response tag, else by the recorded puid.  An
        unresolvable tag (renamed/removed predictor, garbage client
        tag) falls through to the puid lookup rather than giving up."""
        for msg in (feedback.response, feedback.request):
            if msg is None:
                continue
            name = msg.meta.tags.get("predictor")
            if name:
                svc = self.by_name(str(name))
                if svc is not None:
                    return svc
            if msg.meta.puid:
                with self._served_lock:
                    name = self._served.get(msg.meta.puid)
                if name:
                    svc = self.by_name(name)
                    if svc is not None:
                        return svc
        return None

    @property
    def predictors(self) -> List[PredictorService]:
        return [p for p, _ in self.entries]

    def pick(self) -> PredictorService:
        r = self._rng.random()
        acc = 0.0
        for (svc, _), w in zip(self.entries, self._weights):
            acc += w
            if r < acc:
                return svc
        return self.entries[-1][0]

    def by_name(self, name: str) -> Optional[PredictorService]:
        for svc in self.predictors:
            if svc.name == name:
                return svc
        return None

    async def predict(self, request: InternalMessage, predictor: Optional[str] = None) -> InternalMessage:
        svc = self.by_name(predictor) if predictor else None
        if svc is None:
            svc = self.pick()
        # shadow traffic: fire-and-forget isolated copies, responses
        # dropped — the primary and shadows each mutate their own meta
        # (puid assignment), never a shared one
        for shadow in self.shadows:
            asyncio.ensure_future(shadow.predict(request.copy()))
        response = await svc.predict(request)
        response = self.finalize_response(response, request, svc)
        if self.request_logger is not None:
            # buffered sinks return immediately; the JSONL sink does
            # one small write — either way a logging failure must lose
            # a pair, never a request
            try:
                self.request_logger(request, response)
            except Exception:  # noqa: BLE001 — lose a pair, never a request
                logger.exception("gateway request logger failed")
        return response

    async def send_feedback(self, feedback: InternalFeedback) -> InternalMessage:
        # feedback goes ONLY to the predictor that served the request
        # (predictor tag or recorded puid).  Unidentifiable feedback is
        # a counted drop — never a broadcast: the reference follows the
        # recorded routing path and nothing else
        # (reference: PredictiveUnitBean.java:206-246); broadcasting
        # would teach every predictor's bandit from traffic it never
        # served, silently corrupting A/B statistics.
        target = self._feedback_target(feedback)
        if target is None and len(self.entries) == 1 and not self._has_identifiers(feedback):
            # single-predictor gateway AND the feedback never carried a
            # tag/puid (the reference client's bare request-only shape):
            # the route is unambiguous.  Feedback whose identifiers
            # FAILED to resolve (stale tag from a removed predictor,
            # evicted puid) still drops — it may belong to a predictor
            # that no longer exists here.
            target = self.entries[0][0]
        if target is None:
            self._count_unrouted_feedback()
            msg = InternalMessage(
                payload=None,
                kind="jsonData",
                status={
                    "status": "FAILURE",
                    "code": 404,
                    "info": "feedback not routable: no predictor tag and "
                            "puid unknown (expired or never served here)",
                    "reason": "FEEDBACK_UNROUTED",
                },
            )
            return msg
        return await target.send_feedback(feedback)

    @staticmethod
    def _has_identifiers(feedback: InternalFeedback) -> bool:
        """True when the feedback carries any routing identifier (a
        predictor tag or a puid) on its response or request."""
        for msg in (feedback.response, feedback.request):
            if msg is not None and (msg.meta.tags.get("predictor") or msg.meta.puid):
                return True
        return False

    def _count_unrouted_feedback(self) -> None:
        logger.warning("dropping unroutable feedback (no predictor tag, puid unknown)")
        from seldon_core_tpu.utils.metrics import increment_counter

        increment_counter(
            "seldon_api_gateway_feedback_unrouted",
            "feedback messages dropped because the serving predictor "
            "could not be identified",
        )

    async def ready(self) -> bool:
        checks = await asyncio.gather(*(p.ready() for p in self.predictors))
        return all(checks)

    def pause(self) -> None:
        for p in self.predictors:
            p.pause()

    def unpause(self) -> None:
        for p in self.predictors:
            p.unpause()

    async def close(self) -> None:
        await asyncio.gather(*(p.close() for p in self.predictors))
        if self.request_logger is not None and hasattr(self.request_logger, "close"):
            try:
                self.request_logger.close()
            except Exception:  # noqa: BLE001 — shutdown must finish
                logger.exception("gateway request logger close failed")


def components_health(gateway) -> Dict[str, Dict[str, object]]:
    """predictor -> node -> the local component's ``health_status()``:
    the gateway's ``GET /health/status`` body on either frontend, so a
    client of a deployment can tell which device served it (the
    microservice CLI serves the same hook per component)."""
    out: Dict[str, Dict[str, object]] = {}
    for svc in gateway.predictors:
        nodes = {}
        for unit in svc.graph.walk():
            fn = getattr(svc.executor.component(unit.name), "health_status", None)
            if fn is not None:
                nodes[unit.name] = fn()
        out[svc.name] = nodes
    return out


def _pull(it, written: Optional[float], sentinel):
    """The next event of a component's blocking ``predict_stream``
    generator (on an executor thread), ``sentinel`` at its end.
    ``written``: the ``time.monotonic()`` at which the transport's
    write of the event before returned — sent into the generator,
    which counts the event's way out to there
    (``PagedEngine.stream_events``); None on the first pull."""
    try:
        if written is not None and hasattr(it, "send"):
            return it.send(written)
        return next(it)
    except StopIteration:
        return sentinel


def _http_status(out: InternalMessage) -> int:
    """HTTP code for a gateway response: FAILURE statuses surface their
    code (clamped to a valid HTTP error range), everything else is 200.
    Shared by the REST handlers and the native lane's bridge handler
    (native/frontserver.py)."""
    if out.status and out.status.get("status") == "FAILURE":
        code = int(out.status.get("code", 500))
        return code if 400 <= code < 600 else 500
    return 200


def build_gateway_app(gateway: Gateway, auth=None) -> web.Application:
    """``auth`` is an ``utils.auth.OAuthConfig``; when set, the data
    endpoints require ``Authorization: Bearer`` tokens issued by this
    gateway's ``/oauth/token`` (client-credentials grant — the
    reference's legacy API-gateway flow,
    reference: seldon_client.py:1186-1227). Health/metrics endpoints
    stay open, like the reference's probe surface."""
    issuer = None
    if auth is not None:
        from seldon_core_tpu.utils.auth import TokenIssuer, parse_basic_auth

        issuer = TokenIssuer(auth)

        @web.middleware
        async def require_token(request: web.Request, handler):
            # data endpoints AND mutating admin verbs (/pause, /unpause)
            # need a token; probes + /metrics + /oauth/token stay open
            guarded = (
                request.path.startswith("/api/")
                or request.path in ("/predict", "/pause", "/unpause")
            )
            if guarded and not issuer.verify_header(request.headers.get("Authorization")):
                from seldon_core_tpu.utils.auth import UNAUTHENTICATED_MSG

                resp = web.json_response(
                    {"status": {"status": "FAILURE", "code": 401,
                                "info": UNAUTHENTICATED_MSG,
                                "reason": "UNAUTHORIZED"}},
                    status=401,
                )
                # small declared bodies drain (keeps keep-alive sockets
                # reusable); body-less requests (GET/HEAD probes, POSTs
                # with no Content-Length and no Transfer-Encoding) have
                # nothing to drain and keep their socket too; only
                # chunked/unsized uploads or oversized declared bodies
                # force a close — buffering those for a 401 would pay
                # for bytes we are rejecting
                cl = request.content_length
                chunked = "chunked" in request.headers.get("Transfer-Encoding", "").lower()
                if cl is not None and cl <= 1 << 20:
                    await request.read()
                elif cl is None and not chunked:
                    pass  # no body on the wire — nothing to drain
                else:
                    resp.force_close()
                return resp
            return await handler(request)

        app = web.Application(
            client_max_size=1024 * 1024 * 512, middlewares=[require_token]
        )

        async def oauth_token(request: web.Request) -> web.Response:
            creds = parse_basic_auth(request.headers.get("Authorization"))
            if creds is None or not issuer.check_credentials(*creds):
                return web.json_response({"error": "invalid_client"}, status=401)
            return web.json_response(issuer.issue())

        app.router.add_post("/oauth/token", oauth_token)
    else:
        app = web.Application(client_max_size=1024 * 1024 * 512)

    async def predictions(request: web.Request) -> web.Response:
        from seldon_core_tpu.runtime.rest import _remote_ctx, _remote_deadline_ms
        from seldon_core_tpu.utils import deadlines as _deadlines
        from seldon_core_tpu.utils.tracing import activate_context

        try:
            body = await _request_body(request)
            msg = InternalMessage.from_json(body)
            # SLO ingress: X-Seldon-Deadline-Ms mints the end-to-end
            # budget (carried by contextvar through every hop below);
            # X-Seldon-Priority lands in meta.tags so the generation
            # engine's admission/shedding sees it.  An explicit tag in
            # the body wins over the header.
            prio = _deadlines.extract_priority(request.headers)
            if prio is not None and "priority" not in msg.meta.tags:
                msg.meta.tags["priority"] = prio
            # X-Seldon-Adapter selects the LoRA weight set (r16); an
            # explicit tag in the body wins, same precedence as priority
            adapter = _deadlines.extract_adapter(request.headers)
            if adapter and "adapter" not in msg.meta.tags:
                msg.meta.tags["adapter"] = adapter
            # an external caller's traceparent makes the gateway's
            # predictor.predict span a child of ITS trace — the whole
            # graph then stitches under the caller's root
            with activate_context(_remote_ctx(request)), \
                    _deadlines.activate_ms(_remote_deadline_ms(request)):
                _deadlines.check("gateway ingress /api/v0.1/predictions")
                out = await gateway.predict(msg, predictor=request.query.get("predictor"))
            return web.json_response(out.to_json(), status=_http_status(out))
        except Exception as e:  # noqa: BLE001
            return _error_response(e)

    async def explanations(request: web.Request) -> web.Response:
        from seldon_core_tpu.runtime.rest import _remote_ctx, _remote_deadline_ms
        from seldon_core_tpu.utils import deadlines as _deadlines
        from seldon_core_tpu.utils.tracing import activate_context

        try:
            body = await _request_body(request)
            msg = InternalMessage.from_json(body)
            svc = gateway.by_name(request.query.get("predictor", "")) or gateway.pick()
            # every ingress mints the deadline and adopts the caller's
            # trace (graftlint: propagation) — explanations included
            with activate_context(_remote_ctx(request)), \
                    _deadlines.activate_ms(_remote_deadline_ms(request)):
                _deadlines.check("gateway ingress /api/v0.1/explanations")
                out = await svc.explain(msg)
            return web.json_response(out.to_json(), status=_http_status(out))
        except Exception as e:  # noqa: BLE001
            return _error_response(e)

    async def generate_stream_sse(request: web.Request) -> web.StreamResponse:
        """Token streaming over HTTP: Server-Sent Events, one
        ``data: {"tokens": [...]}`` event per engine chunk, then
        ``event: end`` carrying the puid (the REST twin of the gRPC
        ``Seldon/GenerateStream`` lane; same eligibility rule)."""
        import asyncio as _asyncio
        import json as _json
        import time as _mono_time

        import numpy as _np

        from seldon_core_tpu.runtime.component import MicroserviceError

        t_ingress = _mono_time.monotonic()
        try:
            body = await _request_body(request)
            msg = InternalMessage.from_json(body)
        except Exception as e:  # noqa: BLE001
            return _error_response(e)
        svc = gateway.by_name(request.query.get("predictor", "")) or gateway.pick()
        fast = svc.single_local_model()
        component = fast[1] if fast is not None else None
        gen_fn = getattr(component, "predict_stream", None)
        if gen_fn is None:
            return web.json_response(
                {"status": {"status": "FAILURE", "code": 501,
                            "info": "token streaming needs a single-local-model "
                                    "predictor whose component implements "
                                    "predict_stream (e.g. STREAMING_LM)",
                            "reason": "NOT_IMPLEMENTED"}},
                status=501,
            )
        # t_ingress: the handler's entry stamp.  The generator's first
        # next() submits and then waits for a slot and a prefill —
        # seconds, under a standing queue — so it runs on the shared
        # dispatch pool (runtime/executor_pool.py: 128 threads that
        # spend their life blocked); every later next() waits one wave
        # at most and takes the loop's default executor, min(32,
        # cpu_count + 4) threads.  On that executor alone, more callers
        # waiting for a slot than it has threads took every one of them
        # and the decoding streams went silent (ROADMAP S9: 160 callers
        # on 128 slots, 10-19 s across a window's opening).  The engine
        # counts entry -> submit as ingress_wait_s, the queue it cannot
        # see (same process, so a monotonic stamp is a valid carrier)
        meta = {"tags": dict(msg.meta.tags), "puid": msg.meta.puid,
                "t_ingress": t_ingress}
        # the streaming generator runs on plain executor threads (no
        # contextvar copy), so the SLO headers ride meta.tags instead
        # of the ambient budget (tags in the body win).  The expiry is
        # minted ABSOLUTE here, at ingress: a relative deadline_ms tag
        # re-minted at submit would silently refund the executor
        # queueing time (this lane calls the local model in-process,
        # so a monotonic timestamp is a valid carrier)
        from seldon_core_tpu.utils import deadlines as _deadlines

        sse_ms = _deadlines.extract_ms(request.headers)
        if sse_ms is not None:
            meta["tags"].setdefault(
                "deadline_at_monotonic", _mono_time.monotonic() + sse_ms / 1000.0
            )
        sse_prio = _deadlines.extract_priority(request.headers)
        if sse_prio is not None:
            meta["tags"].setdefault("priority", sse_prio)
        sse_adapter = _deadlines.extract_adapter(request.headers)
        if sse_adapter:
            meta["tags"].setdefault("adapter", sse_adapter)
        loop = _asyncio.get_running_loop()
        sentinel = object()
        # pull the FIRST chunk before sending headers: bad prompts /
        # engine rejections surface as proper HTTP errors, not an
        # abruptly-closed 200 stream (the gRPC twin aborts with status)
        try:
            arr = msg.array()
            it = gen_fn(arr, [], meta=meta)
            first = await loop.run_in_executor(
                dispatch_pool(), _pull, it, None, sentinel)
        except Exception as e:  # noqa: BLE001
            return _error_response(e)
        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
        })
        try:
            await resp.prepare(request)
            chunk = first
            while True:
                if chunk is sentinel:
                    await resp.write(
                        (f"event: end\ndata: {_json.dumps({'puid': msg.meta.puid})}\n\n").encode()
                    )
                    break
                payload = _json.dumps({"tokens": _np.asarray(chunk).tolist()})
                await resp.write(f"data: {payload}\n\n".encode())
                # the write has returned: the event's way out ends here,
                # and the stamp rides the next pull back to the engine
                written = _mono_time.monotonic()
                try:
                    chunk = await loop.run_in_executor(
                        None, _pull, it, written, sentinel)
                except MicroserviceError as e:
                    await resp.write(
                        (f"event: error\ndata: {_json.dumps(e.to_status())}\n\n").encode()
                    )
                    break
                except Exception as e:  # noqa: BLE001 — mid-stream engine fault:
                    # the consumer must see an error event, never a
                    # silent truncation that reads as completion
                    status = {"status": "FAILURE", "code": 500,
                              "info": str(e), "reason": "ENGINE_ERROR"}
                    await resp.write(
                        (f"event: error\ndata: {_json.dumps(status)}\n\n").encode()
                    )
                    break
            await resp.write_eof()
        except (ConnectionResetError, ConnectionError, _asyncio.CancelledError):
            pass  # client went away; the finally-clause frees the stream
        finally:
            await loop.run_in_executor(None, it.close)
        return resp

    async def feedback(request: web.Request) -> web.Response:
        from seldon_core_tpu.runtime.rest import _remote_ctx, _remote_deadline_ms
        from seldon_core_tpu.utils import deadlines as _deadlines
        from seldon_core_tpu.utils.tracing import activate_context

        try:
            body = await _request_body(request)
            fb = InternalFeedback.from_json(body)
            # feedback is exempt from RETRIES/hedging, not from the
            # ingress contract: the budget still rides (and fast-fails)
            # and reward spans still stitch under the caller's trace
            with activate_context(_remote_ctx(request)), \
                    _deadlines.activate_ms(_remote_deadline_ms(request)):
                _deadlines.check("gateway ingress /api/v0.1/feedback")
                out = await gateway.send_feedback(fb)
            return web.json_response(out.to_json(), status=_http_status(out))
        except Exception as e:  # noqa: BLE001
            return _error_response(e)

    async def ping(_r: web.Request) -> web.Response:
        return web.Response(text="pong")

    async def live(_r: web.Request) -> web.Response:
        return web.Response(text="live")

    async def ready(_r: web.Request) -> web.Response:
        ok = await gateway.ready()
        return web.Response(text="ready" if ok else "not ready", status=200 if ok else 503)

    async def health_status(_r: web.Request) -> web.Response:
        status = await asyncio.to_thread(components_health, gateway)
        return web.json_response({"frontend": "python", "predictors": status})

    async def pause(_r: web.Request) -> web.Response:
        gateway.pause()
        return web.Response(text="paused")

    async def unpause(_r: web.Request) -> web.Response:
        gateway.unpause()
        return web.Response(text="unpaused")

    async def metrics_endpoint(_r: web.Request) -> web.Response:
        from prometheus_client import CONTENT_TYPE_LATEST, generate_latest

        return web.Response(body=generate_latest(), content_type=CONTENT_TYPE_LATEST.split(";")[0])

    def _local_engines():
        """(predictor, node, engine) of every local component that runs
        a generation engine (anything with ``engine_stats``)."""
        for svc in gateway.predictors:
            for unit in svc.graph.walk():
                engine = getattr(svc.executor.component(unit.name), "engine", None)
                if hasattr(engine, "engine_stats"):
                    yield svc.name, unit.name, engine

    async def debug_engine(request: web.Request) -> web.Response:
        """Generation-engine stats for every local component that runs a
        paged engine, keyed predictor -> node.  ``?detail=1`` adds the
        flight recorder's per-chunk ring (the post-incident forensics
        payload; see docs/architecture.md §Generation observability)."""
        detail = request.query.get("detail", "") in ("1", "true", "yes")
        out: Dict[str, Dict[str, object]] = {}
        for predictor, node, engine in _local_engines():
            try:
                stats = engine.engine_stats(detail=detail)
            except TypeError:  # engines predating the detail arg
                stats = engine.engine_stats()
            out.setdefault(predictor, {})[node] = stats
        return web.json_response(out)

    async def debug_profile(request: web.Request) -> web.Response:
        """``POST /debug/profile?seconds=<s>`` arms a ``jax.profiler``
        window on the running engine: it opens at the next wave
        boundary, closes at the first one after ``s`` seconds and is
        written under ``SELDON_TPU_PROFILE_DIR`` (unset = 409: a serving
        process writes no profiles unless told where).  One profiler per
        process, so the first engine found is armed.  ``GET`` returns
        each engine's window: state, directory, the two
        ``time.monotonic()`` stamps and the ``engine_stats()`` snapshot
        taken at each (docs/architecture.md §Generation observability)."""
        engines = [e for e in _local_engines() if hasattr(e[2], "arm_profile")]
        try:
            if request.method == "POST":
                if not engines:
                    return web.json_response(
                        {"status": {"status": "FAILURE", "code": 404,
                                    "info": "no local paged engine to profile",
                                    "reason": "NOT_FOUND"}}, status=404)
                try:
                    seconds = float(request.query.get("seconds", ""))
                except ValueError:
                    seconds = float("nan")  # arm() refuses it with a 400
                engines[0][2].arm_profile(seconds)
        except Exception as e:  # noqa: BLE001
            return _error_response(e)
        out: Dict[str, Dict[str, object]] = {}
        for predictor, node, engine in engines:
            out.setdefault(predictor, {})[node] = engine.profile_status()
        return web.json_response(out)

    async def debug_workers(_r: web.Request) -> web.Response:
        """Supervised-worker lifecycle (r12): alive/ready/restarts plus
        the ``exhausted`` flag — a worker whose restart budget is spent
        is DEAD until redeployed, and this endpoint is where the
        breaker/alert layer (and operators) see that instead of
        inferring it from connection refusals."""
        sup = gateway.supervisor
        health = sup.health() if sup is not None else {}
        # engine health (r17): the device-health watchdog's state per
        # local paged engine — healthy | degraded | evacuating — plus
        # the quarantine/migration counters the evacuation layer and
        # alerting read alongside the process lifecycle states above
        engines: Dict[str, Dict[str, object]] = {}
        for predictor, node, engine in _local_engines():
            try:
                s = engine.engine_stats()
            except Exception:  # noqa: BLE001 — one sick engine must
                # not take the whole debug surface down
                engines[f"{predictor}/{node}"] = {"error": True}
                continue
            engines[f"{predictor}/{node}"] = {
                "health": s.get("health", "healthy"),
                "health_state": s.get("health_state", 0),
                "watchdog_trips": s.get("watchdog_trips", 0),
                "quarantined": s.get("quarantined", 0),
                "migrated_out": s.get("migrated_out", 0),
                "migrated_in": s.get("migrated_in", 0),
            }
        return web.json_response({
            "workers": health,
            "engines": engines,
            "degraded": sorted(
                name for name, h in engines.items()
                if h.get("health") not in (None, "healthy")
            ),
            "exhausted": sorted(
                name for name, h in health.items() if h.get("exhausted")
            ),
        })

    async def debug_traces(request: web.Request) -> web.Response:
        """Spans from the in-process tracer ring: ``?trace_id=<puid>``
        for one trace (the engine request span + its gen.* lifecycle
        spans), else the newest ``?limit=`` spans — the debug surface
        the tracing module promises."""
        from seldon_core_tpu.utils.tracing import get_tracer

        tracer = get_tracer()
        if tracer is None:
            return web.json_response(
                {"enabled": False, "spans": [],
                 "info": "tracing not set up (call setup_tracing / set "
                         "OTEL_EXPORTER_OTLP_ENDPOINT)"},
            )
        trace_id = request.query.get("trace_id", "")
        try:
            limit = max(1, min(int(request.query.get("limit", "256")), 4096))
        except ValueError:
            limit = 256
        if trace_id:
            spans = tracer.find(trace_id)
        else:
            with tracer._lock:  # noqa: SLF001 — same package, read-only copy
                spans = list(tracer.spans)
        return web.json_response(
            {"enabled": True, "spans": [s.to_dict() for s in spans[-limit:]]}
        )

    async def debug_weights(_r: web.Request) -> web.Response:
        """The weight-multiplexing surface (r16): the process weight
        registry's residency/budget state (null when this process never
        touched it) plus every local paged engine's adapter-pool
        stats, keyed predictor -> node — "which weight sets is this
        gateway actually serving" as one curl."""
        from seldon_core_tpu.models.registry import registry_snapshot

        engines: Dict[str, Dict[str, object]] = {}
        for svc in gateway.predictors:
            nodes = {}
            for unit in svc.graph.walk():
                component = svc.executor.component(unit.name)
                engine = getattr(component, "engine", None)
                stats_fn = getattr(engine, "adapter_stats", None)
                if stats_fn is None:
                    continue
                nodes[unit.name] = stats_fn()
            if nodes:
                engines[svc.name] = nodes
        return web.json_response({
            "registry": registry_snapshot(),
            "engines": engines,
        })

    async def debug_telemetry(request: web.Request) -> web.Response:
        """This process's replica telemetry snapshot (r20): the
        versioned time-series-ring payload, ``?window=<s>`` bounded.
        One engine-bearing component (the common topology) serves its
        snapshot directly — the shape the fleet aggregator polls;
        multi-component graphs nest per-node snapshots."""
        try:
            window_s = float(request.query.get("window", "0") or 0.0)
        except ValueError:
            window_s = 0.0
        snaps: Dict[str, object] = {}
        for svc in gateway.predictors:
            for unit in svc.graph.walk():
                component = svc.executor.component(unit.name)
                snap_fn = getattr(component, "telemetry_snapshot", None)
                if snap_fn is None:
                    continue
                snap = snap_fn(window_s)
                if snap is not None:
                    snaps[f"{svc.name}/{unit.name}"] = snap
        if not snaps:
            from seldon_core_tpu.utils import telemetry as _telemetry

            return web.json_response(
                {"enabled": _telemetry.telemetry_enabled(), "components": {},
                 "info": "no telemetry ring in this process "
                         "(SELDON_TPU_TELEMETRY=0 or no generation engine)"},
            )
        if len(snaps) == 1:
            return web.json_response(next(iter(snaps.values())))
        from seldon_core_tpu.utils import telemetry as _telemetry

        return web.json_response({
            "schema_version": _telemetry.TELEMETRY_SCHEMA_VERSION,
            "components": snaps,
        })

    async def debug_fleet(_r: web.Request) -> web.Response:
        """The merged fleet view (r20): per-replica freshness +
        saturation, adapter/prefix residency maps and the fleet rollup.
        Endpoints come from ``SELDON_TPU_FLEET_ENDPOINTS``, else from
        the local supervisor's workers; polls happen at most once per
        poll interval, executor-side (urllib must not block the loop)."""
        import asyncio as _asyncio
        import time as _time

        agg = getattr(gateway, "_fleet_aggregator", None)
        if agg is None:
            from seldon_core_tpu.controlplane import fleetview

            endpoints = fleetview.endpoints_from_knob()
            if not endpoints and gateway.supervisor is not None:
                endpoints = fleetview.endpoints_from_supervisor(
                    gateway.supervisor
                )
            if not endpoints:
                return web.json_response({
                    "enabled": False,
                    "info": "no fleet endpoints (set "
                            "SELDON_TPU_FLEET_ENDPOINTS or run workers "
                            "under the local supervisor)",
                })
            agg = fleetview.TelemetryAggregator(endpoints)
            try:
                from seldon_core_tpu.utils.metrics import (
                    FleetPrometheusBridge,
                )

                agg.bridge = FleetPrometheusBridge(agg)
            except Exception:  # noqa: BLE001 — metrics never block the view
                logger.exception("fleet prometheus bridge unavailable")
            gateway._fleet_aggregator = agg
            gateway._fleet_last_poll = 0.0
        now = _time.monotonic()
        if now - getattr(gateway, "_fleet_last_poll", 0.0) >= agg.poll_s:
            gateway._fleet_last_poll = now
            await _asyncio.get_running_loop().run_in_executor(
                None, agg.poll_once
            )
        return web.json_response({"enabled": True, **agg.fleet_view()})

    async def debug_request(request: web.Request) -> web.Response:
        """One request's stitched forensics timeline (r21): the stored
        capture container (knob snapshot, sampling recipe, five-phase
        latency split, per-wave recorder slice, cost totals, payload
        frames unless redacted) merged with the live span ring — the
        "why was THIS request slow" surface.  404 only when neither
        plane knows the puid."""
        import dataclasses as _dc

        import numpy as np

        from seldon_core_tpu.utils import capture as _capture
        from seldon_core_tpu.utils.tracing import get_tracer

        puid = request.match_info["puid"]
        cap = None
        if _capture.capture_enabled():
            try:
                cap = await asyncio.get_running_loop().run_in_executor(
                    None, _capture.default_store().get, puid
                )
            except Exception:  # noqa: BLE001 — a corrupt container must
                # not take the debug surface down; spans may still match
                logger.exception("capture load failed (puid=%s)", puid)
        tracer = get_tracer()
        spans = [s.to_dict() for s in tracer.find(puid)] if tracer else []
        if cap is None and not spans:
            return web.json_response(
                {"puid": puid, "found": False,
                 "info": "no capture container and no spans for this puid "
                         "(capture off, not triggered, or evicted)"},
                status=404,
            )
        capture_doc = None
        timeline = []
        if cap is not None:
            capture_doc = _dc.asdict(cap)
            capture_doc["prompt"] = (
                np.asarray(cap.prompt).reshape(-1).tolist()
                if cap.prompt is not None else []
            )
            capture_doc["tokens"] = (
                np.asarray(cap.tokens).reshape(-1).tolist()
                if cap.tokens is not None else []
            )
            stamps = (cap.phases or {}).get("stamps") or {}
            for name, t in stamps.items():
                if t:
                    timeline.append(
                        {"t": float(t), "event": name, "source": "stream"}
                    )
        for s in spans:
            timeline.append({
                "t": s["startTimeUnixNano"] / 1e9,
                "event": f"span:{s['name']}",
                "duration_ms": round(s["durationNano"] / 1e6, 3),
                "source": "tracer",
            })
        timeline.sort(key=lambda e: e["t"])
        return web.json_response({
            "puid": puid,
            "found": True,
            "capture": capture_doc,
            "spans": spans,
            "timeline": timeline,
        })

    async def debug_knobs(_r: web.Request) -> web.Response:
        """The central knob registry (runtime/knobs.py) with this
        process's effective values: "what is this gateway actually
        running with" as one curl instead of a grep through env dumps.
        Declared metadata only — no secrets live in SELDON_TPU_*."""
        from seldon_core_tpu.runtime import knobs as _knobs

        snap = _knobs.snapshot()
        return web.json_response({
            "knobs": snap,
            "set": sorted(k["name"] for k in snap if k["set"]),
        })

    async def openapi_endpoint(_r: web.Request) -> web.Response:
        from seldon_core_tpu.runtime.openapi import gateway_openapi

        return web.json_response(gateway_openapi())

    app.router.add_get("/seldon.json", openapi_endpoint)
    app.router.add_post("/api/v0.1/predictions", predictions)
    app.router.add_get("/api/v0.1/predictions", predictions)
    app.router.add_post("/predict", predictions)  # convenience alias
    app.router.add_post("/api/v0.1/feedback", feedback)
    app.router.add_post("/api/v0.1/generate/stream", generate_stream_sse)
    app.router.add_post("/api/v0.1/explanations", explanations)
    app.router.add_get("/ping", ping)
    app.router.add_get("/live", live)
    app.router.add_get("/ready", ready)
    app.router.add_get("/health/status", health_status)
    app.router.add_route("*", "/pause", pause)
    app.router.add_route("*", "/unpause", unpause)
    app.router.add_get("/metrics", metrics_endpoint)
    app.router.add_get("/debug/engine", debug_engine)
    app.router.add_get("/debug/profile", debug_profile)
    app.router.add_post("/debug/profile", debug_profile)
    app.router.add_get("/debug/workers", debug_workers)
    app.router.add_get("/debug/traces", debug_traces)
    app.router.add_get("/debug/knobs", debug_knobs)
    app.router.add_get("/debug/weights", debug_weights)
    app.router.add_get("/debug/telemetry", debug_telemetry)
    app.router.add_get("/debug/fleet", debug_fleet)
    app.router.add_get("/debug/request/{puid}", debug_request)
    return app


def add_seldon_service(server: grpc.aio.Server, gateway: Gateway, auth=None) -> None:
    """Register the external Seldon gRPC service.  With ``auth`` set,
    calls must carry ``authorization: Bearer <token>`` metadata."""
    issuer = None
    if auth is not None:
        from seldon_core_tpu.utils.auth import TokenIssuer

        issuer = TokenIssuer(auth)

    async def check_auth(context) -> None:
        if issuer is not None and not issuer.verify_grpc(context):
            from seldon_core_tpu.utils.auth import UNAUTHENTICATED_MSG

            await context.abort(grpc.StatusCode.UNAUTHENTICATED, UNAUTHENTICATED_MSG)

    async def predict(request: pb.SeldonMessage, context) -> pb.SeldonMessage:
        await check_auth(context)
        from seldon_core_tpu.runtime.grpc_server import (
            _grpc_deadline_ms,
            _grpc_remote_ctx,
        )
        from seldon_core_tpu.utils import deadlines as _deadlines
        from seldon_core_tpu.utils.tracing import activate_context

        msg = InternalMessage.from_proto(request)
        prio = _deadlines.extract_priority(context.invocation_metadata() or ())
        if prio is not None and "priority" not in msg.meta.tags:
            msg.meta.tags["priority"] = prio
        try:
            with activate_context(_grpc_remote_ctx(context)), \
                    _deadlines.activate_ms(_grpc_deadline_ms(context)):
                _deadlines.check("gateway grpc ingress Seldon/Predict")
                out = await gateway.predict(msg)
        except MicroserviceError as e:  # ingress fast-fail (DEADLINE_EXCEEDED)
            out = failure_message(e, msg.meta.puid)
        return out.to_proto()

    async def send_feedback(request: pb.Feedback, context) -> pb.SeldonMessage:
        await check_auth(context)
        from seldon_core_tpu.runtime.grpc_server import (
            _grpc_deadline_ms,
            _grpc_remote_ctx,
        )
        from seldon_core_tpu.utils import deadlines as _deadlines
        from seldon_core_tpu.utils.tracing import activate_context

        fb = InternalFeedback.from_proto(request)
        try:
            with activate_context(_grpc_remote_ctx(context)), \
                    _deadlines.activate_ms(_grpc_deadline_ms(context)):
                _deadlines.check("gateway grpc ingress Seldon/SendFeedback")
                out = await gateway.send_feedback(fb)
        except MicroserviceError as e:  # ingress fast-fail (DEADLINE_EXCEEDED)
            out = failure_message(e, fb.request.meta.puid if fb.request else "")
        return out.to_proto()

    async def generate_stream(request: pb.SeldonMessage, context):
        """Token streaming on the aio server — same eligibility rule as
        the sync lane: a single-local-model predictor whose component
        implements ``predict_stream``.  The blocking generator is
        driven from the default executor so the event loop never
        blocks on a decode chunk."""
        await check_auth(context)
        import numpy as np

        from seldon_core_tpu.runtime.component import MicroserviceError

        msg = InternalMessage.from_proto(request)
        svc = gateway.pick()
        fast = svc.single_local_model()
        component = fast[1] if fast is not None else None
        gen_fn = getattr(component, "predict_stream", None)
        if gen_fn is None:
            await context.abort(
                grpc.StatusCode.UNIMPLEMENTED,
                "GenerateStream needs a single-local-model predictor whose "
                "component implements predict_stream (e.g. STREAMING_LM)",
            )
        import time as _mono_time

        # t_ingress: as in the SSE twin; this lane's first next() (the
        # wait for a slot) runs on the dispatch pool too
        meta = {"tags": dict(msg.meta.tags), "puid": msg.meta.puid,
                "t_ingress": _mono_time.monotonic()}
        # SLO parity with the SSE twin: the streaming generator runs on
        # plain executor threads (no contextvar copy), so the deadline
        # and priority ride meta.tags as an ABSOLUTE monotonic expiry
        # minted here at ingress (tags in the body win).  Without this
        # the gRPC stream lane silently ignored x-seldon-deadline-ms.

        from seldon_core_tpu.runtime.grpc_server import _grpc_deadline_ms
        from seldon_core_tpu.utils import deadlines as _deadlines

        md_ms = _grpc_deadline_ms(context)
        if md_ms is not None:
            meta["tags"].setdefault(
                "deadline_at_monotonic", _mono_time.monotonic() + md_ms / 1000.0
            )
        md_prio = _deadlines.extract_priority(context.invocation_metadata() or ())
        if md_prio is not None:
            meta["tags"].setdefault("priority", md_prio)
        md_adapter = _deadlines.extract_adapter(
            context.invocation_metadata() or ()
        )
        if md_adapter:
            meta["tags"].setdefault("adapter", md_adapter)
        loop = asyncio.get_running_loop()
        it = gen_fn(msg.array(), [], meta=meta)
        sentinel = object()
        pool = dispatch_pool()  # the first pull only: the wait for a slot
        written = None
        try:
            while True:
                try:
                    chunk = await loop.run_in_executor(
                        pool, _pull, it, written, sentinel)
                    pool = None
                except MicroserviceError as e:
                    await context.abort(
                        grpc.StatusCode.INVALID_ARGUMENT
                        if 400 <= e.status_code < 500
                        else grpc.StatusCode.INTERNAL,
                        str(e),
                    )
                if chunk is sentinel:
                    break
                out = InternalMessage(
                    payload=np.asarray(chunk)[None, :], kind="ndarray"
                )
                out.meta.puid = msg.meta.puid
                yield out.to_proto()
                # the yield has been taken: the SSE twin's stamp
                written = _mono_time.monotonic()
        finally:
            # client cancel/disconnect: closing the generator triggers
            # its finally-clause, which cancels the engine stream
            await loop.run_in_executor(None, it.close)

    async def predict_stream(request_iterator, context):
        """Chunked predict: reassemble -> predict -> stream the reply.

        The stream lane has its own total-size cap (the per-frame gRPC
        limit no longer bounds memory once frames accumulate)."""
        await check_auth(context)  # fail before buffering the stream
        parts = []
        total = 0
        async for chunk in request_iterator:
            total += len(chunk.data)
            if total > services.STREAM_MAX_BYTES:
                await context.abort(
                    grpc.StatusCode.RESOURCE_EXHAUSTED,
                    f"stream exceeds {services.STREAM_MAX_BYTES} bytes",
                )
            parts.append(chunk.data)
        request = pb.SeldonMessage.FromString(b"".join(parts))
        from seldon_core_tpu.runtime.grpc_server import (
            _grpc_deadline_ms,
            _grpc_remote_ctx,
        )
        from seldon_core_tpu.utils import deadlines as _deadlines
        from seldon_core_tpu.utils.tracing import activate_context

        # chunked predict is a unary call once reassembled: the
        # standard ingress contract applies (deadline minted AFTER the
        # stream is buffered — reassembly time counts against the
        # caller's budget only if they set the native gRPC deadline)
        msg = InternalMessage.from_proto(request)
        try:
            with activate_context(_grpc_remote_ctx(context)), \
                    _deadlines.activate_ms(_grpc_deadline_ms(context)):
                _deadlines.check("gateway grpc ingress Seldon/PredictStream")
                out = await gateway.predict(msg)
        except MicroserviceError as e:  # ingress fast-fail (DEADLINE_EXCEEDED)
            out = failure_message(e, msg.meta.puid)
        for chunk in services.chunk_message(out.to_proto()):
            yield chunk

    server.add_generic_rpc_handlers(
        (
            services.generic_handler(
                "Seldon",
                {
                    "Predict": predict,
                    "SendFeedback": send_feedback,
                    "PredictStream": predict_stream,
                    "GenerateStream": generate_stream,
                },
            ),
        )
    )


class GrpcServerHandle:
    """Uniform async facade over the sync and aio gRPC servers."""

    def __init__(self, server, is_aio: bool):
        self.server = server
        self.is_aio = is_aio

    async def stop(self, grace=None):
        if self.is_aio:
            await self.server.stop(grace)
        else:
            event = self.server.stop(grace)
            await asyncio.get_running_loop().run_in_executor(None, event.wait)


async def serve_gateway(
    gateway: Gateway,
    host: str = "0.0.0.0",
    http_port: int = 8000,
    grpc_port: int = 5001,
    max_message_bytes: int = 512 * 1024 * 1024,
    grpc_mode: str = "sync",  # sync (fast path, default) | aio
    tls=None,  # utils.tls.TlsConfig — terminates TLS on both listeners
    auth=None,  # utils.auth.OAuthConfig — bearer tokens on both listeners
):
    """Start REST + gRPC front servers; returns (runner, GrpcServerHandle)."""
    from seldon_core_tpu.runtime import rest
    from seldon_core_tpu.utils.tls import add_grpc_port

    app = build_gateway_app(gateway, auth=auth)
    runner = await rest.serve(app, host=host, port=http_port, tls=tls)
    if grpc_mode == "sync":
        from seldon_core_tpu.engine.sync_server import build_sync_seldon_server

        server = build_sync_seldon_server(
            gateway, asyncio.get_running_loop(), max_message_bytes=max_message_bytes,
            auth=auth,
        )
        add_grpc_port(server, f"{host}:{grpc_port}", tls)
        server.start()
        return runner, GrpcServerHandle(server, is_aio=False)
    server = grpc.aio.server(
        options=[
            ("grpc.max_send_message_length", max_message_bytes),
            ("grpc.max_receive_message_length", max_message_bytes),
        ]
    )
    add_seldon_service(server, gateway, auth=auth)
    add_grpc_port(server, f"{host}:{grpc_port}", tls)
    await server.start()
    return runner, GrpcServerHandle(server, is_aio=True)
