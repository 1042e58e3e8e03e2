"""Native ingress wiring: put the C++ front server in front of a Gateway.

The reference fronts every predictor with its Java engine; this module
fronts a deployment's ``Gateway`` with the C++ epoll server
(``native/frontserver.cc``) instead of the Python aiohttp app
(reference: doc/source/graph/svcorch.md:1-8 — the data plane does not
run in the model language).

Lane assignment:

* **fast lane** (zero per-request Python) — available when the
  deployment is a single primary predictor whose graph is one
  in-process MODEL exposing ``raw_batch_call`` (JaxServer does);
  request tensors are decoded, coalesced, and batched in C++ and the
  jitted XLA program is invoked once per batch.
* **fallback lane** — everything else (multi-node graphs, traffic
  splits, shadows, exotic payloads, feedback, explanations) bridges
  into the running asyncio engine via ``GatewayRawHandler`` with full
  semantics.

Readiness: the C++ server answers ``/ready`` from a flag that a
background task refreshes from ``gateway.ready()`` (the graph walk).
"""

from __future__ import annotations

import asyncio
import json
import logging
from typing import Optional, Tuple

logger = logging.getLogger(__name__)


def fast_lane_for(gateway) -> Optional[dict]:
    """Fast-lane configuration for a gateway, or None when ineligible.

    Eligibility mirrors ``PredictorService.single_local_model`` plus
    gateway-level constraints: one primary predictor (a traffic split
    must run the weighted pick per request) and no shadows (the fast
    lane would bypass them).
    """
    if len(gateway.entries) != 1 or gateway.shadows:
        return None
    svc = gateway.entries[0][0]
    fast = svc.single_local_model()
    if fast is None:
        return None
    unit, component = fast
    raw_call = getattr(component, "raw_batch_call", None)
    if raw_call is None:
        return None
    try:
        feature_dim = int(component.flat_feature_dim())
        out_dim = int(component.flat_out_dim())
    except Exception:  # noqa: BLE001 — component without flat-shape support
        return None
    names = None
    try:
        cn = component.class_names()
        if cn and len(cn) == out_dim:
            names = [str(n) for n in cn]
    except Exception:  # noqa: BLE001 — class_names is an optional probe
        pass
    buckets = None
    batcher = getattr(component, "batcher", None)
    if batcher is not None and getattr(batcher, "buckets", None):
        buckets = list(batcher.buckets)
    return {
        "feature_dim": feature_dim,
        "out_dim": out_dim,
        "names": names,
        "model_name": unit.name,
        "max_batch": getattr(component, "max_batch_size", 64),
        "buckets": buckets,
    }


def _live_model_fn(gateway, feature_dim: int, out_dim: int):
    """Batch callback that re-resolves the component through the
    gateway on every call, so a rolling swap serves the NEW generation
    on the fast lane too (capturing raw_batch_call at startup would pin
    the old weights forever).  A swap that changes the model's flat
    shapes makes the fast lane error loudly rather than serve wrong
    tensors — re-serve the deployment to renegotiate dims."""

    def model_fn(batch):
        lane_svc = gateway.entries[0][0] if len(gateway.entries) == 1 else None
        fast = lane_svc.single_local_model() if lane_svc is not None else None
        if fast is None:
            raise RuntimeError("fast lane no longer eligible after rolling update")
        component = fast[1]
        if (int(component.flat_feature_dim()) != feature_dim
                or int(component.flat_out_dim()) != out_dim):
            raise RuntimeError(
                "model shape changed across rolling update; re-serve the deployment"
            )
        return component.raw_batch_call(batch)

    return model_fn


class NativeIngressHandle:
    def __init__(self, server, ready_task):
        self.server = server
        self._ready_task = ready_task
        self.port = server.port

    def stats(self) -> dict:
        return self.server.stats()

    async def stop(self) -> None:
        if self._ready_task is not None:
            self._ready_task.cancel()
            try:
                await self._ready_task
            except asyncio.CancelledError:
                pass
            self._ready_task = None
        # off-loop: server.stop() joins worker threads that may be
        # blocked on run_coroutine_threadsafe into THIS loop — joining
        # on the loop thread would deadlock until their timeout
        await asyncio.to_thread(self.server.stop)

    async def cleanup(self) -> None:
        """aiohttp-runner-compatible shutdown, so callers that do
        ``await runner.cleanup()`` work unchanged with frontend=native."""
        await self.stop()


class _DeploymentRawHandler:
    """GatewayRawHandler plus the non-engine GET endpoints the Python
    app serves (/metrics, /seldon.json, /health/status) so the native
    ingress is a drop-in replacement on the HTTP port."""

    def __init__(self, gateway, loop):
        from seldon_core_tpu.native.frontserver import GatewayRawHandler

        self._inner = GatewayRawHandler(gateway, loop)

    def __call__(self, method: str, path: str, body: bytes) -> Tuple[int, str, bytes]:
        # the C++ lane forwards the full target; match our GET endpoints
        # on a stripped copy but pass the original through (the inner
        # gateway handler reads ?predictor= / ?json= from the query)
        bare = path.split("?", 1)[0]
        if method == "GET" and bare == "/metrics":
            try:
                from prometheus_client import CONTENT_TYPE_LATEST, generate_latest

                return 200, CONTENT_TYPE_LATEST.split(";")[0], generate_latest()
            except Exception as e:  # noqa: BLE001
                return 500, "text/plain", str(e).encode()
        if method == "GET" and bare == "/health/status":
            from seldon_core_tpu.engine.server import components_health

            status = components_health(self._inner.gateway)
            return 200, "application/json", json.dumps(
                {"frontend": "native", "predictors": status}).encode()
        if method == "GET" and bare == "/seldon.json":
            from seldon_core_tpu.runtime.openapi import gateway_openapi

            return 200, "application/json", json.dumps(gateway_openapi()).encode()
        return self._inner(method, path, body)


class _DeploymentGrpcHandler:
    """Full-contract unary gRPC fallback for the native ingress: any
    Seldon method the in-C++ fast lane does not express (SendFeedback,
    Predict with non-tensor payloads, …) arrives here whole and runs
    through the Gateway with full engine semantics — one native server
    for the entire contract, like the reference's Java engine
    (reference: engine/src/main/java/io/seldon/engine/grpc/
    SeldonService.java:30-67)."""

    def __init__(self, gateway, loop):
        self.gateway = gateway
        self.loop = loop

    def __call__(self, path: str, body: bytes):
        from seldon_core_tpu.proto import pb
        from seldon_core_tpu.runtime.component import MicroserviceError
        from seldon_core_tpu.runtime.message import InternalFeedback, InternalMessage

        try:
            if path == "/seldon.protos.Seldon/PredictRaw":
                # zero-copy h2c lane: the gRPC message IS one SRT1 frame
                # (gRPC's own length-prefixed framing delimits it) — no
                # proto parse anywhere on the request path; the reply is
                # the response frame.  Gated like the HTTP frame lane.
                from seldon_core_tpu import codec

                if not codec.zero_copy_enabled():
                    return 12, ("PredictRaw needs SELDON_TPU_ZERO_COPY=1; "
                                "use Seldon/Predict"), b""
                import numpy as np

                try:
                    views = codec.unpack_frames(body)
                except codec.PayloadError as e:
                    return 3, str(e)[:200], b""
                if len(views) > 1:
                    # multi-frame container = the batched-submission
                    # surface (same eligibility rule as the HTTP lane:
                    # single-local-MODEL, no shadows/splits)
                    fast = None
                    if len(self.gateway.entries) == 1 and not self.gateway.shadows:
                        fast = self.gateway.entries[0][0].single_local_model()
                    raw_views = getattr(fast[1], "raw_batch_views", None) if fast else None
                    if raw_views is None:
                        return 3, ("multi-frame containers need a "
                                   "single-local-MODEL predictor with "
                                   "raw_batch_views"), b""
                    try:
                        return 0, "", codec.pack_frames(raw_views(views))
                    except codec.PayloadError as e:
                        # container shape/dtype mismatch is the CLIENT's
                        # fault — INVALID_ARGUMENT, matching the HTTP
                        # twin's 400 for the identical body
                        return 3, str(e)[:200], b""
                msg = InternalMessage(payload=views[0], kind="rawTensor")
                out = asyncio.run_coroutine_threadsafe(
                    self.gateway.predict(msg), self.loop
                ).result(timeout=120.0)
                if out.status and out.status.get("status") == "FAILURE":
                    code = int(out.status.get("code", 500) or 500)
                    return (3 if 400 <= code < 500 else 13), str(
                        out.status.get("info", "engine failure")
                    ), b""
                try:
                    return 0, "", codec.pack_frame(np.asarray(out.host_payload()))
                except codec.PayloadError as e:
                    # healthy answer, un-frameable dtype (strings): the
                    # frame-only lane cannot express it — point the
                    # client at the full-contract method
                    return 3, f"response not frameable ({e}); use Seldon/Predict", b""
            if path == "/seldon.protos.Seldon/Predict":
                msg = InternalMessage.from_proto(pb.SeldonMessage.FromString(body))
                fut = asyncio.run_coroutine_threadsafe(
                    self.gateway.predict(msg), self.loop
                )
            elif path == "/seldon.protos.Seldon/SendFeedback":
                fb = InternalFeedback.from_proto(pb.Feedback.FromString(body))
                fut = asyncio.run_coroutine_threadsafe(
                    self.gateway.send_feedback(fb), self.loop
                )
            else:
                return 12, f"native ingress: no handler for {path}", b""
            out = fut.result(timeout=120.0)
            return 0, "", out.to_proto().SerializeToString()
        except MicroserviceError as e:
            return (3 if 400 <= e.status_code < 500 else 13), str(e), b""
        except Exception as e:  # noqa: BLE001 — wire-level INTERNAL
            logger.exception("native grpc fallback failed for %s", path)
            return 13, str(e)[:200], b""


class _DeploymentGrpcStreamHandler:
    """Seldon/GenerateStream on the native lane: token chunks leave
    through C++ h2 DATA frames as the engine emits them.  The accept
    callback returns immediately; a daemon producer thread drives the
    component's blocking ``predict_stream`` generator and pushes each
    chunk — a dead push (client disconnect) closes the generator, which
    cancels the engine stream (same lifecycle as the Python lane,
    engine/server.py generate_stream)."""

    def __init__(self, gateway, server_ref):
        self.gateway = gateway
        self._server_ref = server_ref  # callable -> NativeFrontServer

    def __call__(self, path: str, body: bytes, handle: int) -> int:
        import threading

        from seldon_core_tpu.proto import pb
        from seldon_core_tpu.runtime.message import InternalMessage

        if path != "/seldon.protos.Seldon/GenerateStream":
            return 12
        server = self._server_ref()
        if server is None:
            return 13
        try:
            msg = InternalMessage.from_proto(pb.SeldonMessage.FromString(body))
        except Exception:  # noqa: BLE001 — malformed request proto
            server.stream_close(handle, 3, "malformed SeldonMessage")
            return 0
        threading.Thread(
            target=self._produce, args=(server, msg, handle),
            name=f"native-genstream-{handle}", daemon=True,
        ).start()
        return 0

    def _produce(self, server, msg, handle: int) -> None:
        import numpy as np

        from seldon_core_tpu.runtime.component import MicroserviceError
        from seldon_core_tpu.runtime.message import InternalMessage

        it = None
        try:
            svc = self.gateway.pick()
            fast = svc.single_local_model()
            component = fast[1] if fast is not None else None
            gen_fn = getattr(component, "predict_stream", None)
            if gen_fn is None:
                server.stream_close(
                    handle, 12,
                    "GenerateStream needs a single-local-model predictor whose "
                    "component implements predict_stream (e.g. STREAMING_LM)",
                )
                return
            meta = {"tags": dict(msg.meta.tags), "puid": msg.meta.puid}
            it = gen_fn(msg.array(), [], meta=meta)
            dead = False
            for chunk in it:
                out = InternalMessage(
                    payload=np.asarray(chunk)[None, :], kind="ndarray"
                )
                out.meta.puid = msg.meta.puid
                if server.stream_push(handle, out.to_proto().SerializeToString()) < 0:
                    dead = True  # client gone: stop decoding
                    break
            # ALWAYS close: the close event is what releases the C++
            # handle and the connection's inflight count — skipping it
            # on a dead stream would leak both for the process lifetime
            # (the server tolerates closing a stream whose h2 side or
            # connection is already gone)
            server.stream_close(handle, 1 if dead else 0,
                                "client cancelled" if dead else "")
        except MicroserviceError as e:
            server.stream_close(
                handle, 3 if 400 <= e.status_code < 500 else 13, str(e)[:200]
            )
        except Exception as e:  # noqa: BLE001 — mid-stream engine fault
            logger.exception("native GenerateStream producer failed")
            server.stream_close(handle, 13, str(e)[:200])
        finally:
            if it is not None:
                it.close()


async def serve_native_ingress(
    gateway,
    host: str = "0.0.0.0",
    http_port: int = 8000,
    max_batch: Optional[int] = None,
    max_wait_ms: float = 1.0,
    batch_threads: Optional[int] = None,
) -> NativeIngressHandle:
    """Start the C++ front server on ``http_port`` for ``gateway``.

    Raises RuntimeError when the native library is unavailable —
    callers fall back to the Python app.
    """
    from seldon_core_tpu.native.frontserver import NativeFrontServer

    import os

    loop = asyncio.get_running_loop()
    handler = _DeploymentRawHandler(gateway, loop)
    grpc_handler = _DeploymentGrpcHandler(gateway, loop)
    server_box: list = [None]
    grpc_stream_handler = _DeploymentGrpcStreamHandler(
        gateway, lambda: server_box[0]
    )
    lane = fast_lane_for(gateway)
    from seldon_core_tpu.runtime import knobs

    if batch_threads is None:
        batch_threads = int(knobs.raw("SELDON_TPU_NATIVE_BATCH_THREADS", "4"))
    # the raw-worker pool now also carries the gRPC fallback lanes
    # (unary SendFeedback/Predict block in fut.result; stream accepts
    # must never queue behind them) — default well above the bare
    # HTTP-fallback sizing of 2
    raw_workers = int(knobs.raw("SELDON_TPU_NATIVE_RAW_WORKERS", "8"))
    kwargs = dict(port=http_port, raw_handler=handler, grpc_handler=grpc_handler,
                  grpc_stream_handler=grpc_stream_handler,
                  max_wait_ms=max_wait_ms, host=host,
                  batch_threads=batch_threads, raw_workers=raw_workers)
    if lane is not None:
        kwargs.update(
            model_fn=_live_model_fn(gateway, lane["feature_dim"], lane["out_dim"]),
            feature_dim=lane["feature_dim"],
            out_dim=lane["out_dim"],
            names=lane["names"],
            model_name=lane["model_name"],
            max_batch=max_batch or lane["max_batch"],
            buckets=lane["buckets"],
        )
        logger.info(
            "native ingress fast lane: model=%s feature_dim=%d out_dim=%d",
            lane["model_name"], lane["feature_dim"], lane["out_dim"],
        )
    else:
        logger.info("native ingress: fallback lane only (graph not fast-lane eligible)")
    server = NativeFrontServer(**kwargs)
    server_box[0] = server
    server.start()

    async def _refresh_ready():
        while True:
            try:
                ok = await gateway.ready()
                server.set_ready(bool(ok))
            except Exception:  # noqa: BLE001 — readiness poll failure = not ready
                server.set_ready(False)
            await asyncio.sleep(0.5)

    task = asyncio.ensure_future(_refresh_ready())
    return NativeIngressHandle(server, task)
