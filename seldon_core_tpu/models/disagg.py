"""Disaggregated prefill/decode serving: dedicated prefill workers
streaming finished KV pages into a decode engine's pool (r15).

Chunked prefill (``SELDON_TPU_CHUNK_TOKEN_BUDGET``) removes most of the
prefill/decode interference inside ONE engine; DistServe
(arXiv:2401.09670) shows the rest goes away by not sharing the engine
at all: prompt prefill runs on dedicated PREFILL workers and only the
finished KV pages enter the DECODE worker's pool, so decode waves never
carry prefill FLOPs and interactive TTFT stops competing with batch
prompts for the decode engine's cadence.

Two handoff lanes, one wire format (the SRT1 container of
``codec/bufview.pack_kv_handoff``):

* **local (in-process workers)** — the payload's page buffers pass BY
  REFERENCE (metered as ``zero_copy_bytes``); the decode engine's page
  scatter is the single copy the hardware requires — re-encoding
  through the wire container in-process would be a full host memcpy
  per request.  This is the ICI-attached topology: prefill and decode
  engines in one process, different chips.
* **DCN (remote workers)** — :class:`PrefillLM` is an ordinary
  deployable microservice returning the same container as a uint8
  rawTensor proto; :class:`DisaggregatedLM` dials it through the
  standard transport clients (breakers, retries, tracing and deadline
  re-injection apply unchanged).

Admission prices a request by its PREDICTED prefill+decode cost
(``PagedEngine.predict_cost_s`` — measured rates, no tuning): a
deadline the prediction cannot meet is rejected with 504
``DEADLINE_UNREACHABLE`` before a prefill worker burns a single FLOP on
it.  The r10 priority/preemption machinery is untouched — priorities
and deadlines ride the handoff into the decode engine's ordinary
``submit`` path.
"""

from __future__ import annotations

import logging
import queue as _pyqueue
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from seldon_core_tpu.models.paged import PagedEngine, StreamingLM
from seldon_core_tpu.runtime import knobs as _knobs
from seldon_core_tpu.runtime.component import MicroserviceError

logger = logging.getLogger(__name__)

__all__ = ["PrefillLM", "DisaggregatedLM", "evacuate_streams",
           "migration_journal_entry"]


# ---------------------------------------------------------------------------
# live-stream evacuation coordinator (r17)
# ---------------------------------------------------------------------------


def migration_journal_entry(payload: Dict[str, Any]) -> Dict[str, Any]:
    """A drain-journal entry from a migration payload — the fallback
    recipe when a stream's export succeeded but no peer would take it:
    the respawned (or surviving) engine re-derives the stream exactly
    as an r12 journal replay would.  Schema comes from the ONE shared
    builder (``models/paged.journal_entry``), so the two journal lanes
    cannot drift."""
    from seldon_core_tpu.models.paged import journal_entry

    return journal_entry(
        req_id=payload.get("req_id"),
        prompt=[int(t) for t in np.asarray(payload["prompt"]).reshape(-1)],
        max_new_tokens=int(payload.get("max_new_tokens", 32)),
        temperature=float(payload.get("temperature", 0.0)),
        top_k=int(payload.get("top_k", 0)),
        eos_id=int(payload.get("eos_id", -1)),
        seed=int(payload.get("seed", 0)),
        priority=int(payload.get("priority", 0)),
        deadline_remaining_ms=payload.get("deadline_remaining_ms"),
        streamed=int(payload.get("streamed") or 0),
        stream_tokens=bool(payload.get("stream_tokens")),
        tokens_decoded=int(
            np.asarray(payload.get("tokens", [])).reshape(-1).shape[0]
        ),
        adapter=payload.get("adapter"),
    )


def _peer_cost_s(engine: PagedEngine, payload: Dict[str, Any]) -> float:
    """Predicted seconds for ``payload``'s REMAINING work on ``engine``
    (the PR 13 cost model applied to evacuation placement).  A cold
    peer prices 0.0 — an idle engine is the best destination anyway;
    queue depth breaks ties so one peer doesn't absorb the whole
    evacuation wave."""
    remaining = max(
        1,
        int(payload.get("max_new_tokens", 32))
        - int(np.asarray(payload.get("tokens", [])).reshape(-1).shape[0]),
    )
    cost = engine.predict_cost_s(0, remaining)  # KV arrives computed:
    # the peer pays decode only, never the prompt's prefill FLOPs
    stats = engine.engine_stats()
    backlog = stats["queued_streams"] + stats["active_slots"]
    return (cost or 0.0) + 0.001 * backlog


def evacuate_streams(
    src_engine: PagedEngine,
    peers: List[PagedEngine],
    *,
    transport: str = "local",
) -> Dict[str, Any]:
    """Live-migrate ``src_engine``'s exportable streams onto healthy
    ``peers`` (r17): priority-ordered (highest first — the evacuation
    window's budget goes to the most important streams), each placed on
    the HEALTHY peer whose predicted remaining-work cost is lowest (the
    PR 13 cost model; degraded/evacuating peers are never targets).
    The in-process lane adopts the source's stream objects, so waiter
    events and token queues survive the move — zero token loss.

    A stream every peer refuses (pool too small, engine closed, shed)
    falls back to the r12 discipline: its waiter resolves 503
    ``MIGRATING`` and its re-derivation recipe lands in the returned
    ``journal`` list for the caller to persist.  Returns
    ``{"migrated", "failed", "journal"}``."""
    from seldon_core_tpu.engine.transport import migration_hop

    exported = src_engine.migrate_export()
    healthy = [
        p for p in peers
        if p is not src_engine
        and p.engine_stats().get("health", "healthy") == "healthy"
    ]
    out: Dict[str, Any] = {"migrated": 0, "failed": 0, "journal": []}
    err = MicroserviceError(
        "stream could not be live-migrated during evacuation; its "
        "recipe is journaled for re-derivation",
        status_code=503, reason="MIGRATING",
    )
    for payload, stream in sorted(
        exported, key=lambda ps: -ps[0]["priority"]
    ):
        placed = False
        for peer in sorted(healthy, key=lambda p: _peer_cost_s(p, payload)):
            try:
                with migration_hop("evacuate", transport) as hop:
                    if hop is not None:
                        hop.zero_copy_bytes = (
                            int(np.asarray(payload["k"]).nbytes)
                            + int(np.asarray(payload["v"]).nbytes)
                        )
                    peer.migrate_import(payload, stream=stream)
                placed = True
                break
            except MicroserviceError as exc:
                logger.warning(
                    "peer refused migrated req %s: %s",
                    payload.get("req_id"), exc,
                )
        if placed:
            out["migrated"] += 1
        else:
            out["failed"] += 1
            out["journal"].append(migration_journal_entry(payload))
            src_engine.fail_stream(stream, err)
    return out


class PrefillLM(StreamingLM):
    """Deployable PREFILL-WORKER role: admits one prompt per request,
    runs its (chunked, when the budget knob is on) prefill, and returns
    the KV-page handoff container as a uint8 row — which the runtime
    encodes as a rawTensor proto, the DCN wire form of the handoff.
    Decode never runs here: every stream is ``kv_export``, so the
    engine's waves are pure prefill and its prefix cache stays warm
    across exports (a shared system prompt is computed once per
    worker)."""

    def predict(self, X, names, meta=None):
        if self.engine is None:
            self.load()  # idempotent + internally locked
        meta = meta or {}
        tags = meta.get("tags", {})
        X = np.atleast_2d(np.asarray(X, np.int32))
        if X.shape[0] != 1:
            raise MicroserviceError(
                "prefill workers serve one prompt per request (the KV "
                "handoff is per stream); send rows separately",
                status_code=400, reason="BAD_REQUEST",
            )
        priority, deadline = self._slo_terms(tags)
        stream = self.engine.submit(
            X[0], max_new_tokens=1, priority=priority, deadline=deadline,
            kv_export=True, adapter=self._request_adapter(tags),
        )
        self._wake.set()
        stream.event.wait()
        if stream.error is not None:
            raise stream.error
        from seldon_core_tpu.codec.bufview import pack_kv_handoff

        buf = pack_kv_handoff(stream.kv_payload)
        return np.frombuffer(buf, np.uint8)[None, :]

    def metrics(self):
        out = super().metrics()
        if self.engine is not None:
            s = self.engine.engine_stats()
            out.append({
                "type": "GAUGE", "key": "paged_kv_exports",
                "value": s["kv_exports"],
            })
        return out


class _PrefillJob:
    """One prompt waiting for a prefill worker.  Orders by (priority
    desc, arrival) in the shared PriorityQueue — the same
    highest-class-first discipline the decode engine's admission uses,
    so a batch prompt cannot starve interactive prefills either."""

    __slots__ = ("seq", "prompt", "priority", "submit_kw", "event",
                 "stream", "error", "cancelled")

    def __init__(self, seq: int, prompt: np.ndarray, priority: int,
                 submit_kw: Dict[str, Any]):
        self.seq = seq
        self.prompt = prompt
        self.priority = priority
        self.submit_kw = submit_kw
        self.event = threading.Event()
        self.stream = None
        self.error: Optional[Exception] = None
        # set by the coordinator's error cleanup: a job still queued
        # when a sibling fails must not burn prefill FLOPs and decode
        # capacity on a result nobody will read
        self.cancelled = False

    def __lt__(self, other: "_PrefillJob") -> bool:
        return (-self.priority, self.seq) < (-other.priority, other.seq)


class DisaggregatedLM(StreamingLM):
    """Decode-worker front with dedicated prefill workers.

    ``prefill_workers=N`` (or ``SELDON_TPU_PREFILL_WORKERS``) builds N
    in-process prefill engines (``prefill_slots`` admission slots each)
    fed from one priority job queue; ``prefill_endpoints=[...]``
    instead dials remote :class:`PrefillLM` microservices (``"host:
    port"`` or ``"grpc://"``/``"rest://"`` URLs) — the supervisor's
    ``disagg_worker_specs`` wires exactly that topology up.  With
    neither configured this degrades to a plain :class:`StreamingLM`.

    ``predict``/``predict_stream`` route every prompt through a prefill
    worker and admit only the finished KV pages into the decode engine,
    so the decode loop's waves carry decode (and KV scatters) only.
    Greedy decode is bit-exact with unified serving: the imported pages
    are the same deterministic prefill KV, and the decode stream's rng
    keys derive from the same per-request seed rule."""

    def __init__(
        self,
        *args: Any,
        prefill_workers: int = 0,
        prefill_slots: int = 2,
        prefill_endpoints: Any = None,
        admission_pricing: Optional[bool] = None,
        **kwargs: Any,
    ):
        super().__init__(*args, **kwargs)
        if not prefill_workers:
            prefill_workers = int(
                _knobs.raw("SELDON_TPU_PREFILL_WORKERS", "0") or 0
            )
        self.prefill_workers = max(0, int(prefill_workers))
        self.prefill_slots = max(1, int(prefill_slots))
        if isinstance(prefill_endpoints, str):
            # deployment parameters arrive as a JSON string
            import json as _json

            prefill_endpoints = (
                _json.loads(prefill_endpoints) if prefill_endpoints else []
            )
        self.prefill_endpoints = list(prefill_endpoints or [])
        if admission_pricing is None:
            admission_pricing = _knobs.flag("SELDON_TPU_ADMISSION_PRICING")
        self.admission_pricing = bool(admission_pricing)
        self._prefill_engines: List[PagedEngine] = []
        self._prefill_threads: List[threading.Thread] = []
        self._jobs: "_pyqueue.PriorityQueue[_PrefillJob]" = (
            _pyqueue.PriorityQueue()
        )
        self._workers_stop = False
        self._job_seq = 0
        self._disagg_lock = threading.Lock()

    # ---- lifecycle --------------------------------------------------------

    def _disagg_enabled(self) -> bool:
        return bool(self.prefill_workers or self.prefill_endpoints)

    def load(self) -> None:
        super().load()
        role = _knobs.raw("SELDON_TPU_DISAGG_ROLE", "") or ""
        if role:
            # supervisor-pinned role: surfaced in logs and /debug/knobs
            # so operators can tell a worker's role without guessing
            # from its traffic
            logger.info("disaggregated role pin: %s", role)
        if not self._disagg_enabled():
            return
        with self._disagg_lock:
            if self._prefill_threads:
                return
            if self.prefill_endpoints:
                for i, ep in enumerate(self.prefill_endpoints):
                    t = threading.Thread(
                        target=self._remote_prefill_loop, args=(ep,),
                        name=f"disagg-prefill-dcn-{i}", daemon=True,
                    )
                    t.start()
                    self._prefill_threads.append(t)
                return
            import jax.numpy as jnp

            from seldon_core_tpu.models.generate import load_lm_params

            # same URI/config/seed as the decode engine -> identical
            # params, which is the bit-exactness precondition of the
            # handoff (documented in docs §5b-quater)
            # ... cast once as the engines hold it (the float32 tree let
            # go): every prefill engine is handed this one tree and
            # takes it by identity, so N workers share one residency
            params = PagedEngine.resting_tree(
                load_lm_params(self.model_uri, self.config, self.seed),
                dtype=jnp.bfloat16,
                quantize=self.engine_config["quantize"],
                precision=self.engine_config["precision"], **self.config)
            eng_cfg = dict(self.engine_config)
            eng_cfg.update(
                max_slots=self.prefill_slots,
                # prefill-only engines never decode: speculative verify
                # and queue bounds belong to the decode worker
                speculative=None, max_queue=0,
            )
            # adapter-carrying prompts prefill WITH their adapter (the
            # exported KV must match the decode worker's weight set) —
            # prefill engines resolve the same registry names
            registry = self._register_adapters()
            for i in range(self.prefill_workers):
                eng = PagedEngine(
                    params, dtype=jnp.bfloat16, tp=self.tp or None,
                    max_adapters=self.max_adapters,
                    lora_rank=self.lora_rank, weight_registry=registry,
                    **self.config, **eng_cfg,
                )
                self._prefill_engines.append(eng)
                t = threading.Thread(
                    target=self._prefill_loop, args=(eng,),
                    name=f"disagg-prefill-{i}", daemon=True,
                )
                t.start()
                self._prefill_threads.append(t)

    def shutdown(self) -> None:
        self._workers_stop = True
        super().shutdown()
        for eng in self._prefill_engines:
            try:
                eng.close()
            except Exception:  # noqa: BLE001 — teardown must finish even if
                # a worker engine already failed
                logger.exception("prefill engine close failed")

    # ---- priced admission -------------------------------------------------

    def _price_admission(
        self, prompt_len: int, max_new: int, deadline: Optional[float]
    ) -> None:
        """DistServe-style priced admission: a request whose PREDICTED
        prefill+decode cost cannot fit its remaining deadline is
        rejected BEFORE a prefill worker burns FLOPs on it — dead-on-
        arrival work is the overload amplifier the r10 shedding policy
        cannot see (it prices queue position, not service time)."""
        if (
            not self.admission_pricing
            or deadline is None
            or self.engine is None
        ):
            return
        cost = self.engine.predict_cost_s(int(prompt_len), int(max_new))
        if cost is None:
            return  # cold engine: nothing measured yet, admit unpriced
        remaining = deadline - time.monotonic()
        if cost > remaining:
            raise MicroserviceError(
                f"admission priced out: predicted prefill+decode cost "
                f"{cost * 1000.0:.0f} ms exceeds the remaining deadline "
                f"{max(0.0, remaining) * 1000.0:.0f} ms",
                status_code=504, reason="DEADLINE_UNREACHABLE",
            )

    # ---- prefill workers --------------------------------------------------

    def _enqueue_prefill(
        self, prompt: np.ndarray, priority: int, submit_kw: Dict[str, Any]
    ) -> _PrefillJob:
        with self._disagg_lock:
            self._job_seq += 1
            job = _PrefillJob(self._job_seq, prompt, priority, submit_kw)
        self._jobs.put(job)
        return job

    def _hand_off_local(self, job: _PrefillJob, payload: Dict[str, Any]) -> None:
        """In-process handoff: the payload's page buffers pass BY
        REFERENCE into the decode engine (its donated scatter is the
        single copy the hardware requires — re-encoding through the
        wire container here would be a full host memcpy per request),
        metered through the transport surface (``method="kv_handoff"``,
        ``zero_copy_bytes``) so dashboards price the lane next to the
        request lanes it displaces."""
        from seldon_core_tpu.engine.transport import kv_handoff_hop

        with kv_handoff_hop("disagg-prefill", "local") as hop:
            if hop is not None:
                hop.zero_copy_bytes = sum(
                    int(np.asarray(payload[k]).nbytes)
                    for k in ("k", "v", "last_logits", "prompt",
                              "k_scales", "v_scales")
                    if k in payload
                )
            job.stream = self.engine.submit_prefilled(
                payload, **job.submit_kw
            )
        self._wake.set()

    def _hand_off_container(self, job: _PrefillJob, buf: bytes) -> None:
        """DCN handoff: reopen the received SRT1 container as zero-copy
        views and admit the pages, metering the transferred bytes.  The
        ``transport.corrupt`` chaos point flips payload bytes HERE —
        the CRC32C trailer must turn the flip into a named rejection
        the waiter sees, never a silent garbage-KV scatter."""
        from seldon_core_tpu.codec.bufview import unpack_kv_handoff
        from seldon_core_tpu.engine.transport import kv_handoff_hop
        from seldon_core_tpu.utils import faults as _faults

        buf = _faults.corrupt_bytes("transport.corrupt", buf)
        with kv_handoff_hop("disagg-prefill", "dcn") as hop:
            if hop is not None:
                hop.request_bytes = len(buf)
            payload = unpack_kv_handoff(buf)
            job.stream = self.engine.submit_prefilled(
                payload, **job.submit_kw
            )
        self._wake.set()

    def _prefill_loop(self, eng: PagedEngine) -> None:
        """In-process worker: pop a job, prefill-export on this
        worker's own engine (it owns the step loop — the single-stepper
        invariant holds per engine), hand the pages off by reference."""
        while not self._workers_stop:
            try:
                job = self._jobs.get(timeout=0.2)
            except _pyqueue.Empty:
                continue
            if job.cancelled:
                job.event.set()
                continue
            try:
                payload = eng.prefill_export(
                    job.prompt,
                    priority=job.priority,
                    deadline=job.submit_kw.get("deadline"),
                    adapter=job.submit_kw.get("adapter"),
                )
                if job.cancelled:  # cancelled mid-export: don't admit
                    continue
                self._hand_off_local(job, payload)
            except Exception as exc:  # noqa: BLE001 — the waiter gets the
                # error; the worker thread must survive any one job
                job.error = exc
            finally:
                job.event.set()

    def _remote_prefill_loop(self, endpoint: str) -> None:
        """DCN worker: pop a job, call the remote :class:`PrefillLM`'s
        predict through the standard transport clients' model-call
        method (``transform_input`` — the executor's MODEL predict
        verb; breakers/retries/deadline re-injection apply), hand the
        returned container off.  One thread per endpoint, with ONE
        persistent event loop for its lifetime: ``GrpcClient`` caches
        ``grpc.aio`` channels per address, and a channel outliving a
        per-call ``asyncio.run`` loop would fail every RPC after the
        first ("event loop is closed")."""
        import asyncio

        from seldon_core_tpu.engine.graph import Endpoint, UnitSpec
        from seldon_core_tpu.engine.transport import GrpcClient, RestClient
        from seldon_core_tpu.runtime.message import InternalMessage

        scheme, sep, rest = endpoint.partition("://")
        if not sep:
            scheme, rest = "grpc", endpoint
        host, _, port = rest.partition(":")
        spec = UnitSpec(
            name=f"prefill@{rest}",
            endpoint=Endpoint(
                host=host or "localhost", port=int(port or 9000),
                transport="REST" if scheme == "rest" else "GRPC",
            ),
        )
        client = (
            RestClient(spec) if scheme == "rest" else GrpcClient(spec)
        )
        loop = asyncio.new_event_loop()
        try:
            while not self._workers_stop:
                try:
                    job = self._jobs.get(timeout=0.2)
                except _pyqueue.Empty:
                    continue
                if job.cancelled:
                    job.event.set()
                    continue
                try:
                    msg = InternalMessage(payload=np.atleast_2d(job.prompt))
                    msg.meta.tags["priority"] = job.priority
                    # the remote PrefillLM must prefill with the SAME
                    # weight set the decode engine will decode with
                    if job.submit_kw.get("adapter"):
                        msg.meta.tags["adapter"] = job.submit_kw["adapter"]
                    # the deadline must CROSS the DCN hop: the remote
                    # PrefillLM mints its own expiry from the remaining
                    # budget (its _slo_terms reads deadline_ms), and a
                    # job already expired while queued here fast-fails
                    # before burning a remote prefill on it
                    deadline = job.submit_kw.get("deadline")
                    if deadline is not None:
                        remaining_ms = (deadline - time.monotonic()) * 1000.0
                        if remaining_ms <= 0:
                            from seldon_core_tpu.utils.deadlines import (
                                deadline_exceeded,
                            )

                            raise deadline_exceeded(
                                "disaggregated prefill queue"
                            )
                        msg.meta.tags["deadline_ms"] = remaining_ms
                    reply = loop.run_until_complete(
                        client.transform_input(msg)
                    )
                    buf = np.ascontiguousarray(
                        reply.array(), dtype=np.uint8
                    ).tobytes()
                    if job.cancelled:  # cancelled mid-call: don't admit
                        continue
                    self._hand_off_container(job, buf)
                except Exception as exc:  # noqa: BLE001 — the waiter gets
                    # the error; the worker thread must survive any one job
                    job.error = exc
                finally:
                    job.event.set()
        finally:
            loop.close()

    # ---- serving fronts ---------------------------------------------------

    def predict(self, X, names, meta=None):
        if self.engine is None:
            self.load()  # idempotent + internally locked
        if not self._disagg_enabled():
            return super().predict(X, names, meta)
        meta = meta or {}
        tags = meta.get("tags", {})
        max_new = int(tags.get("max_new_tokens", self.max_new_tokens))
        temperature = float(tags.get("temperature", self.temperature))
        top_k = int(tags.get("top_k", self.top_k))
        request_seed = self._request_seed(tags, meta)
        priority, deadline = self._slo_terms(tags)
        adapter = self._request_adapter(tags)
        X = np.atleast_2d(np.asarray(X, np.int32))
        jobs: List[_PrefillJob] = []
        try:
            for i, row in enumerate(X):
                self._price_admission(len(row), max_new, deadline)
                jobs.append(self._enqueue_prefill(
                    row, priority,
                    dict(
                        max_new_tokens=max_new, temperature=temperature,
                        top_k=top_k, eos_id=self.eos_id,
                        seed=self.seed ^ (request_seed * 1000003 + i),
                        priority=priority, deadline=deadline,
                        adapter=adapter,
                    ),
                ))
            out = []
            for job in jobs:
                job.event.wait()
                if job.error is not None:
                    raise job.error
                job.stream.event.wait()
                if job.stream.error:
                    raise job.stream.error
                out.append(job.stream.result)
            return np.stack(out)
        except BaseException:
            # one row priced out/shed/errored: the siblings must not
            # keep burning prefill FLOPs or decoding unread (same
            # discipline as StreamingLM) — jobs still queued are
            # flagged so the workers skip them, jobs already handed
            # off cancel their decode streams
            for job in jobs:
                job.cancelled = True
                s = job.stream
                if s is not None and s.result is None and s.error is None:
                    self.engine.cancel(s)
            raise

    def predict_stream(self, X, names=None, meta=None):
        if self.engine is None:
            self.load()  # idempotent + internally locked
        if not self._disagg_enabled():
            yield from super().predict_stream(X, names, meta)
            return
        meta = meta or {}
        tags = meta.get("tags", {})
        max_new = int(tags.get("max_new_tokens", self.max_new_tokens))
        temperature = float(tags.get("temperature", self.temperature))
        top_k = int(tags.get("top_k", self.top_k))
        request_seed = self._request_seed(tags, meta)
        priority, deadline = self._slo_terms(tags)
        X = np.atleast_2d(np.asarray(X, np.int32))
        if X.shape[0] != 1:
            raise MicroserviceError(
                "token streaming serves one prompt per stream; send rows "
                "separately (predict() batches them)",
                status_code=400, reason="BAD_REQUEST",
            )
        self._price_admission(X.shape[1], max_new, deadline)
        job = self._enqueue_prefill(
            X[0], priority,
            dict(
                max_new_tokens=max_new, temperature=temperature,
                top_k=top_k, eos_id=self.eos_id,
                seed=self.seed ^ (request_seed * 1000003),
                priority=priority, deadline=deadline,
                stream_tokens=True,
                adapter=self._request_adapter(tags),
            ),
        )
        job.event.wait()
        if job.error is not None:
            raise job.error
        stream = job.stream
        try:
            yield from self.engine.stream_events(stream)
            if stream.error:
                raise stream.error
        finally:
            self.engine.cancel(stream)

    def metrics(self):
        out = super().metrics()
        if self.engine is not None:
            s = self.engine.engine_stats()
            out.append({
                "type": "GAUGE", "key": "paged_kv_imports",
                "value": s["kv_imports"],
            })
        exports = 0
        for eng in self._prefill_engines:
            exports += eng.engine_stats()["kv_exports"]
        if self._disagg_enabled():
            out.append({
                "type": "GAUGE", "key": "paged_prefill_workers",
                "value": (
                    len(self._prefill_engines) or len(self.prefill_endpoints)
                ),
            })
            out.append({
                "type": "GAUGE", "key": "paged_kv_exports", "value": exports,
            })
        return out
