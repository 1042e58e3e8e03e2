"""The Seldon component over the paged engine."""

from __future__ import annotations

import logging
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from seldon_core_tpu.runtime import knobs as _knobs
from seldon_core_tpu.runtime.component import MicroserviceError, TPUComponent
from seldon_core_tpu.utils import faults as _faults
from seldon_core_tpu.utils import telemetry as _telemetry

from .engine import PagedEngine

logger = logging.getLogger(__package__)


# process-wide id source for bridge labels: each engine gets a distinct
# model_name so shared-registry timeseries never merge across engines
_BRIDGE_SEQ = 0
_BRIDGE_SEQ_LOCK = threading.Lock()


class StreamingLM(TPUComponent):
    """Deployable continuous-batching generation component.

    Concurrent ``predict`` calls share one :class:`PagedEngine`: each
    request's rows become streams, a background loop steps the engine,
    and every caller blocks only until *its* streams finish — short
    generations return while long ones keep decoding (contrast
    :class:`GenerativeLM`, which batches rectangularly per request).

    Per-request overrides via ``meta.tags``: ``max_new_tokens``,
    ``temperature``, ``top_k``, ``seed``.
    """

    device_exclusive = True  # TPU-resident weights/KV: one process per chip

    def __init__(
        self,
        vocab_size: int = 32000,
        d_model: int = 256,
        num_layers: int = 4,
        num_heads: int = 8,
        max_len: int = 2048,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_k: int = 0,
        eos_id: int = -1,
        model_uri: str = "",
        seed: int = 0,
        page_size: int = 64,
        num_pages: int = 0,
        max_slots: int = 8,
        steps_per_call: int = 8,
        max_steps_per_call: int = 0,
        mesh_axes: Optional[Dict[str, int]] = None,
        tp: int = 0,
        dp: int = 0,
        quantize: str = "",
        precision: str = "",
        speculative: Optional[Dict[str, Any]] = None,
        prefix_cache: Optional[bool] = None,
        max_queue: int = 0,
        chunk_token_budget: int = 0,
        max_adapters: int = 0,
        lora_rank: int = 8,
        adapters: Any = None,
        arch: str = "gpt2",
        num_experts: int = 0,
        experts_per_tok: int = 0,
        expert_width: int = 0,
        arch_sizes: Any = None,
        prompt_buckets: Any = None,
        **kwargs: Any,
    ):
        super().__init__(**kwargs)
        from seldon_core_tpu.models.spec import model_spec

        # the block the deployment serves (models/spec.py): ``arch``
        # names it, the sizes (0 = as published) resize it; an unknown
        # arch fails here, at construction
        # ``arch_sizes`` (a JSON object) resizes any further field the
        # arch has, by the names models/spec.py gives them: a replica's
        # share of an expert-parallel layer (experts_held,
        # expert_offset), its layer kinds (dense_layers), a test's
        # ranks and head widths
        if isinstance(arch_sizes, str):
            import json as _json

            arch_sizes = _json.loads(arch_sizes) if arch_sizes else None
        self.spec = model_spec(str(arch), **{
            "num_experts": num_experts, "experts_per_tok": experts_per_tok,
            "expert_width": expert_width, **dict(arch_sizes or {})})
        self.config = dict(
            vocab_size=int(vocab_size), d_model=int(d_model),
            num_layers=int(num_layers), num_heads=int(num_heads),
            max_len=int(max_len),
        )
        from seldon_core_tpu.ops.surgery import (
            validate_precision,
            validate_quantize_mode,
        )

        self.engine_config = dict(
            page_size=int(page_size), num_pages=int(num_pages) or None,
            max_slots=int(max_slots), steps_per_call=int(steps_per_call),
            max_steps_per_call=int(max_steps_per_call),
            quantize=validate_quantize_mode(quantize),  # fail at construction
            precision=validate_precision(precision),
            # speculative={"draft": "ngram", "draft_k": k, "ngram": n}:
            # per-slot draft/verify INSIDE the continuous-batching
            # engine — greedy-exact, one verify forward per chunk
            speculative=dict(speculative) if speculative else None,
            # page-granular automatic prefix caching: None defers to
            # SELDON_TPU_PREFIX_CACHE (default on; "0" disables)
            prefix_cache=prefix_cache,
            # bounded run queue with priority shedding (0 defers to
            # SELDON_TPU_MAX_QUEUE; 0 = unbounded)
            max_queue=int(max_queue),
            # chunked-prefill co-scheduling (0 defers to
            # SELDON_TPU_CHUNK_TOKEN_BUDGET; 0 = monolithic prefill)
            chunk_token_budget=int(chunk_token_budget),
        )
        # the prefill buckets, where the doubling ladder to max_len is
        # not the one wanted (a JSON list)
        if isinstance(prompt_buckets, str):
            import json as _json

            prompt_buckets = _json.loads(prompt_buckets) if prompt_buckets else None
        if prompt_buckets:
            self.engine_config["prompt_buckets"] = [int(b) for b in prompt_buckets]
        # multi-LoRA (r16): adapter pool slots (0 defers to
        # SELDON_TPU_MAX_ADAPTERS; 0 = adapters off) + the factor rank
        # every registered adapter must share (one pool shape), and the
        # deployment's named adapter catalogue — dict name -> spec
        # ({"seed": n} deterministic synthetic factors, {"uri": ...} a
        # msgpack checkpoint) registered into the process weight
        # registry at load (loaders: nothing materialises until a
        # request selects it).  Deployment parameters arrive as JSON.
        self.max_adapters = int(max_adapters)
        self.lora_rank = int(lora_rank)
        if isinstance(adapters, str):
            import json as _json

            adapters = _json.loads(adapters) if adapters else None
        self.adapters = dict(adapters) if adapters else {}
        self.mesh_axes = dict(mesh_axes) if mesh_axes else None
        # serving-mesh degrees (r11 tp, r19 dp): `tp=N` / `dp=D` (or
        # SELDON_TPU_TP / SELDON_TPU_DP when 0) are the deployment-
        # facing spelling of mesh_axes={"data": D, "model": N}; an
        # explicit mesh_axes wins.  Degrades shrink-data-first with a
        # WARN on hosts with fewer devices (resolve_mesh).
        self.tp = int(tp)
        self.dp = int(dp)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.eos_id = int(eos_id)
        self.model_uri = model_uri
        self.seed = int(seed)
        self.engine: Optional[PagedEngine] = None
        self._prom_bridge = None
        self._loop_thread: Optional[threading.Thread] = None
        self._wake = threading.Event()
        self._stop = False
        # drain/handoff (r12): set by drain() so the exiting decode loop
        # leaves the engine alone (drain serializes the live streams;
        # the loop's usual close() would error them out uselessly first)
        self._draining = False
        self._load_lock = threading.Lock()
        self._counter = 0
        self._counter_lock = threading.Lock()
        # fleet telemetry plane (r20): per-replica sample ring, fed from
        # the decode loop's throttled collect hook; None when
        # SELDON_TPU_TELEMETRY=0 (no ring, no /debug/telemetry route)
        self._telemetry_ring = None
        # per-request cost ledger handoff: predict() leaves the request's
        # cost totals here and the dispatcher's get_custom_tags() call
        # (same thread, immediately after predict) picks them up via
        # tags() — thread-local because dispatch threads run concurrently
        self._request_cost = threading.local()

    def load(self) -> None:
        # IDEMPOTENT, and it must be: the executor calls load() on graph
        # build while lazy predict paths may already have loaded — a
        # second load would replace self.engine and start a SECOND
        # decode-loop thread, and both threads (the orphaned one reads
        # self.engine dynamically) would step ONE engine concurrently,
        # racing the donated pool buffers ("Array has been deleted")
        with self._load_lock:
            if self.engine is not None:
                return
            import jax.numpy as jnp

            from seldon_core_tpu.models.generate import load_lm_params

            # the tree as the engine will hold it, and the loader's
            # float32 one let go before the engine allocates its pool:
            # both at once would be the process's peak
            params = PagedEngine.resting_tree(
                load_lm_params(
                    self.model_uri, self.config, self.seed, spec=self.spec),
                dtype=jnp.bfloat16, spec=self.spec,
                quantize=self.engine_config["quantize"],
                precision=self.engine_config["precision"], **self.config)
            from seldon_core_tpu.parallel.mesh import mesh_from_axes

            mesh = mesh_from_axes(self.mesh_axes)
            # multi-LoRA: the deployment's adapter catalogue registers
            # into the process weight registry (loaders only — cold
            # adapters materialise on first selection, budget-priced),
            # and the engine resolves names through it at submit
            registry = self._register_adapters()
            # tp/dp passed THROUGH so the engine resolves the knobs
            # exactly once: an explicit tp=1/dp=1 here must force the
            # axis off even with SELDON_TPU_TP / SELDON_TPU_DP
            # exported (mesh_axes still wins)
            engine = PagedEngine(
                params, dtype=jnp.bfloat16, mesh=mesh, tp=self.tp or None,
                dp=self.dp or None,
                max_adapters=self.max_adapters, lora_rank=self.lora_rank,
                weight_registry=registry, spec=self.spec,
                **self.config, **self.engine_config,
            )
            # canonical seldon_tpu_engine_* metrics on the process
            # registry (the gateway's /metrics endpoint serves it);
            # collected from the decode loop.  SELDON_TPU_PROM_BRIDGE=0
            # opts out; a missing prometheus_client degrades to none.
            import os as _os

            if _knobs.flag("SELDON_TPU_PROM_BRIDGE"):
                try:
                    from seldon_core_tpu.utils.metrics import (
                        GenerationPrometheusBridge,
                    )

                    # distinct model_name per engine: two StreamingLMs
                    # in one process (multi-model graph, rolling
                    # re-apply overlap) must not merge into one
                    # timeseries — gauges would flap between engines
                    # and the model_name-keyed dashboards would group
                    # everything under ""
                    global _BRIDGE_SEQ
                    with _BRIDGE_SEQ_LOCK:
                        seq = _BRIDGE_SEQ
                        _BRIDGE_SEQ += 1
                    self._prom_bridge = GenerationPrometheusBridge(
                        engine, model_name=f"streaminglm-{seq}",
                    )
                except Exception:  # noqa: BLE001 — metrics never block serving
                    logger.exception("prometheus bridge unavailable")
            if _telemetry.telemetry_enabled():
                self._telemetry_ring = _telemetry.TelemetryRing(
                    capacity=int(
                        _knobs.raw("SELDON_TPU_TELEMETRY_RING", "256") or 256
                    ),
                )
            # drain/handoff replay (r12): a journal left by a drained
            # predecessor (SIGTERM → drain → exit; the supervisor keeps
            # the path stable across respawns) re-submits its live
            # streams BEFORE the decode loop starts — by first chunk the
            # respawned engine is already re-deriving, and the prompts'
            # prefix pages re-enter the cache where the original
            # callers' retries find them warm.  Unary replay: the
            # original streaming consumers died with the old process.
            journal = _knobs.raw("SELDON_TPU_DRAIN_JOURNAL", "")
            if journal and _os.path.exists(journal):
                try:
                    import json as _json

                    with open(journal) as f:
                        entries = [
                            _json.loads(line)
                            for line in f if line.strip()
                        ]
                    _os.unlink(journal)  # consumed: never replay twice
                    if entries:
                        replayed = engine.replay(entries, stream_tokens=False)
                        logger.info(
                            "drain journal %s: replayed %d/%d streams",
                            journal, len(replayed), len(entries),
                        )
                except Exception:  # noqa: BLE001 — a corrupt journal
                    # must never block serving; the streams it described
                    # are re-derived by caller retries instead
                    logger.exception("drain-journal replay failed (%s)", journal)
            self._loop_thread = threading.Thread(
                target=self._loop, name="streaminglm-decode", daemon=True
            )
            # publish the engine only after full construction; the loop
            # thread reads self.engine
            self.engine = engine
            self._loop_thread.start()

    def _loop(self) -> None:
        import time as _time

        last_collect = 0.0

        def collect(min_interval_s: float) -> None:
            # throttled INSIDE the drain loop too: under sustained load
            # has_work() never goes false, and metrics that only update
            # at idle would freeze during exactly the backlog the
            # queue-depth alert exists for
            nonlocal last_collect
            if self._prom_bridge is None and self._telemetry_ring is None:
                return
            now = _time.monotonic()
            if now - last_collect >= min_interval_s:
                last_collect = now
                if self._prom_bridge is not None:
                    self._prom_bridge.collect()  # internally exception-safe
                if self._telemetry_ring is not None:
                    try:
                        self._telemetry_ring.sample_engine(self.engine)
                    except Exception:  # noqa: BLE001 — telemetry never
                        # blocks serving
                        logger.exception("telemetry sample failed")

        while not self._stop:
            self._wake.wait(timeout=0.5)
            self._wake.clear()
            # one wave deep: wave N+1 is launched, from the state wave N
            # will leave, before wave N's tokens are read back — the
            # device finds its next programs queued when a chunk ends.
            # The same two halves step() runs back to back
            prev = None
            try:
                self.engine.wave_boundary()
                while self.engine.has_work():
                    if self._stop:
                        break
                    nxt = self.engine.launch()
                    self.engine.harvest(prev)
                    prev = nxt
                    collect(2.0)
                # stopping (shutdown, drain, evacuation): the last wave
                # is read before anyone looks at stream state
                if prev is not None and not prev.done:
                    self.engine.harvest(prev)
            except Exception as exc:  # surface to all waiters, don't die silently
                self.engine.fail_all(exc)
            collect(0.5)
        # loop stopped: nothing will ever step streams again — reject
        # future submits and unblock every current waiter.  EXCEPT when
        # a drain is in progress: drain() owns the live streams (it
        # journals them for the respawned engine before erroring the
        # waiters with DRAINING), so closing here would destroy the
        # handoff payload.
        if self.engine is not None and not self._draining:
            self.engine.close(
                MicroserviceError("component shut down", status_code=503,
                                  reason="SHUTTING_DOWN")
            )

    def shutdown(self) -> None:
        self._stop = True
        self._wake.set()

    def drain(self, journal_path: Optional[str] = None,
              timeout_s: float = 30.0) -> List[Dict[str, Any]]:
        """Drain-then-exit (r12): stop the decode loop at the next chunk
        boundary, journal every live stream's re-derivation recipe, and
        error their local waiters with a clean 503 ``DRAINING``.  The
        journal is written (JSONL, atomic rename) to ``journal_path`` or
        ``SELDON_TPU_DRAIN_JOURNAL`` — the path the supervisor pins per
        worker, so the respawned process replays it on load.  Wired to
        SIGTERM by the microservice runtime; idempotent and safe on a
        never-loaded component (returns [])."""
        import os as _os

        path = journal_path if journal_path is not None else \
            _knobs.raw("SELDON_TPU_DRAIN_JOURNAL", "")
        if self.engine is None:
            return []
        self._quiesce_loop(timeout_s)
        # SIGTERM-with-evacuation (r17): with a peer endpoint
        # configured, live mid-decode streams migrate THERE first —
        # their KV pages, cursors and RNG state resume on the peer at
        # the exact next token instead of re-deriving from scratch.
        # Export or ship failures fall back to ordinary journal
        # entries, so the journal remains the safety net it was in r12.
        entries: List[Dict[str, Any]] = []
        peer = _knobs.raw("SELDON_TPU_EVACUATE_TO", "") or ""
        if peer:
            entries.extend(self._evacuate_remote(peer))
        entries.extend(self.engine.drain())
        if path and entries:
            try:
                import json as _json

                tmp = f"{path}.tmp"
                with open(tmp, "w") as f:
                    for e in entries:
                        f.write(_json.dumps(e) + "\n")
                _os.replace(tmp, path)  # atomic: a respawn never reads half
                logger.info(
                    "drained %d live streams to %s", len(entries), path
                )
            except OSError:
                logger.exception("drain journal write failed (%s)", path)
        return entries

    def _quiesce_loop(self, timeout_s: float = 30.0) -> None:
        """Stop the decode loop at the next chunk boundary (drain and
        evacuation both require no chunk in flight — neither may
        serialize state a device call is still mutating)."""
        self._draining = True
        self._stop = True
        self._wake.set()
        if self._loop_thread is not None and self._loop_thread.is_alive():
            self._loop_thread.join(timeout=timeout_s)
            if self._loop_thread.is_alive():
                logger.error(
                    "decode loop still running after %.0fs drain wait — "
                    "journaling anyway (chunk results for this wave may "
                    "be lost, re-derivation covers them)", timeout_s,
                )

    def evacuate(
        self,
        peers: Sequence[Any],
        journal_path: Optional[str] = None,
        timeout_s: float = 30.0,
    ) -> Dict[str, Any]:
        """In-process live evacuation (r17): quiesce the decode loop,
        live-migrate every exportable stream to a healthy peer
        (priority-ordered, priced by the PR 13 cost model —
        models/disagg.evacuate_streams), journal the rest, and close
        this engine.  ``peers`` are :class:`PagedEngine`s or components
        exposing ``.engine``.  Streaming consumers keep their token
        queues across the move — zero token loss."""
        if self.engine is None:
            return {"migrated": 0, "journaled": 0, "failed": 0}
        from seldon_core_tpu.models.disagg import evacuate_streams

        self._quiesce_loop(timeout_s)
        engines = [getattr(p, "engine", None) or p for p in peers]
        summary = evacuate_streams(self.engine, engines)
        for p in peers:
            wake = getattr(p, "_wake", None)
            if wake is not None:
                wake.set()  # adopted streams resume without the 0.5s poll
        entries = list(summary.pop("journal", []))
        entries.extend(self.engine.drain())
        path = journal_path if journal_path is not None else \
            _knobs.raw("SELDON_TPU_DRAIN_JOURNAL", "")
        if path and entries:
            try:
                import json as _json
                import os as _os

                tmp = f"{path}.tmp"
                with open(tmp, "w") as f:
                    for e in entries:
                        f.write(_json.dumps(e) + "\n")
                _os.replace(tmp, path)
            except OSError:
                logger.exception("evacuation journal write failed (%s)", path)
        summary["journaled"] = len(entries)
        logger.info(
            "evacuation: %d stream(s) live-migrated, %d journaled, "
            "%d failed", summary.get("migrated", 0), len(entries),
            summary.get("failed", 0),
        )
        return summary

    def _evacuate_remote(self, endpoint: str) -> List[Dict[str, Any]]:
        """Ship this engine's exportable streams to ``endpoint`` as SRT1
        migration containers (the DCN lane: one transport-client call
        per stream, metered as ``method="migrate"`` hops).  Returns
        journal entries for every stream that could NOT be shipped;
        shipped streams' local waiters resolve 503 ``MIGRATING`` (their
        state lives on the peer now — upstream retries land there).

        Semantics of the DCN lane, honestly: the zero-token-loss
        guarantee belongs to the IN-PROCESS adoption lane (the consumer
        keeps its token queue).  Across processes the original
        consumer's connection dies with this process; what shipping the
        KV buys is (a) the stream completes on the peer instead of
        being lost, and (b) its prompt's prefix pages register into the
        peer's cache at import — a caller retry against the peer
        re-prefills only the suffix instead of paying the full prompt
        FLOPs a journal replay would."""
        import asyncio
        import time as _time

        from seldon_core_tpu.codec.bufview import pack_kv_migration
        from seldon_core_tpu.engine.graph import Endpoint, UnitSpec
        from seldon_core_tpu.engine.transport import (
            GrpcClient,
            RestClient,
            migration_hop,
        )
        from seldon_core_tpu.models.disagg import migration_journal_entry
        from seldon_core_tpu.runtime.message import InternalMessage

        exported = self.engine.migrate_export()
        if not exported:
            return []
        scheme, sep, rest = endpoint.partition("://")
        if not sep:
            scheme, rest = "grpc", endpoint
        host, _, port = rest.partition(":")
        spec = UnitSpec(
            name=f"evacuate@{rest}",
            endpoint=Endpoint(
                host=host or "localhost", port=int(port or 9000),
                transport="REST" if scheme == "rest" else "GRPC",
            ),
        )
        client = RestClient(spec) if scheme == "rest" else GrpcClient(spec)
        loop = asyncio.new_event_loop()
        fallback: List[Dict[str, Any]] = []
        migrated = 0
        err = MicroserviceError(
            "stream live-migrated to a peer engine during evacuation",
            status_code=503, reason="MIGRATING",
        )
        try:
            # priority-ordered: the most important streams get the
            # evacuation window's budget first
            for payload, stream in sorted(
                exported, key=lambda ps: -ps[0]["priority"]
            ):
                try:
                    buf = pack_kv_migration(payload)
                    with migration_hop("streaminglm-evacuate", "dcn") as hop:
                        if hop is not None:
                            hop.request_bytes = len(buf)
                        msg = InternalMessage(
                            payload=np.frombuffer(buf, np.uint8)[None, :]
                        )
                        msg.meta.tags["kv_migration"] = 1
                        loop.run_until_complete(client.transform_input(msg))
                    migrated += 1
                except Exception:  # noqa: BLE001 — ship failure falls back
                    # to the journal; evacuation must not lose the recipe
                    logger.exception(
                        "migration ship failed for req %s — journaling",
                        payload.get("req_id"),
                    )
                    fallback.append(migration_journal_entry(payload))
                self.engine.fail_stream(stream, err)
        finally:
            try:
                loop.run_until_complete(client.close())
            except Exception:  # noqa: BLE001 — client teardown is
                # best-effort during process exit
                pass
            loop.close()
        logger.info(
            "remote evacuation to %s: %d migrated, %d journaled",
            endpoint, migrated, len(fallback),
        )
        return fallback

    def _register_adapters(self):
        """Register the deployment's adapter catalogue in the process
        weight registry (called from load(), before the engine exists).
        Returns the registry the engine resolves names through, or
        None when multi-LoRA is off entirely."""
        if not (self.adapters or self.max_adapters):
            return None
        from seldon_core_tpu.models.registry import get_registry
        from seldon_core_tpu.ops.lora import target_dims

        registry = get_registry()
        dims = target_dims(self.config["d_model"])
        hint = 4 * self.config["num_layers"] * sum(
            (d_in + d_out) * self.lora_rank for d_in, d_out in dims.values()
        )
        for name, spec in self.adapters.items():
            registry.register(
                name, self._adapter_loader(name, spec), bytes_hint=hint,
            )
        return registry

    def _adapter_loader(self, name: str, spec: Any):
        """One adapter's loader closure: ``{"seed": n}`` builds
        deterministic synthetic factors (bench/tests — deterministic so
        drain-replay and disaggregated workers re-derive identical
        weights), ``{"uri": ...}`` overlays a flax msgpack checkpoint
        on the factor template, and a raw ``{target: (A, B)}`` dict
        passes through (in-process composition)."""
        cfg = dict(self.config)
        rank = self.lora_rank

        def loader():
            from seldon_core_tpu.ops.lora import (
                LORA_TARGETS,
                make_lora_params,
            )

            if isinstance(spec, dict) and any(
                t in spec for t in LORA_TARGETS
            ):
                return spec
            if isinstance(spec, dict) and "uri" in spec:
                from flax import serialization

                from seldon_core_tpu.utils import storage

                template = make_lora_params(
                    0, num_layers=cfg["num_layers"], d_model=cfg["d_model"],
                    rank=rank,
                )
                with open(storage.download(spec["uri"]), "rb") as f:
                    return serialization.from_bytes(template, f.read())
            seed = int(spec.get("seed", 0)) if isinstance(spec, dict) else int(spec)
            alpha = (
                float(spec.get("alpha", rank)) if isinstance(spec, dict)
                else float(rank)
            )
            return make_lora_params(
                seed, num_layers=cfg["num_layers"], d_model=cfg["d_model"],
                rank=rank, alpha=alpha,
            )

        return loader

    @staticmethod
    def _request_adapter(tags) -> Optional[str]:
        """The per-request adapter selection: ``meta.tags.adapter``
        (the ``X-Seldon-Adapter`` header lands here at every ingress;
        an explicit body tag wins).  Empty/None = base model.  Tag and
        header normalize through ONE rule, so both carriers always
        resolve one adapter to one table key."""
        from seldon_core_tpu.utils.deadlines import normalize_adapter

        return normalize_adapter(tags.get("adapter"))

    def _request_seed(self, tags, meta) -> int:
        """The per-request sampling seed rule shared by every serving
        front (unary, streaming, disaggregated): explicit ``seed`` tag
        wins, else the request puid hashes deterministically (a retried
        request reproduces its continuation), else a per-process
        counter keeps distinct requests actually sampling."""
        if "seed" in tags:
            return int(tags["seed"])
        puid = meta.get("puid", "")
        if puid:
            import zlib

            return zlib.crc32(puid.encode())
        with self._counter_lock:
            self._counter += 1
            return self._counter

    @staticmethod
    def _slo_terms(tags) -> Tuple[int, Optional[float]]:
        """Per-request SLO terms: the ``priority`` tag (higher wins,
        clamped like the ingress header — an unauthenticated tag must
        not be an unbounded preemption weapon) and the TIGHTEST of the
        ``deadline_at_monotonic`` tag (absolute expiry the in-process
        streaming lanes mint at ingress), the ``deadline_ms`` tag
        (relative, minted here), and the ambient transport budget
        (utils/deadlines contextvar — run_dispatch copies contextvars
        onto this thread, the same hand-off the trace context rides),
        as an absolute monotonic expiry."""
        import time as _time

        from seldon_core_tpu.utils import deadlines as _deadlines

        try:
            priority = _deadlines.clamp_priority(
                int(float(tags.get("priority", 0)))
            )
        except (TypeError, ValueError):
            priority = 0
        deadline = None
        raw_abs = tags.get("deadline_at_monotonic")
        if raw_abs is not None:
            try:
                deadline = float(raw_abs)
            except (TypeError, ValueError):
                deadline = None
        raw = tags.get("deadline_ms")
        if raw is not None:
            try:
                rel = _time.monotonic() + max(0.0, float(raw)) / 1000.0
                deadline = rel if deadline is None else min(deadline, rel)
            except (TypeError, ValueError):
                pass
        ambient = _deadlines.current_deadline()
        if ambient is not None:
            deadline = (
                ambient.expires_at if deadline is None
                else min(deadline, ambient.expires_at)
            )
        return priority, deadline

    def _accept_migration(self, X) -> np.ndarray:
        """Migration ingress (r17): a peer evacuating its streams POSTs
        each one as a uint8 SRT1 migration container (CRC-checked,
        ``transport.corrupt`` chaos applies); the stream resumes
        decoding HERE at the exact next token.  Returns a 1x1 ack row
        carrying the resumed stream's req id — the sender only needs
        the admission to have succeeded (the original consumers retry
        against this replica through the normal routing layer)."""
        from seldon_core_tpu.codec.bufview import unpack_kv_migration
        from seldon_core_tpu.engine.transport import migration_hop

        buf = np.ascontiguousarray(
            np.asarray(X, np.uint8).reshape(-1)
        ).tobytes()
        buf = _faults.corrupt_bytes("transport.corrupt", buf)
        with migration_hop("streaminglm-ingress", "dcn") as hop:
            if hop is not None:
                hop.request_bytes = len(buf)
            try:
                payload = unpack_kv_migration(buf)
            except Exception as exc:
                raise MicroserviceError(
                    f"malformed migration container: {exc}",
                    status_code=400, reason="BAD_MIGRATION_PAYLOAD",
                ) from exc
            stream = self.engine.migrate_import(payload, stream_tokens=False)
        self._wake.set()
        return np.asarray([[stream.req_id]], np.int32)

    def _capture_model_config(self) -> Dict[str, Any]:
        """The StreamingLM ctor kwargs a replay needs to rebuild THIS
        model (tools/seldon_replay.py): architecture, engine shape and
        numeric regime.  Runtime knobs travel separately in the
        capture's knob snapshot — this is only what the constructor
        pins.  Every value must survive the container's JSON meta
        frame, so non-serializable entries are dropped (a replay of
        such a deployment reconstructs them by hand)."""
        import json as _json

        eng = self.engine_config
        cfg = {
            **self.config,
            "max_new_tokens": self.max_new_tokens,
            "temperature": self.temperature,
            "top_k": self.top_k,
            "eos_id": self.eos_id,
            "model_uri": self.model_uri,
            "seed": self.seed,
            "page_size": eng["page_size"],
            "num_pages": int(eng["num_pages"] or 0),
            "max_slots": eng["max_slots"],
            "steps_per_call": eng["steps_per_call"],
            "max_steps_per_call": eng["max_steps_per_call"],
            "quantize": eng["quantize"] or "",
            "precision": eng["precision"] or "",
            "speculative": eng["speculative"],
            "prefix_cache": eng["prefix_cache"],
            "max_queue": eng["max_queue"],
            "chunk_token_budget": eng["chunk_token_budget"],
            "mesh_axes": self.mesh_axes,
            "tp": self.tp,
            "dp": self.dp,
            "max_adapters": self.max_adapters,
            "lora_rank": self.lora_rank,
            "adapters": self.adapters,
        }
        out = {}
        for k, v in cfg.items():
            try:
                _json.dumps(v)
            except (TypeError, ValueError):
                continue
            out[k] = v
        return out

    def _maybe_capture(self, streams, *, tags, meta, request_seed,
                       status="ok", reason="", tokens=None) -> None:
        """Per-request black-box write (r21): evaluate the trigger
        matrix for the request's first stream and, when it fires,
        store the capture container.  Multi-row requests capture row 0
        — replay re-submits the whole request, so one container
        recovers every row.  Contained: forensics never breaks
        serving."""
        engine = self.engine
        if engine is None or not engine._capture_enabled or not streams:
            return
        try:
            stream = streams[0]
            puid = str(
                meta.get("puid", "") or stream.puid
                or stream.trace_id or f"req-{stream.req_id}"
            )
            trigger = engine.capture_trigger(
                puid, stream.error if status != "ok" else None,
            )
            if trigger is None and status != "ok":
                trigger = "error"  # raised before/around submit
            if trigger is None:
                return
            deadline_remaining_ms = None
            if stream.deadline is not None:
                import time as _time

                deadline_remaining_ms = max(
                    0.0, (stream.deadline - _time.monotonic()) * 1000.0
                )
            engine.capture_request(
                stream, puid=puid, trigger=trigger, status=status,
                reason=reason, tokens=tokens,
                extra={
                    "request_seed": int(request_seed),
                    "model": self._capture_model_config(),
                    "tags": {
                        k: v for k, v in tags.items()
                        if isinstance(v, (str, int, float, bool))
                    },
                    "rows": len(streams),
                    "deadline_remaining_ms": deadline_remaining_ms,
                },
            )
        except Exception:  # noqa: BLE001 — forensics must not break serving
            logger.exception("request capture failed")

    def predict(self, X, names, meta=None):
        if self.engine is None:
            self.load()  # idempotent + internally locked
        meta = meta or {}
        tags = meta.get("tags", {})
        if tags.get("kv_migration"):
            return self._accept_migration(X)
        max_new = int(tags.get("max_new_tokens", self.max_new_tokens))
        temperature = float(tags.get("temperature", self.temperature))
        top_k = int(tags.get("top_k", self.top_k))
        # sampling must actually sample across requests unless pinned:
        # tag override > puid > per-process counter (GenerativeLM's rule)
        request_seed = self._request_seed(tags, meta)
        priority, deadline = self._slo_terms(tags)
        adapter = self._request_adapter(tags)
        X = np.atleast_2d(np.asarray(X, np.int32))
        streams = []
        try:
            for i, row in enumerate(X):
                # multiplicative row spread: (seed ^ c) + i style
                # additive mixing collides across neighbouring requests
                streams.append(self.engine.submit(
                    row, max_new_tokens=max_new, temperature=temperature,
                    top_k=top_k, eos_id=self.eos_id,
                    seed=self.seed ^ (request_seed * 1000003 + i),
                    priority=priority, deadline=deadline, adapter=adapter,
                    puid=str(meta.get("puid", "")),
                    t_ingress=meta.get("t_ingress"),
                ))
            self._wake.set()
            for stream in streams:
                stream.event.wait()
                if stream.error:
                    raise stream.error
            if self.engine._telemetry_enabled:
                # cost ledger handoff: the dispatcher reads tags() on
                # THIS thread right after predict returns, so the
                # request's cost totals ride meta.tags.cost on the
                # response the caller actually sees
                self._request_cost.value = {
                    "page_seconds": round(
                        sum(s.cost_page_s for s in streams), 6
                    ),
                    "prefill_tokens": sum(
                        s.cost_prefill_tokens for s in streams
                    ),
                    "decode_tokens": sum(
                        s.cost_decode_tokens for s in streams
                    ),
                    "preemptions": sum(s.cost_preempts for s in streams),
                    "restores": sum(s.cost_restores for s in streams),
                    "adapter": adapter or "base",
                }
            result = np.stack([s.result for s in streams])
            self._maybe_capture(
                streams, tags=tags, meta=meta, request_seed=request_seed,
                status="ok", tokens=streams[0].result,
            )
            return result
        except BaseException as exc:
            # one row shed/expired/errored: the siblings must not keep
            # decoding unread — they hold slots and KV pages exactly
            # when the engine is overloaded enough to shed
            for s in streams:
                if s.result is None and s.error is None:
                    self.engine.cancel(s)
            self._maybe_capture(
                streams, tags=tags, meta=meta, request_seed=request_seed,
                status="error", reason=repr(exc),
            )
            raise

    def predict_stream(self, X, names=None, meta=None):
        """Token streaming for ONE prompt: a generator yielding int32
        arrays of newly decoded tokens as the engine emits them (the
        serving UX modern generation stacks expose; the reference
        predates it).  Same per-request overrides as predict; greedy
        re-runs after an eviction resume exactly where the consumer
        left off (deterministic seeds + the streamed cursor).
        """
        if self.engine is None:
            self.load()  # idempotent + internally locked
        meta = meta or {}
        tags = meta.get("tags", {})
        max_new = int(tags.get("max_new_tokens", self.max_new_tokens))
        temperature = float(tags.get("temperature", self.temperature))
        top_k = int(tags.get("top_k", self.top_k))
        # same seed rule as predict: tag override > puid > counter, so a
        # streamed request samples identically to the unary predict of
        # the same request (and a retried stream with the same puid
        # reproduces its continuation)
        request_seed = self._request_seed(tags, meta)
        X = np.atleast_2d(np.asarray(X, np.int32))
        if X.shape[0] != 1:
            raise MicroserviceError(
                "token streaming serves one prompt per stream; send rows "
                "separately (predict() batches them)",
                status_code=400, reason="BAD_REQUEST",
            )
        priority, deadline = self._slo_terms(tags)
        stream = self.engine.submit(
            X[0], max_new_tokens=max_new, temperature=temperature,
            top_k=top_k, eos_id=self.eos_id,
            seed=self.seed ^ (request_seed * 1000003),
            stream_tokens=True,
            priority=priority, deadline=deadline,
            adapter=self._request_adapter(tags),
            puid=str(meta.get("puid", "")),
            t_ingress=meta.get("t_ingress"),
        )
        self._wake.set()
        try:
            # (a consumer that send()s the time.monotonic() at which its
            # transport's write returned has its delivery counted to
            # there: PagedEngine.stream_events)
            yield from self.engine.stream_events(stream)
            if stream.error:
                err = stream.error
                self._maybe_capture(
                    [stream], tags=tags, meta=meta,
                    request_seed=request_seed, status="error",
                    reason=repr(err),
                )
                raise err
            # normal completion (a mid-stream disconnect skips capture:
            # the consumer leaving is not a serving incident)
            self._maybe_capture(
                [stream], tags=tags, meta=meta,
                request_seed=request_seed, status="ok",
            )
        finally:
            # consumer gone (disconnect/cancel) or done: an abandoned
            # stream must not keep decoding into an unread queue,
            # holding a slot and pages against live requests
            self.engine.cancel(stream)

    def tags(self):
        """Response meta tags: the LAST predict's cost-ledger totals on
        this dispatch thread (dispatch calls get_custom_tags right after
        predict on the same thread).  Pop-once so a later request that
        fails before submit cannot inherit a stale ledger."""
        cost = getattr(self._request_cost, "value", None)
        self._request_cost.value = None
        return {"cost": cost} if cost else {}

    def telemetry_snapshot(self, window_s: float = 0.0):
        """The versioned per-replica telemetry payload.  Takes one fresh
        engine sample first: pollers arriving between decode-loop
        collect ticks (or while the engine idles) must still see current
        queue depth / residency, not the last busy-period point."""
        if self._telemetry_ring is None:
            return None
        if self.engine is not None:
            try:
                self._telemetry_ring.sample_engine(self.engine)
            except Exception:  # noqa: BLE001 — serve what the ring has
                logger.exception("telemetry sample failed")
        return self._telemetry_ring.snapshot(window_s)

    def custom_routes(self):
        """``GET /debug/telemetry`` on the worker's own REST surface —
        what the fleet aggregator polls.  No ring (telemetry off) means
        no route: the =0 lane serves the exact pre-telemetry routes."""
        if self._telemetry_ring is None:
            return {}

        def debug_telemetry(request):
            try:
                window_s = float(request.query.get("window", "0") or 0.0)
            except (ValueError, AttributeError):
                window_s = 0.0
            return self.telemetry_snapshot(window_s)

        return {"/debug/telemetry": debug_telemetry}

    def health_status(self):
        """Where this replica runs: the device as jax reports it, the
        serving-mesh degrees the engine actually got (a degraded
        ``tp=``/``dp=`` request shows here) and the decode lane."""
        from seldon_core_tpu.parallel.mesh import device_report

        out: Dict[str, Any] = {
            "loaded": self.engine is not None,
            "device": device_report(),
        }
        if self.engine is not None:
            out.update(self.engine.lane_report())
        return out

    def metrics(self):
        """Paged-engine health for the dashboards.  All GAUGEs:
        metrics() is collected after every request, so cumulative values
        exported as COUNTERs would be inc()'d repeatedly (same
        convention as jaxserver/SpeculativeLM)."""
        if self.engine is None:
            return []
        s = self.engine.engine_stats()
        total = max(1, s["pool_pages_total"])
        return [
            {"type": "GAUGE", "key": "paged_active_slots", "value": s["active_slots"]},
            {"type": "GAUGE", "key": "paged_queued_streams", "value": s["queued_streams"]},
            {"type": "GAUGE", "key": "paged_pool_utilization", "value": s["pool_pages_used"] / total},
            {"type": "GAUGE", "key": "paged_evictions", "value": s["evictions"]},
            {"type": "GAUGE", "key": "paged_stall_events", "value": s["stalls"]},
            {"type": "GAUGE", "key": "paged_chunks", "value": s["chunks"]},
            {"type": "GAUGE", "key": "paged_tokens_emitted", "value": s["tokens"]},
            {"type": "GAUGE", "key": "paged_streams_completed", "value": s["completed"]},
            {"type": "GAUGE", "key": "paged_prefix_hit_rate",
             "value": s["prefix_hits"]
             / max(1, s["prefix_hits"] + s["prefix_misses"])},
            {"type": "GAUGE", "key": "paged_prefix_pages_cached",
             "value": s["prefix_pages_cached"]},
            {"type": "GAUGE", "key": "paged_prefix_tokens_saved",
             "value": s["prefix_tokens_saved"]},
            {"type": "GAUGE", "key": "paged_tp_degree",
             "value": s["tp_degree"]},
            {"type": "GAUGE", "key": "paged_dp_degree",
             "value": s["dp_degree"]},
            {"type": "GAUGE", "key": "paged_adapters_resident",
             "value": s["adapters_resident"]},
        ] + (
            [
                {"type": "GAUGE", "key": "speculative_acceptance_rate",
                 "value": s["spec_accepted"] / max(1, s["spec_drafted"])},
                {"type": "GAUGE", "key": "speculative_rounds",
                 "value": s["chunks"]},
            ]
            if self.engine.speculative is not None else []
        )

    def class_names(self):
        return []
