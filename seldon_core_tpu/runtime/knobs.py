"""Central registry of every runtime tuning knob.

Every ``SELDON_TPU_*`` environment variable, every ``seldon.io/*``
deployment annotation and every ``X-Seldon-*`` request header the
package reads is DECLARED here — name, type, default, whether ``=0``
spells OFF, one line of doc, and the docs section that explains it.
The registry is load-bearing three ways:

* **Reads go through it.**  :func:`raw` / :func:`flag` are the only
  sanctioned ways to read a ``SELDON_TPU_*`` env var inside
  ``seldon_core_tpu/`` — they raise :class:`UndeclaredKnobError` for a
  name that is not registered, so a knob cannot exist without an entry
  (and therefore without docs).  ``tools/graftlint``'s knob-registry
  checker enforces the same invariant statically: a direct
  ``os.environ`` read of a ``SELDON_TPU_*`` literal anywhere outside
  this module fails the lint.

* **``=0`` spells OFF.**  A PR 7 review caught ``SELDON_TPU_TP=0``
  crashing engine load; the fleet-wide convention since is that ``=0``
  on any knob means "feature off", never an error.  ``zero_off``
  records which knobs carry that contract so the lint and the tests
  can police it.

* **It is an operational surface.**  :func:`snapshot` renders the
  whole registry with current effective values — the gateway serves it
  at ``GET /debug/knobs`` so "what is this process actually running
  with" is one curl, not a grep.

The module is import-light on purpose (stdlib only): utils modules read
knobs from hot-ish paths and must not drag the serving stack in.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

__all__ = [
    "Knob",
    "Annotation",
    "Header",
    "ENV_KNOBS",
    "ANNOTATIONS",
    "HEADERS",
    "UndeclaredKnobError",
    "raw",
    "flag",
    "declared",
    "snapshot",
]


class UndeclaredKnobError(KeyError):
    """A ``SELDON_TPU_*`` read of a name missing from the registry —
    a programming error (declare the knob), never a runtime condition."""


@dataclass(frozen=True)
class Knob:
    """One declared environment knob.

    ``kind`` is documentation of the accepted value shape (``flag`` |
    ``int`` | ``float`` | ``str`` | ``path`` | ``spec``); parsing stays
    at the read site so migration to the registry is behaviour-
    identical.  ``default`` is the effective value when unset, as the
    reader interprets it.  ``zero_off`` declares the ``=0``-means-OFF
    contract.  ``anchor`` names the docs section that documents the
    knob (the lint additionally requires the knob name to appear in
    ``docs/``)."""

    name: str
    kind: str
    default: str
    zero_off: bool
    doc: str
    anchor: str


@dataclass(frozen=True)
class Annotation:
    """One declared ``seldon.io/*`` deployment annotation."""

    name: str
    kind: str
    doc: str


@dataclass(frozen=True)
class Header:
    """One declared ``X-Seldon-*`` request header (case-insensitive on
    the wire; gRPC metadata uses the lowercase spelling)."""

    name: str
    kind: str
    doc: str


def _knobs(*knobs: Knob) -> Dict[str, Knob]:
    out: Dict[str, Knob] = {}
    for k in knobs:
        if k.name in out:
            raise ValueError(f"duplicate knob declaration {k.name!r}")
        out[k.name] = k
    return out


ENV_KNOBS: Dict[str, Knob] = _knobs(
    # ---- runtime / process ------------------------------------------------
    Knob("SELDON_TPU_PLATFORM", "str", "", False,
         "force the jax platform (cpu|tpu|...) for the microservice CLI",
         "operations.md"),
    Knob("SELDON_TPU_DISPATCH_THREADS", "int", "128", False,
         "dispatch thread-pool size for component calls",
         "architecture.md §2"),
    Knob("SELDON_TPU_TRACE_EXPORT", "path", "", False,
         "per-process JSONL span sink (tools/profile_trace_stitch.py reads it)",
         "architecture.md §5c-bis"),
    Knob("SELDON_TPU_DRAIN_JOURNAL", "path", "", False,
         "drain/handoff journal path (pinned per worker by the supervisor)",
         "operations.md failure-containment"),
    Knob("SELDON_TPU_MODEL_CACHE", "path", "", False,
         "model-artifact download cache directory (default: tmpdir)",
         "architecture.md §3"),
    Knob("SELDON_TPU_NATIVE_SO", "path", "", False,
         "override the native front-server shared object (TSan/ASan builds)",
         "architecture.md §9"),
    Knob("SELDON_TPU_NATIVE_BATCH_THREADS", "int", "4", False,
         "native ingress batch-submit thread count",
         "architecture.md §9"),
    Knob("SELDON_TPU_NATIVE_RAW_WORKERS", "int", "8", False,
         "native ingress raw/gRPC fallback worker count",
         "architecture.md §9"),
    # ---- transport / telemetry -------------------------------------------
    Knob("SELDON_TPU_ZERO_COPY", "flag", "1", True,
         "buffer-view SeldonMessage lane: SRT1 frames decode to zero-copy "
         "views from native ingress to device buffers (0 = proto/JSON "
         "path only, behaviour-identical to the pre-lane engine)",
         "architecture.md §9a"),
    Knob("SELDON_TPU_BREAKER", "flag", "1", True,
         "per-endpoint circuit breakers (0 = off; breaker-off is "
         "byte-identical to the pre-breaker transport)",
         "operations.md failure-containment"),
    Knob("SELDON_TPU_TRANSPORT_TELEMETRY", "flag", "1", True,
         "per-hop transport metrics (0 = off; the bench's trace_prop "
         "contrast flips this)",
         "architecture.md §5c-bis"),
    Knob("SELDON_TPU_FAULT", "spec", "", True,
         "fault-injection spec 'point[:k=v,..];..' (empty/0 = disarmed)",
         "operations.md fault-injection"),
    # ---- generation engine ------------------------------------------------
    Knob("SELDON_TPU_TP", "int", "0", True,
         "tensor-parallel degree over the 'model' mesh axis "
         "(unset/empty/0 = single-chip)",
         "architecture.md §5b-ter"),
    Knob("SELDON_TPU_DP", "int", "0", True,
         "data-parallel degree over the 'data' mesh axis of the 2-D "
         "serving mesh (unset/empty/0 = one replica group)",
         "architecture.md §5b-octies"),
    Knob("SELDON_TPU_SEQ_SHARD", "flag", "1", True,
         "shard the KV pool's page dim over the 'data' axis (sequence/"
         "long-context sharding; 0 = replicate the pool — pure "
         "throughput replicas, no capacity claim)",
         "architecture.md §5b-octies"),
    Knob("SELDON_TPU_PAGED_KERNEL", "str", "auto", True,
         "pallas decode-kernel lane ('0' | '1' | 'auto' | 'force'; "
         "default 'auto' = on for single-chip TPU backends, off "
         "elsewhere — '0' restores the XLA gather lane byte-for-byte); "
         "a replica that cannot run it (TP mesh, other dtype, or on a "
         "TPU heads*head_dim not in multiples of 128) keeps the gather",
         "architecture.md §5b-septies"),
    Knob("SELDON_TPU_KV_DTYPE", "str", "bf16", False,
         "KV pool element dtype ('bf16' | 'int8'); int8 stores pages "
         "quantised with one f32 scale per page per k/v in a sibling "
         "scale table — halves pool bytes, single-chip pool-impl only",
         "architecture.md §5b-septies"),
    Knob("SELDON_TPU_CHUNK_IMPL", "str", "", False,
         "chunk program implementation ('ring' | 'pool'; empty = auto)",
         "architecture.md §5b"),
    Knob("SELDON_TPU_CTX_BUCKETS", "int", "2", False,
         "context-length buckets per chunk program ('1' disables, '2' default)",
         "architecture.md §5b"),
    Knob("SELDON_TPU_PREFIX_CACHE", "flag", "1", True,
         "page-granular automatic prefix caching (0 = off)",
         "architecture.md §5b-bis"),
    Knob("SELDON_TPU_PAGED_DEBUG", "flag", "0", False,
         "chunk-boundary allocator state-machine audit (1 = on)",
         "architecture.md §5b-bis"),
    Knob("SELDON_TPU_MAX_QUEUE", "int", "0", True,
         "bounded run-queue depth for priority shedding (0 = unbounded)",
         "operations.md overload-runbook"),
    Knob("SELDON_TPU_CHUNK_TOKEN_BUDGET", "int", "0", True,
         "chunked-prefill co-scheduling: max tokens one engine wave may "
         "carry, filled decode-first then with page-aligned prompt "
         "slices (0 = off: monolithic prefill, the historical engine)",
         "architecture.md §5b-quater"),
    Knob("SELDON_TPU_PREFILL_WORKERS", "int", "0", True,
         "disaggregated serving: dedicated prefill workers streaming "
         "finished KV pages into the decode engine's pool (0 = off: "
         "unified prefill+decode engine)",
         "architecture.md §5b-quater"),
    Knob("SELDON_TPU_DISAGG_ROLE", "str", "", False,
         "role pin for supervisor-spawned disaggregated workers "
         "('prefill' | 'decode'; empty = unified engine)",
         "architecture.md §5b-quater"),
    Knob("SELDON_TPU_ADMISSION_PRICING", "flag", "1", True,
         "disaggregated admission prices a request by predicted "
         "prefill+decode cost and fast-fails deadlines it cannot meet "
         "(0 = admit everything, price nothing)",
         "architecture.md §5b-quater"),
    Knob("SELDON_TPU_MAX_ADAPTERS", "int", "0", True,
         "multi-LoRA serving: adapter slots in the engine's factor "
         "pool (0 = adapters off, byte-identical pre-adapter programs)",
         "architecture.md §5b-quinquies"),
    Knob("SELDON_TPU_WEIGHT_BUDGET_GIB", "float", "0", True,
         "HBM budget for the process weight registry's named weight "
         "sets (base models + LoRA adapters; 0 = unbudgeted loads)",
         "architecture.md §5b-quinquies"),
    Knob("SELDON_TPU_KV_CHECKSUM", "flag", "1", True,
         "CRC32C integrity trailer on KV handoff/migration containers "
         "(0 = off; default on — a flipped DCN byte rejects as a named "
         "PayloadError instead of decoding as garbage KV)",
         "architecture.md §5b-sexies"),
    Knob("SELDON_TPU_KV_OFFLOAD", "flag", "0", True,
         "hierarchical KV tier: demote LRU-reclaimed prefix/session "
         "pages into a budgeted host-RAM store (optionally spilling to "
         "disk) and promote them back through the donated-scatter "
         "import on the next chain hit (0 = off, byte-identical "
         "programs and discard-on-reclaim as before)",
         "architecture.md §5b-nonies"),
    Knob("SELDON_TPU_KV_HOST_BUDGET_GIB", "float", "4", False,
         "host-RAM byte budget for the KV tier's container store "
         "(oldest entries spill to disk or drop when exceeded)",
         "architecture.md §5b-nonies"),
    Knob("SELDON_TPU_KV_SPILL_DIR", "path", "", False,
         "disk level below the host KV tier: CRC-trailered containers "
         "written atomic tmp+rename, LRU-evicted to the spill budget "
         "(empty = no disk level, host-budget overflow drops)",
         "architecture.md §5b-nonies"),
    Knob("SELDON_TPU_KV_SPILL_GIB", "float", "16", False,
         "disk byte budget for the KV tier's spill directory",
         "architecture.md §5b-nonies"),
    Knob("SELDON_TPU_NAN_GUARD", "flag", "1", True,
         "post-chunk NaN/Inf screen on served logits: a non-finite lane "
         "retires ONLY its stream with 500 NUMERIC_POISON (0 = off; "
         "decode lane only — speculative verify emits argmax ids, its "
         "logits never reach the host)",
         "operations.md evacuation-runbook"),
    Knob("SELDON_TPU_WATCHDOG", "flag", "1", True,
         "device-health watchdog driving the engine health state "
         "machine healthy -> degraded -> evacuating (0 = off)",
         "operations.md evacuation-runbook"),
    Knob("SELDON_TPU_WATCHDOG_CHUNK_MS", "float", "0", True,
         "chunk-wall-time ceiling (ms) the watchdog counts breaches "
         "against; compile waves are exempt (0 = ceiling off)",
         "operations.md evacuation-runbook"),
    Knob("SELDON_TPU_WATCHDOG_FAULT_RATE", "float", "0.5", False,
         "chunk-fault fraction of the watchdog window that degrades "
         "the engine",
         "operations.md evacuation-runbook"),
    Knob("SELDON_TPU_WATCHDOG_COMPILES", "int", "0", True,
         "jit-compile storm threshold per watchdog window under "
         "traffic (0 = off; first-chunk cold compiles never count)",
         "operations.md evacuation-runbook"),
    Knob("SELDON_TPU_WATCHDOG_HBM_PCT", "float", "0", True,
         "pool-page occupancy percentage counted as allocator "
         "pressure by the watchdog (0 = off)",
         "operations.md evacuation-runbook"),
    Knob("SELDON_TPU_WATCHDOG_WINDOW", "int", "32", False,
         "watchdog sliding-window length in engine waves",
         "operations.md evacuation-runbook"),
    Knob("SELDON_TPU_WATCHDOG_BREACHES", "int", "8", False,
         "window breaches that drive healthy -> degraded (a clean "
         "window recovers degraded -> healthy)",
         "operations.md evacuation-runbook"),
    Knob("SELDON_TPU_FORCE_EVACUATE", "flag", "0", False,
         "force the engine health state to 'evacuating' (operator "
         "forced-migration switch; 1 = on)",
         "operations.md evacuation-runbook"),
    Knob("SELDON_TPU_EVACUATE_TO", "str", "", False,
         "peer endpoint ('grpc://host:port' | 'rest://host:port') that "
         "drain() live-migrates streams to before exiting; failures "
         "fall back to the drain journal (empty = journal only)",
         "operations.md evacuation-runbook"),
    Knob("SELDON_TPU_JIT_SENTINEL", "flag", "1", True,
         "XLA recompile sentinel on engine jit entry points (0 = off)",
         "architecture.md §5c"),
    Knob("SELDON_TPU_PROM_BRIDGE", "flag", "1", True,
         "auto-wired GenerationPrometheusBridge in StreamingLM.load (0 = off)",
         "architecture.md §5c"),
    # ---- observability / forensics ---------------------------------------
    Knob("SELDON_TPU_FLIGHT_RECORDER", "str", "512", True,
         "per-chunk flight-recorder ring capacity (0 = off, digits = size)",
         "architecture.md §5c"),
    Knob("SELDON_TPU_DUMP_P99_MS", "float", "0", True,
         "chunk-wall p99 breach threshold that auto-dumps the ring (0 = off)",
         "architecture.md §5c"),
    Knob("SELDON_TPU_DUMP_DIR", "path", "", False,
         "directory for p99-breach flight-recorder JSONL dumps",
         "architecture.md §5c"),
    Knob("SELDON_TPU_PROFILE_DIR", "path", "", False,
         "where the profile windows armed with POST /debug/profile are "
         "written (unset = the route answers 409)",
         "architecture.md §5c"),
    # ---- fleet telemetry plane (r20) --------------------------------------
    Knob("SELDON_TPU_TELEMETRY", "flag", "1", True,
         "fleet telemetry plane: per-replica telemetry ring, per-request "
         "cost ledger and histogram trace exemplars (0 = off, behaviour-"
         "identical to the pre-telemetry build — no new metric series)",
         "architecture.md §5c-ter"),
    Knob("SELDON_TPU_TELEMETRY_RING", "int", "256", False,
         "telemetry time-series ring capacity (samples per replica)",
         "architecture.md §5c-ter"),
    Knob("SELDON_TPU_FLEET_ENDPOINTS", "str", "", True,
         "comma-separated replica base URLs (name=http://host:port,...) "
         "the gateway's fleet aggregator polls for /debug/fleet (empty/0 "
         "= derive from the local supervisor's workers, else fleet view "
         "off)",
         "architecture.md §5c-ter"),
    Knob("SELDON_TPU_FLEET_POLL_S", "float", "2", False,
         "fleet aggregator poll interval (seconds)",
         "architecture.md §5c-ter"),
    Knob("SELDON_TPU_FLEET_STALE_S", "float", "10", False,
         "age after which a non-responding replica's fleet entry is "
         "marked stale (it keeps its last snapshot; the poll loop never "
         "fails over one dead replica)",
         "architecture.md §5c-ter"),
    # ---- per-request black-box capture + replay forensics (r21) -----------
    Knob("SELDON_TPU_CAPTURE", "flag", "0", True,
         "per-request black-box capture plane: head-sampled / on-error / "
         "p99-breach requests are serialized as SRT1 capture containers "
         "for GET /debug/request/<puid> and tools/seldon_replay.py "
         "(0 = off, bit-exact pre-capture serving and no new stats keys)",
         "architecture.md §5c-quater"),
    Knob("SELDON_TPU_CAPTURE_SAMPLE", "int", "0", True,
         "head-sampling rate: capture every Nth completed request "
         "(0 = no head sampling; error/breach triggers still fire when "
         "the capture plane is on)",
         "architecture.md §5c-quater"),
    Knob("SELDON_TPU_CAPTURE_DIR", "path", "", False,
         "bounded on-disk capture store directory (LRU-by-bytes "
         "eviction); empty = per-process temp directory",
         "architecture.md §5c-quater"),
    Knob("SELDON_TPU_CAPTURE_PAYLOADS", "flag", "1", True,
         "keep ingress/output payload frames in capture containers; 0 = "
         "capture.redact drops prompt/token ids (lengths and metadata "
         "survive, replay becomes impossible — the privacy posture)",
         "architecture.md §5c-quater"),
)


def _annotations(*anns: Annotation) -> Dict[str, Annotation]:
    return {a.name: a for a in anns}


ANNOTATIONS: Dict[str, Annotation] = _annotations(
    Annotation("seldon.io/frontend", "str",
               "gateway frontend selection (e.g. 'native')"),
    Annotation("seldon.io/breaker", "flag",
               "per-deployment circuit-breaker enable/disable"),
    Annotation("seldon.io/breaker-failures", "int",
               "consecutive transient failures that open the breaker"),
    Annotation("seldon.io/breaker-reset-ms", "int",
               "open -> half-open probe delay"),
    Annotation("seldon.io/breaker-probes", "int",
               "half-open probe budget"),
    Annotation("seldon.io/hedge-ms", "int",
               "first-wins duplicate delay for idempotent unary calls"),
    Annotation("seldon.io/grpc-retries", "int",
               "bounded gRPC retry budget on transient statuses"),
    Annotation("seldon.io/grpc-read-timeout", "int",
               "gRPC per-call timeout (ms)"),
    Annotation("seldon.io/rest-retries", "int",
               "bounded REST retry budget on 502/503/504 + connection faults"),
    Annotation("seldon.io/rest-read-timeout", "int",
               "REST read timeout (ms)"),
    Annotation("seldon.io/rest-connection-timeout", "int",
               "REST connect timeout (ms)"),
    Annotation("seldon.io/worker-ready-timeout-s", "float",
               "supervised remote-worker readiness deadline"),
    Annotation("seldon.io/oauth-key", "str", "gateway OAuth client key"),
    Annotation("seldon.io/oauth-secret", "str", "gateway OAuth client secret"),
    Annotation("seldon.io/oauth-token-ttl-s", "int", "OAuth token lifetime"),
    Annotation("seldon.io/tls-cert", "path", "TLS certificate file"),
    Annotation("seldon.io/tls-key", "path", "TLS private-key file"),
    Annotation("seldon.io/tls-ca", "path", "TLS CA bundle for client auth"),
    Annotation("seldon.io/tls-require-client-auth", "flag",
               "require mTLS client certificates"),
    Annotation("seldon.io/request-log-url", "str",
               "request/response logger HTTP sink"),
    Annotation("seldon.io/request-log-jsonl", "path",
               "request/response logger JSONL sink"),
    Annotation("seldon.io/request-log-kafka", "str",
               "request/response logger Kafka sink (broker/topic)"),
    Annotation("seldon.io/request-logger", "str",
               "gateway-level request/response pair logger sink spec: "
               "http(s)://url | kafka:brokers/topic | a JSONL file path "
               "(pairs stamped with puid + traceparent + cost)"),
)


HEADERS: Dict[str, Header] = {
    h.name: h for h in (
        Header("X-Seldon-Deadline-Ms", "int",
               "end-to-end budget minted at ingress; re-injected with the "
               "remaining budget on every downstream hop"),
        Header("X-Seldon-Priority", "int",
               "admission priority class for the generation engine's "
               "shedding/preemption policy"),
        Header("X-Seldon-Adapter", "str",
               "named LoRA adapter (weight set) this request decodes "
               "with; lands in meta.tags.adapter — an explicit tag in "
               "the body wins"),
    )
}

# lowercase alias set for gRPC-metadata spellings: the wire carries
# either case, the registry declares each header once
_HEADER_NAMES_LOWER = {h.lower() for h in HEADERS}


def declared(name: str) -> bool:
    """True when ``name`` is a registered env knob, annotation, or
    header (headers match case-insensitively)."""
    return (
        name in ENV_KNOBS
        or name in ANNOTATIONS
        or name.lower() in _HEADER_NAMES_LOWER
    )


def _require(name: str) -> Knob:
    knob = ENV_KNOBS.get(name)
    if knob is None:
        raise UndeclaredKnobError(
            f"{name!r} is not declared in runtime/knobs.py — every "
            "SELDON_TPU_* env read must go through the registry"
        )
    return knob


def raw(name: str, default: Optional[str] = None) -> Optional[str]:
    """Registered passthrough for ``os.environ.get(name, default)``.

    Parsing stays at the call site (the registry's ``kind``/``default``
    fields are documentation + the /debug/knobs surface), so migrating
    a read here is behaviour-identical by construction."""
    _require(name)
    return os.environ.get(name, default)


def flag(name: str) -> bool:
    """The canonical on/off read: ``=0`` spells OFF, anything else
    (including unset, for default-on knobs) follows the declared
    default.  Only valid for knobs registered with kind='flag'."""
    knob = _require(name)
    if knob.kind != "flag":
        raise UndeclaredKnobError(
            f"{name!r} is kind={knob.kind!r}, not a flag — read it with "
            "knobs.raw() and parse at the call site"
        )
    val = os.environ.get(name)
    if val is None:
        val = knob.default
    if knob.default == "1":
        return val != "0"  # default-on: =0 spells OFF
    return val == "1"  # default-off: =1 spells ON


def snapshot(environ: Optional[Dict[str, str]] = None) -> List[Dict[str, Any]]:
    """The whole env-knob registry with current raw values — the
    ``GET /debug/knobs`` payload.  ``environ`` overrides the process
    environment (tests)."""
    e = environ if environ is not None else os.environ
    out: List[Dict[str, Any]] = []
    for knob in sorted(ENV_KNOBS.values(), key=lambda k: k.name):
        val = e.get(knob.name)
        out.append({
            "name": knob.name,
            "kind": knob.kind,
            "default": knob.default,
            "zero_off": knob.zero_off,
            "set": val is not None,
            "value": val,
            "doc": knob.doc,
            "anchor": knob.anchor,
        })
    return out
