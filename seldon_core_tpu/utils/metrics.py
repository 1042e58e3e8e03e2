"""Prometheus metrics with the reference's canonical names.

The reference engine exposes micrometer histograms
``seldon_api_engine_server_requests_duration_seconds`` /
``..._client_requests_duration_seconds``, feedback counters
``seldon_api_model_feedback(_reward)``, and re-registers node custom
metrics with deployment/predictor/model tags
(reference: doc/source/analytics/analytics.md:9-16,
PredictiveUnitBean.java:323-357, metrics/CustomMetricsManager.java).
Same names and tag semantics here on prometheus_client, so the
reference's Grafana dashboards work against a TPU deployment unchanged.

``PrometheusObserver`` plugs into the engine's observer hook; metric
objects are created lazily and cached by (name, labelnames) since user
metric tag sets are dynamic.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Dict, Iterable, Optional, Tuple

logger = logging.getLogger(__name__)

_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)


class _MetricCache:
    """Lazily-created prometheus metrics keyed by (kind, name, labels)."""

    def __init__(self, registry=None):
        import prometheus_client as prom

        self._prom = prom
        self.registry = registry if registry is not None else prom.REGISTRY
        self._cache: Dict[Tuple[str, str, Tuple[str, ...]], Any] = {}
        self._lock = threading.Lock()


    def get(self, kind: str, name: str, labelnames: Tuple[str, ...], documentation: str = ""):
        key = (kind, name, labelnames)
        with self._lock:
            metric = self._cache.get(key)
            if metric is None:
                cls = {
                    "counter": self._prom.Counter,
                    "gauge": self._prom.Gauge,
                    "histogram": self._prom.Histogram,
                }[kind]
                kwargs = {"labelnames": labelnames, "registry": self.registry}
                if kind == "histogram":
                    kwargs["buckets"] = _BUCKETS
                metric = cls(name, documentation or name, **kwargs)
                self._cache[key] = metric
        return metric


# one cache per registry: prometheus_client raises Duplicated timeseries
# on re-registration, so observers sharing a registry (two predictors of
# one deployment, rolling re-apply in one process) must share the
# metric objects and differ only in label values
_CACHES: Dict[int, _MetricCache] = {}
_CACHES_LOCK = threading.Lock()


def _cache_for(registry=None) -> _MetricCache:
    import prometheus_client as prom

    reg = registry if registry is not None else prom.REGISTRY
    with _CACHES_LOCK:
        cache = _CACHES.get(id(reg))
        if cache is None:
            cache = _MetricCache(reg)
            _CACHES[id(reg)] = cache
        return cache


def increment_counter(name: str, documentation: str = "", registry=None) -> None:
    """Public label-less counter increment against the (default)
    registry.  Never raises: metrics must not break the data path —
    failures are logged so a broken counter is visible, not silent."""
    try:
        _cache_for(registry).get("counter", name, (), documentation).inc()
    except Exception:  # noqa: BLE001 — a broken counter is logged, never fatal
        logger.exception("failed to increment counter %s", name)


class PrometheusObserver:
    """Engine observer -> prometheus.

    Handles the executor/service event stream:
      * ``predict_done`` (payload: seconds) -> server request histogram
      * ``node_metrics`` (payload: list of metric dicts) -> custom
        counters/gauges/timers tagged deployment/predictor/model
      * ``node_feedback`` (payload: reward) -> feedback counters
    """

    def __init__(
        self,
        deployment_name: str = "",
        predictor_name: str = "",
        registry=None,
    ):
        self.deployment_name = deployment_name
        self.predictor_name = predictor_name
        self._cache = _cache_for(registry)

    # ---- base tags --------------------------------------------------------

    def _model_labels(self, unit: str) -> Dict[str, str]:
        return {
            "deployment_name": self.deployment_name,
            "predictor_name": self.predictor_name,
            "model_name": unit,
        }

    # ---- observer protocol -----------------------------------------------

    def __call__(self, event: str, unit: str, payload: Any) -> None:
        try:
            if event == "predict_done":
                self.observe_api("predictions", float(payload))
            elif event == "node_call":
                method, seconds = payload
                self.observe_node_call(unit, method, float(seconds))
            elif event == "node_metrics":
                for metric in payload or []:
                    self._apply_custom(unit, metric)
            elif event == "node_feedback":
                labels = self._model_labels(unit)
                names = tuple(sorted(labels))
                self._cache.get("counter", "seldon_api_model_feedback", names).labels(
                    **labels
                ).inc()
                self._cache.get("counter", "seldon_api_model_feedback_reward", names).labels(
                    **labels
                ).inc(float(payload or 0.0))
        except Exception:  # observers must never break the data plane
            logger.exception("metrics observer failed for %s/%s", event, unit)

    def observe_api(self, method: str, seconds: float, code: str = "200") -> None:
        labels = {
            "deployment_name": self.deployment_name,
            "predictor_name": self.predictor_name,
            "method": method,
            "code": code,
        }
        hist = self._cache.get(
            "histogram",
            "seldon_api_engine_server_requests_duration_seconds",
            tuple(sorted(labels)),
            "external API request latency",
        )
        hist.labels(**labels).observe(seconds)

    def observe_node_call(self, unit: str, method: str, seconds: float) -> None:
        labels = dict(self._model_labels(unit), method=method)
        hist = self._cache.get(
            "histogram",
            "seldon_api_engine_client_requests_duration_seconds",
            tuple(sorted(labels)),
            "engine->node call latency",
        )
        hist.labels(**labels).observe(seconds)

    def _apply_custom(self, unit: str, metric: Dict[str, Any]) -> None:
        key = metric.get("key")
        if not key:
            return
        labels = self._model_labels(unit)
        labels.update({str(k): str(v) for k, v in (metric.get("tags") or {}).items()})
        names = tuple(sorted(labels))
        value = float(metric.get("value", 0.0))
        mtype = metric.get("type", "COUNTER")
        if mtype == "COUNTER":
            self._cache.get("counter", key, names).labels(**labels).inc(value)
        elif mtype == "GAUGE":
            self._cache.get("gauge", key, names).labels(**labels).set(value)
        elif mtype == "TIMER":  # milliseconds, like the reference
            self._cache.get("histogram", key, names).labels(**labels).observe(value / 1000.0)


class HistogramQuantileSampler:
    """Windowed quantile over a prometheus Histogram child.

    Each call diffs the cumulative bucket counters against the previous
    sample and interpolates the quantile from the window's bucket deltas
    (the same estimate PromQL's histogram_quantile(rate(...)) gives) —
    the latency signal the autoscaler consumes for target_p95_ms
    policies.  Returns 0.0 until traffic arrives.
    """

    def __init__(self, histogram_child, quantile: float = 0.95):
        self._child = histogram_child
        self.quantile = float(quantile)
        self._last: Optional[List[float]] = None

    def _cumulative(self) -> Tuple[List[float], List[float]]:
        bounds = [float(b) for b in self._child._upper_bounds]  # noqa: SLF001
        counts = [float(acc.get()) for acc in self._child._buckets]  # noqa: SLF001
        # _buckets are per-bucket (non-cumulative) in prometheus_client
        cum = []
        total = 0.0
        for c in counts:
            total += c
            cum.append(total)
        return bounds, cum

    def __call__(self) -> float:
        bounds, cum = self._cumulative()
        if self._last is None:
            self._last = cum
            return 0.0
        deltas = [c - p for c, p in zip(cum, self._last)]
        self._last = cum
        if any(d < 0 for d in deltas):
            # counter reset (histogram re-registered / process-level
            # restart observed mid-window): negative deltas would make
            # the interpolation below nonsense — treat this sample as a
            # fresh baseline and report no traffic, like the first call
            # (PromQL's rate() makes the same choice on resets)
            return 0.0
        total = deltas[-1]
        if total <= 0:
            return 0.0
        rank = self.quantile * total
        prev_bound = 0.0
        prev_cum = 0.0
        for bound, c in zip(bounds, deltas):
            if c >= rank:
                if bound == float("inf"):
                    return prev_bound
                span = c - prev_cum
                frac = (rank - prev_cum) / span if span > 0 else 1.0
                return prev_bound + frac * (bound - prev_bound)
            prev_bound, prev_cum = bound, c
        return prev_bound


# ---------------------------------------------------------------------------
# generation-engine bridge (the TPU data plane's canonical metrics)
# ---------------------------------------------------------------------------

# PagedEngine.engine_stats() key -> (kind, canonical metric name, doc).
# COMPLETE BY CONTRACT: every engine_stats() key must appear here or in
# ENGINE_STATS_EXCLUDED (tests/test_gen_observability.py), so a new
# engine counter cannot silently skip Prometheus export.
ENGINE_STATS_METRICS: Dict[str, Tuple[str, str, str]] = {
    "chunks": ("counter", "seldon_tpu_engine_chunks_total",
               "decode/verify chunk programs executed"),
    "bucketed_chunks": ("counter", "seldon_tpu_engine_bucketed_chunks_total",
                        "chunks that ran the length-bucketed ctx gather"),
    "tokens": ("counter", "seldon_tpu_engine_tokens_total",
               "tokens emitted by the generation engine"),
    "evictions": ("counter", "seldon_tpu_engine_evictions_total",
                  "streams evicted to the queue under pool pressure"),
    "stalls": ("counter", "seldon_tpu_engine_stalls_total",
               "stream-chunk stalls on pool pressure"),
    "prefills": ("counter", "seldon_tpu_engine_prefills_total",
                 "streams admitted and prefilled"),
    "completed": ("counter", "seldon_tpu_engine_streams_completed_total",
                  "streams finished (result delivered)"),
    "spec_drafted": ("counter", "seldon_tpu_engine_spec_drafted_total",
                     "speculative tokens drafted"),
    "spec_accepted": ("counter", "seldon_tpu_engine_spec_accepted_total",
                      "speculative tokens accepted by verify"),
    "prefix_hits": ("counter", "seldon_tpu_engine_prefix_cache_hits_total",
                    "admissions that mapped >=1 cached prefix page"),
    "prefix_misses": ("counter", "seldon_tpu_engine_prefix_cache_misses_total",
                      "admissions with no cached prefix to reuse"),
    "prefix_evictions": ("counter",
                         "seldon_tpu_engine_prefix_cache_evictions_total",
                         "LRU-cached prefix pages reclaimed under pool pressure"),
    "prefix_tokens_saved": ("counter",
                            "seldon_tpu_engine_prefix_cache_tokens_saved_total",
                            "prompt tokens whose prefill was skipped via "
                            "cached prefix pages"),
    # chunked-prefill co-scheduling (r15): the prefill/decode token
    # split — "tokens" counts decode, these count the prompt side and
    # the prefill device calls that carried it, so the chunk-mix
    # dashboards can decompose a wave's work
    "prefill_tokens": ("counter", "seldon_tpu_engine_prefill_tokens_total",
                       "prompt tokens whose KV was computed by prefill "
                       "programs (cache hits and KV imports excluded)"),
    "prefill_chunks": ("counter", "seldon_tpu_engine_prefill_chunks_total",
                       "prefill device calls (whole prompts and "
                       "token-budget chunk slices alike)"),
    # the wave loop's own counts (PR 24): work where it is done and
    # waiting where it happens, beside the counters that time the same
    # layers from outside
    "prefill_padded_tokens": ("counter",
                              "seldon_tpu_engine_prefill_padded_tokens_total",
                              "positions the prefill programs computed: "
                              "each call pays its group rounded up to a "
                              "power of two times its prompt bucket"),
    "prefill_head_rows": ("counter",
                          "seldon_tpu_engine_prefill_head_rows_total",
                          "rows the prefill programs unembedded: one a "
                          "prompt of each call's padded group, whatever "
                          "the positions it computed"),
    "prefill_fused_positions": ("counter",
                                "seldon_tpu_engine_prefill_fused_positions_total",
                                "of those positions, the ones of from-zero "
                                "prefill calls whose attention ran in the "
                                "fused causal kernel"),
    "prefill_indexed_fused_positions": (
        "counter", "seldon_tpu_engine_prefill_indexed_fused_positions_total",
        "of those positions, the ones of from-zero prefill calls whose "
        "indexed layers attended in the fused causal kernel under the "
        "selection's mask (0 for a model without an indexer)"),
    "decode_kv_tokens": ("counter",
                         "seldon_tpu_engine_decode_kv_tokens_total",
                         "cached tokens attended, summed over every "
                         "decode step of every lane that ran"),
    "decode_lane_steps": ("counter",
                          "seldon_tpu_engine_decode_lane_steps_total",
                          "decode steps run, summed over lanes"),
    "decode_page_slots": ("counter",
                          "seldon_tpu_engine_decode_page_slots_total",
                          "block-table slots handed to the decode "
                          "attention: steps launched x lanes x table "
                          "width, summed over length buckets"),
    "decode_live_pages": ("counter",
                          "seldon_tpu_engine_decode_live_pages_total",
                          "KV pages the decode steps' lanes held: "
                          "ceil(cached / page_size) summed over lane-steps"),
    "waves_overlapped": ("counter",
                         "seldon_tpu_engine_waves_overlapped_total",
                         "decode chunks enqueued while an earlier wave's "
                         "tokens were still unread (of chunks_total): the "
                         "device found them queued when that wave ended"),
    "queue_wait_s": ("counter", "seldon_tpu_engine_queue_wait_seconds_total",
                     "seconds streams spent in the engine's queue, "
                     "submit to first prefill slice"),
    "queue_waits": ("counter", "seldon_tpu_engine_queue_waits_total",
                    "streams whose queue wait was counted"),
    "ingress_wait_s": ("counter",
                       "seldon_tpu_engine_ingress_wait_seconds_total",
                       "seconds between a streaming handler's entry and "
                       "engine.submit (the wait for an executor thread)"),
    "ingress_waits": ("counter", "seldon_tpu_engine_ingress_waits_total",
                      "submits that carried a handler entry stamp"),
    "host_gap_s": ("counter", "seldon_tpu_engine_host_gap_seconds_total",
                   "seconds the engine had work and nothing in flight: "
                   "last readback of a wave to the return of the next "
                   "dispatch (blind wherever a chunk is enqueued ahead: "
                   "see seldon_tpu_engine_device_idle_seconds_total)"),
    # the device on the engine's own clock (PR 50): a completion stamp a
    # dispatched program of the wave loop, busy + idle = first enqueue
    # to last completion; the idle is the labelled DEVICE_IDLE_METRIC
    # below, by where the engine thread was
    "device_busy_s": ("counter",
                      "seldon_tpu_engine_device_busy_seconds_total",
                      "seconds the device ran the wave loop's programs, "
                      "from each one's start (its enqueue, or the "
                      "completion before it) to its completion stamp"),
    "device_programs": ("counter",
                        "seldon_tpu_engine_device_programs_total",
                        "dispatched programs of the wave loop whose "
                        "completion was stamped"),
    "xla_compiles": ("counter", "seldon_tpu_engine_xla_compiles_total",
                     "backend (XLA) compiles of the process since the "
                     "engine was built, an eager operation's included"),
    "xla_compile_s": ("counter",
                      "seldon_tpu_engine_xla_compile_seconds_total",
                      "seconds of those compiles"),
    # the host half on the engine's own clock (monotonic stamps, closed
    # at readbacks): the engine thread at work / blocked in a readback,
    # a request's way to its first token and from there to its finish,
    # a token event's way from the harvest to the transport's write
    "host_work_s": ("counter", "seldon_tpu_engine_host_work_seconds_total",
                    "seconds of the engine thread in the wave loop's "
                    "phases other than wait (admit, prefill, launch, "
                    "harvest, record): the host's work"),
    "host_wait_s": ("counter", "seldon_tpu_engine_host_wait_seconds_total",
                    "seconds the engine thread was blocked in a wave's "
                    "readback: the device set the pace"),
    "ttft_s": ("counter", "seldon_tpu_engine_ttft_seconds_total",
               "seconds from a request's ingress stamp (else its submit) "
               "to the harvest that held its first token"),
    "ttfts": ("counter", "seldon_tpu_engine_ttfts_total",
              "streams whose first token was counted"),
    "first_token_s": ("counter",
                      "seldon_tpu_engine_first_token_seconds_total",
                      "seconds from a stream's admission (first prefill "
                      "slice) to the harvest that held its first token: "
                      "its own wave, prefill and chunk"),
    "first_tokens": ("counter", "seldon_tpu_engine_first_tokens_total",
                     "admitted streams whose first token was counted"),
    "decode_stream_s": ("counter",
                        "seldon_tpu_engine_decode_stream_seconds_total",
                        "seconds from a stream's first token to its "
                        "finish, summed as streams finish"),
    "decode_stream_tokens": ("counter",
                             "seldon_tpu_engine_decode_stream_tokens_total",
                             "tokens after the first of finished streams "
                             "(with decode_stream_seconds_total: the "
                             "time per token a stream saw)"),
    "deliver_lag_s": ("counter",
                      "seldon_tpu_engine_deliver_lag_seconds_total",
                      "seconds from a token event's push at the harvest "
                      "to the return of the transport's write"),
    "deliveries": ("counter", "seldon_tpu_engine_deliveries_total",
                   "token events written to a consumer"),
    "deliveries_behind": ("counter",
                          "seldon_tpu_engine_deliveries_behind_total",
                          "token events picked up with their stream's "
                          "next event already queued: the consumer was "
                          "a whole wave behind"),
    # disaggregated prefill/decode (r15): the KV-page handoff lane
    "kv_exports": ("counter", "seldon_tpu_engine_kv_exports_total",
                   "prefills exported as KV-page handoff payloads "
                   "(prefill-worker role)"),
    "kv_imports": ("counter", "seldon_tpu_engine_kv_imports_total",
                   "KV-page payloads scatter-written into this pool "
                   "(decode-worker role)"),
    # multi-LoRA weight multiplexing (r16): adapter pool-slot churn +
    # submit-time residency — the AdapterThrash alert reads the
    # eviction/hit-rate pair exactly like PrefixCacheThrash reads the
    # prefix pair
    "adapter_loads": ("counter", "seldon_tpu_engine_adapter_loads_total",
                      "adapters installed into the engine's factor pool "
                      "(cold loads + explicit warm-ups)"),
    "adapter_evictions": ("counter",
                          "seldon_tpu_engine_adapter_evictions_total",
                          "refcount-0 adapters LRU-reclaimed from the "
                          "factor pool to make room for a cold load"),
    "adapter_hits": ("counter", "seldon_tpu_engine_adapter_hits_total",
                     "adapter-carrying submits that found their adapter "
                     "resident in the pool"),
    "adapter_misses": ("counter", "seldon_tpu_engine_adapter_misses_total",
                       "adapter-carrying submits that had to cold-load "
                       "through the weight registry"),
    "multi_adapter_chunks": ("counter",
                             "seldon_tpu_engine_multi_adapter_chunks_total",
                             "engine waves whose runnable lanes mixed >= 2 "
                             "distinct adapter slots — served by ONE "
                             "grouped-matmul program, never per-adapter "
                             "lanes"),
    # self-healing lifecycle (r12): drain/handoff observability — a
    # drained engine journals its live streams for a respawned engine
    # to replay through the ordinary submit path
    "drained": ("counter", "seldon_tpu_engine_drained_total",
                "live streams journaled (and error-terminated) by an "
                "engine drain for handoff to a respawned engine"),
    "replayed": ("counter", "seldon_tpu_engine_replayed_total",
                 "journaled streams re-submitted into this engine "
                 "(the restore half of drain/handoff)"),
    # live migration + poison quarantine (r17): watchdog-driven
    # failover observability — an evacuating engine's streams move to
    # peers WITHOUT losing a token, and numerically-poisoned streams
    # retire alone instead of killing their wave
    "migrated_out": ("counter", "seldon_tpu_engine_migrated_out_total",
                     "mid-decode streams live-exported to a peer engine "
                     "(KV pages + cursors + RNG state)"),
    "migrated_in": ("counter", "seldon_tpu_engine_migrated_in_total",
                    "migrated streams imported and resumed at the exact "
                    "next token on this engine"),
    "quarantined": ("counter", "seldon_tpu_engine_quarantined_total",
                    "streams retired by the post-chunk NaN/Inf screen "
                    "(500 NUMERIC_POISON, wave-mates unaffected)"),
    "watchdog_trips": ("counter", "seldon_tpu_engine_watchdog_trips_total",
                       "healthy -> degraded transitions of the device-"
                       "health watchdog"),
    # SLO lifecycle (r10): the overload/degradation observability —
    # GoodputCollapse alerts and the generation dashboard's SLO panel
    # read these
    "shed": ("counter", "seldon_tpu_engine_shed_total",
             "streams dropped by the bounded queue's shedding policy"),
    "expired": ("counter", "seldon_tpu_engine_expired_total",
                "streams whose end-to-end deadline expired "
                "(queued or mid-decode)"),
    "preempted": ("counter", "seldon_tpu_engine_preempted_total",
                  "streams preemptively evicted for a higher-priority "
                  "admission"),
    "restored": ("counter", "seldon_tpu_engine_restored_total",
                 "preempted streams re-admitted (progress restored)"),
    "chunk_faults": ("counter", "seldon_tpu_engine_chunk_faults_total",
                     "chunk failures contained without fail_all "
                     "(fault injection / graceful degradation)"),
    "active_slots": ("gauge", "seldon_tpu_engine_slot_occupancy",
                     "slots holding a live stream"),
    "queued_streams": ("gauge", "seldon_tpu_engine_queue_depth",
                       "streams waiting for a slot"),
    "pool_pages_used": ("gauge", "seldon_tpu_engine_pool_pages_used",
                        "KV pool pages in use"),
    "pool_pages_total": ("gauge", "seldon_tpu_engine_pool_pages_total",
                         "KV pool pages available"),
    "prefix_pages_cached": ("gauge",
                            "seldon_tpu_engine_prefix_cache_pages_cached",
                            "pages parked on the LRU prefix cache "
                            "(refcount 0, reclaimable on demand)"),
    # tensor-parallel lane (r11): capacity planning reads the PER-SHARD
    # pool residency (the global pool is sliced over heads on the
    # `model` axis, so per-device bytes shrink with the degree)
    "tp_degree": ("gauge", "seldon_tpu_engine_tp_degree",
                  "tensor-parallel degree the engine runs at "
                  "(1 = single-chip)"),
    # 2-D serving mesh (r19): the data-axis degree — replica groups
    # sharing one weight residency, and (seq-shard default) the factor
    # the pool's page dim is spread by for long-context capacity
    "dp_degree": ("gauge", "seldon_tpu_engine_dp_degree",
                  "data-parallel degree the engine runs at "
                  "(1 = single replica group)"),
    "pool_shard_bytes": ("gauge", "seldon_tpu_engine_pool_shard_bytes",
                         "K+V pool bytes ONE device holds (per-shard "
                         "under tensor parallelism, full pool at tp=1)"),
    "chunk_token_budget": ("gauge", "seldon_tpu_engine_chunk_token_budget",
                           "token budget one engine wave may carry "
                           "(0 = monolithic prefill)"),
    "adapters_resident": ("gauge", "seldon_tpu_engine_adapters_resident",
                          "adapters resident in the factor pool "
                          "(pinned + LRU-cached slots)"),
    "adapter_slots": ("gauge", "seldon_tpu_engine_adapter_slots",
                      "adapter slots the factor pool was built with "
                      "(0 = multi-LoRA off)"),
    "health_state": ("gauge", "seldon_tpu_engine_health_state",
                     "device-health watchdog state (0 = healthy, "
                     "1 = degraded, 2 = evacuating)"),
    "kernel_active": ("gauge", "seldon_tpu_engine_kernel_active",
                      "decode lane actually running (1 = fused Pallas "
                      "paged-decode kernel, 0 = XLA gather fallback)"),
    "kv_dtype_int8": ("gauge", "seldon_tpu_engine_kv_dtype_int8",
                      "KV pool element type (1 = int8 pages with "
                      "per-page scales, 0 = native compute dtype)"),
    # per-request cost ledger (r20): work attribution totals, accrued
    # exactly once per stream at termination (finish/fail/shed/export).
    # page_seconds is the KV occupancy INTEGRAL (pages x wall seconds,
    # stamped at every page-count change), the capacity quantity a
    # tenant's bill prices — tokens alone can't see a stream that sat
    # on pages.  Keys absent when SELDON_TPU_TELEMETRY=0 (the bridge
    # must export no new series on the off lane).
    "cost_page_seconds": ("counter",
                          "seldon_tpu_engine_cost_page_seconds_total",
                          "KV page-seconds consumed by terminated "
                          "streams (occupancy integral)"),
    "cost_prefill_tokens": ("counter",
                            "seldon_tpu_engine_cost_prefill_tokens_total",
                            "prompt tokens attributed to terminated "
                            "streams by the cost ledger"),
    "cost_decode_tokens": ("counter",
                           "seldon_tpu_engine_cost_decode_tokens_total",
                           "decode tokens attributed to terminated "
                           "streams by the cost ledger"),
    # per-request black-box capture plane (r21).  Keys absent when
    # SELDON_TPU_CAPTURE=0 (default off — the bridge must export no
    # new series on the off lane, same contract as the cost keys).
    "captures": ("counter", "seldon_tpu_engine_captures_total",
                 "request capture containers written to the bounded "
                 "on-disk store (sample/error/breach triggers)"),
    "capture_store_bytes": ("gauge",
                            "seldon_tpu_engine_capture_store_bytes",
                            "on-disk footprint of the bounded request "
                            "capture store (LRU-evicted by bytes)"),
    # hierarchical KV tier (r22).  Keys absent when
    # SELDON_TPU_KV_OFFLOAD=0 (default off — no new series on the off
    # lane, same contract as the capture keys).  The KvTierThrash
    # alert reads the demotion rate against the host/disk hit share
    # exactly like PrefixCacheThrash reads the prefix pair.
    "kv_tier_demotions": ("counter",
                          "seldon_tpu_engine_kv_tier_demotions_total",
                          "LRU-reclaimed prefix pages demoted into the "
                          "host KV tier instead of discarded"),
    "kv_tier_promotions": ("counter",
                           "seldon_tpu_engine_kv_tier_promotions_total",
                           "admissions whose chain walk promoted >= 1 "
                           "tier page back into HBM via the scatter "
                           "import"),
    "kv_tier_host_hits": ("counter",
                          "seldon_tpu_engine_kv_tier_host_hits_total",
                          "tier pages promoted from the host-RAM level"),
    "kv_tier_disk_hits": ("counter",
                          "seldon_tpu_engine_kv_tier_disk_hits_total",
                          "tier pages promoted from the disk spill level"),
    "kv_tier_misses": ("counter",
                       "seldon_tpu_engine_kv_tier_misses_total",
                       "uncached full prompt pages the tier ALSO missed "
                       "(they re-prefilled — the hit-rate denominator's "
                       "other half)"),
    "kv_tier_evictions": ("counter",
                          "seldon_tpu_engine_kv_tier_evictions_total",
                          "entries the tier byte budgets pushed out of "
                          "host AND disk entirely"),
    "kv_tier_bytes_demoted": ("counter",
                              "seldon_tpu_engine_kv_tier_bytes_demoted_total",
                              "container bytes demoted into the tier"),
    "kv_tier_bytes_promoted": ("counter",
                               "seldon_tpu_engine_kv_tier_bytes_promoted_total",
                               "container bytes promoted back into HBM"),
    "moe_assignments": ("counter", "seldon_tpu_engine_moe_assignments_total",
                        "(token, expert) assignments of real tokens, over "
                        "all layers of a routed model"),
    "moe_active_expert_steps": (
        "counter", "seldon_tpu_engine_moe_active_expert_steps_total",
        "experts hit, summed over the decode (layer, step)s that ran"),
    "moe_layer_steps": ("counter", "seldon_tpu_engine_moe_layer_steps_total",
                        "decode (layer, step)s of a routed model that ran"),
    "moe_local_assignments": (
        "counter", "seldon_tpu_engine_moe_local_assignments_total",
        "assignments that fell to experts this replica holds (a replica "
        "holding a share of an expert-parallel layer; 0 otherwise)"),
    "moe_held_active_expert_steps": (
        "counter", "seldon_tpu_engine_moe_held_active_expert_steps_total",
        "held experts hit, summed over the decode (routed layer, step)s "
        "that ran"),
    "moe_zero_assignments": (
        "counter", "seldon_tpu_engine_moe_zero_assignments_total",
        "picks that fell on identity (zero-computation) experts (a router "
        "that scores them; 0 otherwise)"),
    "moe_routed_tokens": (
        "counter", "seldon_tpu_engine_moe_routed_tokens_total",
        "(token, layer)s routed by a router that scores identity experts"),
    "moe_few_real_tokens": (
        "counter", "seldon_tpu_engine_moe_few_real_tokens_total",
        "(token, layer)s that chose at most a third of their picks among "
        "the real experts"),
    "moe_many_real_tokens": (
        "counter", "seldon_tpu_engine_moe_many_real_tokens_total",
        "(token, layer)s that chose all or all but one of their picks "
        "among the real experts"),
    "latent_kv_tokens": ("counter",
                         "seldon_tpu_engine_latent_kv_tokens_total",
                         "cached latent rows read by decode lane-steps, "
                         "over all layers (a latent pool; 0 otherwise)"),
    # a residual of several rows (PR 45, ops/hyper.py): the positions its
    # mixing ran; 0 on any other engine
    "hyper_prefill_positions": (
        "counter", "seldon_tpu_engine_hyper_prefill_positions_total",
        "padded positions x mixed sub-layers the prefill calls ran (a "
        "residual of several rows; 0 otherwise)"),
    "hyper_decode_positions": (
        "counter", "seldon_tpu_engine_hyper_decode_positions_total",
        "lanes x mixed sub-layers the decode steps ran (a residual of "
        "several rows; 0 otherwise)"),
    # linear-attention layers (PR 48, ops/delta.py): the state a lane's
    # work and size; 0 on any other engine
    "delta_lane_steps": (
        "counter", "seldon_tpu_engine_delta_lane_steps_total",
        "decode lane-steps x linear-attention layers: state updates run "
        "(0 without such layers)"),
    "delta_prefill_positions": (
        "counter", "seldon_tpu_engine_delta_prefill_positions_total",
        "padded positions x linear-attention layers the prefill calls "
        "scanned"),
    "delta_prefill_real_positions": (
        "counter", "seldon_tpu_engine_delta_prefill_real_positions_total",
        "real prompt positions x linear-attention layers the prefill calls "
        "scanned"),
    "delta_scan_kernel_positions": (
        "counter", "seldon_tpu_engine_delta_scan_kernel_positions_total",
        "padded positions x linear-attention layers whose prefill scan ran "
        "in the kernel delta_chunk_scan (the rest took XLA's form)"),
    "delta_state_bytes": (
        "gauge", "seldon_tpu_engine_delta_state_bytes",
        "bytes every slot's linear-attention state takes as it rests"),
    "delta_slots_live": (
        "gauge", "seldon_tpu_engine_delta_slots_live",
        "slots whose linear-attention state belongs to a live stream"),
    # state-space layers (PR 54, ops/ssm.py): the other recurrence's
    # work and size; 0 on any other engine, as the delta_* ones are 0 here
    "ssm_lane_steps": (
        "counter", "seldon_tpu_engine_ssm_lane_steps_total",
        "decode lane-steps x state-space layers: state updates run (0 "
        "without such layers)"),
    "ssm_prefill_positions": (
        "counter", "seldon_tpu_engine_ssm_prefill_positions_total",
        "padded positions x state-space layers the prefill calls scanned"),
    "ssm_prefill_real_positions": (
        "counter", "seldon_tpu_engine_ssm_prefill_real_positions_total",
        "real prompt positions x state-space layers the prefill calls "
        "scanned"),
    "ssm_state_bytes": (
        "gauge", "seldon_tpu_engine_ssm_state_bytes",
        "bytes every slot's state-space state takes as it rests"),
    "ssm_slots_live": (
        "gauge", "seldon_tpu_engine_ssm_slots_live",
        "slots whose state-space state belongs to a live stream"),
    "hyper_streams": (
        "gauge", "seldon_tpu_engine_hyper_streams",
        "rows of a token's residual (0: the one row of every other arch)"),
    "hyper_sinkhorn_iters": (
        "gauge", "seldon_tpu_engine_hyper_sinkhorn_iters",
        "Sinkhorn iterations of each mixed sub-layer (0: one row)"),
    # a spec with layer kinds (PR 38): its selection's and its windows'
    # reads, and the two allocators' pages; 0 on any other engine
    "index_keys_scored": (
        "counter", "seldon_tpu_engine_index_keys_scored_total",
        "cached indexer keys scored by decode lane-steps, over the full "
        "layers"),
    "sparse_rows_read": (
        "counter", "seldon_tpu_engine_sparse_rows_read_total",
        "cached rows the full layers' decode attention read (the chosen "
        "ones where a bucket selects)"),
    "sparse_rows_cached": (
        "counter", "seldon_tpu_engine_sparse_rows_cached_total",
        "rows cached for the lane-steps sparse_rows_read counts"),
    "sparse_lane_steps": (
        "counter", "seldon_tpu_engine_sparse_lane_steps_total",
        "decode lane-steps holding index_topk cached rows or more"),
    "window_rows_read": (
        "counter", "seldon_tpu_engine_window_rows_read_total",
        "cached rows the window layers' decode attention read"),
    "sparse_rows_moved": (
        "counter", "seldon_tpu_engine_sparse_rows_moved_total",
        "cached rows the page loop streamed under a selection's mask "
        "(over sparse_rows_read: what a page-skipping kernel could save)"),
    # grouped-query heads over K/V pools of kinds (PR 41): what decode
    # read of the cache and what it would read with no window; 0 on any
    # other engine
    "gqa_kv_rows_read": (
        "counter", "seldon_tpu_engine_gqa_kv_rows_read_total",
        "cached K/V rows decode lane-steps read, over the layers (a full "
        "layer's every cached row, a window layer's live ones)"),
    "gqa_kv_rows_cached": (
        "counter", "seldon_tpu_engine_gqa_kv_rows_cached_total",
        "K/V rows cached for the lane-steps gqa_kv_rows_read counts, "
        "over the layers (what they would read with no window)"),
    "window_pages_released": (
        "counter", "seldon_tpu_engine_window_pages_released_total",
        "window-layer pages given back to their allocator behind the "
        "window"),
    "full_pages_held": (
        "gauge", "seldon_tpu_engine_full_pages_held",
        "pages out of the block table's allocator (a cache of row kinds)"),
    "window_pages_held": (
        "gauge", "seldon_tpu_engine_window_pages_held",
        "pages out of the window layers' allocator"),
    "window_pages_total": (
        "gauge", "seldon_tpu_engine_window_pool_pages",
        "pages the window layers' allocator has"),
    "moe_held_pass_rows": (
        "gauge", "seldon_tpu_engine_moe_held_pass_rows",
        "rows one pass of a decode step's held experts computes (a "
        "replica holding a share of an expert-parallel layer; 0 otherwise)"),
    "prefill_held_rows": (
        "counter", "seldon_tpu_engine_prefill_held_rows_total",
        "rows the held-experts passes of prefill calls computed (passes a "
        "routed layer x the program's rows a pass; by the call's routing "
        "histogram, which leaves pad positions out)"),
    "prefill_held_local": (
        "counter", "seldon_tpu_engine_prefill_held_local_total",
        "local assignments those passes were for: over prefill_held_rows, "
        "how full a pass is"),
    "prefill_held_extra_passes": (
        "counter", "seldon_tpu_engine_prefill_held_extra_passes_total",
        "held-experts passes of prefill calls beyond a layer's first"),
    "prefill_expert_layer_calls": (
        "counter", "seldon_tpu_engine_prefill_expert_layer_calls_total",
        "routed layers of the prefill calls dispatched (0 for a dense "
        "model)"),
    "prefill_expert_layer_calls_tiled": (
        "counter", "seldon_tpu_engine_prefill_expert_layer_calls_tiled_total",
        "of those, the layers of programs whose grouped expert matmuls "
        "run in the tiled kernel (ops/moe.py): over "
        "prefill_expert_layer_calls, the lane's engagement"),
    "moe_load_max": ("gauge", "seldon_tpu_engine_moe_load_max",
                     "cumulative assignments of the busiest (layer, "
                     "expert) pair"),
    "moe_load_mean": ("gauge", "seldon_tpu_engine_moe_load_mean",
                      "mean cumulative assignments per (layer, expert) "
                      "pair"),
    "kv_tier_host_bytes": ("gauge",
                           "seldon_tpu_engine_kv_tier_host_bytes",
                           "live container bytes parked in the tier's "
                           "host-RAM level"),
    "kv_tier_disk_bytes": ("gauge",
                           "seldon_tpu_engine_kv_tier_disk_bytes",
                           "live container bytes parked in the tier's "
                           "disk spill level"),
}

# keys intentionally NOT exported as their own series: the wall-clock
# accumulators feed the chunk-duration HISTOGRAM (via the flight
# recorder's per-chunk records) — exporting the sums next to it would
# double-count the same signal under a non-canonical name;
# jit_compiles is exported by utils/jitwatch.py itself as
# seldon_tpu_jit_compiles_total{program=...} (per-program labels the
# summed stat can't carry); adapter_requests is a name->count dict the
# bridge exports itself as
# seldon_tpu_engine_adapter_requests_total{adapter=...} (per-adapter
# labels the flat mapping can't carry)
# "health" is the state STRING twin of the health_state gauge — the
# debug surfaces read it, prometheus reads the numeric code;
# cost_by_adapter is an adapter->totals dict the bridge exports itself
# with adapter labels (COST_LEDGER_METRICS below — the flat mapping
# can't carry labels, same shape as adapter_requests)
# clock_s is the snapshot's own time.monotonic(): what a reader of two
# snapshots divides by, no series
# device_idle_by_s is a where->seconds dict the bridge exports itself as
# seldon_tpu_engine_device_idle_seconds_total{where=...}; device_idle_s
# is that series summed over where
ENGINE_STATS_EXCLUDED = {"chunk_wall_s", "clock_s", "jit_compiles",
                         "adapter_requests", "health", "cost_by_adapter",
                         "device_idle_by_s", "device_idle_s"}

ADAPTER_REQUESTS_METRIC = "seldon_tpu_engine_adapter_requests_total"

DEVICE_IDLE_METRIC = "seldon_tpu_engine_device_idle_seconds_total"

CHUNK_DURATION_METRIC = "seldon_tpu_engine_chunk_duration_seconds"

# cost_by_adapter field -> (kind, canonical metric name, doc): the
# per-adapter labeled split of the cost_* counters above.  COMPLETE BY
# CONTRACT like the flat mapping (graftlint's metrics-contract checker
# verifies naming; the per-adapter sums must equal the flat totals —
# tests/test_telemetry.py asserts it).
COST_LEDGER_METRICS: Dict[str, Tuple[str, str, str]] = {
    "page_seconds": ("counter",
                     "seldon_tpu_engine_cost_adapter_page_seconds_total",
                     "KV page-seconds by adapter (base = no adapter)"),
    "prefill_tokens": ("counter",
                       "seldon_tpu_engine_cost_adapter_prefill_tokens_total",
                       "prompt tokens by adapter"),
    "decode_tokens": ("counter",
                      "seldon_tpu_engine_cost_adapter_decode_tokens_total",
                      "decode tokens by adapter"),
    "streams": ("counter",
                "seldon_tpu_engine_cost_adapter_streams_total",
                "terminated streams by adapter"),
}


def _trace_exemplar() -> Optional[Dict[str, str]]:
    """OpenMetrics exemplar payload for the active trace, or None when
    telemetry is off / no span is active.  Exemplars ride histogram
    observations on the hot lanes (chunk duration, transport hops) so a
    latency bucket links back to ONE real request's trace id."""
    from seldon_core_tpu.utils import telemetry as _telemetry

    if not _telemetry.telemetry_enabled():
        return None
    from seldon_core_tpu.utils.tracing import current_span

    span = current_span()
    tid = getattr(span, "trace_id", "") if span is not None else ""
    if not tid:
        return None
    # OpenMetrics caps exemplar label runes at 128 total
    return {"trace_id": str(tid)[:100]}


class GenerationPrometheusBridge:
    """PagedEngine stats + flight-recorder records -> canonical
    Prometheus metrics, through the same ``_MetricCache`` machinery the
    graph-layer observer uses (shared registry safe: two engines in one
    process share metric objects and differ only in label values).

    Call :meth:`collect` periodically (StreamingLM's decode loop does);
    cumulative engine counters are exported as true Prometheus counters
    by diffing against the previous collect (an engine replacement /
    counter reset re-baselines instead of inc()-ing garbage), gauges are
    set directly, and the recorder's per-chunk wall times feed the
    ``seldon_tpu_engine_chunk_duration_seconds`` histogram incrementally
    by record seq — each chunk is observed exactly once.
    """

    def __init__(
        self,
        engine,
        deployment_name: str = "",
        predictor_name: str = "",
        model_name: str = "",
        registry=None,
    ):
        self.engine = engine
        self._labels = {
            "deployment_name": deployment_name,
            "predictor_name": predictor_name,
            "model_name": model_name,
        }
        self._names = tuple(sorted(self._labels))
        self._cache = _cache_for(registry)
        self._last: Dict[str, float] = {}
        self._last_seq = 0

    def _metric(self, kind: str, name: str, doc: str = ""):
        return self._cache.get(kind, name, self._names, doc).labels(**self._labels)

    def _advance(self, key: str, value) -> float:
        """What cumulative ``value`` grew by since the last collect (a
        counter that went back was reset: the bridge rebases on it)."""
        prev = self._last.get(key, 0.0)
        cur = float(value)
        self._last[key] = cur
        return cur - prev if cur >= prev else cur

    def collect(self) -> None:
        """Never raises — the bridge must not take the decode loop down."""
        try:
            self._collect()
        except Exception:  # noqa: BLE001 — the bridge never takes the decode loop down
            logger.exception("generation prometheus bridge collect failed")

    def _collect(self) -> None:
        stats = self.engine.engine_stats()
        # per-adapter request rate (r16): labeled export the flat
        # mapping can't carry — same counter-delta discipline, one
        # child per adapter name
        for adapter, count in (stats.get("adapter_requests") or {}).items():
            delta = self._advance(f"adapter_requests:{adapter}", count)
            if delta > 0:
                labels = dict(self._labels, adapter=adapter)
                self._cache.get(
                    "counter", ADAPTER_REQUESTS_METRIC,
                    tuple(sorted(labels)),
                    "adapter-carrying requests submitted, by adapter name",
                ).labels(**labels).inc(delta)
        # the device's idle seconds by where the engine thread was
        # (PR 50): one child a phase of the seam
        for where, seconds in (stats.get("device_idle_by_s") or {}).items():
            delta = self._advance(f"device_idle:{where}", seconds)
            if delta > 0:
                labels = dict(self._labels, where=where)
                self._cache.get(
                    "counter", DEVICE_IDLE_METRIC, tuple(sorted(labels)),
                    "seconds the device sat between two programs of the "
                    "wave loop, by where the engine thread was (no_work: "
                    "no stream admitted or queued)",
                ).labels(**labels).inc(delta)
        # per-adapter cost attribution (r20): labeled export of the
        # ledger's adapter split — same counter-delta discipline.  The
        # key is absent entirely when SELDON_TPU_TELEMETRY=0, so the
        # off lane exports no cost series at all.
        for adapter, fields in (stats.get("cost_by_adapter") or {}).items():
            for field, spec in COST_LEDGER_METRICS.items():
                kind, name, doc = spec
                delta = self._advance(
                    f"cost_adapter:{adapter}:{field}", fields.get(field, 0.0))
                if delta > 0:
                    labels = dict(self._labels, adapter=adapter)
                    self._cache.get(
                        kind, name, tuple(sorted(labels)), doc,
                    ).labels(**labels).inc(delta)
        for key, value in stats.items():
            spec = ENGINE_STATS_METRICS.get(key)
            if spec is None:
                continue  # contract-tested: unmapped => in the exclusion set
            kind, name, doc = spec
            metric = self._metric(kind, name, doc)
            if kind == "gauge":
                metric.set(float(value))
            else:
                delta = self._advance(key, value)
                if delta > 0:
                    metric.inc(delta)
        recorder = getattr(self.engine, "recorder", None)
        if recorder is not None:
            hist = self._metric(
                "histogram", CHUNK_DURATION_METRIC,
                "wall time of one decode/verify chunk program",
            )
            for rec in recorder.since(self._last_seq):
                self._last_seq = max(self._last_seq, rec["seq"])
                # trace exemplar (r20): the chunk record carries the
                # trace id of one traced stream in its wave (telemetry-
                # gated at the engine) — an OpenMetrics scrape links
                # the latency bucket to a real request
                tid = str(rec.get("trace_id", "") or "")
                hist.observe(
                    float(rec.get("wall_ms", 0.0)) / 1000.0,
                    exemplar={"trace_id": tid[:100]} if tid else None,
                )
            self._metric(
                "gauge", "seldon_tpu_engine_chunk_p99_ms",
                "chunk-wall p99 over the flight recorder window",
            ).set(float(recorder.stats()["chunk_p99_ms"]))


# ---------------------------------------------------------------------------
# fleet telemetry bridge (controlplane/fleetview.py -> seldon_tpu_fleet_*)
# ---------------------------------------------------------------------------

# TelemetryAggregator.fleet_rollup() key -> (kind, metric name, doc).
# COMPLETE BY CONTRACT like the engine bridge: every rollup key must
# appear here or in FLEET_EXCLUDED (graftlint metrics-contract
# GL406/GL407), so a new fleet aggregate cannot silently skip export.
# All gauges: the rollup is a point-in-time merge, re-summed per poll.
FLEET_METRICS: Dict[str, Tuple[str, str, str]] = {
    "replicas_total": ("gauge", "seldon_tpu_fleet_replicas",
                       "replica endpoints the aggregator polls"),
    "replicas_ok": ("gauge", "seldon_tpu_fleet_replicas_ok",
                    "replicas with a fresh telemetry snapshot"),
    "replicas_stale": ("gauge", "seldon_tpu_fleet_replicas_stale",
                       "replicas whose last snapshot aged past the "
                       "staleness window (not crashed — unpolled)"),
    "replicas_incompatible": ("gauge",
                              "seldon_tpu_fleet_replicas_incompatible",
                              "replicas answering with a future/invalid "
                              "telemetry schema"),
    "fleet_queue_depth": ("gauge", "seldon_tpu_fleet_queue_depth",
                          "queued streams across ok replicas"),
    "fleet_active_slots": ("gauge", "seldon_tpu_fleet_active_slots",
                           "live decode slots across ok replicas"),
    "fleet_slots_total": ("gauge", "seldon_tpu_fleet_slot_capacity",
                          "decode slot capacity across ok replicas"),
    "fleet_goodput_tok_s": ("gauge", "seldon_tpu_fleet_goodput_tok_s",
                            "decode tokens/s served across ok replicas"),
    "fleet_prefill_tok_s": ("gauge", "seldon_tpu_fleet_prefill_tok_s",
                            "prefill tokens/s across ok replicas"),
    "fleet_completed_s": ("gauge", "seldon_tpu_fleet_completed_s",
                          "streams completed/s across ok replicas"),
    "fleet_shed_s": ("gauge", "seldon_tpu_fleet_shed_s",
                     "streams shed/s across ok replicas"),
    "fleet_preempted_s": ("gauge", "seldon_tpu_fleet_preempted_s",
                          "streams preempted/s across ok replicas"),
    "fleet_migrated_out_s": ("gauge", "seldon_tpu_fleet_migrated_out_s",
                             "streams live-migrated/s across ok replicas"),
    "fleet_pool_pages_used": ("gauge", "seldon_tpu_fleet_pool_pages_used",
                              "KV pool pages in use across ok replicas"),
    "fleet_pool_pages_total": ("gauge", "seldon_tpu_fleet_pool_page_capacity",
                               "KV pool page capacity across ok replicas"),
    "fleet_cost_page_s_s": ("gauge", "seldon_tpu_fleet_cost_page_s_s",
                            "KV page-seconds accrued per second across "
                            "ok replicas (cost ledger burn rate)"),
    "fleet_prefix_hit_pct": ("gauge", "seldon_tpu_fleet_prefix_hit_pct",
                             "mean prefix-cache hit % across ok replicas"),
    "fleet_saturation_max": ("gauge", "seldon_tpu_fleet_saturation_max",
                             "worst replica saturation score [0,1] — the "
                             "FleetReplicaSaturated alert reads this"),
    "fleet_saturation_mean": ("gauge", "seldon_tpu_fleet_saturation_mean",
                              "mean replica saturation score [0,1]"),
    "fleet_chunk_p99_ms": ("gauge", "seldon_tpu_fleet_chunk_p99_ms",
                           "worst per-replica chunk-wall p99 (ms)"),
    "fleet_predict_cost_s_max": ("gauge",
                                 "seldon_tpu_fleet_predict_cost_s_max",
                                 "worst predicted service seconds for a "
                                 "nominal request across ok replicas"),
    "fleet_kv_tier_host_bytes": ("gauge",
                                 "seldon_tpu_fleet_kv_tier_host_bytes",
                                 "demoted KV bytes parked in host RAM "
                                 "across ok replicas (r22 KV tier)"),
    "fleet_kv_tier_hit_rate": ("gauge",
                               "seldon_tpu_fleet_kv_tier_hit_rate",
                               "mean KV-tier promote hit rate [0,1] "
                               "across replicas running the tier"),
}

# rollup keys not exported as their own series ("t" is the poll stamp)
FLEET_EXCLUDED = {"t"}

FLEET_REPLICA_SATURATION_METRIC = "seldon_tpu_fleet_replica_saturation"
FLEET_REPLICA_STATE_METRIC = "seldon_tpu_fleet_replica_state"

# replica freshness encoding for the per-replica state gauge
FLEET_STATE_CODES = {"ok": 0, "stale": 1, "incompatible": 2, "never": 3}


class FleetPrometheusBridge:
    """TelemetryAggregator fleet view -> ``seldon_tpu_fleet_*`` gauges,
    collected after every poll (the aggregator calls :meth:`collect`
    when attached as its ``bridge``).  Complete-by-contract against
    FLEET_METRICS/FLEET_EXCLUDED; per-replica saturation and state
    export with a ``replica`` label the flat rollup can't carry."""

    def __init__(self, aggregator, registry=None):
        self.aggregator = aggregator
        self._cache = _cache_for(registry)

    def collect(self) -> None:
        """Never raises — the bridge must not take the poll loop down."""
        try:
            self._collect()
        except Exception:  # noqa: BLE001 — same discipline as the engine bridge
            logger.exception("fleet prometheus bridge collect failed")

    def _collect(self) -> None:
        rollup = self.aggregator.fleet_rollup()
        for key, value in rollup.items():
            spec = FLEET_METRICS.get(key)
            if spec is None:
                continue  # contract-tested: unmapped => in FLEET_EXCLUDED
            kind, name, doc = spec
            self._cache.get(kind, name, (), doc).set(float(value))
        for replica, row in self.aggregator.replica_states().items():
            self._cache.get(
                "gauge", FLEET_REPLICA_SATURATION_METRIC, ("replica",),
                "per-replica saturation score [0,1]",
            ).labels(replica=replica).set(float(row.get("saturation", 0.0)))
            self._cache.get(
                "gauge", FLEET_REPLICA_STATE_METRIC, ("replica",),
                "replica telemetry freshness (0 ok, 1 stale, "
                "2 incompatible, 3 never polled)",
            ).labels(replica=replica).set(
                FLEET_STATE_CODES.get(row.get("state"), 3)
            )


# ---------------------------------------------------------------------------
# per-hop transport telemetry (engine -> node clients)
# ---------------------------------------------------------------------------

TRANSPORT_LABELS = ("unit", "method", "transport")

# HopRecord field -> (kind, canonical metric name, doc).  COMPLETE BY
# CONTRACT like the engine bridge: every quantitative HopRecord field
# must appear here or in TRANSPORT_RECORD_EXCLUDED
# (tests/test_trace_propagation.py), so a new per-hop measurement
# cannot silently skip Prometheus export.
TRANSPORT_METRICS: Dict[str, Tuple[str, str, str]] = {
    "requests": ("counter", "seldon_tpu_transport_requests_total",
                 "node-client calls issued (one per NodeClient method call)"),
    "errors": ("counter", "seldon_tpu_transport_errors_total",
               "node-client calls that raised after exhausting retries"),
    "retries": ("counter", "seldon_tpu_transport_retries_total",
                "extra attempts beyond the first (REST/gRPC retry loops)"),
    "failovers": ("counter", "seldon_tpu_transport_failovers_total",
                  "replica failovers by BalancedClient"),
    "request_bytes": ("counter", "seldon_tpu_transport_request_bytes_total",
                      "serialized request payload bytes put on the wire"),
    "response_bytes": ("counter", "seldon_tpu_transport_response_bytes_total",
                       "serialized response payload bytes read off the wire"),
    "zero_copy_bytes": ("counter", "seldon_tpu_transport_zero_copy_bytes_total",
                        "payload bytes passed BY REFERENCE on co-located "
                        "hops (buffer views / device handles) — the bytes "
                        "the zero-copy lane did NOT re-encode"),
    "serialize_seconds": ("histogram", "seldon_tpu_transport_serialize_seconds",
                          "encode+decode (codec) share of one hop"),
    "network_seconds": ("histogram", "seldon_tpu_transport_network_seconds",
                        "on-the-wire share of one hop (total - codec)"),
}

# label-shaped fields of HopRecord, not exported as their own series
TRANSPORT_RECORD_EXCLUDED = {"unit", "method", "transport", "error"}

TRANSPORT_INFLIGHT_METRIC = "seldon_tpu_transport_inflight"


def transport_telemetry_enabled() -> bool:
    """SELDON_TPU_TRANSPORT_TELEMETRY=0 turns the per-hop metrics off
    (the bench's trace_prop on/off contrast flips this)."""
    from seldon_core_tpu.runtime import knobs

    return knobs.flag("SELDON_TPU_TRANSPORT_TELEMETRY")


class _BoundHop:
    """Pre-bound metric children for one (unit, method, transport) —
    the label resolution (two lock hops per metric in
    prometheus_client) happens once per hop identity, not once per
    request; a hop record is then a handful of plain inc()/observe()s."""

    __slots__ = tuple(TRANSPORT_METRICS) + ("inflight",)

    def __init__(self, unit: str, method: str, transport: str, registry=None):
        cache = _cache_for(registry)
        labels = {"unit": unit, "method": method, "transport": transport}
        for field, (kind, name, doc) in TRANSPORT_METRICS.items():
            setattr(
                self, field,
                cache.get(kind, name, TRANSPORT_LABELS, doc).labels(**labels),
            )
        self.inflight = cache.get(
            "gauge", TRANSPORT_INFLIGHT_METRIC, TRANSPORT_LABELS,
            "node-client calls currently awaiting a response",
        ).labels(**labels)


_BOUND_HOPS: Dict[Tuple[str, str, str, int], _BoundHop] = {}
_BOUND_HOPS_LOCK = threading.Lock()


def _bound_hop(unit: str, method: str, transport: str, registry=None) -> _BoundHop:
    key = (unit, method, transport, id(registry))
    hop = _BOUND_HOPS.get(key)
    if hop is None:
        with _BOUND_HOPS_LOCK:
            hop = _BOUND_HOPS.get(key)
            if hop is None:
                hop = _BoundHop(unit, method, transport, registry)
                _BOUND_HOPS[key] = hop
    return hop


def record_transport_hop(
    unit: str,
    method: str,
    transport: str,
    *,
    request_bytes: int = 0,
    response_bytes: int = 0,
    zero_copy_bytes: int = 0,
    serialize_seconds: float = 0.0,
    network_seconds: float = 0.0,
    retries: int = 0,
    error: bool = False,
    registry=None,
) -> None:
    """Record one completed NodeClient hop.  Never raises — transport
    telemetry must not take the data plane down."""
    if not transport_telemetry_enabled():
        return
    try:
        hop = _bound_hop(unit, method, transport, registry)
        hop.requests.inc()
        if error:
            hop.errors.inc()
        if retries > 0:
            hop.retries.inc(retries)
        if request_bytes > 0:
            hop.request_bytes.inc(request_bytes)
        if response_bytes > 0:
            hop.response_bytes.inc(response_bytes)
        if zero_copy_bytes > 0:
            hop.zero_copy_bytes.inc(zero_copy_bytes)
        if transport != "local":
            # the local transport has no codec or wire share by design
            # (device payloads pass by handle); observing constant 0.0
            # would poison the histograms' lower buckets.  The wire
            # share carries a trace exemplar (telemetry-gated): the
            # hop runs inside the caller's span, so the active trace
            # IS the request this observation belongs to.
            ex = _trace_exemplar()
            hop.serialize_seconds.observe(max(0.0, serialize_seconds))
            hop.network_seconds.observe(max(0.0, network_seconds), exemplar=ex)
    except Exception:  # noqa: BLE001 — telemetry never fails the hop
        logger.exception("transport telemetry failed for %s/%s", unit, method)


def record_transport_failover(
    unit: str, method: str, transport: str = "balanced", registry=None
) -> None:
    """One replica failover (BalancedClient) — counted separately from
    requests: the failed underlying call already recorded its own hop."""
    if not transport_telemetry_enabled():
        return
    try:
        kind, name, doc = TRANSPORT_METRICS["failovers"]
        _cache_for(registry).get(kind, name, TRANSPORT_LABELS, doc).labels(
            unit=unit, method=method, transport=transport
        ).inc()
    except Exception:  # noqa: BLE001 — telemetry never fails the failover
        logger.exception("transport failover counter failed for %s/%s", unit, method)


def transport_inflight(unit: str, method: str, transport: str, registry=None):
    """The in-flight gauge child for one (unit, method, transport), or
    None when telemetry is off/broken.  Callers inc()/dec() around the
    await so a wedged upstream is visible as a stuck positive gauge."""
    if not transport_telemetry_enabled():
        return None
    try:
        return _bound_hop(unit, method, transport, registry).inflight
    except Exception:  # noqa: BLE001 — telemetry never fails the hop
        logger.exception("transport inflight gauge failed for %s/%s", unit, method)
        return None


# ---------------------------------------------------------------------------
# self-healing telemetry: circuit breakers, hedged requests, workers
# ---------------------------------------------------------------------------

# breaker state encoding for the gauge (alert rules key on it):
# 0 = closed, 1 = half-open, 2 = open
BREAKER_STATE_CODES = {"closed": 0, "half_open": 1, "open": 2}

BREAKER_STATE_METRIC = "seldon_tpu_transport_breaker_state"
BREAKER_TRANSITIONS_METRIC = "seldon_tpu_transport_breaker_transitions_total"
BREAKER_FASTFAIL_METRIC = "seldon_tpu_transport_breaker_fastfail_total"
HEDGES_METRIC = "seldon_tpu_transport_hedges_total"
HEDGE_WINS_METRIC = "seldon_tpu_transport_hedge_wins_total"


def record_breaker_state(endpoint: str, state: str, registry=None) -> None:
    """Set the per-endpoint breaker state gauge + count the transition.
    Called on every state CHANGE (not per call), so the cost is tied to
    incidents, not traffic.  Never raises."""
    if not transport_telemetry_enabled():
        return
    try:
        cache = _cache_for(registry)
        cache.get(
            "gauge", BREAKER_STATE_METRIC, ("endpoint",),
            "circuit-breaker state per endpoint (0 closed, 1 half-open, 2 open)",
        ).labels(endpoint=endpoint).set(BREAKER_STATE_CODES.get(state, 0))
        cache.get(
            "counter", BREAKER_TRANSITIONS_METRIC, ("endpoint", "to"),
            "circuit-breaker state transitions",
        ).labels(endpoint=endpoint, to=state).inc()
    except Exception:  # noqa: BLE001 — telemetry never fails the breaker
        logger.exception("breaker state metric failed for %s", endpoint)


def record_breaker_fastfail(
    unit: str, method: str, transport: str, registry=None
) -> None:
    """One call rejected BEFORE dispatch because its endpoint's breaker
    was open (or half-open past the probe budget).  Never raises."""
    if not transport_telemetry_enabled():
        return
    try:
        _cache_for(registry).get(
            "counter", BREAKER_FASTFAIL_METRIC, TRANSPORT_LABELS,
            "calls fast-failed by an open circuit breaker before dispatch",
        ).labels(unit=unit, method=method, transport=transport).inc()
    except Exception:  # noqa: BLE001 — telemetry never fails the fast-fail
        logger.exception("breaker fastfail counter failed for %s/%s", unit, method)


def record_transport_hedge(
    unit: str, method: str, transport: str, won: bool = False, registry=None
) -> None:
    """One hedge duplicate fired (``won=False``) or one hedge winning
    the race (``won=True`` — counted separately so win rate is a plain
    ratio of two counters).  Never raises."""
    if not transport_telemetry_enabled():
        return
    try:
        cache = _cache_for(registry)
        name, doc = (
            (HEDGE_WINS_METRIC, "hedged duplicates that returned first")
            if won else
            (HEDGES_METRIC, "hedged duplicate requests fired after the "
                            "per-node hedge delay")
        )
        cache.get("counter", name, TRANSPORT_LABELS, doc).labels(
            unit=unit, method=method, transport=transport
        ).inc()
    except Exception:  # noqa: BLE001 — telemetry never fails the hedge
        logger.exception("hedge counter failed for %s/%s", unit, method)


def record_worker_health(
    worker: str, restarts: int, exhausted: bool, registry=None
) -> None:
    """Supervised-worker lifecycle for the alert layer: cumulative
    restart count and the restart-budget-exhausted flag (the silent-dead
    state ``WorkerRestartsExhausted`` alerts on).  Never raises."""
    try:
        cache = _cache_for(registry)
        cache.get(
            "gauge", "seldon_tpu_worker_restarts", ("worker",),
            "restarts performed by the supervisor for this worker",
        ).labels(worker=worker).set(float(restarts))
        cache.get(
            "gauge", "seldon_tpu_worker_exhausted", ("worker",),
            "1 when the worker exceeded its restart budget and the "
            "supervisor gave up (the worker is dead until redeployed)",
        ).labels(worker=worker).set(1.0 if exhausted else 0.0)
    except Exception:  # noqa: BLE001 — metrics never break supervision
        logger.exception("worker health metric failed for %s", worker)


def api_latency_sampler(
    observer: "PrometheusObserver", quantile: float = 0.95, method: str = "predictions"
) -> HistogramQuantileSampler:
    """Quantile sampler over an observer's server-request histogram
    (seconds); multiply by 1000 at the call site for ms targets."""
    labels = {
        "deployment_name": observer.deployment_name,
        "predictor_name": observer.predictor_name,
        "method": method,
        "code": "200",
    }
    hist = observer._cache.get(  # noqa: SLF001 — same module
        "histogram",
        "seldon_api_engine_server_requests_duration_seconds",
        tuple(sorted(labels)),
        "external API request latency",
    )
    return HistogramQuantileSampler(hist.labels(**labels), quantile=quantile)
