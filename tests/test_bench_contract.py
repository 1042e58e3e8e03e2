"""Driver-certification contract for bench.py's output.

The round driver records only the last ~2000 chars of bench stdout and
json-parses the final line (r3's full line outgrew the window and the
round's numbers went uncertified).  These tests pin the contract: the
final line is a compact summary that always fits, carries the scalars
the judge checks (int8, generation, native-model, MFU), and the full
result round-trips through bench_full.json.
"""

import importlib.util
import json
import os

import pytest

_BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _r3_like_full_result():
    """A full result at least as large as the r3 line that broke the
    2000-char tail window, with every phase populated."""
    return {
        "metric": "resnet50_grpc_p50_ms",
        "value": 117.093,
        "unit": "ms",
        "vs_baseline": 0.085,
        "extra": {
            "device": "TPU v5 lite0",
            "served_by": "native-ingress (C++ h2c gRPC fast lane)",
            "setup_s": 32.4,
            "python_grpc_p50_ms": 116.758,
            "inprocess_images_per_s": 3558.4,
            "inprocess_payload": "constant",
            "roofline": {
                "raw_device_images_per_s": 4235.9,
                "staging_s": 4.62,
                "batches": 64,
                "depth": 32,
                "mfu_pct": 8.82,
            },
            "device_loop": {"images_per_s": 21000.0, "mfu_pct": 43.7, "iters": 64},
            "server_latency": {
                "p50_ms": 3.1, "p99_ms": 9.8, "count": 4000,
                "attached_p50_bound_ms": 4.333,
                "attached_p99_bound_ms": 14.048,
                "attached_p99_terms_ms": {
                    "parse": 0.0057, "decode": 0.023, "pad": 0.1458,
                    "queue_wait": 13.45, "forward": 0.213,
                    "serialise": 0.0257,
                },
                "p99_dominant": "queue_wait",
            },
            "inprocess_vs_distinct_roofline": 0.84,
            "native_model": {
                "payload_content": "constant",
                "images_per_s": 96.0,
                "requests_per_s": 12.0,
                "grpc_images_per_s": 92.0,
                "grpc_requests_per_s": 11.5,
                "grpc_p50_ms": None,
                "rows_per_request": 8,
                "connections": 4,
                "client_depth": 4,
                "p50_ms": 111.11,
                "fast_requests": 746,
                "batches": 576,
                "errors": 0,
                "dropped_orphans": 1,
                "vs_python_lane": 1.2,
            },
            "zero_copy": {
                "native_model_qps": 9500.0,
                "zero_copy_off_qps": 3100.0,
                "zero_copy_x": 3.06,
                "bit_exact": True,
                "mix": "1x16 int8 (extension wire dtype -> python lane), "
                       "single-MODEL mlp, 8 conns x depth 4, C++ load "
                       "client, best-of-3 windows/side",
            },
            "stub_engine_qps": 18687.0,
            "stub_vs_reference_grpc": 0.661,
            "native_front_qps": 112147.8,
            "native_vs_reference_grpc": 3.969,
            "native_grpc_qps": 111044.0,
            "native_grpc_vs_reference": 3.93,
            "int8": {
                "fp_images_per_s": 12839.8,
                "int8_images_per_s": 12758.9,
                "int8_vs_fp": 0.99,
                "w8a8_images_per_s": 21000.0,
                "w8a8_vs_fp": 1.64,
                "fp_big_images_per_s": 13000.0,
                "w8a8_big_images_per_s": 24000.0,
                "w8a8_loop_vs_fp": 1.85,
                "w8a8_top1_agree": 0.997,
                "w8a8_mxu_lowered": True,
                "w8a8_vs_a100_triton": 0.62,
                "w8a8_hlo": {"verdict": "int8", "int8_ops": 49,
                             "int_widened_ops": 0, "float_ops": 4,
                             "evidence": ["%convolution = s32[...] convolution(s8[...], s8[...])"]},
            },
            "generation": {
                "decode_tokens_per_s": 8877.5,
                "overall_tokens_per_s": 5149.1,
                "prefill_ms": 84.42,
                "batch": 8,
                "prompt_len": 128,
                "max_new": 128,
                "config": "d512 L8 H8 v16384 bf16",
                "int8_decode_tokens_per_s": 9723.1,
                "int8_vs_fp_decode": 1.1,
                "paged_decode_tokens_per_s": 89.8,
                "paged_serving_tokens_per_s": 4400.0,
                "paged_serving64_tokens_per_s": 16015.6,
                "paged_serving128_tokens_per_s": 28831.6,
                "paged_serving256_tokens_per_s": 30784.0,
                "paged_bimodal_tokens_per_s": 13500.0,
                "paged_bimodal_mix": "64 streams, prompts 32/448 alternating, 384 new tokens each",
                "paged_capacity": {
                    "streams": 220, "ctx_len": 512, "budget_gib": 8.0,
                    "accounting": "donated", "streams_if_copied": 150,
                    "streams_int8_kv": 436, "streams_bf16_pool": 220,
                    "int8_capacity_x": 1.98,
                },
                "kernel_lane": {
                    "hbm_bytes_per_step_bf16": 268435456,
                    "hbm_bytes_per_step_int8": 134742016,
                    "hbm_bytes_x": 1.99,
                    "mosaic_grid_steps": 512,
                    "kernel_tok_s": 6600.0, "xla_tok_s": 4400.0,
                    "int8_kernel_tok_s": 7100.0,
                    "paged_kernel_x": 1.5, "int8_kernel_x": 1.61,
                },
                "paged_tokenwise_tokens_per_s": 12.7,
                "paged_spec_oracle_tokens_per_s": 56.1,
                "spec_oracle_vs_tokenwise": 4.4,
                "spec_oracle_vs_plain_decode": 0.62,
                "tokenwise_chunks": 64,
                "spec_oracle_acceptance": 1.0,
                "spec_ngram_acceptance": 0.541,
                "spec_draft_acceptance": 0.87,
                "spec_oracle_chunks": 13,
                "plain_chunks": 8,
                "obs_overhead_pct": 0.84,
                "obs_on_tokens_per_s": 4363.0,
                "obs_off_tokens_per_s": 4400.0,
                "prefix_shared_tokens_per_s": 7300.0,
                "prefix_off_tokens_per_s": 4400.0,
                "prefix_speedup_x": 1.66,
                "prefix_hit_pct": 100.0,
                "prefix_tokens_saved": 12288,
                "prefix_shared_mix": "16 streams, 256-token shared system prompt + distinct suffixes, 64 new tokens each",
                "kv_tier_promote_x": 4.6,
                "kv_tier_hit_pct": 100.0,
                "kv_tier_on_revisit_ms": 120.4,
                "kv_tier_off_revisit_ms": 553.8,
                "kv_tier_demotions": 7,
                "kv_tier_promotions": 6,
                "kv_tier_resident_delta_pct": -0.8,
                "kv_tier_mix": "2 returning sessions, 512-token history, 4 new tokens/revisit, 9-page pool",
                "paged_tp_tokens_per_s": 8100.0,
                "paged_tp_degree": 4,
                "paged_tp_eff_pct": 46.0,
                "paged_mesh_tokens_per_s": 7400.0,
                "paged_mesh_axes": "2x2 (data x model)",
                "paged_mesh_eff_pct": 42.0,
                "longctx_max_len": 81920,
                "longctx_decode_tokens_per_s": "n/a",
                "longctx": {
                    "ctx_len": 32768, "budget_bytes": 31462400,
                    "shard_peak_bytes": 12584960,
                    "full_peak_bytes": 50339840,
                    "mesh": "dp=2 x tp=2",
                    "admits_single_chip": False, "admits_mesh": True,
                    "max_len_single_chip": 20416,
                },
                "multi_lora_tokens_per_s": 4100.0,
                "multi_lora_resident_tokens_per_s": 4350.0,
                "resident_tok_s_delta_pct": 1.14,
                "multi_lora": {
                    "adapters_registered": 6,
                    "pool_slots": 4,
                    "rank": 8,
                    "mixed_wave_stats": {
                        "chunks": 4, "multi_adapter_chunks": 4,
                        "adapter_loads": 0, "adapter_evictions": 0,
                    },
                    "one_program": True,
                    "churn_round_stats": {
                        "chunks": 4, "multi_adapter_chunks": 0,
                        "adapter_loads": 2, "adapter_evictions": 2,
                    },
                    "adapter_loads": 14,
                    "adapter_evictions": 10,
                    "adapter_hit_rate": 0.75,
                    "registry": {
                        "loads": 9, "evictions": 3, "hits": 5, "misses": 9,
                        "budget_bytes": 167772160,
                        "reclaimable_weight_bytes": 100663296,
                    },
                    "mix": "16 streams x 384 new tokens, K=4 distinct "
                           "adapters cycling; churn arm loads 2 cold "
                           "adapters per round through a 4-slot pool + "
                           "5-set registry budget",
                },
                "goodput_pct": 97.2,
                "shed_pct": 33.3,
                "interactive_p99_ms": 240.5,
                "interactive_unloaded_p99_ms": 180.1,
                "interactive_p99_x": 1.34,
                "overload_expired_streams": 0,
                "overload_mix": "24 batch (prio 0, 128 new) + 8 interactive (prio 2, 16 new, 60s deadline) into 8 slots, queue bound 16",
                "ttft_p99_ms": 310.2,
                "ttft_unchunked_p99_ms": 905.7,
                "ttft_x": 2.92,
                "gen_p99_terms_ms": {
                    "queue_wait": 45.0, "prefill": 60.1, "decode": 210.4,
                },
                "gen_p99_terms_unchunked_ms": {
                    "queue_wait": 620.3, "prefill": 160.9, "decode": 300.2,
                },
                "gen_p99_dominant": "decode",
                "chunk_mix": {
                    "budget": 256, "window_prefill_tokens": 8400,
                    "window_decode_tokens": 3800, "interactive_served": 8,
                },
                "chunked_prefill_protocol": "16 batch (448-token prompts, 96 new, prio 0) + 8 interactive (24-40 tokens, 16 new, prio 2, mid-decode) into 8 slots; budget 256 vs monolithic",
            },
            "trace_prop": {
                "trace_on_tok_s": 4360.0,
                "trace_off_tok_s": 4440.0,
                "trace_prop_overhead_pct": 1.8,
                "protocol": "16-way StreamingLM graph serving, best-of-3",
            },
            "telemetry": {
                "telemetry_on_tok_s": 4390.0,
                "telemetry_off_tok_s": 4450.0,
                "telemetry_overhead_pct": 1.35,
                "protocol": "16-way StreamingLM graph serving, best-of-3",
            },
            "capture": {
                "capture_on_tok_s": 4370.0,
                "capture_off_tok_s": 4445.0,
                "capture_overhead_pct": 1.69,
                "protocol": "16-way StreamingLM graph serving, best-of-3, SAMPLE=1",
            },
            "chaos": {
                "chaos_goodput_pct": 95.8,
                "breaker_fastfail_pct": 87.5,
                "hedge_win_pct": 66.7,
                "offered": 48,
                "served": 46,
                "wall_s": 21.4,
                "hedges_fired": 9,
                "hedge_wins": 6,
                "dead_endpoint_breaker": {
                    "state": "open", "streak": 0, "trips": 1, "reopens": 4,
                    "closes": 0, "fastfails": 21, "probes": 4,
                    "transient_failures": 3,
                },
                "mix": "48 unary requests round-robined over 2 remote "
                       "StreamingLM workers; worker 0 SIGKILLed at request 16",
                "migrate_ttr_ms": 42.5,
                "migrate_token_loss": 0,
                "migration": {
                    "migrate_ttr_ms": 42.5,
                    "migrate_token_loss": 0,
                    "replay_ttr_ms": 161.0,
                    "migrated": 8,
                    "replayed": 8,
                    "streams": 8,
                    "max_new_tokens": 24,
                    "mix": "8 streaming requests evacuated after 3 waves",
                },
            },
            "lint": {
                "violations": 0,
                "counts": {},
                "allowlisted": 7,
                "files_scanned": 92,
                "checkers": 6,
            },
            "mean_batch_rows": 26.69,
            "device_batches": 1106,
            "latency_phase": {
                "concurrency": 4,
                "qps": 29.7,
                "p50_ms": 117.093,
                "p90_ms": 174.635,
                "p99_ms": 214.328,
                "mean_ms": 134.642,
                "errors": 0,
            },
            "throughput_phase": {
                "concurrency": 8,
                "client_batch": 32,
                "images_per_s": 582.4,
                "requests_per_s": 18.2,
                "p50_ms": 423.421,
                "errors": 0,
            },
        },
    }


def test_compact_line_fits_tail_window(bench):
    full = _r3_like_full_result()
    assert len(json.dumps(full)) > 2000  # the failure mode being pinned
    compact = bench._compact_result(full)
    line = json.dumps(compact)
    assert len(line) <= bench.COMPACT_BUDGET
    assert compact["metric"] == full["metric"]
    assert compact["value"] == full["value"]
    assert compact["vs_baseline"] == full["vs_baseline"]


def test_compact_line_carries_judge_scalars(bench):
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    # int8 + generation + native-model (the r2/r3 certification asks)
    assert e["int8_fwd_x"] == 0.99
    assert e["int8_decode_x"] == 1.1
    # the w8a8 certification keys (r6 acceptance: the compact line must
    # print the ratio pair + top-1 agreement + the upcast guard)
    assert e["w8a8_fwd_x"] == 1.64
    assert e["w8a8_loop_x"] == 1.85
    assert e["w8a8_top1_agree"] == 0.997
    assert e["w8a8_mxu"] is True
    assert e["w8a8_vs_a100"] == 0.62
    assert e["gen_tok_s"] == 8877.5
    assert e["paged_tok_s"] == 4400.0
    assert e["native_img_s"] == 96.0
    assert e["mfu_pct"] == 8.82
    assert e["loop_mfu_pct"] == 43.7
    assert e["server_p50_ms"] == 3.1
    assert e["full"] == os.path.basename(bench.FULL_RESULT_FILE)


def test_compact_line_carries_capacity_story(bench):
    """r6 certification keys (VERDICT r5 #2/#3/#5): the bimodal
    mixed-length point, the 256-stream point (previously uncertified
    prose), the capacity field, and the p99-dominant term — with the
    types/units the glossary promises (rates are floats in tok/s,
    capacity is an integer stream count, p99_dominant names a
    component)."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["paged_bimodal_tok_s"], float)
    assert e["paged_bimodal_tok_s"] == 13500.0
    assert isinstance(e["paged256_tok_s"], float)
    assert e["paged256_tok_s"] == 30784.0
    assert isinstance(e["paged_cap_streams"], int)
    assert e["paged_cap_streams"] == 220
    assert e["p99_dominant"] in (
        "parse", "decode", "pad", "queue_wait", "forward", "serialise"
    )
    assert e["attached_p99_bound_ms"] == 14.048


def test_compact_line_carries_kernel_lane_story(bench):
    """r18 certification keys: the fused-kernel speedup multiple and
    the int8-KV capacity multiple ride the compact line (glossary-typed
    — kernel_x a float on TPU runs or the literal "n/a" off-platform,
    capacity_x a float from host arithmetic, certifiable anywhere); the
    per-arm rates and HBM byte terms stay in bench_full.json."""
    full = _r3_like_full_result()
    e = bench._compact_result(full)["extra"]
    assert e["paged_kernel_x"] == 1.5
    assert e["int8_kv_cap_x"] == 1.98
    # raw arms are full-blob-only
    assert "kernel_tok_s" not in e and "hbm_bytes_x" not in e
    # off-platform runs keep the schema with the sentinel, never a hole
    full["extra"]["generation"]["kernel_lane"]["paged_kernel_x"] = "n/a"
    e2 = bench._compact_result(full)["extra"]
    assert e2["paged_kernel_x"] == "n/a"
    assert e2["int8_kv_cap_x"] == 1.98


def test_compact_line_carries_observability_overhead(bench):
    """r7 certification key: the compact line prints the paged
    throughput cost of full observability (spans + flight recorder) as
    a float percentage — the <2% always-on-recorder gate; the raw
    on/off rates stay in bench_full.json."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["obs_overhead_pct"], float)
    assert e["obs_overhead_pct"] == 0.84
    # raw rates are full-blob-only: the compact line stays lean
    assert "obs_on_tokens_per_s" not in e


def test_compact_line_carries_trace_prop_overhead(bench):
    """r8 certification key: the serving cost of full cross-process
    trace propagation + per-hop transport telemetry, as a float
    percentage gated < 2 (same posture as obs_overhead_pct); the raw
    on/off rates stay in bench_full.json under trace_prop."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["trace_prop_overhead_pct"], float)
    assert e["trace_prop_overhead_pct"] == 1.8
    assert "trace_on_tok_s" not in e
    assert "protocol" not in e


def test_compact_line_carries_telemetry_overhead(bench):
    """r20 certification key: the serving cost of the full telemetry
    plane (replica ring + cost ledger + exemplar capture) vs
    SELDON_TPU_TELEMETRY=0, as a float percentage gated < 2; the raw
    on/off rates stay in bench_full.json under telemetry."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["telemetry_overhead_pct"], float)
    assert e["telemetry_overhead_pct"] == 1.35
    assert "telemetry_on_tok_s" not in e


def test_compact_line_carries_capture_overhead(bench):
    """r21 certification key: the serving cost of the black-box capture
    plane at its worst-case sampling rate (SELDON_TPU_CAPTURE_SAMPLE=1,
    every request captured) vs SELDON_TPU_CAPTURE=0, as a float
    percentage gated < 2; the raw on/off rates stay in bench_full.json
    under capture."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["capture_overhead_pct"], float)
    assert e["capture_overhead_pct"] == 1.69
    assert "capture_on_tok_s" not in e


def test_compact_line_carries_prefix_cache_story(bench):
    """r9 certification keys: the shared-system-prompt workload's
    throughput with automatic prefix caching on (gate: >=1.3x the
    cache-off arm) and its admission hit rate; the cache-off rate and
    the speedup ratio stay in bench_full.json."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["prefix_shared_tok_s"], float)
    assert e["prefix_shared_tok_s"] == 7300.0
    assert isinstance(e["prefix_hit_pct"], float)
    assert e["prefix_hit_pct"] == 100.0
    # raw contrast arm + ratio are full-blob-only
    assert "prefix_off_tokens_per_s" not in e
    assert "prefix_speedup_x" not in e
    assert "prefix_shared_mix" not in e


def test_compact_line_carries_kv_tier_story(bench):
    """r22 certification keys: the returning-session phase's promote-
    vs-re-prefill speedup (gate >= 2.0 with promotion greedy bit-exact
    in f32) and the warm-round promote hit rate; the raw revisit
    walls, tier counters, resident +-5% delta, and mix description
    stay in bench_full.json."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["kv_tier_promote_x"], float)
    assert e["kv_tier_promote_x"] == 4.6
    assert isinstance(e["kv_tier_hit_pct"], float)
    assert e["kv_tier_hit_pct"] == 100.0
    # raw walls + counters + resident contrast are full-blob-only
    assert "kv_tier_on_revisit_ms" not in e
    assert "kv_tier_off_revisit_ms" not in e
    assert "kv_tier_demotions" not in e
    assert "kv_tier_resident_delta_pct" not in e
    assert "kv_tier_mix" not in e


def test_compact_line_carries_overload_story(bench):
    """r10 certification keys: the 2x-offered-load phase's goodput
    (in-deadline tokens / decoded tokens, gate >= 90), shed share, and
    the interactive class's loaded p99 (gate <= 1.5x unloaded — the
    ratio and mix stay in bench_full.json)."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["goodput_pct"], float)
    assert e["goodput_pct"] == 97.2
    assert isinstance(e["shed_pct"], float)
    assert e["shed_pct"] == 33.3
    assert isinstance(e["interactive_p99_ms"], float)
    assert e["interactive_p99_ms"] == 240.5
    # the ratio arm + mix description are full-blob-only
    assert "interactive_p99_x" not in e
    assert "interactive_unloaded_p99_ms" not in e
    assert "overload_mix" not in e


def test_compact_line_carries_chunked_prefill_story(bench):
    """r15 certification keys: interactive TTFT p99 under bimodal load
    with the token-budget chunk scheduler on, and the dominant term of
    the per-request p99 decomposition (the ROADMAP-2 gate: queue_wait
    no longer dominant).  The unchunked contrast arm, the full terms
    breakdown, and the chunk mix stay in bench_full.json."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["ttft_p99_ms"], float)
    assert e["ttft_p99_ms"] == 310.2
    assert e["gen_p99_dominant"] == "decode"
    assert "ttft_unchunked_p99_ms" not in e
    assert "ttft_x" not in e
    assert "gen_p99_terms_ms" not in e
    assert "gen_p99_terms_unchunked_ms" not in e
    assert "chunk_mix" not in e
    assert "chunked_prefill_protocol" not in e


def test_capacity_accounting_prices_inflight_prefill():
    """r15 bugfix: a prompt admitted but still chunking holds its whole
    block table mapped while contributing no decode — the accounting
    must reserve those pages off the top, or chunked prefill
    over-admits during the chunking window."""
    from seldon_core_tpu.models.paged import (
        paged_capacity_streams,
        paged_hbm_accounting,
    )

    kw = dict(
        d_model=512, num_layers=8, page_size=64, steps_per_call=8,
        dtype_bytes=2, chunk_impl="ring",
    )
    zero = paged_hbm_accounting(streams=1, ctx_len=512, **kw)
    one = paged_hbm_accounting(
        streams=1, ctx_len=512, inflight_prefill_tokens=512, **kw
    )
    assert zero["inflight_prefill_bytes"] == 0
    assert one["inflight_prefill_bytes"] > 0
    # the reservation lands in peak_bytes, nothing else moves
    assert one["peak_bytes"] == (
        zero["peak_bytes"] + one["inflight_prefill_bytes"]
    )
    assert one["pool_bytes"] == zero["pool_bytes"]
    # capacity: 8 streams' worth of in-flight prefill displaces at
    # most 8 admissions (pool bytes only — no working-set term), and
    # at least one
    base = paged_capacity_streams(8 << 30, 512, **kw)
    chunking = paged_capacity_streams(
        8 << 30, 512, inflight_prefill_tokens=8 * 512, **kw
    )
    assert base - 8 <= chunking < base
    # partial pages round UP to whole mapped pages
    part = paged_hbm_accounting(
        streams=1, ctx_len=512, inflight_prefill_tokens=65, **kw
    )
    assert part["inflight_prefill_bytes"] == paged_hbm_accounting(
        streams=1, ctx_len=512, inflight_prefill_tokens=128, **kw
    )["inflight_prefill_bytes"]


def test_compact_line_carries_chaos_story(bench):
    """r12 certification keys: the kill-one-of-two-workers phase's
    goodput (served/offered, gate >= 80 with half the fleet dead), the
    dead endpoint's open-circuit fast-fail share (high = post-trip
    calls skip the retry+backoff ladder), and the hedge win rate — all
    floats; the raw counts, breaker counter dump, and mix string stay
    in bench_full.json."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["chaos_goodput_pct"], float)
    assert e["chaos_goodput_pct"] == 95.8
    assert isinstance(e["breaker_fastfail_pct"], float)
    assert e["breaker_fastfail_pct"] == 87.5
    assert isinstance(e["hedge_win_pct"], float)
    assert e["hedge_win_pct"] == 66.7
    # raw counters + breaker dump + mix are full-blob-only
    assert "hedges_fired" not in e
    assert "dead_endpoint_breaker" not in e
    assert "mix" not in e


def test_compact_line_carries_migration_story(bench):
    """r17 certification keys: the live-migration arm's time-to-resume
    on the peer (float ms) and the zero-token-loss gate (int, MUST be
    0); the journal-replay contrast and raw counts stay in
    bench_full.json chaos.migration."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["migrate_ttr_ms"], float)
    assert e["migrate_ttr_ms"] == 42.5
    assert isinstance(e["migrate_token_loss"], int)
    assert e["migrate_token_loss"] == 0
    # the full migration blob (replay contrast, counts, mix) is
    # full-blob-only
    assert "replay_ttr_ms" not in e
    assert "migration" not in e


def test_compact_line_carries_zero_copy_story(bench):
    """r14 certification keys (ROADMAP 4): the small-tensor
    native→model qps through the python buffer-view lane (gate >= 0.5 x
    stub_qps) and the lane-on/lane-off ratio (gate >= 2.0, outputs
    bit-exact both lanes); the off-arm rate and the mix string stay in
    bench_full.json."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["native_model_qps"], float)
    assert e["native_model_qps"] == 9500.0
    assert isinstance(e["zero_copy_x"], float)
    assert e["zero_copy_x"] == 3.06
    # raw contrast arm + provenance are full-blob-only
    assert "zero_copy_off_qps" not in e
    assert "bit_exact" not in e
    assert "mix" not in e


def test_compact_line_carries_lint_violations(bench):
    """r13 certification key: unsuppressed graftlint violations at
    bench time — an int that MUST be 0 (per-checker counts, allowlist
    burn-down size and files_scanned stay in bench_full.json lint)."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert e["lint_violations"] == 0
    assert isinstance(e["lint_violations"], int)
    # the breakdown is full-blob-only
    assert "allowlisted" not in e
    assert "files_scanned" not in e


def test_lint_phase_runs_suite_clean(bench):
    """The real lint phase against the real tree: 0 violations with
    the committed allowlist, >=6 checkers, schema the compact pick
    reads."""
    res = bench.lint_phase()
    assert res["violations"] == 0
    assert res["checkers"] >= 6
    assert res["files_scanned"] > 50
    assert isinstance(res["counts"], dict)


def test_compact_line_carries_tp_story(bench):
    """r11 certification keys: the tensor-parallel 16-stream serving
    point and its per-chip efficiency vs the TP=1 ideal; the degree
    itself stays in bench_full.json (`paged_tp_degree`)."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["paged_tp_tok_s"], float)
    assert e["paged_tp_tok_s"] == 8100.0
    assert isinstance(e["paged_tp_eff_pct"], float)
    assert e["paged_tp_eff_pct"] == 46.0
    assert "paged_tp_degree" not in e


def test_compact_line_carries_mesh_story(bench):
    """r19 certification keys: the (dp=2, tp=2) 16-stream serving point,
    its per-chip efficiency vs the TP=1 ideal, and the accounting-priced
    long-context ceiling; the axes string and the per_shard < budget <
    full certificate stay in bench_full.json (`paged_mesh_axes` /
    `longctx`)."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["paged_mesh_tok_s"], float)
    assert e["paged_mesh_tok_s"] == 7400.0
    assert isinstance(e["paged_mesh_eff_pct"], float)
    assert e["paged_mesh_eff_pct"] == 42.0
    assert isinstance(e["longctx_max_len"], int)
    assert e["longctx_max_len"] == 81920
    assert "paged_mesh_axes" not in e
    assert "longctx" not in e
    assert "longctx_decode_tokens_per_s" not in e


def test_compact_line_mesh_na_on_small_host(bench):
    """Hosts under 4 devices emit the literal "n/a" for the measured
    mesh pair while longctx_max_len stays numeric (host arithmetic runs
    everywhere) — the compact line is schema-stable on every host."""
    full = _r3_like_full_result()
    full["extra"]["generation"]["paged_mesh_tokens_per_s"] = "n/a"
    full["extra"]["generation"]["paged_mesh_eff_pct"] = "n/a"
    compact = bench._compact_result(full)
    assert compact["extra"]["paged_mesh_tok_s"] == "n/a"
    assert compact["extra"]["paged_mesh_eff_pct"] == "n/a"
    assert compact["extra"]["longctx_max_len"] == 81920


def test_dp_hbm_accounting_per_shard():
    """dp_degree > 1 prices the page-dim sharding of the 2-D mesh: KV
    terms divide by tp x dp, the tp_degree key never inflates, and an
    indivisible pool (shard_decode_state's fallback) prices FULL page
    bytes."""
    from seldon_core_tpu.models.paged import (
        paged_hbm_accounting,
        paged_max_context,
    )

    kw = dict(d_model=512, num_layers=8, page_size=64, steps_per_call=8,
              dtype_bytes=2, chunk_impl="ring")
    one = paged_hbm_accounting(streams=4, ctx_len=512, **kw)
    both = paged_hbm_accounting(
        streams=4, ctx_len=512, tp_degree=2, dp_degree=2, **kw
    )
    assert both["pool_bytes"] == one["pool_bytes"] // 4
    assert both["tp_degree"] == 2 and both["dp_degree"] == 2
    rep = paged_hbm_accounting(
        streams=4, ctx_len=512, dp_degree=2, num_pool_pages=33, **kw
    )
    assert rep["pool_bytes"] == one["pool_bytes"] and rep["dp_degree"] == 1
    # the longctx_max_len key's function: the admissible context under
    # a fixed budget multiplies with the data axis
    budget = 256 << 20
    assert paged_max_context(budget, dp_degree=2, **kw) > paged_max_context(
        budget, **kw
    )


def test_compact_line_carries_multi_lora_story(bench):
    """r16 certification keys: the K=4 mixed-adapter serving rate and
    the N-model churn gate (resident-rate delta vs paged_tok_s);
    adapter/registry churn details stay in bench_full.json
    (`multi_lora`)."""
    compact = bench._compact_result(_r3_like_full_result())
    e = compact["extra"]
    assert isinstance(e["multi_lora_tok_s"], float)
    assert e["multi_lora_tok_s"] == 4100.0
    assert isinstance(e["resident_tok_s_delta_pct"], float)
    assert e["resident_tok_s_delta_pct"] == 1.14
    assert "multi_lora" not in e
    assert "multi_lora_resident_tokens_per_s" not in e


def test_adapter_capacity_accounting_reserved_off_the_top():
    """The factor pool's bytes reserve off the capacity budget BEFORE
    the per-stream division, and reclaimable registry weights report
    next to reclaimable pages, never in peak."""
    from seldon_core_tpu.models.paged import (
        paged_capacity_streams,
        paged_hbm_accounting,
    )

    kw = dict(ctx_len=512, d_model=512, num_layers=8)
    one = paged_hbm_accounting(streams=1, **kw)
    with_pool = paged_hbm_accounting(
        streams=1, adapter_bytes=123456, reclaimable_weight_bytes=777, **kw
    )
    assert with_pool["peak_bytes"] == one["peak_bytes"] + 123456
    assert with_pool["reclaimable_bytes"] == one["reclaimable_bytes"] + 777
    budget = 2 << 30
    base = paged_capacity_streams(budget, 512, d_model=512, num_layers=8)
    halved = paged_capacity_streams(
        budget, 512, d_model=512, num_layers=8, adapter_bytes=budget // 2
    )
    assert halved <= (base + 1) // 2


def test_compact_line_tp_na_on_single_chip(bench):
    """Single-chip hosts emit the literal "n/a" for the tp keys — the
    compact line stays schema-stable everywhere (a missing key would
    read as a phase crash, a 0.0 as a collapsed lane)."""
    full = _r3_like_full_result()
    full["extra"]["generation"]["paged_tp_tokens_per_s"] = "n/a"
    full["extra"]["generation"]["paged_tp_eff_pct"] = "n/a"
    full["extra"]["generation"]["paged_tp_degree"] = 1
    compact = bench._compact_result(full)
    assert compact["extra"]["paged_tp_tok_s"] == "n/a"
    assert compact["extra"]["paged_tp_eff_pct"] == "n/a"


def test_tp_hbm_accounting_per_shard():
    """tp_degree > 1 prices the PER-SHARD bytes one device holds: every
    KV term divides by the degree, so capacity under a fixed per-chip
    budget SCALES with it."""
    from seldon_core_tpu.models.paged import (
        paged_capacity_streams,
        paged_hbm_accounting,
    )

    kw = dict(d_model=512, num_layers=8, page_size=64, steps_per_call=8,
              dtype_bytes=2, chunk_impl="ring")
    one = paged_hbm_accounting(streams=4, ctx_len=512, **kw)
    four = paged_hbm_accounting(streams=4, ctx_len=512, tp_degree=4, **kw)
    assert four["pool_bytes"] == one["pool_bytes"] // 4
    assert four["working_set_bytes"] == one["working_set_bytes"] // 4
    assert four["tp_degree"] == 4 and one["tp_degree"] == 1
    # an indivisible head count serves with a REPLICATED pool
    # (shard_decode_state's fallback) — the accounting must price the
    # full bytes, never certify capacity that config cannot deliver
    rep = paged_hbm_accounting(
        streams=4, ctx_len=512, tp_degree=4, num_heads=6, **kw
    )
    assert rep["pool_bytes"] == one["pool_bytes"] and rep["tp_degree"] == 1
    ok = paged_hbm_accounting(
        streams=4, ctx_len=512, tp_degree=4, num_heads=8, **kw
    )
    assert ok["pool_bytes"] == one["pool_bytes"] // 4
    budget = 8 << 30
    assert paged_capacity_streams(
        budget, 512, tp_degree=4, **kw
    ) >= 4 * paged_capacity_streams(budget, 512, **kw) - 4


def test_prefix_capacity_accounting_reclaimable():
    """LRU-cached prefix pages never shrink admissible capacity: they
    price as reclaimable_bytes, not peak_bytes."""
    from seldon_core_tpu.models.paged import (
        paged_capacity_streams,
        paged_hbm_accounting,
    )

    kw = dict(d_model=512, num_layers=8, page_size=64, steps_per_call=8,
              dtype_bytes=2, chunk_impl="ring")
    cold = paged_hbm_accounting(streams=1, ctx_len=512, **kw)
    warm = paged_hbm_accounting(
        streams=1, ctx_len=512, cached_prefix_pages=64, **kw
    )
    assert warm["peak_bytes"] == cold["peak_bytes"]
    assert warm["reclaimable_bytes"] == 64 * 64 * (512 * 2 * 2 * 8)
    assert cold["reclaimable_bytes"] == 0
    budget = 8 << 30
    assert paged_capacity_streams(budget, 512, **kw) == paged_capacity_streams(
        budget, 512, cached_prefix_pages=64, **kw
    )


def test_capacity_accounting_donated_vs_copied():
    """The capacity model prices donation correctly: the chunk donates
    pk/pv so ONE pool copy is live; pricing the copied world must
    strictly shrink capacity, and capacity scales ~linearly with the
    budget."""
    from seldon_core_tpu.models.paged import (
        paged_capacity_streams,
        paged_hbm_accounting,
    )

    kw = dict(d_model=512, num_layers=8, page_size=64, steps_per_call=8,
              dtype_bytes=2, chunk_impl="ring")
    budget = 8 << 30
    donated = paged_capacity_streams(budget, 512, donated=True, **kw)
    copied = paged_capacity_streams(budget, 512, donated=False, **kw)
    assert donated > copied > 0
    assert paged_capacity_streams(2 * budget, 512, donated=True, **kw) >= 2 * donated - 1
    one = paged_hbm_accounting(streams=1, ctx_len=512, donated=True, **kw)
    # flat pool stores logical bytes: 8 pages x 64 x (512 d_model x 2B
    # x 2 kv x 8 layers) = 8 MiB; ring working set adds the split
    # (2.0x-padded) ctx copy + ring
    assert one["pool_bytes"] == 8 * 64 * (512 * 2 * 2 * 8)
    assert one["peak_bytes"] == one["pool_bytes"] + one["working_set_bytes"]


def test_compact_drops_low_priority_on_overflow(bench):
    full = _r3_like_full_result()
    # blow the budget with a giant but low-priority string field
    full["extra"]["served_by"] = "x" * 5000
    compact = bench._compact_result(full)
    line = json.dumps(compact)
    assert len(line) <= bench.COMPACT_BUDGET
    # headline + highest-priority scalars survive
    assert compact["value"] == full["value"]
    assert "lat_p50_ms" in compact["extra"]
    assert "served_by" not in compact["extra"]


def test_emit_writes_full_and_prints_compact(bench, tmp_path, capsys, monkeypatch):
    monkeypatch.setattr(bench, "FULL_RESULT_FILE", str(tmp_path / "bench_full.json"))
    full = _r3_like_full_result()
    bench._emit(full)
    printed = capsys.readouterr().out.strip().splitlines()[-1]
    assert len(printed) <= bench.COMPACT_BUDGET
    parsed = json.loads(printed)
    assert parsed["value"] == full["value"]
    with open(tmp_path / "bench_full.json") as f:
        roundtrip = json.load(f)
    assert roundtrip == full  # nothing lost — the full blob is on disk


def test_emit_flags_failed_full_write(bench, tmp_path, capsys, monkeypatch):
    # unwritable full path: the line must carry full_write_error so a
    # stale bench_full.json is never attributed to this run
    monkeypatch.setattr(
        bench, "FULL_RESULT_FILE", str(tmp_path / "nodir" / "bench_full.json")
    )
    bench._emit(_r3_like_full_result())
    parsed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert parsed["extra"]["full_write_error"] is True


def test_failed_phases_found_at_any_depth(bench):
    """A phase that recorded an error makes the run exit non-zero: the
    scan must see top-level keys and the ones generation_phase nests."""
    extra = {
        "device": "TPU v5 lite0",
        "int8_error": "boom",
        "generation": {"paged_serving_error": "oom", "decode_tokens_per_s": 1.0},
        "roofline": {"mfu_pct": 5.0},
    }
    assert sorted(bench.failed_phases(extra)) == [
        "generation.paged_serving_error", "int8_error"]
    assert bench.failed_phases(_r3_like_full_result()["extra"]) == []


def test_peaks_refuse_unknown_device_kind(bench):
    """The MFU denominator is keyed by device_kind; a chip that is not
    in the table is refused, not priced as a v5e."""
    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    assert bench.tpu_peaks(Dev)["bf16_flops"] == 197e12
    Dev.device_kind = "TPU v9 imaginary"
    with pytest.raises(SystemExit, match="no published peak"):
        bench.tpu_peaks(Dev)
