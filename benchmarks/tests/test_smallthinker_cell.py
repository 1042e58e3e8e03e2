"""The ``smallthinker-21b-a3b`` configuration at a toy size through
``run.py --rehearse-cpu`` (grouped-query heads over K/V pools of kinds,
served by the deployer as the cell serves it: ``arch``, ``arch_sizes``,
the reference, the counters over HTTP; the kind's one-prompt sample), the
six new readers and the joined ones on recorded counters and a recorded
fixture of operation names, and the shipped configuration against the
catalog's row and its own arithmetic."""

import json
import os
import subprocess
import sys

import pytest

import build_tree
from harness import lengths, manifest, warmup

CELL = "tiny-smallthinker.tiny-notes"
SHIPPED = "smallthinker-21b-a3b.mixed-window-saturated"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("gqa_kernel_roofline", "gqa_kernel_time_share_pct", "gqa_rows_read_pct",
       "gqa_prefill_attention_mfu_pct", "relu_experts_decode_roofline",
       "smallthinker_step_mfu_pct")
# accepted metrics that read counters or clocks alone and mean the same here
JOINED = ("chunk_tokens_mean", "prefill_group_mean", "queue_ttft_p50_ms", "decode_step_ms",
          "hbm_peak_gib", "ingress_wait_mean_ms", "queue_wait_mean_ms", "prefill_pad_pct",
          "host_gap_pct", "host_busy_pct", "decode_ctx_tokens_mean", "pool_move_share_pct",
          "wave_overlap_pct", "engine_out_tok_s", "deliver_lag_mean_ms", "deliver_behind_pct",
          "expert_load_max_over_mean", "window_pages_held_pct")
# ... and those whose readers go through another family's keys (mla_work.share,
# moe_work.routed, step_work.family, peaks' GPT-2 bytes) or take another cache's
# shapes or every 3-D Pallas kernel for one: not listed for this cell
LEFT_OUT = ("routed_local_share_pct", "experts_held_active_mean", "step_mfu_pct",
            "expert_share_decode_roofline", "held_experts_time_share_pct",
            "moe_decode_roofline", "moe_time_share_pct", "experts_active_mean",
            "paged_kernel_roofline", "paged_kernel_time_share_pct", "kv_pool_used_pct",
            "decode_live_page_pct", "tpot_p50_ms", "engine_tpot_mean_ms",
            "prefill_time_share_pct", "prefill_fused_pct")
PEAKS = {"hbm_bytes_per_s": 8.19e11, "bf16_flops": 1.97e14}


def config():
    return manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs",
                                           "smallthinker-21b-a3b.json"))


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dest = build_tree.build(str(tmp_path_factory.mktemp("checkout")))
    path = os.path.join(dest, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "tiny-smallthinker", "source": "none: rehearsal",
                         "reduced": [], "file": "benchmarks/configs/tiny-smallthinker.json",
                         "why": "rehearsal"})
    m["workloads"].append({"name": CELL, "config": "tiny-smallthinker", "traffic": "tiny-notes",
                           "chips": 1, "why": "rehearsal"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if SHIPPED in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(m, f, indent=1)
    return dest


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses(tree, trace):
    out = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 41), "--seconds", "4", "--trace", trace, "--rehearse-cpu"],
        cwd=tree, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    earlier = out.stdout
    # the sample: one judged prompt (the toy cell has none past 4,352: its
    # longest, 28, three toy windows long), generation_share's two limits
    assert "(prompts of [28])" in earlier and "ok=True" in earlier, earlier[-2000:]
    assert "window compiles: 0" in earlier, earlier[-2000:]
    assert set(result["compared"]) == {"worst_gap_stds", "off_share", "failed_requests"}
    if trace == "0":
        assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
        return
    got = result["metrics"]
    # the counter readers find the program's counters; the device readers
    # find no device trace on the CPU
    assert 0 < got["gqa_rows_read_pct"]["value"] < 100
    assert 0 < got["window_pages_held_pct"]["value"] < 100
    assert "expert_load_max_over_mean" in got and "chunk_tokens_mean" in got
    for name in ("gqa_kernel_roofline", "relu_experts_decode_roofline", "decode_step_ms",
                 "smallthinker_step_mfu_pct") + LEFT_OUT:
        assert name not in got, name


def test_a_program_without_the_counters_leaves_the_new_metrics_out():
    """The parent serves no such arch and has none of the counters: every
    new reader returns None on its context and raises nothing."""
    cfg = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs", "olmoe-1b-7b.json"))
    stats = {"pool_pages_used": 10, "pool_pages_total": 100, "decode_kv_tokens": 5}
    ctx = {"engine": {"window": [stats, stats], "trace": [stats, stats], "samples": [stats]},
           "trace": {"busy_s": 1.0, "window_s": 1.0,
                     "ops": {"pallas_kernel_f32_64_32_128_": {"count": 3, "seconds": 0.1}}},
           "config": cfg, "peaks": PEAKS}
    for name in NEW:
        assert manifest.reader("layer_metrics", name)(ctx) is None, name
    ctx["config"] = config()  # the new configuration on a program without the counters
    for name in NEW:  # (the time share reads the trace alone)
        got = manifest.reader("layer_metrics", name)(ctx)
        assert got is None or name == "gqa_kernel_time_share_pct", name
    # ... with no trace at all, and with no engine reading
    ctx["trace"], ctx["engine"] = None, {"window": None, "trace": None, "samples": []}
    for name in NEW:
        assert manifest.reader("layer_metrics", name)(ctx) is None, name


def test_the_other_families_readers_know_nothing_of_this_one():
    from layer_metrics import mla_work, moe_work, step_work

    cfg = config()
    assert step_work.family(cfg) is None
    assert moe_work.routed(cfg) is None and mla_work.share(cfg) is None
    assert not any(k in cfg["model"] for k in (
        "num_experts", "n_routed_experts", "num_experts_per_tok", "n_embd", "kv_lora_rank"))


def test_the_configuration_is_the_catalog_s_row_but_for_the_cuts():
    cfg = config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    reduced = set(cfg["reduced"])
    assert reduced == {"num_hidden_layers", "moe_num_primary_experts", "vocab_size",
                       "max_position_embeddings"}
    entry = next(c for c in manifest.load_json(manifest.MANIFEST)["configs"]
                 if c["name"] == "smallthinker-21b-a3b")
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg["model"][key] == cfg[key], key          # the two blocks agree
        if key in reduced:
            assert cfg[key] != value and cfg["published"][key] == value
            assert key in cfg["reduced_why"]
        else:
            assert cfg[key] == value, key                  # nothing else moved: no width
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (12, 16, 37984, 10240)
    assert cfg["vocab_size"] * 4 == row["config"]["vocab_size"]      # a quarter
    m = cfg["model"]
    assert (m["moe_num_primary_experts_published"], m["expert_offset"]) == (64, 0)
    assert len(cfg["rope_layout"]) == len(cfg["sliding_window_layout"]) == 52
    assert cfg["rope_layout"][:12] == cfg["sliding_window_layout"][:12] == [0, 1, 1, 1] * 3
    assert {"activation", "secondary_experts", "router", "positions", "window", "heads",
            "no_bias_no_qk_norm", "judgement"} <= set(cfg["assumed"])
    assert cfg["deployment_stands_for"].startswith("one chip of 4 that share each layer")
    params = {p["name"]: p["value"]
              for p in cfg["deployment"]["predictors"][0]["graph"]["parameters"]}
    assert params["arch"] == "smallthinker"
    assert json.loads(params["arch_sizes"]) == {"experts_held": 16, "expert_offset": 0}
    assert json.loads(params["prompt_buckets"]) == cfg["engine"]["prompt_buckets"]
    assert (int(params["d_model"]), int(params["num_layers"]), int(params["num_heads"]),
            int(params["vocab_size"])) == (2560, 12, 28, 37984)
    engine = cfg["engine"]
    for key in ("max_len", "page_size", "max_slots", "steps_per_call"):
        assert int(params[key]) == engine[key]
    # every slot can reach max_len (the window pool is the engine's to size)
    assert int(params["num_pages"]) == 64 * 10240 // 64 + 1 == 10241
    # the program's spec for this block is the published one but for the share
    from dataclasses import replace

    from reference import smallthinker as ref
    from seldon_core_tpu.models.spec import SMALLTHINKER

    spec, sizes = ref.spec_and_config(cfg["model"])
    assert spec == replace(SMALLTHINKER, experts_held=16,
                           layer_kinds=SMALLTHINKER.layer_kinds[:12])
    assert sizes == dict(vocab_size=37984, d_model=2560, num_layers=12, num_heads=28)
    assert spec.window_table_pages(64, 8) == 66


def test_the_byte_arithmetic_of_the_cut_is_the_engine_s():
    """``reduced_why``'s numbers against what the engine would hold at the
    cell's sizes: shapes only (the program's own declared tree and
    ``lane_report()``'s ``weight_bytes`` rule), no weight is made."""
    import jax

    from reference import smallthinker as ref
    from seldon_core_tpu.models.paged import (
        paged_hbm_accounting, prefill_position_bytes, prefill_positions_max)
    from seldon_core_tpu.models.spec import declared_tree

    cfg = config()
    spec, sizes = ref.spec_and_config(cfg["model"])
    leaves = jax.tree_util.tree_leaves(declared_tree(spec, sizes))
    count = sum(int(leaf.size) for leaf in leaves)
    weight_bytes = sum(int(leaf.size) * leaf.dtype.itemsize for leaf in leaves)
    d = 2560
    attention = d * 3584 + 2 * d * 512 + 3584 * d
    expert, router = 3 * d * 768, d * 64
    assert round(attention / 1e6, 2) == 20.97 and round(expert / 1e6, 2) == 5.90
    assert round((attention + router + 64 * expert) / 1e6, 1) == 398.6   # a whole layer
    held = 12 * (attention + router + 16 * expert) + 2 * 37984 * d
    assert round(held / 1e9, 2) == 1.58 and round(2 * held / 1e9, 2) == 3.16
    norms = count - held
    f32 = 12 * router + norms                 # routers and norm scales rest in float32
    assert norms == (2 * 12 + 1) * d
    assert weight_bytes == 2 * (count - f32) + 4 * f32
    # ... which is what the served engine said of itself on the chip
    # (lane_report weight_bytes in the server's log; my chip run, PR 41)
    assert weight_bytes == 3_165_317_120
    # the pools: three full layers and nine window layers, K and V of 512 lanes
    kinds = spec.cache_kinds(12)
    assert kinds == (("full", 3, 512), ("window", 9, 512)) and spec.cache_pools == 2
    full_pool = 2 * 3 * 10241 * 64 * 512 * 2
    window_pool = 2 * 9 * (64 * 66 + 1) * 64 * 512 * 2
    assert round(full_pool / 1e9, 2) == 4.03 and round(window_pool / 1e9, 2) == 4.98
    assert round(2 * 9 * 10241 * 64 * 512 * 2 / 1e9, 1) == 12.1     # the windows unreleased
    priced = paged_hbm_accounting(
        streams=64, ctx_len=10240, d_model=0, num_layers=0, cache_pools=1,
        chunk_impl="pool", steps_per_call=8, weight_bytes=weight_bytes,
        cache_kinds=[(layers, lanes, spec.window if name == "window" else 0)
                     for name, layers, lanes in kinds for _pool in ("k", "v")])
    trash = 2 * (3 + 9) * 64 * 512 * 2
    assert priced["pool_bytes"] == full_pool + window_pool - trash
    total = weight_bytes + full_pool + window_pool
    assert 12.1e9 < total < 12.3e9 and total > 0.25 * 16 * 2**30     # the driver's floor
    # the prefill cap: the held experts' rows are the widest a position keeps
    # (6 assignments a token at the pass's headroom), so a call of 8,192
    # positions is priced at 2.5 GB and the cap comes out at 4,096 on the
    # chip's 15.75 GiB: an 8,192-position prompt is still one call
    per = prefill_position_bytes(spec, d, 37984, 28)
    assert per == 4 * 37984 + 6 * d + 6 * (6 * d + 10 * 768) == 305536
    assert prefill_positions_max(int(15.75 * 2**30) - total, per) == 4096


def test_the_traffic_and_the_manifest_entries():
    m = manifest.load_json(manifest.MANIFEST)
    cellrow, cfg, traffic = manifest.cell(m, SHIPPED)
    work = lengths.multiset(traffic)
    assert len(work) == 192 and traffic["clients"] == 80 > cfg["engine"]["max_slots"] == 64
    assert all(1025 <= p <= 8192 and 256 <= a <= 2048 and p + a <= 10240 for p, a in work)
    assert {warmup.prefill_bucket(p, cfg["engine"]) for p, _a in work} == {
        2048, 3072, 4096, 6144, 8192}
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 4096, "sigma": 0.5,
                                        "min": 1025, "max": 8192}
    assert traffic["new_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 0.5,
                                     "min": 256, "max": 2048}
    assert (traffic["max_total"], traffic["pairing_seed"], traffic["warm_group_max"],
            traffic["protocol"], traffic["loop"], traffic["requests"]) == (
                10240, 1, 2, "sse-generate", "closed", 192)
    assert traffic["ramp"] == {"clients_per_step": 16, "step_s": 0.7,
                               "until_first_tokens": 64}
    # about half the prompts start under the window and half past it
    under = sum(p < cfg["model"]["sliding_window_size"] for p, _a in work)
    assert 0.4 < under / len(work) < 0.6
    # the checked sample: the shortest prompt past 4,352 and 256 tokens
    from harness.kinds import generation_share_window as kind

    judged = kind.sample_prompt(work)
    assert kind.PAST_WINDOW < judged < 4700 and kind.SAMPLE_NEW == 256
    assert kind.sample_prompt([(20, 8), (28, 8)]) == 28          # a toy cell: its longest
    assert cellrow["chips"] == 1 and len(cellrow["why"]) <= 200
    new = [x for x in m["per_layer"] if x["name"] in NEW]
    assert len(new) == 6 and all(x["workloads"] == [SHIPPED] and x["moves"] == "out_tok_s"
                                 for x in new)
    for name in JOINED + ("out_tok_s",):
        metric = next(x for x in m["end_to_end"] + m["per_layer"] if x["name"] == name)
        assert metric["workloads"][-1] == SHIPPED, name
    for name in LEFT_OUT:
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        assert SHIPPED not in metric["workloads"], name
    assert m["workloads"][-1]["name"] == SHIPPED
    assert m["configs"][-1]["name"] == "smallthinker-21b-a3b"


# operation names and seconds of my traced run of the cell (PR 41)
RECORDED = {
    "pallas_kernel_f32_64_32_128_": 1.10, "pallas_kernel_f32_32_32_128_": 0.20,
    "pallas_kernel_f32_512_768_": 0.30, "pallas_kernel_f32_512_2560_": 0.15,
    "pallas_kernel_bf16_28_4096_128_": 0.20, "pallas_kernel_bf16_56_2048_128_": 0.05,
    "pallas_kernel_f32_24576_768_": 0.10, "pallas_kernel_f32_24576_2560_": 0.06,
    "fusion_f32_64_37984_": 0.05, "fusion_bf16_3_10241_64_512_": 0.04,
    "fusion_bf16_64_1_4608_": 0.08, "fusion_f32_64_28_": 0.01,
}
BEFORE = {"gqa_kv_rows_read": 0, "gqa_kv_rows_cached": 0, "moe_held_active_expert_steps": 0,
          "prefill_tokens": 0, "prefills": 0, "decode_lane_steps": 0,
          "moe_local_assignments": 0, "moe_assignments": 0}
AFTER = {"gqa_kv_rows_read": 400_000_000, "gqa_kv_rows_cached": 520_000_000,
         "moe_held_active_expert_steps": 30_000, "prefill_tokens": 20_480, "prefills": 5,
         "decode_lane_steps": 10_240, "moe_local_assignments": 92_000,
         "moe_assignments": 368_000, "moe_held_pass_rows": 512,
         "moe_load_max": 900, "moe_load_mean": 600.0}


def recorded_ctx(ops=None):
    samples = [{"full_pages_held": 5000, "window_pages_held": 3600},
               {"full_pages_held": 5200, "window_pages_held": 3700}, None]
    cfg = config()
    return {"engine": {"window": [dict(BEFORE), dict(AFTER)],
                       "trace": [dict(BEFORE), dict(AFTER)], "samples": samples},
            "trace": {"busy_s": 2.6, "window_s": 3.0,
                      "modules": {"jit_paged_chunk_s8_64x128": {"count": 20, "seconds": 2.2}},
                      "ops": {k: {"count": 1, "seconds": v}
                              for k, v in (ops or RECORDED).items()}},
            "config": cfg, "peaks": PEAKS, "device": {"count": 1}, "memory": {}}


def test_the_new_readers_on_a_recorded_trace():
    ctx = recorded_ctx()
    got = {name: manifest.reader("layer_metrics", name)(ctx) for name in NEW}
    decode = 1.10 + 0.20
    assert got["gqa_rows_read_pct"] == pytest.approx(100 * 400 / 520)
    assert got["gqa_kernel_time_share_pct"] == pytest.approx(100 * decode / 2.6)
    # a row is 2,048 B of K and V and 14,336 FLOP at 28 heads: the bytes bind
    assert got["gqa_kernel_roofline"] == pytest.approx(
        100 * (400_000_000 * 2048 / 8.19e11) / decode)
    assert 400_000_000 * 14_336 / 1.97e14 < 400_000_000 * 2048 / 8.19e11
    # five prompts of 4,096: a full layer's n^2 / 2 pairs, a window layer's the same
    # at 4,096 (cap x n - cap^2 / 2), 14,336 FLOP a pair
    pairs = 5 * 12 * 4096 * 4096 / 2
    assert got["gqa_prefill_attention_mfu_pct"] == pytest.approx(
        100 * 14_336 * pairs / (1.97e14 * (0.20 + 0.05)))
    assert got["relu_experts_decode_roofline"] == pytest.approx(
        100 * (30_000 * 3 * 2560 * 768 * 2 / 8.19e11) / (0.30 + 0.15))
    tokens = 20_480 + 10_240
    flops = (tokens * 12 * (2 * (2 * 2560 * 3584 + 2 * 2560 * 512) + 2 * 2560 * 64)
             + 6 * 2560 * 768 * 92_000 + 2 * 2560 * 37984 * (10_240 + 5)
             + 14_336 * (400_000_000 + pairs))
    assert got["smallthinker_step_mfu_pct"] == pytest.approx(100 * flops / (1.97e14 * 3.0))
    assert all(0 < v < 105 for v in got.values()), got
    # no rule takes another's operation, and none takes the head, the pool's
    # write, the qkv projection or the merge's statistics
    from layer_metrics import smallthinker_work as work

    z = work.sizes(ctx["config"])
    taken = {k: [r.__name__ for r in (work.is_decode_attention, work.is_prefill_attention)
                 if r(k, z)] for k in RECORDED}
    assert taken["pallas_kernel_f32_64_32_128_"] == ["is_decode_attention"]
    assert taken["pallas_kernel_bf16_56_2048_128_"] == ["is_prefill_attention"]
    assert sum(bool(v) for v in taken.values()) == 4, taken


def test_the_readings_do_not_know_what_did_the_work():
    """The same counters and the same seconds, once in the page-loop
    kernel, once in a later kernel that lays its answer flat, once in
    XLA's gather lane: the gqa readers read the same, and
    ``smallthinker_step_mfu_pct`` the same again on a trace whose
    operations are renamed wholesale."""
    names = ("gqa_kernel_roofline", "gqa_kernel_time_share_pct", "smallthinker_step_mfu_pct")
    readers = {name: manifest.reader("layer_metrics", name) for name in names}
    rest = {k: v for k, v in RECORDED.items() if not k.startswith("pallas_kernel_f32_64_32")
            and not k.startswith("pallas_kernel_f32_32_32")}
    forms = {
        "kernel": dict(rest, **{"pallas_kernel_f32_64_32_128_": 1.3}),
        "flat": dict(rest, **{"pallas_kernel_f32_64_1_3584_": 1.3}),
        "xla": dict(rest, **{"fusion_f32_64_4_7_8192_": 0.9, "fusion_f32_64_4_7_128_": 0.4}),
    }
    got = {form: {name: read(recorded_ctx(ops)) for name, read in readers.items()}
           for form, ops in forms.items()}
    assert got["flat"] == pytest.approx(got["kernel"])
    assert got["xla"] == pytest.approx(got["kernel"])
    renamed = {f"op_{i}": v for i, v in enumerate(forms["kernel"].values())}
    blind = {name: read(recorded_ctx(renamed)) for name, read in readers.items()}
    assert blind["smallthinker_step_mfu_pct"] == got["kernel"]["smallthinker_step_mfu_pct"]
    assert blind["gqa_kernel_roofline"] is None


def test_the_joined_readers_on_recorded_counters():
    """Every accepted metric the cell joins reads counters, clocks or the
    trace's programs alone: each gives a number on this family's
    ``engine_stats()`` keys."""
    ctx = recorded_ctx()
    for snapshot, n in zip(ctx["engine"]["window"] + ctx["engine"]["trace"], (0, 1, 0, 1)):
        snapshot.update(
            chunks=40 * n, tokens=20_000 * n, prefills=5 * n, prefill_chunks=4 * n,
            prefill_padded_tokens=24_576 * n, queue_wait_s=3.0 * n, queue_waits=5 * n,
            ingress_wait_s=0.1 * n, ingress_waits=5 * n, decode_kv_tokens=40_000_000 * n,
            waves_overlapped=30 * n, host_work_s=0.4 * n, host_wait_s=2.0 * n,
            deliver_lag_s=0.5 * n, deliveries=2000 * n, deliveries_behind=3 * n,
            decode_stream_s=100.0 * n, decode_stream_tokens=18_000 * n, clock_s=100.0 + 3 * n,
            ttft_s=9.0 * n, ttfts=5 * n)
    # the untraced stretch: a sample between the window's opening and the trace's
    ctx["engine"]["trace"][0]["clock_s"], ctx["engine"]["trace"][1]["clock_s"] = 110.0, 113.0
    ctx["engine"]["samples"].append(dict(ctx["engine"]["window"][1], clock_s=105.0,
                                         full_pages_held=5200, window_pages_held=3700))
    ctx["engine"]["samples"][1] = None
    ctx.update(window=(0.0, 3.0), seconds=3.0, setup_s=100.0, traffic={}, samples={},
               trace_span=(0.5, 2.5), memory={"peak_bytes_in_use": [14 * 2**30]},
               records=[{"t_send": 0.1 * i, "events": [(0.1 * i + 1.0, 1), (0.1 * i + 1.5, 8)],
                         "ok": True, "error": None, "prompt_len": 4096, "asked": 9}
                        for i in range(10)])
    ctx["trace"].update(host_gaps=[], breakdown={}, idle_s=0.4)
    got = {}
    for name in JOINED:
        try:
            got[name] = manifest.reader("layer_metrics", name)(ctx)
        except KeyError as e:  # a key of the live context this fixture does not carry
            got[name] = f"KeyError {e}"
    for name in ("chunk_tokens_mean", "prefill_group_mean", "decode_step_ms", "hbm_peak_gib",
                 "queue_wait_mean_ms", "ingress_wait_mean_ms", "prefill_pad_pct",
                 "decode_ctx_tokens_mean", "pool_move_share_pct", "wave_overlap_pct",
                 "expert_load_max_over_mean", "window_pages_held_pct", "deliver_lag_mean_ms",
                 "deliver_behind_pct", "host_busy_pct", "engine_out_tok_s",
                 "queue_ttft_p50_ms"):
        assert isinstance(got[name], float) and got[name] >= 0, (name, got[name])
    assert got["pool_move_share_pct"] == pytest.approx(100 * 0.04 / 2.6)   # the full pool's write
    assert got["window_pages_held_pct"] == pytest.approx((72.0 + 100 * 3700 / 5200) / 2)
    assert got["expert_load_max_over_mean"] == pytest.approx(1.5)
