"""The ``olmoe-1b-7b`` configuration at a toy size through ``run.py
--rehearse-cpu`` (a routed model served by the deployer as the cell
serves it: ``arch``, the reference, the routing counters over HTTP),
the four ``moe_*`` readers on synthetic ``ctx``, and the shipped
configuration against its source."""

import json
import os
import subprocess
import sys

import pytest

import build_tree
from harness import manifest

CELL = "tiny-olmoe.tiny-chat"
NEW = ("moe_time_share_pct", "moe_decode_roofline", "experts_active_mean",
       "expert_load_max_over_mean", "paged_kernel_time_share_pct", "paged_kernel_roofline")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dest = build_tree.build(str(tmp_path_factory.mktemp("checkout")))
    path = os.path.join(dest, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "tiny-olmoe", "source": "none: rehearsal", "reduced": [],
                         "file": "benchmarks/configs/tiny-olmoe.json", "why": "rehearsal"})
    m["workloads"].append({"name": CELL, "config": "tiny-olmoe", "traffic": "tiny-chat",
                           "chips": 1, "why": "rehearsal"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if "olmoe-1b-7b.chat-saturated" in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(m, f, indent=1)
    return dest


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_routed_cell_rehearses(tree, trace):
    out = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 91), "--seconds", "4", "--trace", trace, "--rehearse-cpu"],
        cwd=tree, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    if trace == "0":
        assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
        return
    got = result["metrics"]
    # the counters' readers found the program's counters; the trace's
    # readers found no device plane on the CPU and left their metric out
    top_k, experts = 2, 8
    assert top_k <= got["experts_active_mean"]["value"] <= experts
    assert got["expert_load_max_over_mean"]["value"] >= 1.0
    assert not {"moe_time_share_pct", "moe_decode_roofline", "paged_kernel_time_share_pct",
                "paged_kernel_roofline"} & set(got)
    assert "decode_ctx_tokens_mean" in got


# ---------------------------------------------------------------------------
# the readers on synthetic ctx
# ---------------------------------------------------------------------------

def config():
    return manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs", "olmoe-1b-7b.json"))


def ctx_of(ops, before=None, after=None, busy_s=1.0):
    return {"trace": {"busy_s": busy_s, "ops": ops}, "config": config(),
            "peaks": {"hbm_bytes_per_s": 819e9},
            "engine": {"trace": [before, after], "window": [before, after]}}


def reader(name):
    return manifest.reader("layer_metrics", name)


OPS = {
    "pallas_kernel_bf16_256_1024_": {"count": 128, "seconds": 0.20},  # gate, up
    "pallas_kernel_bf16_256_2048_": {"count": 64, "seconds": 0.10},   # down
    "pallas_kernel_s32_65_": {"count": 192, "seconds": 0.01},         # group metadata
    "pallas_kernel_f32_16_1_2048_": {"count": 128, "seconds": 0.30},  # paged decode: 3-D
    "fusion_f32_32_64_": {"count": 64, "seconds": 0.02},              # router, a decode step
    "fusion_f32_1024_64_": {"count": 8, "seconds": 0.01},             # router, 256 x 4 prompts
    "fusion_s32_8_66_": {"count": 8, "seconds": 0.01},                # decode accumulator
    "fusion_s32_8_64_": {"count": 2, "seconds": 0.01},                # a prefill's histogram
    "fusion_bf16_32_2048_": {"count": 64, "seconds": 0.05},           # not the layer's
    # what ends in 64 and is not the router: RoPE's angles (half a head
    # is 64 wide), a page-shaped operation (a page is 64 tokens), rows
    # that are no row count
    "fusion_f32_32_1_64_": {"count": 64, "seconds": 0.07},
    "fusion_f32_4_256_64_": {"count": 8, "seconds": 0.07},
    "copy_bf16_513_64_": {"count": 8, "seconds": 0.07},
    "fusion_f32_48_64_": {"count": 8, "seconds": 0.07},
}


def test_moe_time_share_counts_grouped_matmuls_and_router_by_whole_shape():
    from layer_metrics.moe_work import expert_layer_keys

    ctx = ctx_of(OPS)
    assert reader("moe_time_share_pct")(ctx) == pytest.approx(36.0)
    assert sorted(expert_layer_keys(ctx["trace"], ctx["config"])) == sorted(
        k for k in OPS if OPS[k]["seconds"] in (0.20, 0.10, 0.01, 0.02))


def test_the_paged_kernel_is_read_apart_from_the_grouped_matmuls():
    """``harness/window.py kernel_seconds`` would sum all four Pallas
    kernels; these readers take the one with a 3-D output."""
    ctx = ctx_of(OPS)
    assert reader("paged_kernel_time_share_pct")(ctx) == pytest.approx(30.0)
    # 10 chunk executions x 8 steps; 32 streams holding 300 cached
    # tokens each through the traced interval
    ctx["trace"]["modules"] = {"jit_paged_chunk_s8_16x8_16x16": {"count": 10, "seconds": 2.0}}
    ctx["trace_span"] = (100.0, 103.0)
    ctx["records"] = [{"prompt_len": 299, "events": [(90.0, 1), (110.0, 1)]}] * 32
    needed = 2.0 * 2048 * 2 * (32 * 300) * 8 * 80   # K and V, d, bf16, tokens, layers, steps
    assert reader("paged_kernel_roofline")(ctx) == pytest.approx(
        100.0 * needed / 819e9 / 0.30)
    gpt2_only = ctx_of({"pallas_kernel_bf16_256_1024_": {"count": 1, "seconds": 0.2}})
    assert reader("paged_kernel_time_share_pct")(gpt2_only) is None
    assert reader("paged_kernel_roofline")(gpt2_only) is None


def test_moe_decode_roofline_cannot_pass_100_when_every_expert_is_hit():
    """32 lanes x top-8 over 64 experts hit all 64 in every layer-step;
    a kernel running at the HBM peak itself then reads exactly 100."""
    layer_steps = 8 * 8 * 10                     # layers x steps x chunks
    one_expert = 3 * 2048 * 1024 * 2
    floor_s = layer_steps * 64 * one_expert / 819e9
    ops = {"pallas_kernel_bf16_256_1024_": {"count": 2 * layer_steps, "seconds": 0.7 * floor_s},
           "pallas_kernel_bf16_256_2048_": {"count": layer_steps, "seconds": 0.3 * floor_s},
           # a prefill group's rows are not a decode step's
           "pallas_kernel_bf16_8192_1024_": {"count": 16, "seconds": 5.0}}
    before = {"moe_active_expert_steps": 1000, "moe_layer_steps": 20}
    after = {"moe_active_expert_steps": 1000 + 64 * layer_steps,
             "moe_layer_steps": 20 + layer_steps}
    read = reader("moe_decode_roofline")
    assert read(ctx_of(ops, before, after)) == pytest.approx(100.0)
    slower = {k: dict(v, seconds=4 * v["seconds"]) for k, v in ops.items()}
    assert read(ctx_of(slower, before, after)) == pytest.approx(25.0)
    assert reader("experts_active_mean")(ctx_of(ops, before, after)) == pytest.approx(64.0)


def test_counter_readers():
    after = {"moe_load_max": 1500, "moe_load_mean": 1000.0}
    assert reader("expert_load_max_over_mean")(ctx_of({}, None, after)) == pytest.approx(1.5)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_or_a_trace_leaves_the_metric_out(name):
    """The parent commit has no routing counters, a run without
    ``--trace 1`` no trace, a dense configuration no experts: the
    reader returns None and does not raise."""
    stats = {"tokens": 5, "chunks": 1}
    read = reader(name)
    assert read(ctx_of({"fusion_bf16_32_2048_": {"count": 1, "seconds": 0.1}},
                       stats, stats)) in (None, 0.0)
    bare = ctx_of({}, None, None)
    bare["trace"] = None
    assert read(bare) is None
    dense = ctx_of({"fusion_f32_32_64_": {"count": 1, "seconds": 0.1}}, stats, stats)
    dense["config"] = manifest.load_json(
        os.path.join(manifest.BENCH_DIR, "configs", "gpt2-large.json"))
    assert read(dense) is None


def test_the_configuration_keeps_the_source_and_names_its_cuts():
    c = config()
    source = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
              "hidden_size": 2048, "intermediate_size": 1024, "max_position_embeddings": 4096,
              "model_type": "olmoe", "norm_topk_prob": False, "num_attention_heads": 16,
              "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 16,
              "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
              "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}
    changed = {k for k, v in source.items() if c[k] != v}
    assert changed == set(c["reduced"]) == {"num_hidden_layers", "max_position_embeddings"}
    assert all(c["model"][k] == c[k] for k in source)
    served = {p["name"]: p["value"] for p in
              c["deployment"]["predictors"][0]["graph"]["parameters"]}
    assert served["arch"] == "olmoe"
    assert (int(served["d_model"]), int(served["num_layers"]), int(served["num_heads"])) == (
        c["model"]["n_embd"], c["model"]["n_layer"], c["model"]["n_head"]) == (2048, 8, 16)
    assert int(served["max_len"]) == c["max_position_embeddings"] == c["engine"]["max_len"]
    assert {"qk_norm", "router_float32", "weights"} <= set(c["assumed"])
    entry = [e for e in manifest.load_manifest()["configs"] if e["name"] == "olmoe-1b-7b"][0]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
