"""The ``ling-3.0-flash`` configuration (PR 52): a toy size of it through
``run.py --rehearse-cpu`` (Kimi-Delta-Attention layers with a state a lane
beside latent attention over one latent pool, a routed FFN inside a linear
layer, served by the deployer as the cell serves it: ``arch``,
``arch_sizes``, the reference, the counters over HTTP), the five new
readers on a recorded fixture of operation names, ``kda_work.py``'s
arithmetic, the kind's judgement, and the shipped configuration against
its source and its declared tree."""

import json
import os
import subprocess
import sys

import pytest

import build_tree
from harness import lengths, manifest, warmup

CELL = "tiny-ling3.tiny-chat"
SHIPPED = "ling-3.0-flash.long-think-saturated"
NEW = ("kda_state_roofline", "kda_scan_roofline", "kda_time_share_pct",
       "kda_state_share_pct", "ling3_step_mfu_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size",
           "max_position_embeddings", "num_nextn_predict_layers",
           "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dest = build_tree.build(str(tmp_path_factory.mktemp("checkout")))
    path = os.path.join(dest, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "tiny-ling3", "source": "none: rehearsal", "reduced": [],
                         "file": "benchmarks/configs/tiny-ling3.json", "why": "rehearsal"})
    m["workloads"].append({"name": CELL, "config": "tiny-ling3", "traffic": "tiny-chat",
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
         "--seed", str(2**31 + 152), "--seconds", "4", "--trace", trace, "--rehearse-cpu"],
        cwd=tree, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["compared"]) >= {"worst_gap_stds", "off_share"}
    if trace == "0":
        assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
        return
    got = result["metrics"]
    # the counters' readers found the program's counters; the trace's
    # readers found no device plane on the CPU and left their metric out
    assert set(NEW) & set(got) == {"kda_state_share_pct"}
    assert 0 < got["kda_state_share_pct"]["value"] < 100
    assert "decode_ctx_tokens_mean" in got and "routed_local_share_pct" in got
    assert 10 < got["routed_local_share_pct"]["value"] < 45  # 8 of 32 outputs held


# ---------------------------------------------------------------------------
# the readers on a recorded fixture
# ---------------------------------------------------------------------------

def config():
    return manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs", "ling-3.0-flash.json"))


PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def ctx_of(ops, before=None, after=None, busy_s=1.0, cfg=None, samples=()):
    return {"trace": {"busy_s": busy_s, "window_s": 1.25 * busy_s, "ops": ops},
            "config": cfg or config(), "peaks": PEAKS, "device": {"count": 1},
            "engine": {"trace": [before, after], "window": [before, after],
                       "samples": list(samples)}}


def reader(name):
    return manifest.reader("layer_metrics", name)


# operation names as ``trace_reduce.stable_op_name`` writes them, at the
# cell's sizes (128 slots, 32 heads of 128 x 128, 12,288 channels, prefill
# calls of 1 x 2048 and 2 x 1024): taken from the cell's first trace on the
# chip (my chip run, PR 52), the seconds made up
OPS = {
    "pallas_kernel_f32_128_32_128_128_": {"count": 80, "seconds": 0.400},  # the state kernel
    "fusion_f32_128_32_3_128_": {"count": 80, "seconds": 0.060},      # its rows: q, k, the decay
    "fusion_f32_128_32_2_128_": {"count": 80, "seconds": 0.030},      # v and beta over the lanes
    "fusion_f32_128_32_1_128_": {"count": 240, "seconds": 0.010},     # q, k, e^{log alpha} a row
    "fusion_f32_1_32_32_64_64_": {"count": 30, "seconds": 0.012},     # a chunk's (64, 64) products
    "fusion_f32_2_32_16_64_256_": {"count": 12, "seconds": 0.006},    # the solved right-hand sides
    "fusion_f32_1_32_32_4_64_128_": {"count": 12, "seconds": 0.004},  # k's columns a diagonal block
    "fusion_f32_32_1_32_64_128_": {"count": 6, "seconds": 0.003},     # the chunk axis first
    "fusion_f32_1_2048_12288_": {"count": 6, "seconds": 0.010},       # the convolution
    "fusion_f32_128_12288_": {"count": 80, "seconds": 0.005},         # ... of a decode step
    "fusion_bf16_128_3_12288_": {"count": 80, "seconds": 0.002},      # its tail
    "fusion_f32_1_2048_4096_": {"count": 10, "seconds": 0.008},       # the gate's projection
    "fusion_f32_128_1_4096_": {"count": 80, "seconds": 0.006},        # ... of a decode step
    "fusion_bf16_1_2048_12288_": {"count": 8, "seconds": 0.050},      # the qkv projection's output
    "fusion_bf16_128_4096_": {"count": 80, "seconds": 0.030},         # the output projection's input
    "pallas_kernel_f32_128_32_512_": {"count": 16, "seconds": 0.060},  # the latent page loop
    "fusion_f32_128_32_128_": {"count": 16, "seconds": 0.004},        # MLA's attended values
    "fusion_bf16_128_32_640_": {"count": 16, "seconds": 0.004},       # the absorbed q
    "fusion_bf16_1_2048_32_192_": {"count": 4, "seconds": 0.008},     # an MLA layer's heads
    "pallas_kernel_bf16_32_2048_128_": {"count": 4, "seconds": 0.020},  # the fused causal kernel
    "pallas_kernel_bf16_128_768_": {"count": 88, "seconds": 0.100},   # held experts, a decode step
    "fusion_f32_128_19648_": {"count": 8, "seconds": 0.010},          # the head
}


def test_the_arithmetic_of_a_lane_step_and_of_a_position():
    from layer_metrics import delta_work, kda_work

    z = kda_work.sizes(config())
    assert (z["kda_layers"], z["mla_layers"], z["slots"], z["channels"]) == (10, 2, 128, 12_288)
    assert kda_work.step_bytes(z) == 2 * 32 * 128 * 128 * 4 == 4_194_304
    assert kda_work.position_bytes(z) == 2 * 4 * 32 * 128 == 32_768
    assert kda_work.position_flops(z) == 7 * 32 * 128 * 128 == 3_670_016
    # the bytes bound a position (40 ns against 19), and a lane-step outright
    assert kda_work.scan_least_seconds(z, 1e6, PEAKS) == pytest.approx(1e6 * 32_768 / 819e9)
    assert kda_work.step_least_seconds(z, 1e3, PEAKS) == pytest.approx(1e3 * 4_194_304 / 819e9)
    # a mapped page: 576 values in 640 lanes in the two latent layers
    assert kda_work.page_bytes(z) == 2 * 64 * 640 * 2 == 163_840
    assert kda_work.row_flops(z) == 2 * 32 * (576 + 512)
    # the program's own account of a lane's state (bf16 tail beside it)
    sys.path.insert(0, manifest.ROOT)
    from seldon_core_tpu.models.spec import BAILING_HYBRID
    from seldon_core_tpu.ops import delta

    assert BAILING_HYBRID.state_bytes(12) == 10 * (4_194_304 // 2 + 3 * 12_288 * 2)
    assert delta.state_shape(128, 32, 128, 128) == (128, 32, 128, 128)
    # another family has no sizes here, and this one none under delta_work
    for other in ("gigachat3.1-702b-a36b", "olmoe-1b-7b", "gpt2-large", "olmo-hybrid-7b"):
        cfg = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs", other + ".json"))
        assert kda_work.sizes(cfg) is None
    assert delta_work.sizes(config()) is None


def test_the_operations_are_found_by_their_whole_shape():
    from layer_metrics import kda_work, mla_work

    z = kda_work.sizes(config())
    found = {rule.__name__: {k for k in OPS if rule(k, z)}
             for rule in (kda_work.is_step, kda_work.is_scan, kda_work.is_conv,
                          kda_work.is_gate)}
    assert found["is_step"] == {"pallas_kernel_f32_128_32_128_128_", "fusion_f32_128_32_3_128_",
                                "fusion_f32_128_32_2_128_", "fusion_f32_128_32_1_128_"}
    assert found["is_scan"] == {
        "fusion_f32_1_32_32_64_64_", "fusion_f32_2_32_16_64_256_",
        "fusion_f32_1_32_32_4_64_128_", "fusion_f32_32_1_32_64_128_"}
    assert found["is_conv"] == {"fusion_f32_1_2048_12288_", "fusion_f32_128_12288_",
                                "fusion_bf16_128_3_12288_"}
    assert found["is_gate"] == {"fusion_f32_1_2048_4096_", "fusion_f32_128_1_4096_"}
    # no operation is counted twice, and latent attention's are nobody's here
    sets = list(found.values())
    assert all(not (a & b) for i, a in enumerate(sets) for b in sets[i + 1:])
    assert kda_work.seconds_of({"ops": OPS}, z, kda_work.is_step) == pytest.approx(0.5)
    # ... while the accepted latent reader finds its kernel, and only it
    assert {k for k in OPS if mla_work.is_latent_kernel(k, config())} == {
        "pallas_kernel_f32_128_32_512_"}
    # the rule the delta_* readers would apply here also takes MLA's attended
    # values (slots, 32 heads, 128): why this cell is not on their lists
    assert [32, 128] == [z["num_attention_heads"], z["v_head_dim"]]


def test_the_readers_on_the_fixture_and_on_a_program_without_the_counters():
    from layer_metrics import kda_work

    before = {"delta_lane_steps": 10, "delta_prefill_positions": 100, "prefill_tokens": 0,
              "prefills": 0, "decode_lane_steps": 0, "latent_kv_tokens": 0,
              "moe_local_assignments": 0}
    # 8 steps of 120 lanes and three prefill calls (1 x 2048, 2 x 1024), 10 KDA layers
    after = {"delta_lane_steps": 10 + 8 * 120 * 10,
             "delta_prefill_positions": 100 + 3 * 2048 * 10,
             "prefill_tokens": 4000, "prefills": 4, "decode_lane_steps": 960,
             "latent_kv_tokens": 2 * 960 * 2000, "moe_local_assignments": 13_000}
    samples = [{"delta_state_bytes": 128 * 21_708_800, "pool_pages_used": 4000,
                "pool_pages_total": 12_288}] * 3
    ctx = ctx_of(OPS, before, after, busy_s=1.1, samples=samples)
    assert reader("kda_state_roofline")(ctx) == pytest.approx(
        100 * 9600 * 4_194_304 / 819e9 / 0.5)
    assert reader("kda_scan_roofline")(ctx) == pytest.approx(
        100 * 61_440 * 32_768 / 819e9 / 0.025)
    assert reader("kda_time_share_pct")(ctx) == pytest.approx(
        100 * (0.5 + 0.025 + 0.017 + 0.014) / 1.1)
    state, pages = 128 * 21_708_800, 4000 * 163_840
    assert reader("kda_state_share_pct")(ctx) == pytest.approx(100 * state / (state + pages))
    for name in NEW:
        assert 0 < reader(name)(ctx) < 100, name
    # a program without the counters (the parent) gives no reading, and does not raise
    bare = ctx_of(OPS, {"tokens": 1}, {"tokens": 2}, samples=[{"pool_pages_used": 3}])
    assert [reader(name)(bare) for name in NEW if name != "kda_time_share_pct"] == [None] * 4
    assert all(reader(name)(ctx_of(OPS)) is None for name in NEW if name != "kda_time_share_pct")
    # a trace without such operations: nothing to read
    none = {"fusion_f32_8_": {"count": 1, "seconds": 1.0}}
    assert all(reader(name)(ctx_of(none, before, after)) is None for name in NEW[:3])
    # a cell of another family: nothing to read
    for other in ("gigachat3.1-702b-a36b", "olmo-hybrid-7b", "gpt2-large"):
        cfg = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs", other + ".json"))
        assert all(reader(name)(ctx_of(OPS, before, after, cfg=cfg, samples=samples)) is None
                   for name in NEW)
    assert kda_work.context(ctx_of(OPS)) is not None


def test_the_whole_step_s_share_reads_no_operation_and_no_program():
    """``ling3_step_mfu_pct`` is sealed against ``trace["ops"]`` and
    ``trace["modules"]``: counters, sizes, peak and the interval alone."""
    from layer_metrics import kda_work

    class Sealed(dict):
        def __getitem__(self, key):
            assert key not in ("ops", "modules"), key
            return dict.__getitem__(self, key)

        def get(self, key, default=None):
            assert key not in ("ops", "modules"), key
            return dict.get(self, key, default)

    before = dict.fromkeys(kda_work.COUNTERS, 0)
    after = {"prefill_tokens": 4000, "prefills": 4, "decode_lane_steps": 960,
             "latent_kv_tokens": 2 * 960 * 2000, "moe_local_assignments": 13_000}
    ctx = {"trace": Sealed(window_s=0.25, busy_s=0.2), "config": config(), "peaks": PEAKS,
           "device": {"count": 1}, "engine": {"trace": [before, after]}}
    got = reader("ling3_step_mfu_pct")(ctx)
    flops = kda_work.needed_flops(config(), after)
    assert got == pytest.approx(100 * flops / (197e12 * 0.25)) and 0 < got < 100
    # a token's matrices: 2 FLOP a parameter it passes — the attentions, the
    # dense FFN, the routers and shared experts (the held experts apart: by
    # assignment) — within the recurrence's and the convolution's few per cent
    passed = 10 * 52.64e6 + 2 * 31.98e6 + 47.19e6 + 11 * (5.90e6 + 1.31e6)
    rest = (6 * 2560 * 768 * 13_000 + 2 * 2560 * 19_648 * 964
            + 2 * 32 * 1088 * after["latent_kv_tokens"]
            + 2 * 2 * 32 * 320 * 4 * 1000 * 1000 / 2)
    per_token = (flops - rest) / 4960
    assert 2 * passed < per_token < 2.1 * passed
    assert reader("ling3_step_mfu_pct")(dict(ctx, engine={"trace": [None, None]})) is None


# ---------------------------------------------------------------------------
# the shipped configuration
# ---------------------------------------------------------------------------

def source():
    rows = [json.loads(line) for line in open(CATALOG)]
    return next(r for r in rows if r["name"] == "Ling-3.0-flash")


def test_the_configuration_holds_its_source_twice_and_names_every_cut():
    cfg = config()
    reduced = set(cfg["reduced"])
    assert cfg["reduced"] == REDUCED
    assert cfg["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2, "num_experts": 512,
        "vocab_size": 157_184, "max_position_embeddings": 262_144,
        "num_nextn_predict_layers": 1,
        "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
        "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2}
    m = manifest.load_json(manifest.MANIFEST)
    entry = next(c for c in m["configs"] if c["name"] == "ling-3.0-flash")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert entry["file"] == "benchmarks/configs/ling-3.0-flash.json" and len(entry["why"]) <= 200
    restated = {"q_lora_rank"}  # null at the top level, the readers' 0 under model
    if os.path.exists(CATALOG):
        row = source()
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in restated:
                assert cfg["model"][key] == cfg[key], key      # the two blocks agree
            if key in reduced:
                assert cfg[key] != value and cfg["published"][key] == value
                assert key in cfg["reduced_why"]
            else:
                assert cfg[key] == value, key                  # nothing else moved
    assert cfg["q_lora_rank"] is None and cfg["model"]["q_lora_rank"] == 0
    # every width as published, two whole periods, the lists' first twelve
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_attention_heads"], cfg["head_dim"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["num_experts_per_tok"], cfg["short_conv_kernel_size"]) == (
                2560, 6144, 768, 32, 128, 512, 128, 64, 128, 8, 4)
    assert (cfg["num_hidden_layers"], cfg["layer_group_size"], cfg["vocab_size"]) == (
        12, 6, 19_648)
    assert cfg["layer_types"] == (["linear_attention"] * 5 + ["full_attention"]) * 2
    assert cfg["expert_swiglu_limit_list"] == [0] * 12 == cfg["share_expert_swiglu_limit_list"]
    model = cfg["model"]
    assert (model["num_experts"], model["num_experts_published"], model["expert_offset"]) == (
        16, 512, 0)
    # the sizes restated under the accepted readers' names are the same sizes
    assert (model["n_routed_experts"], model["n_routed_experts_published"],
            model["n_shared_experts"]) == (model["num_experts"], 512, model["num_shared_experts"])
    assert {"weights", "gate_ranges", "norm_placement", "layer_rule", "qk_norm",
            "output_gates", "gate_form", "linear_heads", "max_window_layers", "state",
            "router_float32", "swiglu_limits", "mtp", "model_block", "judgement"} <= set(
                cfg["assumed"])
    assert cfg["deployment_stands_for"].startswith("one chip of 32 that share each layer")
    params = {p["name"]: p["value"]
              for p in cfg["deployment"]["predictors"][0]["graph"]["parameters"]}
    assert params["arch"] == "bailing_hybrid" and cfg["reference"] == "ling3_flash"
    assert cfg["kind"] == "generation_share_state"
    assert json.loads(params["arch_sizes"]) == {"experts_held": 16, "expert_offset": 0,
                                                "dense_layers": 1}
    assert (int(params["d_model"]), int(params["num_layers"]), int(params["num_heads"]),
            int(params["vocab_size"])) == (2560, 12, 32, 19_648)
    engine = cfg["engine"]
    assert engine == {"page_size": 64, "max_len": 6144, "max_slots": 128, "steps_per_call": 8,
                      "prompt_buckets": [16, 32, 64, 128, 256, 512, 1024, 2048]}
    for key in ("max_len", "page_size", "max_slots", "steps_per_call"):
        assert int(params[key]) == engine[key]
    assert json.loads(params["prompt_buckets"]) == engine["prompt_buckets"]
    # every slot can reach max_len
    assert int(params["num_pages"]) == 128 * 6144 // 64 + 1 == 12_289


def test_the_file_s_bytes_are_the_declared_tree_s():
    """Weights, state and pool as ``reduced_why`` reckons them, against
    the tree the program itself declares at the served sizes."""
    cfg = config()
    sys.path.insert(0, manifest.ROOT)
    import jax

    from reference import ling3_flash as ref
    from seldon_core_tpu.models.spec import BAILING_HYBRID, declared_tree, model_spec

    spec, sizes = ref.spec_and_config(cfg["model"])
    params = {p["name"]: p["value"]
              for p in cfg["deployment"]["predictors"][0]["graph"]["parameters"]}
    served = model_spec("bailing_hybrid", **json.loads(params["arch_sizes"]))
    from dataclasses import replace

    # what the deployment's parameters make is what the reference's keys make
    assert replace(served, layer_kinds=served.layer_kinds[:12]) == replace(
        spec, expert_swiglu_limits=(), shared_swiglu_limits=())
    assert served == replace(BAILING_HYBRID, experts_held=16, dense_layers=1)
    assert sizes == dict(vocab_size=19_648, d_model=2560, num_layers=12, num_heads=32)
    tree = declared_tree(spec, dict(sizes, max_len=6144))
    leaves = jax.tree_util.tree_leaves
    count = sum(leaf.size for leaf in leaves(tree))
    resting = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves(tree))

    def attention(block):
        ffn = ("mlp_", "experts_", "shared_", "router", "score_bias", "ffn_norm")
        return sum(leaf.size for name, sub in block.items() if not name.startswith(ffn)
                   for leaf in leaves(sub))

    kda, mla = attention(tree["block_1"]), attention(tree["block_5"])
    expert_layer = sum(leaf.size for leaf in leaves(tree["block_1"])) - kda
    dense = sum(leaf.size for leaf in leaves(tree["block_0"])) - attention(tree["block_0"])
    assert abs(kda - 52.64e6) < 0.02e6 and abs(mla - 31.98e6) < 0.02e6
    assert abs(expert_layer - 101.58e6) < 0.02e6 and abs(dense - 47.19e6) < 0.01e6
    assert abs(count - 1855e6) < 1e6 and abs(resting - 3.74e9) < 0.01e9
    assert tree["block_1"]["experts_gate"].shape == (16, 2560, 768)
    assert tree["block_1"]["router"].shape == (2560, 512)
    assert tree["block_5"]["q"]["kernel"].shape == (2560, 32 * 192)
    assert tree["block_1"]["a"].shape == (2560, 4096) and tree["block_1"]["a"].dtype.itemsize == 2
    assert tree["block_1"]["dt_bias"].shape == (4096,) and tree["block_1"]["a_log"].shape == (32,)
    assert tree["block_1"]["gate"]["kernel"].shape == (2560, 32)
    # the catalog's own counts: an expert 5.9 M, embedding + head 805 M whole
    assert 3 * 2560 * 768 == 5_898_240 and abs(2 * 157_184 * 2560 - 804.8e6) < 0.1e6
    state = 128 * spec.state_bytes(12)
    pool = 12_289 * 64 * 640 * 2 * spec.cache_layers(12)
    assert abs(state - 2.78e9) < 0.01e9 and abs(pool - 2.01e9) < 0.01e9
    assert abs(resting + state + pool - 8.53e9) < 0.02e9
    assert (resting + state + pool) / (15.75 * 2**30) > 0.25  # the driver's floor


def test_the_traffic_is_the_issue_s_and_every_request_lands_on_a_warmed_program():
    m = manifest.load_json(manifest.MANIFEST)
    cellrow, cfg, traffic = manifest.cell(m, SHIPPED)
    assert (traffic["protocol"], traffic["loop"], traffic["clients"], traffic["requests"],
            traffic["max_total"], traffic["pairing_seed"], traffic["content"],
            traffic["warm_group_max"]) == (
                "sse-generate", "closed", 160, 192, 6144, 1, "unique", 2)
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 0.45,
                                        "min": 513, "max": 2048}
    assert traffic["new_tokens"] == {"dist": "lognormal", "median": 2048, "sigma": 0.5,
                                     "min": 512, "max": 4096}
    assert traffic["ramp"] == {"clients_per_step": 16, "step_s": 0.7,
                               "until_first_tokens": 128}
    work = lengths.multiset(traffic)
    assert len(work) == 192 and traffic["clients"] == 160 > cfg["engine"]["max_slots"] == 128
    assert all(513 <= p <= 2048 and 512 <= a <= 4096 and p + a <= 6144 for p, a in work)
    assert {warmup.prefill_bucket(p, cfg["engine"]) for p, _a in work} == {1024, 2048}
    targets = warmup.reachable(cfg["engine"], work, traffic["clients"],
                               traffic["warm_group_max"])
    assert targets["prefill"] == {(b, k) for b in (1024, 2048) for k in (1, 2)}
    assert cellrow["chips"] == 1 and len(cellrow["why"]) <= 200
    new = [x for x in m["per_layer"] if x["name"] in NEW]
    assert len(new) == 5 and all(x["workloads"] == [SHIPPED] and x["moves"] == "out_tok_s"
                                 for x in new)
    # appended behind what the benchmark had, not inserted (by membership and
    # order, not by being last: the next cell comes behind this one — D17)
    names = [x["name"] for x in m["per_layer"]]
    assert [n for n in names if n in NEW] == list(NEW)
    assert names.index(NEW[0]) > names.index("ttft_idle_host_pct")
    assert {x["layer"] for x in new} == {"linear attention + a state a lane", "kernels"}
    cells = [w["name"] for w in m["workloads"]]
    configs = [c["name"] for c in m["configs"]]
    assert cells.index(SHIPPED) == cells.index("olmo-hybrid-7b.chat-answer-saturated") + 1
    assert configs.index("ling-3.0-flash") == configs.index("olmo-hybrid-7b") + 1
    assert [w for w in m["workloads"] if w["config"] == "ling-3.0-flash"] == [cellrow]
    out = next(x for x in m["end_to_end"] if x["name"] == "out_tok_s")
    assert SHIPPED in out["workloads"] and out["bound"] == 0.08
    listed = {x["name"] for x in manifest.metrics_of(m, SHIPPED, "per_layer")}
    # the delta_* readers' row rule is also MLA's attended values here, and a
    # 3 s trace can miss every prefill or every finished stream
    assert not listed & {"delta_state_roofline", "delta_scan_roofline", "delta_time_share_pct",
                         "delta_state_share_pct", "olmo_hybrid_step_mfu_pct", "step_mfu_pct",
                         "engine_tpot_mean_ms", "prefill_time_share_pct"}
    assert {"mla_kernel_roofline", "expert_share_decode_roofline", "routed_local_share_pct",
            "hbm_peak_gib", "device_idle_pct", "decode_step_ms"} <= listed
    for name in listed:
        assert callable(manifest.reader("layer_metrics", name))


# ---------------------------------------------------------------------------
# the kind: generation_state's sample under generation_share's judgement
# ---------------------------------------------------------------------------

def test_the_sample_judges_prompts_shorter_than_their_buckets():
    kind = manifest.module("harness/kinds", "generation_share_state")
    state = manifest.module("harness/kinds", "generation_state")
    m = manifest.load_json(manifest.MANIFEST)
    _cell, cfg, traffic = manifest.cell(m, SHIPPED)
    work = lengths.multiset(traffic)
    buckets = cfg["engine"]["prompt_buckets"]
    lens = state.judged_lengths(work, buckets)
    assert lens == [513, 1027, 2023] and not set(lens) & set(buckets)
    # the shortest alone in a bucket nearly twice its length; the median and
    # the longest, of different lengths, in one padded call of the 2,048s
    assert [warmup.prefill_bucket(n, cfg["engine"]) for n in lens] == [1024, 2048, 2048]
    assert kind.SAMPLE_NEW >= 128 and max(lens) + kind.SAMPLE_NEW <= traffic["max_total"]
    assert kind.serve_sample.__module__.endswith("generation_state")


def test_the_warm_up_also_warms_the_groups_the_ramp_forms():
    """The traffic says ``warm_group_max`` 2 (a window's waves); the kind
    warms groups of 4 too, which the ramp's 16 callers a step form, and
    hands the traffic back as it was."""
    kind = manifest.module("harness/kinds", "generation_share_state")
    m = manifest.load_json(manifest.MANIFEST)
    _cell, cfg, traffic = manifest.cell(m, SHIPPED)
    seen = []

    class Served:
        config = cfg

    Served.traffic = traffic
    served = Served()
    served.traffic = traffic
    kind._state.warm_up = lambda served, server, work, seed: (
        seen.append(served.traffic["warm_group_max"]) or {"missing": {}})
    assert kind.warm_up(served, None, [], 5) == {"missing": {}}
    assert seen == [4] and served.traffic is traffic and traffic["warm_group_max"] == 2
    work = lengths.multiset(traffic)
    targets = warmup.reachable(cfg["engine"], work, traffic["clients"], kind.RAMP_GROUP)
    assert targets["prefill"] == {(b, k) for b in (1024, 2048) for k in (1, 2, 4)}
    # (4 x 2,048 positions is the engine's cap on the chip: nothing larger forms there)
    assert 4 * 2048 == 8192


class Rows:
    """A reference whose logits are given: row ``j`` of the tail has its
    top at token 0 and token 2 ``far[j]`` deviations under it."""

    VOCAB = 64

    def __init__(self, far):
        self.far = far

    def logits(self, _params, _model, tokens, tail=None):
        import numpy as np

        rows = np.zeros((tail, self.VOCAB), np.float32)
        rows[:, 0] = 1.0
        for j, far in enumerate(self.far):
            rows[j, 2] = 1.0 - far * rows[j].std()
        return rows


def test_the_kind_judges_under_its_own_limits():
    kind = manifest.module("harness/kinds", "generation_share_state")
    share = manifest.module("harness/kinds", "generation_share")
    assert kind.TIE_STDS == share.TIE_STDS
    assert (kind.OFF_SHARE_MAX, kind.WORST_GAP_STDS) == (0.03, 2.0)

    def verdict(off, gap):
        served = [2] * off + [0] * (128 - off)
        gaps = [gap] * off + [0.0] * (128 - off)
        return kind.judge(Rows(gaps), None, {}, [{"prompt": [1, 2, 3], "tokens": served}])

    most = int(kind.OFF_SHARE_MAX * 128)
    ok = verdict(most, 0.5)
    assert ok["ok"] and ok["off"] == most and ok["positions"] == 128
    assert ok["off_share_max"] == kind.OFF_SHARE_MAX and ok["worst_gap_max"] == kind.WORST_GAP_STDS
    assert not verdict(most + 1, 0.5)["ok"]                       # one more position off
    assert not verdict(1, kind.WORST_GAP_STDS + 0.5)["ok"]        # one position far off
    assert kind.compared(ok) == {"worst_gap_stds": [ok["worst_gap_stds"], kind.WORST_GAP_STDS],
                                 "off_share": [most / 128, kind.OFF_SHARE_MAX]}
    assert "ok=True" in kind.verdict_line(ok)
