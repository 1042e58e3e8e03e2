"""The ``longcat-flash-omni`` configuration at a toy size through
``run.py --rehearse-cpu`` (a double layer behind a softmax router over
real and identity experts, served by the deployer as the cell serves it:
``arch``, ``arch_sizes``, the reference, the counters over HTTP), the
four new readers on a recorded fixture of operation names, and the
shipped configuration against its source and its own arithmetic."""

import json
import os
import subprocess
import sys

import pytest

import build_tree
from harness import lengths, manifest, warmup

CELL = "tiny-longcat.tiny-chat"
GROWING = "tiny-longcat.tiny-think"
SHIPPED = "longcat-flash-omni.think-saturated"
NEW = ("zero_expert_pick_share_pct", "real_experts_per_tok_mean",
       "shortcut_moe_time_share_pct", "dense_pair_time_share_pct")
JOINED = ("mla_kernel_roofline", "mla_kernel_time_share_pct", "expert_share_decode_roofline",
          "experts_held_active_mean", "routed_local_share_pct", "held_experts_time_share_pct",
          "expert_load_max_over_mean")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dest = build_tree.build(str(tmp_path_factory.mktemp("checkout")))
    path = os.path.join(dest, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "tiny-longcat", "source": "none: rehearsal", "reduced": [],
                         "file": "benchmarks/configs/tiny-longcat.json", "why": "rehearsal"})
    m["workloads"].append({"name": CELL, "config": "tiny-longcat", "traffic": "tiny-chat",
                           "chips": 1, "why": "rehearsal"})
    m["workloads"].append({"name": GROWING, "config": "tiny-longcat", "traffic": "tiny-think",
                           "chips": 1, "why": "rehearsal"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if SHIPPED in metric.get("workloads", []):
            metric["workloads"] += [CELL, GROWING]
    with open(path, "w") as f:
        json.dump(m, f, indent=1)
    return dest


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_double_layer_cell_rehearses(tree, trace):
    out = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 131), "--seconds", "4", "--trace", trace, "--rehearse-cpu"],
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
    assert 0 < got["zero_expert_pick_share_pct"]["value"] < 100
    assert 0 < got["real_experts_per_tok_mean"]["value"] < 4
    assert got["real_experts_per_tok_mean"]["value"] == pytest.approx(
        4 * (1 - got["zero_expert_pick_share_pct"]["value"] / 100))
    assert 0 < got["experts_held_active_mean"]["value"] <= 4
    assert 0 < got["routed_local_share_pct"]["value"] < 100
    assert got["expert_load_max_over_mean"]["value"] >= 1.0
    assert not {"shortcut_moe_time_share_pct", "dense_pair_time_share_pct",
                "mla_kernel_roofline", "expert_share_decode_roofline"} & set(got)


def test_block_tables_reached_by_decoding_alone_are_warmed_beside_a_grown_stream(tree):
    """``generation_share_long``'s warm-up: prompts of 17-24 tokens and
    answers of 60-100, so the 16-page table is ten chunks past any
    prompt: one stream is grown to it and its short partners are sent
    beside it; nothing is left for the window to compile."""
    out = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmarks", "run.py"), "--workload", GROWING,
         "--seed", str(2**31 + 137), "--seconds", "4", "--trace", "0", "--rehearse-cpu"],
        cwd=tree, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    setup = json.loads(next(line for line in out.stdout.splitlines()
                            if line.startswith("[bench] set-up: ")).partition(": ")[2])
    warm = setup["warm_up"]
    assert warm["grown"] == [16] and not warm["missing"], warm
    assert warm["met"]["chunk"] >= warm["targets"]["chunk"] and warm["rounds"] == 1
    assert "COMPILED INSIDE THE WINDOW" not in out.stdout


def test_a_prefill_group_past_the_engine_s_cap_is_no_warm_up_target(monkeypatch):
    from harness.kinds import generation_share, generation_share_long as kind

    for name in ("judge", "serve_sample", "verdict_line", "content", "multiset", "counters"):
        assert getattr(kind, name) is getattr(generation_share, name)
    assert (kind.OFF_SHARE_MAX, kind.WORST_GAP_STDS, kind.TIE_STDS, kind.SAMPLE_NEW) == (
        0.03, 2.0, 0.09, 128)
    m = manifest.load_json(manifest.MANIFEST)
    _cell, cfg, traffic = manifest.cell(m, SHIPPED)
    assert cfg["kind"] == "generation_share_long"
    work = lengths.multiset(traffic)
    met = {"prefill": set(), "chunk": set()}
    waves = []

    class Server:
        @staticmethod
        def log_text():
            return ""

    class Served:
        config, base = cfg, "http://none"

    Served.traffic = traffic
    monkeypatch.setattr(kind, "positions_cap", lambda served: 2048)
    monkeypatch.setattr(kind.warmup, "warmed", lambda text: met)
    monkeypatch.setattr(kind, "run_wave", lambda served, wave, seed, serial: waves.append(wave))
    grown = []
    monkeypatch.setattr(kind, "grow_beside", lambda served, engine, h, partners, *a:
                        grown.append((h, partners)))
    report = kind.warm_up(Served, Server, work, seed=5)
    # (1024, 4) is 4,096 positions: the engine serves it as two calls of (1024, 2)
    assert report["targets"] == {"prefill": 5, "chunk": 10} and report["grown"] == [48]
    assert grown == [(48, [8, 16, 32])] * 3              # nothing was met: three rounds
    assert not any("48" in w["for"] for w in waves)
    assert any(w["for"].startswith("prefill (512, 4)") for w in waves)
    assert not any(w["for"].startswith("prefill (1024, 4)") for w in waves)
    monkeypatch.setattr(kind, "positions_cap", lambda served: None)   # a program that says none
    assert kind.warm_up(Served, Server, work, seed=5)["targets"]["prefill"] == 6


def test_a_program_without_the_counters_leaves_the_new_metrics_out():
    """The parent has no such counter and no such configuration: the
    readers return nothing and do not raise."""
    before, after = {"moe_assignments": 1}, {"moe_assignments": 2}
    parent = ctx_of({}, before, after)
    assert all(reader(name)(parent) is None for name in NEW)
    other = dict(ctx_of(OPS, COUNTS[0], COUNTS[1]), config=manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "gigachat3.1-702b-a36b.json")))
    assert all(reader(name)(other) is None for name in NEW)
    assert all(reader(name)({}) is None for name in NEW)


# ---------------------------------------------------------------------------
# the readers on a recorded fixture
# ---------------------------------------------------------------------------

def config():
    return manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "longcat-flash-omni.json"))


PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def ctx_of(ops, before=None, after=None, busy_s=1.0):
    return {"trace": {"busy_s": busy_s, "ops": ops}, "config": config(), "peaks": PEAKS,
            "engine": {"trace": [before, after], "window": [before, after]}}


def reader(name):
    return manifest.reader("layer_metrics", name)


# operation names as ``trace_reduce.stable_op_name`` writes them, at the
# cell's sizes (128 slots in buckets of 64, 64 heads, rank 512, hidden
# 6144, dense 12288, experts of 2048, 768 router outputs, 4 layers)
OPS = {
    "pallas_kernel_f32_64_64_512_": {"count": 128, "seconds": 0.20},   # the latent kernel
    "pallas_kernel_f32_128_2048_": {"count": 32, "seconds": 0.10},     # held experts: gate, up
    "pallas_kernel_f32_128_6144_": {"count": 32, "seconds": 0.05},     # held experts: down
    "pallas_kernel_f32_2048_2048_": {"count": 4, "seconds": 0.04},     # a prefill's pass
    "pallas_kernel_s32_17_": {"count": 64, "seconds": 0.01},           # group metadata: 1-D
    "fusion_f32_128_768_": {"count": 32, "seconds": 0.02},             # router: a decode step
    "fusion_f32_2048_768_": {"count": 4, "seconds": 0.01},             # router: b1024_k2
    "fusion_s32_4_784_": {"count": 8, "seconds": 0.003},               # decode accumulator
    "fusion_s32_4_781_": {"count": 4, "seconds": 0.001},               # a prefill's histogram
    "fusion_bf16_128_12288_": {"count": 128, "seconds": 0.30},         # dense gate, up, product
    "fusion_bf16_1024_12288_": {"count": 16, "seconds": 0.06},         # the same of b1024_k1
    "fusion_bf16_128_1_12288_": {"count": 64, "seconds": 0.03},        # W_qb: three dims
    "fusion_bf16_2_1024_12288_": {"count": 8, "seconds": 0.02},        # W_qb of b1024_k2
    "fusion_f32_128_6144_": {"count": 300, "seconds": 0.12},           # dense down, norms, sums
    "fusion_f32_300_12288_": {"count": 1, "seconds": 1.0},             # no row count of ours
    "fusion_bf16_8_6145_64_640_": {"count": 8, "seconds": 0.03},       # a pool write
}
COUNTS = ({"moe_assignments": 1200, "moe_zero_assignments": 400, "moe_routed_tokens": 100,
           "moe_held_active_expert_steps": 0, "moe_layer_steps": 0, "moe_local_assignments": 30,
           "latent_kv_tokens": 0, "moe_held_pass_rows": 128},
          {"moe_assignments": 1200 + 12_000_000, "moe_zero_assignments": 400 + 4_200_000,
           "moe_routed_tokens": 100 + 1_000_000, "moe_held_active_expert_steps": 448,
           "moe_layer_steps": 32, "moe_local_assignments": 30 + 250_000,
           "latent_kv_tokens": 150_000_000, "moe_held_pass_rows": 128})


def test_the_counter_readers():
    ctx = ctx_of({}, *COUNTS)
    assert reader("zero_expert_pick_share_pct")(ctx) == pytest.approx(35.0)
    assert reader("real_experts_per_tok_mean")(ctx) == pytest.approx(7.8)
    # the joined readers' counts are the program's own: 14 of 16 held experts a
    # (layer, step), 2.083 % of all picks (16 of 768 when even)
    assert reader("experts_held_active_mean")(ctx) == pytest.approx(14.0)
    assert reader("routed_local_share_pct")(ctx) == pytest.approx(100 * 250_000 / 12_000_000)


def test_the_shortcut_and_the_dense_pair_are_found_by_whole_shape():
    from layer_metrics import longcat_work

    cfg = config()
    assert longcat_work.double(cfg) == (6144, 12288, 2048, 768, 512, 12, 4)
    assert sorted(longcat_work.shortcut_keys({"ops": OPS}, cfg)) == sorted([
        "pallas_kernel_f32_128_2048_", "pallas_kernel_f32_128_6144_",
        "pallas_kernel_f32_2048_2048_", "fusion_f32_128_768_", "fusion_f32_2048_768_",
        "fusion_s32_4_784_", "fusion_s32_4_781_"])
    assert sorted(longcat_work.dense_pair_keys({"ops": OPS}, cfg)) == [
        "fusion_bf16_1024_12288_", "fusion_bf16_128_12288_"]
    ctx = ctx_of(OPS, *COUNTS, busy_s=2.0)
    assert reader("shortcut_moe_time_share_pct")(ctx) == pytest.approx(100 * 0.224 / 2.0)
    assert reader("dense_pair_time_share_pct")(ctx) == pytest.approx(100 * 0.36 / 2.0)
    none = {"fusion_f32_8_": {"count": 1, "seconds": 1.0}}
    assert reader("shortcut_moe_time_share_pct")(ctx_of(none)) is None
    assert reader("dense_pair_time_share_pct")(ctx_of(none)) is None
    # the bytes and FLOPs of a decode layer-step, kept with the benchmark
    assert longcat_work.dense_pair_bytes(cfg) == 2 * 3 * 6144 * 12288 * 2 == 905_969_664
    assert longcat_work.expert_bytes(cfg) == 3 * 6144 * 2048 * 2 == 75_497_472
    assert longcat_work.router_bytes(cfg) == 6144 * 768 * 4
    assert longcat_work.dense_pair_flops(cfg, 128) == 2 * 6 * 6144 * 12288 * 128
    assert longcat_work.expert_flops(cfg, 32) == 6 * 6144 * 2048 * 32


def test_the_joined_readers_count_this_block_right_as_they_stand():
    """``mla_work``'s readers take their counts from the program's own
    counters (8 attentions, 4 expert layers, 12 picks of 768) and their
    shapes from keys the ``model`` block repeats under DeepSeek's names:
    the latent kernel ``(lanes, 64, 512)``, the decode pass's rows, no
    shared expert."""
    from layer_metrics import mla_work

    cfg = config()
    assert mla_work.latent(cfg) == (64, 512, 64, 128, 128, 1536, 6144, 4)
    assert mla_work.share(cfg) == (16, 512, 12, 6144, 2048, 0)
    assert mla_work.row_bytes(cfg) == 1152 and mla_work.row_flops(cfg) == 139264
    assert mla_work.matrix_bytes(cfg) == 6144 * 2048 * 2
    ctx = ctx_of(OPS, *COUNTS)
    assert mla_work.latent_kernel_seconds({"ops": OPS}, cfg) == (128, 0.20)
    assert reader("mla_kernel_time_share_pct")(ctx) == pytest.approx(20.0)
    assert reader("mla_kernel_roofline")(ctx) == pytest.approx(
        100.0 * 150e6 * 1152 / 819e9 / 0.20)
    # 448 held experts hit over 32 (layer, step)s, three matrices each, no shared
    # expert's bytes or seconds; the decode pass's kernels are those of 128 rows
    assert reader("expert_share_decode_roofline")(ctx) == pytest.approx(
        100.0 * 3 * 448 * 6144 * 2048 * 2 / 819e9 / 0.15)
    all_hit = [COUNTS[0], dict(COUNTS[1], moe_held_active_expert_steps=16 * 32)]
    at_peak = dict(OPS, **{
        "pallas_kernel_f32_128_2048_": {"count": 32, "seconds": 32 * 16 * 2 * 6144 * 2048 * 2 / 819e9},
        "pallas_kernel_f32_128_6144_": {"count": 32, "seconds": 32 * 16 * 6144 * 2048 * 2 / 819e9}})
    assert reader("expert_share_decode_roofline")(ctx_of(at_peak, *all_hit)) == \
        pytest.approx(100.0)
    assert reader("held_experts_time_share_pct")(ctx) == pytest.approx(100 * 0.19)


# ---------------------------------------------------------------------------
# the shipped configuration
# ---------------------------------------------------------------------------

SOURCE = {  # the catalog row's ``config``
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048, "num_layers": 28,
    "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
    "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}


def test_the_configuration_holds_its_source_twice_and_names_every_cut():
    cfg = config()
    reduced = set(cfg["reduced"])
    assert reduced == {"num_layers", "n_routed_experts", "vocab_size",
                       "max_position_embeddings"}
    entry = next(c for c in manifest.load_json(manifest.MANIFEST)["configs"]
                 if c["name"] == "longcat-flash-omni")
    assert set(entry["reduced"]) == reduced and entry["source"] == cfg["source"]
    for key, value in SOURCE.items():
        assert cfg["model"][key] == cfg[key], key          # the two blocks agree
        if key in reduced:
            assert cfg[key] != value and cfg["published"][key] == value
            assert key in cfg["reduced_why"]
        else:
            assert cfg[key] == value, key                  # nothing else moved
    # no width is cut, the router's among them
    m = cfg["model"]
    assert m["n_routed_experts_published"] == 512 and m["zero_expert_num"] == 256
    assert (cfg["n_routed_experts"], cfg["num_layers"], cfg["vocab_size"]) == (16, 4, 16384)
    assert cfg["vocab_size"] * 8 >= SOURCE["vocab_size"]   # an eighth, the floor
    # the names the harness's readers know repeat the source's
    assert (m["num_hidden_layers"], m["num_experts_per_tok"], m["moe_intermediate_size"],
            m["n_shared_experts"]) == (m["num_layers"], m["moe_topk"],
                                       m["expert_ffn_hidden_size"], 0)
    assert (m["n_embd"], m["n_layer"], m["n_head"]) == (
        m["hidden_size"], m["num_layers"], m["num_attention_heads"])
    assert {"norm_topk_prob", "router_bias", "mla_scales", "language_model_only"} <= set(
        cfg["assumed"])
    assert "one chip of 32" in cfg["deployment_stands_for"]
    params = {p["name"]: p["value"]
              for p in cfg["deployment"]["predictors"][0]["graph"]["parameters"]}
    assert params["arch"] == "longcat_flash"
    assert json.loads(params["arch_sizes"]) == {"experts_held": 16, "expert_offset": 0}
    assert (int(params["d_model"]), int(params["num_layers"]), int(params["num_heads"]),
            int(params["vocab_size"])) == (6144, 4, 64, 16384)
    engine = cfg["engine"]
    for key in ("max_len", "page_size", "max_slots", "steps_per_call"):
        assert int(params[key]) == engine[key]
    assert (engine["page_size"], engine["max_len"], engine["max_slots"],
            engine["steps_per_call"], int(params["num_pages"])) == (64, 3072, 128, 8, 6145)
    # every slot can reach max_len
    assert int(params["num_pages"]) == engine["max_slots"] * engine["max_len"] // 64 + 1
    # the program's spec for this block is the published one but for the share
    from dataclasses import replace

    from reference import longcat_flash as ref
    from seldon_core_tpu.models.spec import LONGCAT_FLASH

    spec, sizes = ref.spec_and_config(cfg["model"])
    assert spec == replace(LONGCAT_FLASH, experts_held=16)
    assert sizes == dict(vocab_size=16384, d_model=6144, num_layers=4, num_heads=64)


def test_the_byte_arithmetic_of_the_cut_is_the_engine_s():
    """``reduced_why``'s numbers against what the engine would hold at
    the cell's sizes: shapes only (``jax.eval_shape`` of the program's
    own tree and ``lane_report()``'s ``weight_bytes`` rule, a leaf's
    bytes as it rests), no weight is made."""
    import jax
    import jax.numpy as jnp

    from reference import longcat_flash as ref
    from seldon_core_tpu.models.paged import get_paged_lm_class, paged_hbm_accounting

    cfg = config()
    spec, sizes = ref.spec_and_config(cfg["model"])
    params = {p["name"]: p["value"]
              for p in cfg["deployment"]["predictors"][0]["graph"]["parameters"]}
    lm = get_paged_lm_class()(dtype=jnp.bfloat16, spec=spec, decode_kernel=False, **sizes)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    pool = jax.ShapeDtypeStruct((spec.cache_layers(4), 2, 8, 640), jnp.bfloat16)
    tree = jax.eval_shape(lm.init, jax.random.key(0), i32(1, 8), i32(1, 8), pool, None,
                          i32(1, 1), i32(1))["params"]
    count = sum(int(leaf.size) for leaf in jax.tree_util.tree_leaves(tree))
    # lane_report()["weight_bytes"] is ops/surgery.tree_hbm_bytes: every leaf's nbytes
    weight_bytes = sum(int(leaf.size) * leaf.dtype.itemsize
                       for leaf in jax.tree_util.tree_leaves(tree))
    d, f, dense, heads = 6144, 2048, 12288, 64
    attention = d * 1536 + 1536 * heads * 192 + d * 576 + 512 * heads * 256 + heads * 128 * d
    assert round(attention / 1e6, 2) == 90.57
    assert round(3 * d * dense / 1e6, 2) == 226.49 and round(3 * d * f / 1e6, 2) == 37.75
    layer = 2 * attention + 2 * 3 * d * dense + d * 768
    assert round(layer / 1e6, 1) == 638.8
    assert round((layer + 512 * 3 * d * f) * 2 / 1e9, 1) == 39.9        # a whole layer: 39.9 GB
    held = 4 * (layer + 16 * 3 * d * f) + 2 * 16384 * d
    assert round(held / 1e6) == 5173
    norms = 4 * (4 * d + 2 * 1536 + 2 * 512 + 768) + d     # scales and the correction bias
    assert count == held + norms
    # bf16 at rest; the router, its bias and the norm scales in float32
    f32 = 4 * d * 768 + norms
    assert weight_bytes == 2 * (count - f32) + 4 * f32
    assert round(2 * held / 1e9, 2) == 10.35               # at 2 B a parameter
    assert weight_bytes == 10_383_495_168                  # as it rests: reduced_why's figure
    assert "10,383,495,168" in cfg["reduced_why"]["num_layers"]
    # the pool: 8 attention sub-layers of 640 lanes, 6145 pages of 64 tokens
    pool_bytes = spec.cache_layers(4) * int(params["num_pages"]) * 64 * spec.cache_width(d) * 2
    assert pool_bytes == 8 * 6145 * 64 * 640 * 2 == 4_027_187_200
    assert spec.cache_width(d) * 2 * 8 == 10_240           # bytes a token
    priced = paged_hbm_accounting(
        streams=128, ctx_len=3072, d_model=640, num_layers=8, cache_pools=1,
        chunk_impl="pool", weight_bytes=weight_bytes)
    assert priced["pool_bytes"] == pool_bytes - 8 * 64 * 640 * 2        # less the trash page
    total = weight_bytes + pool_bytes
    assert round(total / 1e9, 2) == 14.41 and 13.4 < total / 2**30 < 13.45
    assert total > 0.25 * 16 * 2**30                       # the driver's floor


def test_the_traffic_reaches_sixteen_programs_and_every_request_fits():
    m = manifest.load_json(manifest.MANIFEST)
    cellrow, cfg, traffic = manifest.cell(m, SHIPPED)
    work = lengths.multiset(traffic)
    assert len(work) == 192 and traffic["clients"] == 160 > cfg["engine"]["max_slots"] == 128
    assert all(257 <= p <= 1024 and 256 <= a <= 2048 and p + a <= 3072 for p, a in work)
    assert {warmup.prefill_bucket(p, cfg["engine"]) for p, _a in work} == {512, 1024}
    assert (traffic["max_total"], traffic["pairing_seed"], traffic["warm_group_max"],
            traffic["protocol"], traffic["loop"]) == (3072, 1, 4, "sse-generate", "closed")
    assert traffic["ramp"] == {"clients_per_step": 16, "step_s": 0.7,
                               "until_first_tokens": 128}
    targets = warmup.reachable(cfg["engine"], work, traffic["clients"],
                               traffic["warm_group_max"])
    assert targets["prefill"] == {(b, k) for b in (512, 1024) for k in (1, 2, 4)}
    horizons = (8, 16, 32, 48)
    assert targets["chunk"] == (
        {((128, h),) for h in horizons}
        | {((64, a), (64, b)) for a in horizons for b in horizons if a < b})
    prompts, answers = sorted(p for p, _a in work), sorted(a for _p, a in work)
    assert 480 <= prompts[len(prompts) // 2] <= 540
    assert 900 <= answers[len(answers) // 2] <= 1100
    # the checked sample: the shortest, the median and the longest prompt, 128 tokens each
    sample = prompts[0] + prompts[len(prompts) // 2] + prompts[-1] + 3 * 128
    assert sample == 257 + 514 + 1024 + 384
    assert cellrow["chips"] == 1 and len(cellrow["why"]) <= 200
    new = [x for x in m["per_layer"] if x["name"] in NEW]
    assert len(new) == 4 and all(SHIPPED in x["workloads"] and x["moves"] == "out_tok_s"
                                 for x in new)
    for name in JOINED + ("out_tok_s", "decode_step_ms", "hbm_peak_gib"):
        metric = next(x for x in m["end_to_end"] + m["per_layer"] if x["name"] == name)
        assert SHIPPED in metric["workloads"], name
    for name in ("moe_time_share_pct", "moe_decode_roofline", "experts_active_mean",
                 "paged_kernel_roofline", "paged_decode_roofline", "kernel_time_share_pct"):
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        assert SHIPPED not in metric["workloads"], name
    assert SHIPPED in [w["name"] for w in m["workloads"]]
    assert "longcat-flash-omni" in [c["name"] for c in m["configs"]]


def test_the_plain_reference_s_own_continuation_is_correct_and_a_wrong_one_is_not():
    import numpy as np
    from harness.kinds import generation_share as kind
    from reference import longcat_flash as ref

    model = manifest.load_json(os.path.join(
        os.path.dirname(__file__), "fixtures", "add", "configs", "tiny-longcat.json"))["model"]
    params = ref.make_params(model, 11)
    prompt = np.random.default_rng(0).integers(0, model["vocab_size"], size=40).tolist()
    answer = []
    for _ in range(6):
        row = np.asarray(ref.logits(params, model, prompt + answer, tail=1))[0]
        answer.append(int(row.argmax()))
    v = kind.judge(ref, params, model, [{"prompt": prompt, "tokens": answer}])
    assert v["ok"] and v["exact"] == v["positions"] == 6 and v["worst_gap_stds"] == 0.0
    shifted = answer[1:] + answer[:1]                   # each token one position early
    bad = kind.judge(ref, params, model, [{"prompt": prompt, "tokens": shifted}])
    assert not bad["ok"] and bad["off"] > 0.03 * 6
    assert config()["kind"] == "generation_share_long"
    assert config()["reference"] == "longcat_flash"
