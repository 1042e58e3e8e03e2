"""The ``gigachat3.1-702b-a36b`` configuration at a toy size through
``run.py --rehearse-cpu`` (a latent-attention model holding a share of
its experts, served by the deployer as the cell serves it: ``arch``,
``arch_sizes``, the reference, the counters over HTTP), the six new
readers on a recorded fixture of operation names, and the shipped
configuration against its source."""

import json
import os
import subprocess
import sys

import pytest

import build_tree
from harness import lengths, manifest, warmup

CELL = "tiny-gigachat.tiny-chat"
SHIPPED = "gigachat3.1-702b-a36b.long-answer-saturated"
NEW = ("mla_kernel_roofline", "mla_kernel_time_share_pct", "expert_share_decode_roofline",
       "experts_held_active_mean", "routed_local_share_pct", "held_experts_time_share_pct")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dest = build_tree.build(str(tmp_path_factory.mktemp("checkout")))
    path = os.path.join(dest, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "tiny-gigachat", "source": "none: rehearsal", "reduced": [],
                         "file": "benchmarks/configs/tiny-gigachat.json", "why": "rehearsal"})
    m["workloads"].append({"name": CELL, "config": "tiny-gigachat", "traffic": "tiny-chat",
                           "chips": 1, "why": "rehearsal"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if SHIPPED in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(m, f, indent=1)
    return dest


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_latent_cell_rehearses(tree, trace):
    out = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 113), "--seconds", "4", "--trace", trace, "--rehearse-cpu"],
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
    assert 0 < got["experts_held_active_mean"]["value"] <= 4
    assert 0 < got["routed_local_share_pct"]["value"] < 100
    assert not {"mla_kernel_roofline", "mla_kernel_time_share_pct", "held_experts_time_share_pct",
                "expert_share_decode_roofline"} & set(got)
    assert got["expert_load_max_over_mean"]["value"] >= 1.0
    assert "decode_ctx_tokens_mean" in got and "kv_pool_used_pct" in got


# ---------------------------------------------------------------------------
# the readers on a recorded fixture
# ---------------------------------------------------------------------------

def config():
    return manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "gigachat3.1-702b-a36b.json"))


PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def ctx_of(ops, before=None, after=None, busy_s=1.0):
    return {"trace": {"busy_s": busy_s, "ops": ops}, "config": config(), "peaks": PEAKS,
            "engine": {"trace": [before, after], "window": [before, after]}}


def reader(name):
    return manifest.reader("layer_metrics", name)


# operation names as ``trace_reduce.stable_op_name`` writes them, at the
# cell's sizes (128 slots in buckets of 64, 64 heads, rank 512)
OPS = {
    "pallas_kernel_f32_64_64_512_": {"count": 96, "seconds": 0.20},    # the latent kernel
    "pallas_kernel_f32_128_64_512_": {"count": 12, "seconds": 0.05},   # one bucket of 128
    "pallas_kernel_bf16_128_2048_": {"count": 80, "seconds": 0.22},    # held experts: gate, up
    "pallas_kernel_f32_128_7168_": {"count": 40, "seconds": 0.10},     # held experts: down
    "pallas_kernel_s32_9_": {"count": 120, "seconds": 0.01},           # group metadata: 1-D
    "pallas_kernel_bf16_8192_2048_": {"count": 10, "seconds": 0.30},   # a prefill's pass
    "fusion_bf16_128_2048_": {"count": 80, "seconds": 0.08},           # shared gate, up
    "fusion_bf16_128_1_1536_": {"count": 48, "seconds": 0.01},         # W_qa
    "fusion_bf16_128_1_12288_": {"count": 48, "seconds": 0.03},        # W_qb
    "fusion_bf16_128_1_576_": {"count": 48, "seconds": 0.01},          # W_kva
    "fusion_f32_64_64_512_": {"count": 96, "seconds": 0.02},           # W_uk into q, a bucket
    "fusion_f32_64_128_192_": {"count": 48, "seconds": 0.02},          # W_uv, heads leading
    "fusion_bf16_128_1_7168_": {"count": 48, "seconds": 0.04},         # W_o
    "fusion_f32_128_1_7168_": {"count": 300, "seconds": 0.06},         # residual, FFN outputs
    "fusion_f32_128_256_": {"count": 40, "seconds": 0.01},             # router
    "fusion_bf16_4096_1536_": {"count": 10, "seconds": 0.09},          # a prefill's W_qa
    "fusion_bf16_6_8193_64_640_": {"count": 8, "seconds": 0.03},       # a pool write
}


def test_the_latent_kernel_is_found_by_its_whole_shape_and_read_against_rows_needed():
    from layer_metrics import mla_work

    cfg = config()
    assert mla_work.row_bytes(cfg) == 1152 and mla_work.row_flops(cfg) == 139264
    assert mla_work.latent_kernel_seconds({"ops": OPS}, cfg) == (108, 0.25)
    before, after = {"latent_kv_tokens": 10**6}, {"latent_kv_tokens": 10**6 + 150_000_000}
    ctx = ctx_of(OPS, before, after)
    assert reader("mla_kernel_time_share_pct")(ctx) == pytest.approx(25.0)
    # bytes bound it: 1,152 B / 819 GB/s = 1.41 ns a row against
    # 139,264 FLOP / 197 TFLOP/s = 0.71 ns
    assert reader("mla_kernel_roofline")(ctx) == pytest.approx(
        100.0 * 150e6 * 1152 / 819e9 / 0.25)
    # a kernel at the HBM peak that moves the 640 lanes reads 90: never over 100
    at_peak = dict(OPS, **{"pallas_kernel_f32_64_64_512_":
                           {"count": 96, "seconds": 150e6 * 1280 / 819e9 - 0.05}})
    assert reader("mla_kernel_roofline")(ctx_of(at_peak, before, after)) == pytest.approx(90.0)
    for name in ("mla_kernel_roofline", "mla_kernel_time_share_pct"):
        assert reader(name)(ctx_of({"pallas_kernel_f32_16_1_2048_": {"count": 1, "seconds": 1}},
                                   before, after)) is None
    olmoe = dict(ctx, config=manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "olmoe-1b-7b.json")))
    assert all(reader(name)(olmoe) is None for name in NEW)


def test_expert_share_roofline_cannot_pass_100_when_every_held_expert_is_hit():
    layer_steps = 5 * 8 * 10                      # routed layers x steps x chunks
    one = 7168 * 2048 * 2                         # one of an expert's three matrices
    assert 3 * one == 88_080_384
    # 8 held experts whole, and the shared expert's gate and up: its down
    # projection's seconds cannot be found, so its bytes are not asked for
    floor_s = layer_steps * (8 * 3 + 2) * one / 819e9
    ops = {"pallas_kernel_bf16_128_2048_": {"count": 2 * layer_steps, "seconds": 0.5 * floor_s},
           "pallas_kernel_f32_128_7168_": {"count": layer_steps, "seconds": 0.3 * floor_s},
           "fusion_bf16_128_2048_": {"count": 2 * layer_steps, "seconds": 0.2 * floor_s},
           "pallas_kernel_bf16_8192_2048_": {"count": 16, "seconds": 5.0}}
    before = {"moe_held_active_expert_steps": 7, "moe_layer_steps": 20, "moe_held_pass_rows": 128}
    after = dict(before, moe_held_active_expert_steps=7 + 8 * layer_steps,
                 moe_layer_steps=20 + layer_steps)
    assert reader("expert_share_decode_roofline")(ctx_of(ops, before, after)) == \
        pytest.approx(100.0)
    half = dict(after, moe_held_active_expert_steps=7 + 4 * layer_steps)
    assert reader("expert_share_decode_roofline")(ctx_of(ops, before, half)) == \
        pytest.approx(100.0 * (4 * 3 + 2) / (8 * 3 + 2))
    # the rows of a pass are the engine's to say: a program that does not has no reading,
    # and one that says another count finds other kernels
    silent = [{k: v for k, v in d.items() if k != "moe_held_pass_rows"} for d in (before, after)]
    assert reader("expert_share_decode_roofline")(ctx_of(ops, *silent)) is None
    other = [dict(d, moe_held_pass_rows=8192) for d in (before, after)]
    assert reader("expert_share_decode_roofline")(ctx_of(ops, *other)) == \
        pytest.approx(100.0 * floor_s / (5.0 + 0.2 * floor_s))


def test_the_held_experts_time_share_is_every_grouped_matmul_over_busy():
    ctx = ctx_of(OPS, busy_s=2.0)
    # gate and up 0.22, down 0.10, a prefill's pass 0.30; not the 1-D metadata kernel,
    # the 3-D latent kernel or the shared expert
    assert reader("held_experts_time_share_pct")(ctx) == pytest.approx(100.0 * 0.62 / 2.0)
    assert reader("held_experts_time_share_pct")(ctx_of({"fusion_f32_8_": {
        "count": 1, "seconds": 1.0}})) is None


def test_the_counters_readers():
    before = {"moe_held_active_expert_steps": 0, "moe_layer_steps": 0,
              "moe_local_assignments": 10, "moe_assignments": 100}
    after = {"moe_held_active_expert_steps": 790, "moe_layer_steps": 100,
             "moe_local_assignments": 10 + 3125, "moe_assignments": 100 + 100_000}
    ctx = ctx_of({}, before, after)
    assert reader("experts_held_active_mean")(ctx) == pytest.approx(7.9)
    assert reader("routed_local_share_pct")(ctx) == pytest.approx(3.125)
    loaded = ctx_of({}, before, dict(after, moe_load_max=60, moe_load_mean=40.0))
    assert reader("expert_load_max_over_mean")(loaded) == pytest.approx(1.5)
    parent = ctx_of({}, {"moe_assignments": 1}, {"moe_assignments": 2})  # no such counters
    assert reader("experts_held_active_mean")(parent) is None
    assert reader("routed_local_share_pct")(parent) is None


# ---------------------------------------------------------------------------
# the shipped configuration
# ---------------------------------------------------------------------------

SOURCE = {  # the catalog row's ``config``
    "vocab_size": 128256, "max_position_embeddings": 262144, "hidden_size": 7168,
    "intermediate_size": 18432, "moe_intermediate_size": 2048, "num_hidden_layers": 64,
    "num_nextn_predict_layers": 1, "num_attention_heads": 64, "n_shared_experts": 1,
    "n_routed_experts": 256, "ep_size": 1, "routed_scaling_factor": 2.5, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 192, "qk_nope_head_dim": 128,
    "topk_method": "noaux_tc", "n_group": 8, "topk_group": 4, "num_experts_per_tok": 8,
    "moe_layer_freq": 1, "first_k_dense_replace": 3, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "num_key_value_heads": 64, "hidden_act": "silu",
    "rms_norm_eps": 1e-06, "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "rope_type": "yarn"},
    "attention_bias": False, "tie_word_embeddings": False, "model_type": "deepseek_v3"}


def test_the_configuration_holds_its_source_twice_and_names_every_cut():
    cfg = config()
    reduced = set(cfg["reduced"])
    assert reduced == {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
                       "vocab_size", "max_position_embeddings", "num_nextn_predict_layers"}
    for key, value in SOURCE.items():
        assert cfg["model"][key] == cfg[key], key          # the two blocks agree
        if key in reduced:
            assert cfg[key] != value and cfg["published"][key] == value
            assert key in cfg["reduced_why"]
        else:
            assert cfg[key] == value, key                  # nothing else moved
    # no width is cut, the router's among them
    assert cfg["model"]["n_routed_experts_published"] == 256
    assert (cfg["n_routed_experts"], cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["vocab_size"]) == (8, 6, 1, 16032)
    assert cfg["vocab_size"] * 8 >= SOURCE["vocab_size"]   # an eighth, the floor
    m = cfg["model"]
    assert (m["n_embd"], m["n_layer"], m["n_head"]) == (
        m["hidden_size"], m["num_hidden_layers"], m["num_attention_heads"])
    params = {p["name"]: p["value"]
              for p in cfg["deployment"]["predictors"][0]["graph"]["parameters"]}
    assert params["arch"] == "deepseek_v3"
    assert json.loads(params["arch_sizes"]) == {
        "experts_held": 8, "expert_offset": 0, "dense_layers": 1}
    assert (int(params["d_model"]), int(params["num_layers"]), int(params["num_heads"]),
            int(params["vocab_size"])) == (7168, 6, 64, 16032)
    engine = cfg["engine"]
    for key in ("max_len", "page_size", "max_slots"):
        assert int(params[key]) == engine[key]
    # every slot can reach max_len
    assert int(params["num_pages"]) == engine["max_slots"] * engine["max_len"] // 64 + 1
    # the program's spec for this block is the published one but for the share
    from reference import deepseek_v3 as ref
    from seldon_core_tpu.models.spec import DEEPSEEK_V3
    from dataclasses import replace

    spec, sizes = ref.spec_and_config(cfg["model"])
    assert spec == replace(DEEPSEEK_V3, experts_held=8, dense_layers=1)
    assert sizes == dict(vocab_size=16032, d_model=7168, num_layers=6, num_heads=64)
    # the arithmetic of reduced_why: 6.84 GB at rest, a 4.03 GB pool
    per_layer_attn = 7168 * 1536 + 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 \
        + 64 * 512 * (128 + 192) + 64 * 192 * 7168 + 2 * 7168
    expert = 3 * 7168 * 2048
    held = (6 * per_layer_attn + 3 * 7168 * 18432
            + 5 * (7168 * 256 + 256 + 9 * expert) + 2 * 16032 * 7168 + 7168)
    assert abs(held - 3.413e9) < 0.02e9
    assert int(params["num_pages"]) * 64 * spec.cache_width(7168) * 2 * 6 == 4_027_023_360


def test_the_traffic_reaches_twelve_programs_and_every_request_fits():
    m = manifest.load_json(manifest.MANIFEST)
    _cell, cfg, traffic = manifest.cell(m, SHIPPED)
    work = lengths.multiset(traffic)
    assert len(work) == 192 and traffic["clients"] == 160 > cfg["engine"]["max_slots"] == 128
    assert all(513 <= p <= 2048 and 256 <= a <= 2048 and p + a <= 4096 for p, a in work)
    assert {warmup.prefill_bucket(p, cfg["engine"]) for p, _a in work} == {1024, 2048}
    targets = warmup.reachable(cfg["engine"], work, traffic["clients"],
                               traffic["warm_group_max"])
    assert targets["prefill"] == {(b, k) for b in (1024, 2048) for k in (1, 2, 4)}
    assert targets["chunk"] == (
        {((128, h),) for h in (16, 32, 64)}
        | {((64, a), (64, b)) for a, b in ((16, 32), (16, 64), (32, 64))})
    answers = sorted(a for _p, a in work)
    assert 900 <= answers[len(answers) // 2] <= 1100
    cellrow = next(w for w in m["workloads"] if w["name"] == SHIPPED)
    assert cellrow["chips"] == 1 and len(cellrow["why"]) <= 200
    new = [x for x in m["per_layer"] if x["name"] in NEW]
    assert len(new) == 6 and all(SHIPPED in x["workloads"] and x["moves"] == "out_tok_s"
                                 for x in new)
    # how uneven the held experts' load is: OLMoE's reader, which reads the engine's own
    # ``moe_load_max`` / ``moe_load_mean`` (over the held experts where a share is held)
    load = next(x for x in m["per_layer"] if x["name"] == "expert_load_max_over_mean")
    assert SHIPPED in load["workloads"]
    assert not any(x["name"] == "mla_proj_time_share_pct" for x in m["per_layer"])


# ---------------------------------------------------------------------------
# the kind's judgement: generation's rule at all but a share of a long sample
# ---------------------------------------------------------------------------

def tiny():
    cfg = manifest.load_json(os.path.join(
        os.path.dirname(__file__), "fixtures", "add", "configs", "tiny-gigachat.json"))
    return cfg["model"]


class Rows:
    """A reference whose logits are given: row ``j`` of the tail has its
    top at token 0, token 1 ``near`` deviations under it, token 2
    ``far[j]`` deviations under it (``logits`` ignores the weights)."""

    VOCAB = 64

    def __init__(self, far, near=0.05):
        self.far, self.near = far, near

    def logits(self, params, model, tokens, tail=None):
        import numpy as np

        rows = np.zeros((tail, self.VOCAB), np.float32)
        rows[:, 3::2], rows[:, 4::2] = -1.0, -3.0      # the rest: what sets the deviation
        for j in range(tail):
            rows[j, 0] = 4.0
            for _ in range(8):                          # the two gaps move the deviation a little
                std = float(rows[j].std())
                rows[j, 1], rows[j, 2] = 4.0 - self.near * std, 4.0 - self.far[j] * std
        return rows


def judged(off_by, served):
    from harness.kinds import generation_share as kind

    sample = [{"prompt": [5, 6, 7], "tokens": served}]
    return kind, kind.judge(Rows(off_by), None, {}, sample)


@pytest.mark.parametrize("name, served, far, ok, exact, off", [
    ("every token the top-1", [0] * 100, [2.0] * 100, True, 100, 0),
    ("a near-tie is no top-1 and not off", [0] * 99 + [1], [2.0] * 100, True, 99, 0),
    ("three of a hundred off is the share", [2] * 3 + [0] * 97, [0.3] * 100, True, 97, 3),
    ("four of a hundred is over it", [2] * 4 + [0] * 96, [0.3] * 100, False, 96, 4),
    ("one position off by more than the worst allowed", [2] + [0] * 99, [2.4] + [0.3] * 99,
     False, 99, 1),
    ("one position off by less than that", [2] + [0] * 99, [1.8] + [0.3] * 99, True, 99, 1),
])
def test_the_judgement_counts_off_positions_and_caps_the_worst(name, served, far, ok, exact, off):
    kind, v = judged(far, served)
    assert (v["ok"], v["exact"], v["off"], v["positions"]) == (ok, exact, off, 100), name
    assert v["off_share"] == off / 100 and v["off_share_max"] == kind.OFF_SHARE_MAX == 0.03
    assert v["worst_gap_max"] == kind.WORST_GAP_STDS == 2.0 and v["tie_stds"] == 0.09
    # the worst reading is the sample's own, never a second judgement's
    assert v["worst_gap_stds"] == pytest.approx(max(
        [f for f, t in zip(far, served) if t == 2] + [0.05 * (1 in served)]), abs=1e-3)
    assert [p["served"] for p in v["failed"]] == [2] * min(off, 8)
    assert f"ok={ok}" in kind.verdict_line(v) and f"{off} lie over 0.09" in kind.verdict_line(v)


def test_the_plain_reference_s_own_continuation_is_correct_and_a_wrong_one_is_not():
    import numpy as np
    from harness.kinds import generation_share as kind
    from reference import deepseek_v3 as ref

    model = tiny()
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


def test_the_kind_is_generation_but_for_its_sample_and_judgement(monkeypatch):
    from harness.kinds import generation, generation_share as kind

    for name in ("multiset", "content", "fields", "counters", "warm_up", "run_wave"):
        assert getattr(kind, name) is getattr(generation, name)
    assert kind.TIE_STDS == generation.TIE_STDS == 0.09
    assert kind.SAMPLE_NEW == 128 == 16 * generation.SAMPLE_NEW
    assert config()["kind"] == "generation_share"
    assert not os.path.exists(os.path.join(manifest.BENCH_DIR, "harness", "kinds",
                                           "generation_routed.py"))
    # the same three prompts as generation's sample, each asked for SAMPLE_NEW tokens
    waves = []

    def run_wave(served, wave, seed, serial):
        waves.append(wave)
        return [([n], list(range(a))) for n, a in wave["requests"]]

    monkeypatch.setattr(kind, "run_wave", run_wave)
    work = [(513, 300), (700, 256), (1027, 900), (1500, 256), (2048, 2048)]

    class Served:
        traffic = {"max_total": 4096}

    sample = kind.serve_sample(Served, work, seed=3)
    assert [w["requests"] for w in waves] == [[(513, 128)], [(1027, 128), (2048, 128), (2048, 128)]]
    assert [w["blocker"] for w in waves] == [False, True]
    assert [(s["prompt"], len(s["tokens"])) for s in sample] == [([513], 128), ([1027], 128),
                                                                 ([2048], 128)]
    Served.traffic = {"max_total": 2100}                # a mix whose totals leave less room
    del waves[:]
    kind.serve_sample(Served, work, seed=3)
    assert waves[1]["requests"] == [(1027, 128), (2048, 52), (2048, 52)]
