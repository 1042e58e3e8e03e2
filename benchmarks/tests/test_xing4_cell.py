"""The ``xing4.0-29b-a4b`` configuration (PR 45): a toy size of it through
``run.py --rehearse-cpu`` (a residual of four rows over latent attention,
served by the deployer as the cell serves it: ``arch``, ``arch_sizes``,
the reference, the counters over HTTP), the three new readers on a
recorded fixture of operation names, and the shipped configuration
against its source."""

import json
import os
import subprocess
import sys

import pytest

import build_tree
from harness import lengths, manifest, warmup

CELL = "tiny-xing4.tiny-chat"
SHIPPED = "xing4.0-29b-a4b.doc-answer-saturated"
NEW = ("hyper_mix_time_share_pct", "hyper_mix_roofline")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dest = build_tree.build(str(tmp_path_factory.mktemp("checkout")))
    path = os.path.join(dest, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "tiny-xing4", "source": "none: rehearsal", "reduced": [],
                         "file": "benchmarks/configs/tiny-xing4.json", "why": "rehearsal"})
    m["workloads"].append({"name": CELL, "config": "tiny-xing4", "traffic": "tiny-chat",
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
         "--seed", str(2**31 + 145), "--seconds", "4", "--trace", trace, "--rehearse-cpu"],
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
    assert not set(NEW) & set(got)
    assert "decode_ctx_tokens_mean" in got and "kv_pool_used_pct" in got
    assert got["expert_load_max_over_mean"]["value"] >= 1.0


# ---------------------------------------------------------------------------
# the readers on a recorded fixture
# ---------------------------------------------------------------------------

def config():
    return manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "xing4.0-29b-a4b.json"))


PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def ctx_of(ops, before=None, after=None, busy_s=1.0, cfg=None):
    return {"trace": {"busy_s": busy_s, "ops": ops}, "config": cfg or config(),
            "peaks": PEAKS, "engine": {"trace": [before, after], "window": [before, after]}}


def reader(name):
    return manifest.reader("layer_metrics", name)


# operation names as ``trace_reduce.stable_op_name`` writes them, at the
# cell's sizes (4 rows of 3,584, 128 lanes, prompts of 3,072 and 4,096)
OPS = {
    "pallas_kernel_f32_1_4096_3584_": {"count": 12, "seconds": 0.040},   # hyper_pre_mix
    "pallas_kernel_f32_4_4096_3584_": {"count": 12, "seconds": 0.100},   # hyper_post_mix
    "pallas_kernel_f32_1_3072_3584_": {"count": 12, "seconds": 0.030},
    "pallas_kernel_f32_4_3072_3584_": {"count": 12, "seconds": 0.075},
    "pallas_kernel_f32_1_128_3584_": {"count": 96, "seconds": 0.002},    # a decode step's
    "pallas_kernel_f32_4_128_3584_": {"count": 96, "seconds": 0.006},
    "pallas_kernel_f32_128_32_512_": {"count": 48, "seconds": 0.050},    # the latent kernel
    "pallas_kernel_bf16_32_4096_128_": {"count": 6, "seconds": 0.060},   # fused prefill attention
    "pallas_kernel_bf16_16384_1024_": {"count": 10, "seconds": 0.200},   # held experts, a prefill
    "pallas_kernel_f32_2048_3584_": {"count": 40, "seconds": 0.080},     # held experts, a step
    "fusion_f32_128_1_3584_": {"count": 300, "seconds": 0.020},          # a step's rows of d
    "fusion_f32_1_4096_3584_": {"count": 30, "seconds": 0.020},          # XLA: not the kernel
    "fusion_bf16_4096_3584_": {"count": 30, "seconds": 0.030},           # a normed copy
    "fusion_f32_4096_128_": {"count": 24, "seconds": 0.004},             # the coefficients' pad
}


def test_the_arithmetic_of_a_position():
    from layer_metrics import hyper_work

    cfg = config()
    assert hyper_work.streams(cfg) == (4, 3584, 12)
    assert hyper_work.position_bytes(cfg) == 4 * 3584 * 13 == 186_368
    assert hyper_work.position_flops(cfg) == 2 * 14_336 * 24 + 2 * 14_336 + 2 * 14_336 * 5
    # 4.6 FLOP/B: the bytes bound it
    assert hyper_work.least_seconds(cfg, 1e6, PEAKS) == pytest.approx(1e6 * 186_368 / 819e9)
    # the program's own account of the same bytes
    sys.path.insert(0, manifest.ROOT)
    from seldon_core_tpu.ops import hyper

    assert hyper.position_bytes(4, 3584) == hyper_work.position_bytes(cfg)


def test_the_mixing_is_found_by_its_whole_shape():
    from layer_metrics import hyper_work

    cfg = config()
    mine = {k for k in OPS if hyper_work.is_mixing(k, cfg)}
    assert mine == {k for k in OPS if k.startswith("pallas_kernel_f32_")
                    and k.endswith("_3584_") and k.count("_") == 6}
    calls, seconds = hyper_work.mixing_seconds({"ops": OPS}, cfg)
    assert calls == 240 and seconds == pytest.approx(0.253)
    # XLA's form of the rows written (a lane without the kernels) counts too
    assert hyper_work.is_mixing("fusion_f32_4_128_3584_", cfg)
    assert hyper_work.is_mixing("fusion_f32_4_1_4096_3584_", cfg)
    assert not hyper_work.is_mixing("fusion_bf16_4_4096_3584_", cfg)


def test_the_readers_on_the_fixture_and_on_a_cell_without_streams():
    before = {"hyper_prefill_positions": 1000, "hyper_decode_positions": 500}
    # six prompts of 3,072 and 4,096 and eight steps of 128 lanes, 12 sub-layers each
    after = {"hyper_prefill_positions": 1000 + 6 * (3072 + 4096) * 12 // 2,
             "hyper_decode_positions": 500 + 8 * 128 * 12}
    ctx = ctx_of(OPS, before, after, busy_s=1.1)
    assert reader("hyper_mix_time_share_pct")(ctx) == pytest.approx(100 * 0.253 / 1.1)
    positions = 6 * (3072 + 4096) * 6 + 8 * 128 * 12
    assert reader("hyper_mix_roofline")(ctx) == pytest.approx(
        100.0 * positions * 186_368 / 819e9 / 0.253)
    assert 0 < reader("hyper_mix_roofline")(ctx) < 100
    # a program without the counters (the parent) gives no reading, and does not raise
    assert reader("hyper_mix_roofline")(ctx_of(OPS, {"tokens": 1}, {"tokens": 2})) is None
    assert reader("hyper_mix_roofline")(ctx_of(OPS)) is None
    assert reader("hyper_mix_time_share_pct")(ctx_of({"fusion_f32_8_": {
        "count": 1, "seconds": 1.0}})) is None
    # a cell whose residual is one row: nothing to read
    for other in ("gigachat3.1-702b-a36b", "olmoe-1b-7b", "gpt2-large"):
        cfg = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs", other + ".json"))
        assert all(reader(name)(ctx_of(OPS, before, after, cfg=cfg)) is None for name in NEW)
    # the shared readers take the configuration's sizes: 32 heads, rank 512, 64 held
    from layer_metrics import mla_work, step_work

    assert mla_work.latent(config())[:2] == (32, 512)
    assert mla_work.share(config()) == (64, 64, 4, 3584, 1024, 1)
    assert mla_work.is_latent_kernel("pallas_kernel_f32_128_32_512_", config())
    assert step_work.family(config()) == "latent_share"


# ---------------------------------------------------------------------------
# the shipped configuration
# ---------------------------------------------------------------------------

def source():
    """The catalog row's ``config``."""
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")]
    return next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")["config"]


SOURCE = {  # as the catalog holds it
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu",
    "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
    "max_position_embeddings": 262144, "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}


def test_the_configuration_holds_its_source_twice_and_names_every_cut():
    cfg = config()
    if os.path.exists("/opt/skills/guides/model-configs/architectures.jsonl"):
        assert source() == SOURCE
    reduced = set(cfg["reduced"])
    assert reduced == {"num_hidden_layers", "first_k_dense_replace", "vocab_size",
                       "max_position_embeddings", "num_nextn_predict_layers"}
    m = manifest.load_json(manifest.MANIFEST)
    entry = next(c for c in m["configs"] if c["name"] == "xing4.0-29b-a4b")
    assert set(entry["reduced"]) == reduced and entry["source"] == cfg["source"]
    for key, value in SOURCE.items():
        assert cfg["model"][key] == cfg[key], key          # the two blocks agree
        if key in reduced:
            assert cfg[key] != value and cfg["published"][key] == value
            assert key in cfg["reduced_why"]
        else:
            assert cfg[key] == value, key                  # nothing else moved
    assert set(cfg["published"]) == reduced
    # no width is cut, and every expert of a layer is held
    assert (cfg["n_routed_experts"], cfg["model"]["n_routed_experts_published"]) == (64, 64)
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (6, 1, 16384, 4608)
    assert cfg["vocab_size"] * 8 >= SOURCE["vocab_size"]   # an eighth, the floor
    model = cfg["model"]
    assert (model["n_embd"], model["n_layer"], model["n_head"]) == (
        model["hidden_size"], model["num_hidden_layers"], model["num_attention_heads"])
    params = {p["name"]: p["value"]
              for p in cfg["deployment"]["predictors"][0]["graph"]["parameters"]}
    assert params["arch"] == "xing4_0" and cfg["reference"] == "xing4"
    assert json.loads(params["arch_sizes"]) == {
        "experts_held": 64, "expert_offset": 0, "dense_layers": 1}
    assert (int(params["d_model"]), int(params["num_layers"]), int(params["num_heads"]),
            int(params["vocab_size"])) == (3584, 6, 32, 16384)
    engine = cfg["engine"]
    for key in ("max_len", "page_size", "max_slots", "steps_per_call"):
        assert int(params[key]) == engine[key]
    assert json.loads(params["prompt_buckets"]) == engine["prompt_buckets"]
    # every slot can reach max_len
    assert int(params["num_pages"]) == engine["max_slots"] * engine["max_len"] // 64 + 1 == 9217
    # the mixing's unstated details each have their line
    assert {"hc_entry_exit", "hc_norm", "hc_eps", "hc_clamp", "hc_sinkhorn_order",
            "hc_coefficients", "hc_seeded_values", "judgement"} <= set(cfg["assumed"])
    # the program's spec for this block is the published one but for the cut
    sys.path.insert(0, manifest.ROOT)
    from dataclasses import replace

    from reference import xing4 as ref
    from seldon_core_tpu.models.spec import XING4_0

    spec, sizes = ref.spec_and_config(cfg["model"])
    assert spec == replace(XING4_0, experts_held=64, dense_layers=1)
    assert sizes == dict(vocab_size=16384, d_model=3584, num_layers=6, num_heads=32)
    # the arithmetic of reduced_why: 3,971 M parameters, a 4.53 GB pool
    attn = (3584 * 768 + 768 + 768 * 32 * 192 + 3584 * 576 + 512
            + 32 * 512 * (128 + 128) + 32 * 128 * 3584 + 2 * 3584)
    mixing = 2 * (24 * 14_336 + 24 + 3)
    expert = 3 * 3584 * 1024
    held = (6 * (attn + mixing) + 3 * 3584 * 9216
            + 5 * (3584 * 64 + 64 + 65 * expert) + 2 * 16384 * 3584 + 3584)
    assert abs(held - 3.971e9) < 0.01e9
    assert abs(attn - 28.41e6) < 0.02e6 and abs(mixing - 0.69e6) < 0.01e6
    assert int(params["num_pages"]) * 64 * spec.cache_width(3584) * 2 * 6 == 4_530_339_840


def test_the_traffic_is_the_issue_s_and_every_request_lands_on_a_warmed_program():
    m = manifest.load_json(manifest.MANIFEST)
    cellrow, cfg, traffic = manifest.cell(m, SHIPPED)
    assert (traffic["protocol"], traffic["loop"], traffic["clients"], traffic["requests"],
            traffic["max_total"], traffic["pairing_seed"], traffic["content"],
            traffic["warm_group_max"]) == (
                "sse-generate", "closed", 160, 192, 4608, 1, "unique", 1)
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 3072, "sigma": 0.3,
                                        "min": 2049, "max": 4096}
    assert traffic["new_tokens"] == {"dist": "lognormal", "median": 128, "sigma": 0.5,
                                     "min": 32, "max": 512}
    assert traffic["ramp"] == {"clients_per_step": 16, "step_s": 0.7,
                               "until_first_tokens": 128}
    work = lengths.multiset(traffic)
    assert len(work) == 192 and traffic["clients"] == 160 > cfg["engine"]["max_slots"] == 128
    assert all(2049 <= p <= 4096 and 32 <= a <= 512 and p + a <= 4608 for p, a in work)
    assert {warmup.prefill_bucket(p, cfg["engine"]) for p, _a in work} == {3072, 4096}
    targets = warmup.reachable(cfg["engine"], work, traffic["clients"],
                               traffic["warm_group_max"])
    # one prompt a call (the cap is 4,096 positions), three chunk shapes
    assert targets["prefill"] == {(3072, 1), (4096, 1)}
    assert targets["chunk"] == {((128, 64),), ((128, 72),), ((64, 64), (64, 72))}
    # the checked sample — the longest prompt and its 256 tokens — lands there too
    kind = manifest.module("harness/kinds", cfg["kind"])
    longest = max(p for p, _a in work)
    assert cfg["kind"] == "generation_share_whole" and longest == 4096
    assert longest + kind.SAMPLE_NEW <= traffic["max_total"]
    assert warmup.prefill_bucket(longest, cfg["engine"]) == 4096
    assert warmup.horizon(longest + kind.SAMPLE_NEW, cfg["engine"]) == 72
    answers = sorted(a for _p, a in work)
    assert 115 <= answers[len(answers) // 2] <= 140
    assert cellrow["chips"] == 1 and len(cellrow["why"]) <= 200
    new = [x for x in m["per_layer"] if x["name"] in NEW]
    assert len(new) == 2 and all(x["workloads"] == [SHIPPED] and x["moves"] == "out_tok_s"
                                 and x["layer"] == "kernels" for x in new)
    assert [x["name"] for x in m["per_layer"]][-2:] == list(NEW)        # appended, not inserted
    assert m["workloads"][-1]["name"] == SHIPPED and m["configs"][-1]["name"] == "xing4.0-29b-a4b"
    out = next(x for x in m["end_to_end"] if x["name"] == "out_tok_s")
    assert out["workloads"][-1] == SHIPPED and out["bound"] == 0.08
    mfu = next(x for x in m["per_layer"] if x["name"] == "step_mfu_pct")
    assert SHIPPED in mfu["workloads"]


# ---------------------------------------------------------------------------
# the kind's judgement: generation_share's rule under limits of its own
# ---------------------------------------------------------------------------

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
        std = rows[0].std()
        for j, far in enumerate(self.far):
            rows[j, 2] = 1.0 - far * rows[j].std()
        assert abs(rows[0].std() - std) < 0.05
        return rows


def test_the_kind_judges_one_prompt_under_its_own_two_limits():
    kind = manifest.module("harness/kinds", "generation_share_whole")
    share = manifest.module("harness/kinds", "generation_share")
    assert (kind.SAMPLE_NEW, kind.TIE_STDS, kind.FAR_STDS) == (256, share.TIE_STDS, 2.0)
    assert share.OFF_SHARE_MAX < kind.OFF_SHARE_MAX < 0.5

    def verdict(off, gap, far=0):
        served = [2] * (off + far) + [0] * (256 - off - far)
        gaps = [gap] * off + [4.5] * far + [0.0] * (256 - off - far)
        return kind.judge(Rows(gaps), None, {}, [{"prompt": [1, 2, 3], "tokens": served}])

    most = int(kind.OFF_SHARE_MAX * 256)
    ok = verdict(most, 0.5)
    assert ok["ok"] and ok["off"] == most and ok["positions"] == 256 and ok["far"] == 0
    assert ok["exact"] == 256 - most and ok["prompt_lens"] == [3]
    assert not verdict(most + 1, 0.5)["ok"]                       # one more position off
    few = int(kind.FAR_SHARE_MAX * 256)
    assert verdict(0, 0.5, far=few)["ok"]            # a flipped decision or two: sound
    stale = verdict(0, 0.5, far=8)                   # a chunk of stale rows is not
    assert not stale["ok"] and stale["far"] == 8 and stale["worst_gap_stds"] > 3
    assert kind.compared(ok) == {"off_share": [most / 256, kind.OFF_SHARE_MAX],
                                 "far_share": [0.0, kind.FAR_SHARE_MAX]}
    assert "ok=True" in kind.verdict_line(ok) and "ok=False" in kind.verdict_line(stale)
    # the sample: the longest prompt alone, 256 tokens or what max_total leaves
    sent = []

    class Served:
        traffic = {"max_total": 4608}

    kind.run_wave = lambda served, wave, seed, serial: (sent.append(wave) or [([7], [8])])
    assert kind.serve_sample(Served(), [(2049, 40), (4096, 300), (3000, 90)], 5) == [
        {"prompt": [7], "tokens": [8]}]
    assert sent[0]["requests"] == [(4096, 256)] and sent[0]["blocker"] is False
