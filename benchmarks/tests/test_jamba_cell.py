"""The ``jamba2-3b`` configuration (PR 54): a toy size of it through
``run.py --rehearse-cpu`` (state-space layers with a state a lane beside
multi-query layers over K/V pages under a tied head, served by the
deployer as the cell serves it: ``arch``, ``arch_sizes``, the reference,
the counters over HTTP), the five new readers on a recorded fixture of
operation names, ``ssm_work.py``'s arithmetic, and the shipped
configuration against its source, its declared tree and its traffic."""

import json
import os
import subprocess
import sys

import pytest

import build_tree
from harness import lengths, manifest, warmup

CELL = "tiny-jamba.tiny-chat"
SHIPPED = "jamba2-3b.short-chat-saturated"
NEW = ("ssm_state_roofline", "ssm_scan_roofline", "ssm_time_share_pct",
       "ssm_state_share_pct", "jamba_step_mfu_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dest = build_tree.build(str(tmp_path_factory.mktemp("checkout")))
    path = os.path.join(dest, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "tiny-jamba", "source": "none: rehearsal", "reduced": [],
                         "file": "benchmarks/configs/tiny-jamba.json", "why": "rehearsal"})
    m["workloads"].append({"name": CELL, "config": "tiny-jamba", "traffic": "tiny-chat",
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
         "--seed", str(2**31 + 154), "--seconds", "4", "--trace", trace, "--rehearse-cpu"],
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
    # the counters' reader found the program's gauge; the trace's readers
    # found no device plane on the CPU and left their metric out
    assert set(NEW) & set(got) == {"ssm_state_share_pct"}
    assert 0 < got["ssm_state_share_pct"]["value"] < 100
    assert "decode_ctx_tokens_mean" in got and "kv_pool_used_pct" in got


# ---------------------------------------------------------------------------
# the readers on a recorded fixture
# ---------------------------------------------------------------------------

def config():
    return manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs", "jamba2-3b.json"))


PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def ctx_of(ops, before=None, after=None, busy_s=1.0, cfg=None, samples=()):
    return {"trace": {"busy_s": busy_s, "window_s": 1.25 * busy_s, "ops": ops},
            "config": cfg or config(), "peaks": PEAKS, "device": {"count": 1},
            "engine": {"trace": [before, after], "window": [before, after],
                       "samples": list(samples)}}


def reader(name):
    return manifest.reader("layer_metrics", name)


# operation names as ``trace_reduce.stable_op_name`` writes them, at the
# cell's sizes (256 slots, a state of 16 x 5,120, prefill groups of 8 x 512
# and 4 x 256), both forms of the step and of the scan
OPS = {
    "pallas_kernel_f32_256_16_5120_": {"count": 208, "seconds": 0.300},  # ssm_state_step
    "fusion_f32_256_16_5120_": {"count": 26, "seconds": 0.100},          # XLA's form of it
    "pallas_kernel_f32_8_512_5120_": {"count": 26, "seconds": 0.040},    # ssm_scan, y first
    "pallas_kernel_f32_4_256_5120_": {"count": 26, "seconds": 0.010},
    "fusion_f32_8_16_5120_": {"count": 512, "seconds": 0.050},           # XLA's carried state
    "fusion_f32_8_512_5120_": {"count": 52, "seconds": 0.020},           # convolution, Delta
    "fusion_f32_256_5120_": {"count": 208, "seconds": 0.010},            # ... of a decode step
    "fusion_f32_8_512_192_": {"count": 26, "seconds": 0.004},            # W_x's product
    "fusion_f32_256_160_": {"count": 208, "seconds": 0.002},             # delta, normed
    "fusion_bf16_256_3_5120_": {"count": 208, "seconds": 0.004},         # the tail
    "fusion_bf16_8_512_10240_": {"count": 26, "seconds": 0.080},         # in_proj's output
    "fusion_bf16_256_5120_": {"count": 208, "seconds": 0.030},           # y . silu(z), bf16
    "pallas_kernel_f32_256_32_128_": {"count": 16, "seconds": 0.020},    # the page loop
    "pallas_kernel_bf16_160_512_128_": {"count": 2, "seconds": 0.008},   # the causal kernel
    "fusion_f32_256_65536_": {"count": 8, "seconds": 0.040},             # the tied head
    "fusion_bf16_256_8192_": {"count": 224, "seconds": 0.300},           # the SwiGLU
}


def test_the_arithmetic_of_a_lane_step_and_of_a_position():
    from layer_metrics import ssm_work

    z = ssm_work.sizes(config())
    assert (z["ssm_layers"], z["full_layers"], z["slots"], z["channels"], z["low_rank"],
            z["head_dim"]) == (26, 2, 256, 5120, 192, 128)
    assert ssm_work.step_bytes(z) == 2 * 16 * 5120 * 4 == 2 * 327_680
    assert ssm_work.position_bytes(z) == 12 * 5120 + 8 * 16 == 61_568
    assert ssm_work.position_flops(z) == 9 * 5120 * 16 == 737_280
    # the bytes bound a position (75 ns against 3.7: no vector peak is
    # published), and a lane-step outright
    assert ssm_work.scan_least_seconds(z, 1e6, PEAKS) == pytest.approx(1e6 * 61_568 / 819e9)
    assert ssm_work.step_least_seconds(z, 1e3, PEAKS) == pytest.approx(1e3 * 655_360 / 819e9)
    # a mapped page: K and V of the two attention layers, one head of 128
    assert ssm_work.page_bytes(z) == 2 * 2 * 64 * 128 * 2 == 65_536
    # the program's own account of a lane's state (bf16 tail beside it)
    sys.path.insert(0, manifest.ROOT)
    from seldon_core_tpu.models.spec import JAMBA

    assert JAMBA.state_bytes(28) == 26 * (327_680 + 3 * 5120 * 2) == 9_318_400
    assert JAMBA.state_shape(256) == (256, 16, 5120)
    # a configuration without state-space layers has no sizes
    for other in ("olmo-hybrid-7b", "ling-3.0-flash", "gpt2-large", "smallthinker-21b-a3b"):
        cfg = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs", other + ".json"))
        assert ssm_work.sizes(cfg) is None
    # ... and this one is no linear-attention configuration
    from layer_metrics import delta_work, kda_work

    assert delta_work.sizes(config()) is None and kda_work.sizes(config()) is None


def test_the_operations_are_found_by_their_whole_shape_in_both_forms():
    from layer_metrics import ssm_work

    z = ssm_work.sizes(config())
    found = {rule.__name__: {k for k in OPS if rule(k, z)}
             for rule in (ssm_work.is_step, ssm_work.is_scan, ssm_work.is_mixer_rows)}
    assert found["is_step"] == {"pallas_kernel_f32_256_16_5120_", "fusion_f32_256_16_5120_"}
    assert found["is_scan"] == {"pallas_kernel_f32_8_512_5120_", "pallas_kernel_f32_4_256_5120_",
                                "fusion_f32_8_16_5120_"}
    assert found["is_mixer_rows"] == {
        "fusion_f32_8_512_5120_", "fusion_f32_256_5120_", "fusion_f32_8_512_192_",
        "fusion_f32_256_160_", "fusion_bf16_256_3_5120_"}
    # no operation is counted twice, and the attention layers' are nobody's
    assert not (found["is_step"] & found["is_scan"])
    assert not (found["is_scan"] & found["is_mixer_rows"])
    assert ssm_work.seconds_of({"ops": OPS}, z, ssm_work.is_step) == pytest.approx(0.4)


def test_the_readers_on_the_fixture_and_on_a_program_without_the_counters():
    before = {"ssm_lane_steps": 10, "ssm_prefill_positions": 100, "prefill_tokens": 0,
              "prefills": 0, "decode_lane_steps": 0, "decode_kv_tokens": 0}
    # 8 steps of 250 lanes and two prefill calls (8 x 512, 4 x 256), 26 layers
    after = {"ssm_lane_steps": 10 + 8 * 250 * 26,
             "ssm_prefill_positions": 100 + (8 * 512 + 4 * 256) * 26,
             "prefill_tokens": 3600, "prefills": 12, "decode_lane_steps": 2000,
             "decode_kv_tokens": 2000 * 450}
    samples = [{"ssm_state_bytes": 256 * 9_318_400, "pool_pages_used": 1900,
                "pool_pages_total": 6144}] * 3
    ctx = ctx_of(OPS, before, after, busy_s=1.1, samples=samples)
    assert reader("ssm_state_roofline")(ctx) == pytest.approx(
        100 * 52_000 * 655_360 / 819e9 / 0.4)
    assert reader("ssm_scan_roofline")(ctx) == pytest.approx(
        100 * 5120 * 26 * 61_568 / 819e9 / 0.1)
    assert reader("ssm_time_share_pct")(ctx) == pytest.approx(100 * (0.4 + 0.1 + 0.04) / 1.1)
    state, pages = 256 * 9_318_400, 1900 * 65_536
    assert reader("ssm_state_share_pct")(ctx) == pytest.approx(100 * state / (state + pages))
    for name in NEW:
        assert 0 < reader(name)(ctx) < 100, name
    # a program without the counters (the parent) gives no reading, and does not raise
    bare = ctx_of(OPS, {"tokens": 1}, {"tokens": 2}, samples=[{"pool_pages_used": 3}])
    assert [reader(name)(bare) for name in NEW if name != "ssm_time_share_pct"] == [None] * 4
    assert all(reader(name)(ctx_of(OPS)) is None for name in NEW if name != "ssm_time_share_pct")
    # a trace without such operations: nothing to read
    none = {"fusion_f32_8_": {"count": 1, "seconds": 1.0}}
    assert all(reader(name)(ctx_of(none, before, after)) is None for name in NEW[:3])
    # a cell without state-space layers: nothing to read
    for other in ("olmo-hybrid-7b", "olmoe-1b-7b", "gpt2-large"):
        cfg = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs", other + ".json"))
        assert all(reader(name)(ctx_of(OPS, before, after, cfg=cfg, samples=samples)) is None
                   for name in NEW)
    # ... and the linear-attention readers read nothing in this cell
    for name in ("delta_state_roofline", "delta_scan_roofline", "delta_time_share_pct",
                 "delta_state_share_pct", "olmo_hybrid_step_mfu_pct", "gqa_kernel_roofline"):
        assert reader(name)(ctx_of(OPS, before, after, samples=samples)) is None, name


def test_the_whole_step_s_share_reads_no_operation_and_no_program():
    """``jamba_step_mfu_pct`` is sealed against ``trace["ops"]`` and
    ``trace["modules"]``: counters, sizes, peak and the interval alone."""
    from layer_metrics import ssm_work

    class Sealed(dict):
        def __getitem__(self, key):
            assert key not in ("ops", "modules"), key
            return dict.__getitem__(self, key)

        def get(self, key, default=None):
            assert key not in ("ops", "modules"), key
            return dict.get(self, key, default)

    before = dict.fromkeys(ssm_work.COUNTERS, 0)
    after = {"prefill_tokens": 3600, "prefills": 12, "decode_lane_steps": 2000,
             "decode_kv_tokens": 2000 * 450}
    ctx = {"trace": Sealed(window_s=0.25, busy_s=0.2), "config": config(), "peaks": PEAKS,
           "device": {"count": 1}, "engine": {"trace": [before, after]}}
    got = reader("jamba_step_mfu_pct")(ctx)
    flops = ssm_work.needed_flops(config(), after)
    assert got == pytest.approx(100 * flops / (197e12 * 0.25)) and 0 < got < 100
    # a token's matrices: 2 FLOP a parameter of the layers, the tied head
    # (once a lane-step and once a prompt) and the attention apart
    layers = 26 * 104.16e6 + 2 * 76.68e6
    per_token = (flops - 2 * 2560 * 65_536 * 2012
                 - 4 * 2560 * 2 * (2000 * 450 + 12 * 300 * 300 / 2)) / 5600
    assert 2 * layers < per_token < 2.02 * layers
    assert reader("jamba_step_mfu_pct")(dict(ctx, engine={"trace": [None, None]})) is None


# ---------------------------------------------------------------------------
# the shipped configuration
# ---------------------------------------------------------------------------

def source():
    rows = [json.loads(line) for line in open(CATALOG)]
    return next(r for r in rows if r["name"] == "AI21-Jamba2-3B")


def test_the_configuration_holds_its_source_twice_and_names_its_one_cut():
    cfg = config()
    assert cfg["reduced"] == ["max_position_embeddings"]
    assert cfg["published"] == {"max_position_embeddings": 262144}
    m = manifest.load_json(manifest.MANIFEST)
    entry = next(c for c in m["configs"] if c["name"] == "jamba2-3b")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert entry["file"] == "benchmarks/configs/jamba2-3b.json" and len(entry["why"]) <= 200
    if os.path.exists(CATALOG):
        row = source()
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert cfg["model"][key] == cfg[key], key          # the two blocks agree
            if key == "max_position_embeddings":
                assert cfg[key] == 1536 and cfg["published"][key] == value == 262144
                assert key in cfg["reduced_why"]
            else:
                assert cfg[key] == value, key                  # nothing else moved
    # every width as published, all 28 layers, the whole vocabulary
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["vocab_size"], cfg["num_hidden_layers"]) == (
                2560, 8192, 20, 1, 65536, 28)
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_expand"],
            cfg["mamba_dt_rank"], cfg["mamba_conv_bias"], cfg["mamba_proj_bias"]) == (
                16, 4, 2, 160, True, False)
    assert (cfg["attn_layer_period"], cfg["attn_layer_offset"], cfg["num_experts"],
            cfg["tie_word_embeddings"]) == (14, 7, 1, True)
    # the derived order of the layer types: attention at 7 and 21, 26 : 2
    types = cfg["model"]["layer_types"]
    assert len(types) == 28 and [i for i, t in enumerate(types) if t == "attention"] == [7, 21]
    assert {"weights", "ssm_ranges", "tied_embedding_range", "layer_types", "mixer",
            "pre_norm", "state_float32", "head_dim", "positions", "dense_ffn",
            "fused_projections", "judgement"} <= set(cfg["assumed"])
    assert cfg["deployment_stands_for"] == "one chip, the whole model, one replica"
    params = {p["name"]: p["value"]
              for p in cfg["deployment"]["predictors"][0]["graph"]["parameters"]}
    assert params["arch"] == "jamba" and cfg["reference"] == "jamba"
    assert cfg["kind"] == "generation_state_deep" and "arch_sizes" not in params
    assert (int(params["d_model"]), int(params["num_layers"]), int(params["num_heads"]),
            int(params["vocab_size"])) == (2560, 28, 20, 65536)
    engine = cfg["engine"]
    assert engine == {"page_size": 64, "max_len": 1536, "max_slots": 256, "steps_per_call": 8,
                      "prompt_buckets": [16, 32, 64, 128, 256, 512]}
    for key in ("max_len", "page_size", "max_slots", "steps_per_call"):
        assert int(params[key]) == engine[key]
    assert json.loads(params["prompt_buckets"]) == engine["prompt_buckets"]
    # every slot can reach max_len
    assert int(params["num_pages"]) == 256 * 1536 // 64 + 1 == 6145


def test_the_file_s_bytes_are_the_declared_tree_s():
    """Weights (the head counted once), state and pool as ``reduced_why``
    reckons them, against the tree the program itself declares."""
    cfg = config()
    sys.path.insert(0, manifest.ROOT)
    import jax

    from reference import jamba as ref
    from seldon_core_tpu.models.spec import JAMBA, declared_tree

    spec, sizes = ref.spec_and_config(cfg["model"])
    assert spec == JAMBA  # the published spec, nothing given moved it
    assert spec.layer_kinds == tuple(
        "full" if t == "attention" else "ssm" for t in cfg["model"]["layer_types"])
    assert sizes == dict(vocab_size=65536, d_model=2560, num_layers=28, num_heads=20)
    tree = declared_tree(spec, dict(sizes, max_len=1536))
    assert "head" not in tree and tree["tok_embed"]["embedding"].shape == (65536, 2560)
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))
    resting = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree_util.tree_leaves(tree))
    mamba = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree["block_0"]))
    attention = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree["block_7"]))
    assert abs(mamba - 104.16e6) < 0.02e6 and abs(attention - 76.68e6) < 0.02e6
    assert abs(count - 3029e6) < 1e6 and abs(resting - 6.06e9) < 0.01e9
    state = 256 * spec.state_bytes(28)
    pool = 6145 * 64 * 128 * 2 * 2 * spec.cache_layers(28)
    assert spec.cache_layers(28) == 2 and spec.cache_width(2560) == 128
    assert abs(state - 2.39e9) < 0.01e9 and abs(pool - 0.40e9) < 0.01e9
    assert abs(resting + state + pool - 8.85e9) < 0.02e9
    assert (resting + state + pool) / (15.75 * 2**30) > 0.25  # the driver's floor


def test_the_traffic_is_the_issue_s_and_every_request_lands_on_a_warmed_program():
    m = manifest.load_json(manifest.MANIFEST)
    cellrow, cfg, traffic = manifest.cell(m, SHIPPED)
    assert (traffic["protocol"], traffic["loop"], traffic["clients"], traffic["requests"],
            traffic["max_total"], traffic["pairing_seed"], traffic["content"],
            traffic["warm_group_max"]) == (
                "sse-generate", "closed", 320, 384, 1536, 1, "unique", 8)
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.45,
                                        "min": 129, "max": 512}
    assert traffic["new_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.6,
                                     "min": 64, "max": 1024}
    assert traffic["ramp"] == {"clients_per_step": 32, "step_s": 0.7,
                               "until_first_tokens": 256}
    work = lengths.multiset(traffic)
    assert len(work) == 384 and traffic["clients"] == 320 > cfg["engine"]["max_slots"] == 256
    assert all(129 <= p <= 512 and 64 <= a <= 1024 and p + a <= 1536 for p, a in work)
    assert {warmup.prefill_bucket(p, cfg["engine"]) for p, _a in work} == {256, 512}
    targets = warmup.reachable(cfg["engine"], work, traffic["clients"],
                               traffic["warm_group_max"])
    assert targets["prefill"] == {(b, k) for b in (256, 512) for k in (1, 2, 4, 8)}
    assert cellrow["chips"] == 1 and len(cellrow["why"]) <= 200
    new = [x for x in m["per_layer"] if x["name"] in NEW]
    assert len(new) == 5 and all(x["workloads"] == [SHIPPED] and x["moves"] == "out_tok_s"
                                 for x in new)
    assert [x["name"] for x in m["per_layer"]][-5:] == list(NEW)        # appended, not inserted
    assert {x["layer"] for x in new} == {"state-space layers + a state a lane", "kernels"}
    assert m["workloads"][-1]["name"] == SHIPPED and m["configs"][-1]["name"] == "jamba2-3b"
    out = next(x for x in m["end_to_end"] if x["name"] == "out_tok_s")
    assert out["workloads"][-1] == SHIPPED and out["bound"] == 0.08
    # one cell and one configuration more than the ten and nine before
    assert (len(m["workloads"]), len(m["configs"])) == (11, 10)
    # every listed reader exists
    for x in manifest.metrics_of(m, SHIPPED, "per_layer"):
        assert callable(manifest.reader("layer_metrics", x["name"]))


def test_the_sample_judges_prompts_shorter_than_their_buckets():
    kind = manifest.module("harness/kinds", "generation_state_deep")
    m = manifest.load_json(manifest.MANIFEST)
    _cell, cfg, traffic = manifest.cell(m, SHIPPED)
    work = lengths.multiset(traffic)
    buckets = cfg["engine"]["prompt_buckets"]
    lens = kind.judged_lengths(work, buckets)
    assert lens == [129, 255, 508] and not set(lens) & set(buckets)
    # (the median and the longest go out together: one call padded to 512)
    assert [warmup.prefill_bucket(n, cfg["engine"]) for n in lens] == [256, 256, 512]
    assert max(lens) + kind.SAMPLE_NEW <= traffic["max_total"]


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


def test_the_kind_is_generation_state_s_under_a_limit_read_at_this_depth():
    kind = manifest.module("harness/kinds", "generation_state_deep")
    state = manifest.module("harness/kinds", "generation_state")
    # the sample, the warm-up and the rule are generation_state's own code
    # (``manifest.module`` loads each kind as a module of its own)
    for name in ("serve_sample", "warm_up", "judged_lengths", "compared", "verdict_line",
                 "multiset", "content", "fields", "counters"):
        mine, theirs = getattr(kind, name).__code__, getattr(state, name).__code__
        assert (mine.co_filename, mine.co_firstlineno) == (
            theirs.co_filename, theirs.co_firstlineno), name
    assert (kind.TIE_STDS, kind.WORST_GAP_STDS, kind.SAMPLE_NEW) == (
        state.TIE_STDS, state.WORST_GAP_STDS, state.SAMPLE_NEW)
    assert state.OFF_SHARE_MAX == 0.03 < kind.OFF_SHARE_MAX == 0.06  # the one difference

    def verdict(off, gap):
        served = [2] * off + [0] * (128 - off)
        gaps = [gap] * off + [0.0] * (128 - off)
        return kind.judge(Rows(gaps), None, {}, [{"prompt": [1, 2, 3], "tokens": served}])

    most = int(kind.OFF_SHARE_MAX * 128)
    ok = verdict(most, 0.5)
    assert ok["ok"] and ok["off"] == most == 7 and ok["positions"] == 128
    assert ok["off_share_max"] == 0.06 and ok["worst_gap_max"] == 1.0
    assert not verdict(most + 1, 0.5)["ok"]                       # one more position off
    assert not verdict(1, kind.WORST_GAP_STDS + 0.5)["ok"]        # one position far off
    assert kind.compared(ok) == {"worst_gap_stds": [ok["worst_gap_stds"], 1.0],
                                 "off_share": [most / 128, 0.06]}
    # what generation_state's limit would have refused and this one passes:
    # the stated precision's own reading on the chip (12 and 15 of 384: 3.1, 3.9 %)
    assert verdict(5, 0.2)["ok"] and not state.judge(
        Rows([0.2] * 5 + [0.0] * 123), None, {},
        [{"prompt": [1, 2, 3], "tokens": [2] * 5 + [0] * 123}])["ok"]
