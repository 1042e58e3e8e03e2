"""The ``olmo-hybrid-7b`` configuration (PR 48): a toy size of it through
``run.py --rehearse-cpu`` (linear-attention layers with a state a lane
beside full layers over K/V pages, served by the deployer as the cell
serves it: ``arch``, ``arch_sizes``, the reference, the counters over
HTTP), the five new readers on a recorded fixture of operation names,
``delta_work.py``'s arithmetic, the kind's sample and judgement, and the
shipped configuration against its source and its declared tree."""

import json
import os
import subprocess
import sys

import pytest

import build_tree
from harness import lengths, manifest, warmup

CELL = "tiny-olmo-hybrid.tiny-chat"
SHIPPED = "olmo-hybrid-7b.chat-answer-saturated"
NEW = ("delta_state_roofline", "delta_scan_roofline", "delta_time_share_pct",
       "delta_state_share_pct", "olmo_hybrid_step_mfu_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dest = build_tree.build(str(tmp_path_factory.mktemp("checkout")))
    path = os.path.join(dest, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "tiny-olmo-hybrid", "source": "none: rehearsal", "reduced": [],
                         "file": "benchmarks/configs/tiny-olmo-hybrid.json", "why": "rehearsal"})
    m["workloads"].append({"name": CELL, "config": "tiny-olmo-hybrid", "traffic": "tiny-chat",
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
         "--seed", str(2**31 + 148), "--seconds", "4", "--trace", trace, "--rehearse-cpu"],
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
    assert set(NEW) & set(got) == {"delta_state_share_pct"}
    assert 0 < got["delta_state_share_pct"]["value"] < 100
    assert "decode_ctx_tokens_mean" in got and "kv_pool_used_pct" in got


# ---------------------------------------------------------------------------
# the readers on a recorded fixture
# ---------------------------------------------------------------------------

def config():
    return manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs", "olmo-hybrid-7b.json"))


PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def ctx_of(ops, before=None, after=None, busy_s=1.0, cfg=None, samples=()):
    return {"trace": {"busy_s": busy_s, "window_s": 1.25 * busy_s, "ops": ops},
            "config": cfg or config(), "peaks": PEAKS, "device": {"count": 1},
            "engine": {"trace": [before, after], "window": [before, after],
                       "samples": list(samples)}}


def reader(name):
    return manifest.reader("layer_metrics", name)


# operation names as ``trace_reduce.stable_op_name`` writes them, at the
# cell's sizes (128 slots, 30 heads of 96 x 192 resting as 15 x 96 x 384,
# 11,520 channels, prefill groups of 4 x 512 and 2 x 256)
OPS = {
    "fusion_f32_128_15_96_384_": {"count": 48, "seconds": 0.300},     # the state, rewritten
    "fusion_f32_128_15_384_": {"count": 96, "seconds": 0.200},        # its rows: S^T k, S^T q
    "fusion_f32_4_30_8_64_64_": {"count": 30, "seconds": 0.012},      # a chunk's (64, 64) masks
    "fusion_f32_4_30_8_64_288_": {"count": 12, "seconds": 0.006},     # the solved right-hand sides
    "fusion_f32_4_30_96_192_": {"count": 48, "seconds": 0.004},       # the scan's carried state
    "fusion_f32_8_2_30_64_192_": {"count": 6, "seconds": 0.003},      # the chunk axis first
    "fusion_f32_4_512_11520_": {"count": 6, "seconds": 0.010},        # the convolution
    "fusion_f32_128_11520_": {"count": 48, "seconds": 0.005},         # ... of a decode step
    "fusion_bf16_128_3_11520_": {"count": 48, "seconds": 0.002},      # its tail
    "fusion_bf16_4_512_11520_": {"count": 8, "seconds": 0.050},       # a projection's output
    "pallas_kernel_f32_128_32_128_": {"count": 16, "seconds": 0.060}, # the page loop, 30 heads
    "fusion_bf16_4_512_30_128_": {"count": 4, "seconds": 0.008},      # a full layer's heads
    "fusion_f32_128_100352_": {"count": 8, "seconds": 0.040},         # the head
    "fusion_bf16_128_11008_": {"count": 64, "seconds": 0.300},        # the SwiGLU
}


def test_the_arithmetic_of_a_lane_step_and_of_a_position():
    from layer_metrics import delta_work

    z = delta_work.sizes(config())
    assert (z["linear_layers"], z["full_layers"], z["slots"], z["pack"], z["channels"]) == (
        6, 2, 128, 2, 11_520)
    assert delta_work.step_bytes(z) == 2 * 30 * 96 * 192 * 4 == 4_423_680
    assert delta_work.position_bytes(z) == 2 * 30 * (2 * 96 + 2 * 192) == 34_560
    assert delta_work.position_flops(z) == 7 * 30 * 96 * 192 == 3_870_720
    # the bytes bound a position (42 ns against 20), and a lane-step outright
    assert delta_work.scan_least_seconds(z, 1e6, PEAKS) == pytest.approx(1e6 * 34_560 / 819e9)
    assert delta_work.step_least_seconds(z, 1e3, PEAKS) == pytest.approx(1e3 * 4_423_680 / 819e9)
    # a mapped page: K and V of the two full layers
    assert delta_work.page_bytes(z) == 2 * 2 * 64 * 3840 * 2
    # the program's own account of a lane's state (bf16 tail beside it)
    sys.path.insert(0, manifest.ROOT)
    from seldon_core_tpu.models.spec import OLMO_HYBRID
    from seldon_core_tpu.ops import delta

    assert OLMO_HYBRID.state_bytes(8) == 6 * (4_423_680 // 2 + 3 * 11_520 * 2)
    assert delta.state_shape(128, 30, 96, 192) == (128, 15, 96, 384)
    # a configuration without linear layers has no sizes
    for other in ("gigachat3.1-702b-a36b", "olmoe-1b-7b", "gpt2-large", "smallthinker-21b-a3b"):
        cfg = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs", other + ".json"))
        assert delta_work.sizes(cfg) is None


def test_the_operations_are_found_by_their_whole_shape():
    from layer_metrics import delta_work

    z = delta_work.sizes(config())
    found = {rule.__name__: {k for k in OPS if rule(k, z)}
             for rule in (delta_work.is_step, delta_work.is_scan, delta_work.is_conv)}
    assert found["is_step"] == {"fusion_f32_128_15_96_384_", "fusion_f32_128_15_384_"}
    assert found["is_scan"] == {
        "fusion_f32_4_30_8_64_64_", "fusion_f32_4_30_8_64_288_", "fusion_f32_4_30_96_192_",
        "fusion_f32_8_2_30_64_192_"}
    assert found["is_conv"] == {"fusion_f32_4_512_11520_", "fusion_f32_128_11520_",
                                "fusion_bf16_128_3_11520_"}
    # no operation is counted twice, and the full layers' are nobody's
    assert not (found["is_step"] & found["is_scan"]) and not (found["is_scan"] & found["is_conv"])
    assert delta_work.seconds_of({"ops": OPS}, z, delta_work.is_step) == pytest.approx(0.5)


def test_the_readers_on_the_fixture_and_on_a_program_without_the_counters():
    before = {"delta_lane_steps": 10, "delta_prefill_positions": 100, "prefill_tokens": 0,
              "prefills": 0, "decode_lane_steps": 0, "decode_kv_tokens": 0}
    # 8 steps of 120 lanes and six prefill calls (4 x 512, 2 x 256), 6 linear layers
    after = {"delta_lane_steps": 10 + 8 * 120 * 6,
             "delta_prefill_positions": 100 + 6 * (4 * 512 + 2 * 256) * 6 // 2,
             "prefill_tokens": 3000, "prefills": 10, "decode_lane_steps": 960,
             "decode_kv_tokens": 960 * 530}
    samples = [{"delta_state_bytes": 128 * 13_685_760, "pool_pages_used": 1200,
                "pool_pages_total": 3072}] * 3
    ctx = ctx_of(OPS, before, after, busy_s=1.1, samples=samples)
    assert reader("delta_state_roofline")(ctx) == pytest.approx(
        100 * 5760 * 4_423_680 / 819e9 / 0.5)
    assert reader("delta_scan_roofline")(ctx) == pytest.approx(
        100 * 7680 * 6 * 34_560 / 819e9 / 0.025)
    assert reader("delta_time_share_pct")(ctx) == pytest.approx(100 * (0.5 + 0.025 + 0.017) / 1.1)
    state, pages = 128 * 13_685_760, 1200 * 1_966_080
    assert reader("delta_state_share_pct")(ctx) == pytest.approx(100 * state / (state + pages))
    for name in NEW:
        assert 0 < reader(name)(ctx) < 100, name
    # a program without the counters (the parent) gives no reading, and does not raise
    bare = ctx_of(OPS, {"tokens": 1}, {"tokens": 2}, samples=[{"pool_pages_used": 3}])
    assert [reader(name)(bare) for name in NEW if name != "delta_time_share_pct"] == [None] * 4
    assert all(reader(name)(ctx_of(OPS)) is None for name in NEW if name != "delta_time_share_pct")
    # a trace without such operations: nothing to read
    none = {"fusion_f32_8_": {"count": 1, "seconds": 1.0}}
    assert all(reader(name)(ctx_of(none, before, after)) is None for name in NEW[:3])
    # a cell without linear layers: nothing to read
    for other in ("gigachat3.1-702b-a36b", "olmoe-1b-7b", "gpt2-large"):
        cfg = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs", other + ".json"))
        assert all(reader(name)(ctx_of(OPS, before, after, cfg=cfg, samples=samples)) is None
                   for name in NEW)


def test_the_whole_step_s_share_reads_no_operation_and_no_program():
    """``olmo_hybrid_step_mfu_pct`` is sealed against ``trace["ops"]`` and
    ``trace["modules"]``: counters, sizes, peak and the interval alone."""
    from layer_metrics import delta_work

    class Sealed(dict):
        def __getitem__(self, key):
            assert key not in ("ops", "modules"), key
            return dict.__getitem__(self, key)

        def get(self, key, default=None):
            assert key not in ("ops", "modules"), key
            return dict.get(self, key, default)

    before = dict.fromkeys(delta_work.COUNTERS, 0)
    after = {"prefill_tokens": 3000, "prefills": 10, "decode_lane_steps": 960,
             "decode_kv_tokens": 960 * 530}
    ctx = {"trace": Sealed(window_s=0.25, busy_s=0.2), "config": config(), "peaks": PEAKS,
           "device": {"count": 1}, "engine": {"trace": [before, after]}}
    got = reader("olmo_hybrid_step_mfu_pct")(ctx)
    flops = delta_work.needed_flops(config(), after)
    assert got == pytest.approx(100 * flops / (197e12 * 0.25)) and 0 < got < 100
    # a token's matrices: 2 FLOP a parameter of the layers, the head apart
    layers = 6 * 215.56e6 + 2 * 185.79e6
    per_token = (flops - 2 * 3840 * 100_352 * 970
                 - 4 * 3840 * 2 * (960 * 530 + 10 * 300 * 300 / 2)) / 3960
    assert 2 * layers < per_token < 2.02 * layers
    assert reader("olmo_hybrid_step_mfu_pct")(dict(ctx, engine={"trace": [None, None]})) is None


# ---------------------------------------------------------------------------
# the shipped configuration
# ---------------------------------------------------------------------------

def source():
    rows = [json.loads(line) for line in open(CATALOG)]
    return next(r for r in rows if r["name"] == "Olmo-Hybrid-7B")


def test_the_configuration_holds_its_source_twice_and_names_every_cut():
    cfg = config()
    reduced = set(cfg["reduced"])
    assert cfg["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert cfg["published"] == {"num_hidden_layers": 32, "max_position_embeddings": 65536}
    m = manifest.load_json(manifest.MANIFEST)
    entry = next(c for c in m["configs"] if c["name"] == "olmo-hybrid-7b")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert entry["file"] == "benchmarks/configs/olmo-hybrid-7b.json" and len(entry["why"]) <= 200
    if os.path.exists(CATALOG):
        row = source()
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert cfg["model"][key] == cfg[key], key          # the two blocks agree
            if key in reduced:
                assert cfg[key] != value and cfg["published"][key] == value
                assert key in cfg["reduced_why"]
            else:
                assert cfg[key] == value, key                  # nothing else moved
    # every width as published, the period whole, layer_types kept whole
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["vocab_size"]) == (3840, 11008, 30, 30, 100352)
    assert (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"],
            cfg["linear_allow_neg_eigval"]) == (30, 96, 192, 4, True)
    assert len(cfg["layer_types"]) == 32 and cfg["num_hidden_layers"] == 8
    assert cfg["layer_types"][:8] == (["linear_attention"] * 3 + ["full_attention"]) * 2
    assert cfg["rope_parameters"] == {"rope_theta": None}
    assert {"norm_placement", "qk_norm", "positions", "state_float32", "weights",
            "gate_ranges", "linear_layer", "vocabulary_whole", "judgement"} <= set(cfg["assumed"])
    assert cfg["deployment_stands_for"].startswith("one chip of a four-chip host")
    params = {p["name"]: p["value"]
              for p in cfg["deployment"]["predictors"][0]["graph"]["parameters"]}
    assert params["arch"] == "olmo_hybrid" and cfg["reference"] == "olmo_hybrid"
    assert cfg["kind"] == "generation_state" and "arch_sizes" not in params
    assert (int(params["d_model"]), int(params["num_layers"]), int(params["num_heads"]),
            int(params["vocab_size"])) == (3840, 8, 30, 100352)
    engine = cfg["engine"]
    assert engine == {"page_size": 64, "max_len": 1536, "max_slots": 128, "steps_per_call": 8,
                      "prompt_buckets": [16, 32, 64, 128, 256, 512]}
    for key in ("max_len", "page_size", "max_slots", "steps_per_call"):
        assert int(params[key]) == engine[key]
    assert json.loads(params["prompt_buckets"]) == engine["prompt_buckets"]
    # every slot can reach max_len
    assert int(params["num_pages"]) == 128 * 1536 // 64 + 1 == 3073


def test_the_file_s_bytes_are_the_declared_tree_s():
    """Weights, state and pool as ``reduced_why`` reckons them, against
    the tree the program itself declares at the served sizes."""
    cfg = config()
    sys.path.insert(0, manifest.ROOT)
    import jax

    from reference import olmo_hybrid as ref
    from seldon_core_tpu.models.spec import OLMO_HYBRID, declared_tree

    spec, sizes = ref.spec_and_config(cfg["model"])
    from dataclasses import replace

    # the published spec with the first eight of its layers named
    assert spec == replace(OLMO_HYBRID, layer_kinds=OLMO_HYBRID.layer_kinds[:8])
    assert sizes == dict(vocab_size=100352, d_model=3840, num_layers=8, num_heads=30)
    tree = declared_tree(spec, dict(sizes, max_len=1536))
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))
    resting = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree_util.tree_leaves(tree))
    linear = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree["block_0"]))
    full = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree["block_3"]))
    assert abs(linear - 215.56e6) < 0.02e6 and abs(full - 185.79e6) < 0.03e6
    assert abs(count - 2435.7e6) < 0.1e6 and abs(resting - 4.87e9) < 0.01e9
    # the whole model by the same two numbers: the catalog's 7.4 B
    assert abs(24 * linear + 8 * full + 2 * 100352 * 3840 - 7430.6e6) < 1e6
    state = 128 * spec.state_bytes(8)
    pool = 3073 * 64 * 3840 * 2 * 2 * spec.cache_layers(8)
    assert abs(state - 1.75e9) < 0.01e9 and abs(pool - 6.04e9) < 0.01e9
    assert abs(resting + state + pool - 12.66e9) < 0.02e9
    assert (resting + state + pool) / (15.75 * 2**30) > 0.25  # the driver's floor


def test_the_traffic_is_the_issue_s_and_every_request_lands_on_a_warmed_program():
    m = manifest.load_json(manifest.MANIFEST)
    cellrow, cfg, traffic = manifest.cell(m, SHIPPED)
    assert (traffic["protocol"], traffic["loop"], traffic["clients"], traffic["requests"],
            traffic["max_total"], traffic["pairing_seed"], traffic["content"],
            traffic["warm_group_max"]) == (
                "sse-generate", "closed", 160, 192, 1536, 1, "unique", 4)
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.45,
                                        "min": 129, "max": 512}
    assert traffic["new_tokens"] == {"dist": "lognormal", "median": 384, "sigma": 0.5,
                                     "min": 128, "max": 1024}
    assert traffic["ramp"] == {"clients_per_step": 16, "step_s": 0.7,
                               "until_first_tokens": 128}
    work = lengths.multiset(traffic)
    assert len(work) == 192 and traffic["clients"] == 160 > cfg["engine"]["max_slots"] == 128
    assert all(129 <= p <= 512 and 128 <= a <= 1024 and p + a <= 1536 for p, a in work)
    assert {warmup.prefill_bucket(p, cfg["engine"]) for p, _a in work} == {256, 512}
    # the mean context of a decode step stays under the 864 tokens at which
    # the K/V rows read would pass the state's bytes
    mean_ctx = sum(a * (p + a / 2) for p, a in work) / sum(a for _p, a in work)
    assert 450 < mean_ctx < 864
    targets = warmup.reachable(cfg["engine"], work, traffic["clients"],
                               traffic["warm_group_max"])
    assert targets["prefill"] == {(b, k) for b in (256, 512) for k in (1, 2, 4)}
    assert {h for spec in targets["chunk"] for _lanes, h in spec} == {4, 8, 16, 24}
    assert len(targets["chunk"]) == 10
    assert cellrow["chips"] == 1 and len(cellrow["why"]) <= 200
    new = [x for x in m["per_layer"] if x["name"] in NEW]
    assert len(new) == 5 and all(x["workloads"] == [SHIPPED] and x["moves"] == "out_tok_s"
                                 for x in new)
    assert [x["name"] for x in m["per_layer"]][-5:] == list(NEW)        # appended, not inserted
    assert {x["layer"] for x in new} == {"linear attention + a state a lane", "kernels"}
    assert m["workloads"][-1]["name"] == SHIPPED and m["configs"][-1]["name"] == "olmo-hybrid-7b"
    out = next(x for x in m["end_to_end"] if x["name"] == "out_tok_s")
    assert out["workloads"][-1] == SHIPPED and out["bound"] == 0.08
    # one cell and one configuration more than the eight and seven before
    assert (len(m["workloads"]), len(m["configs"])) == (9, 8)
    # every listed reader exists
    for x in manifest.metrics_of(m, SHIPPED, "per_layer"):
        assert callable(manifest.reader("layer_metrics", x["name"]))


# ---------------------------------------------------------------------------
# the kind: a sample that puts the pad rule and the state on the judged path
# ---------------------------------------------------------------------------

def test_the_sample_judges_prompts_shorter_than_their_buckets():
    kind = manifest.module("harness/kinds", "generation_state")
    m = manifest.load_json(manifest.MANIFEST)
    _cell, cfg, traffic = manifest.cell(m, SHIPPED)
    work = lengths.multiset(traffic)
    buckets = cfg["engine"]["prompt_buckets"]
    lens = kind.judged_lengths(work, buckets)
    assert lens == [129, 257, 506] and not set(lens) & set(buckets)
    assert [warmup.prefill_bucket(n, cfg["engine"]) for n in lens] == [256, 512, 512]
    assert kind.SAMPLE_NEW >= 128 and max(lens) + kind.SAMPLE_NEW <= traffic["max_total"]
    # a length that is a bucket's own with none under it to move to stays
    assert kind.judged_lengths([(16, 4), (16, 4), (32, 4)], [16, 32]) == [16, 16, 32]
    sent = []

    class Served:
        traffic = {"max_total": 1536}
        config = {"engine": {"prompt_buckets": buckets}}

    kind.run_wave = lambda served, wave, seed, serial: (
        sent.append(wave) or [([n], [n + 1]) for n, _new in wave["requests"]])
    got = kind.serve_sample(Served(), work, 5)
    assert [s["prompt"] for s in got] == [[129], [257], [506]]
    # the shortest alone, then a group of three of different lengths behind a blocker
    assert sent[0]["requests"] == [(129, 128)] and sent[0]["blocker"] is False
    assert sent[1]["requests"] == [(257, 128), (506, 128), (506, 128)] and sent[1]["blocker"]


def test_the_warm_up_targets_the_programs_a_one_bucket_engine_forms():
    """The engine says ``ctx_buckets`` 1: the six two-bucket chunk specs
    ``warmup.reachable`` lists are left out, the four one-bucket ones and
    the six prefill groups stay; a program that does not say it keeps
    ``generation_share_long``'s warm-up."""
    kind = manifest.module("harness/kinds", "generation_state")
    m = manifest.load_json(manifest.MANIFEST)
    _cell, cfg, traffic = manifest.cell(m, SHIPPED)
    work = lengths.multiset(traffic)
    log = "".join(
        f"jit compile: program=paged_chunk [steps=8,buckets=((128, {h}),)] x\n"
        for h in (4, 8, 16, 24)) + "".join(
        f"jit compile: program=paged_prefill [bucket={b},k={k}] x\n"
        for b in (256, 512) for k in (1, 2, 4, 8))

    class Server:
        def log_text(self):
            return log

    class Served:
        base = "http://127.0.0.1:9"
        config = cfg

    Served.traffic = traffic
    kind.ctx_buckets = lambda served: 1
    kind._long.positions_cap = lambda served: 2048
    out = kind.warm_up(Served(), Server(), work, 5)
    assert out["targets"] == {"prefill": 6, "chunk": 4} and out["missing"] == {}
    assert out["met"] == {"prefill": 6, "chunk": 4} and out["rounds"] == 0
    assert out["grown"] == [24]  # the width only growth reaches
    # nothing answers at that address: the program does not say, so two
    kind = manifest.module("harness/kinds", "generation_state")
    assert kind.ctx_buckets(Served()) == 2


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
    kind = manifest.module("harness/kinds", "generation_state")
    share = manifest.module("harness/kinds", "generation_share")
    assert kind.TIE_STDS == share.TIE_STDS and kind.OFF_SHARE_MAX <= share.OFF_SHARE_MAX

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
