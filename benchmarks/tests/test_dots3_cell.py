"""The ``dots3-note-prev`` configuration at a toy size through ``run.py
--rehearse-cpu`` (layers of two attention kinds over a cache of three row
kinds, served by the deployer as the cell serves it: ``arch``,
``arch_sizes``, ``prompt_buckets``, the reference, the counters over
HTTP; the kind's one-prompt sample), the seven new readers on a recorded
fixture of operation names, and the shipped configuration against the
catalog's row and its own arithmetic."""

import json
import os
import subprocess
import sys

import pytest

import build_tree
from harness import lengths, manifest, warmup

CELL = "tiny-dots3.tiny-notes"
SHIPPED = "dots3-note-prev.long-doc-saturated"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("sparse_rows_read_pct", "index_time_share_pct", "index_score_roofline",
       "sparse_mla_kernel_roofline", "window_mla_kernel_roofline", "window_pages_held_pct",
       "sparse_window_time_share_pct")
LEFT_OUT = ("mla_kernel_roofline", "mla_kernel_time_share_pct", "decode_live_page_pct",
            "kv_pool_used_pct", "tpot_p50_ms", "engine_tpot_mean_ms",
            "prefill_time_share_pct")


def config():
    return manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs",
                                           "dots3-note-prev.json"))


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dest = build_tree.build(str(tmp_path_factory.mktemp("checkout")))
    path = os.path.join(dest, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "tiny-dots3", "source": "none: rehearsal", "reduced": [],
                         "file": "benchmarks/configs/tiny-dots3.json", "why": "rehearsal"})
    m["workloads"].append({"name": CELL, "config": "tiny-dots3", "traffic": "tiny-notes",
                           "chips": 1, "why": "rehearsal"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if SHIPPED in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(m, f, indent=1)
    return dest


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_of_kinds_rehearses(tree, trace):
    out = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 385), "--seconds", "4", "--trace", trace, "--rehearse-cpu"],
        cwd=tree, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    earlier = out.stdout
    # the sample: one judged prompt, the median, past the toy indexer's 16 rows,
    # under the kind's three limits (at 32 wide bfloat16 reads 0 to 2 of 91-97
    # tokens over 0.02 by seed, where the cell's widths read 0 of 256: the
    # seed held reads 0)
    assert "(prompts of [23])" in earlier and "over 0.02 (0.00 % against 2 %)" in earlier, (
        earlier[-2000:])
    # the warm-up met every shape the traffic reaches (a stream grown to the
    # 16-page table, its partners beside it): a compile inside a 4 s window on
    # the CPU leaves no request the time to finish
    assert "window compiles: 0" in earlier, earlier[-2000:]
    if trace == "0":
        assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
        return
    got = result["metrics"]
    # the counter readers find the program's counters; the device readers
    # find no device trace on the CPU
    assert 0 < got["sparse_rows_read_pct"]["value"] < 100
    assert 0 < got["window_pages_held_pct"]["value"] < 100
    assert "experts_held_active_mean" in got and "chunk_tokens_mean" in got
    for name in ("index_score_roofline", "window_mla_kernel_roofline", "decode_step_ms"):
        assert name not in got
    for name in LEFT_OUT:
        assert name not in got


def test_a_program_without_the_counters_leaves_the_new_metrics_out():
    """The parent serves no such arch and has none of the counters: every
    new reader returns None on its context and raises nothing."""
    cfg = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs",
                                          "gigachat3.1-702b-a36b.json"))
    stats = {"pool_pages_used": 10, "pool_pages_total": 100, "latent_kv_tokens": 5}
    ctx = {"engine": {"window": [stats, stats], "trace": [stats, stats], "samples": [stats]},
           "trace": {"busy_s": 1.0, "ops": {"pallas_kernel_f32_128_64_512_":
                                            {"count": 3, "seconds": 0.1}}},
           "config": cfg, "peaks": {"hbm_bytes_per_s": 8.19e11, "bf16_flops": 1.97e14}}
    for name in NEW:
        assert manifest.reader("layer_metrics", name)(ctx) is None, name
    ctx["config"] = config()  # the new configuration on a program without the counters
    for name in NEW:
        assert manifest.reader("layer_metrics", name)(ctx) is None, name


def test_the_configuration_is_the_catalog_s_row_but_for_the_cuts():
    cfg = config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "dots3-note-prev")
    reduced = set(cfg["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts", "vocab_size",
                       "max_position_embeddings"}
    entry = next(c for c in manifest.load_json(manifest.MANIFEST)["configs"]
                 if c["name"] == "dots3-note-prev")
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg["model"][key] == cfg[key], key          # the two blocks agree
        if key in reduced:
            assert cfg[key] != value and cfg["published"][key] == value
            assert key in cfg["reduced_why"]
        else:
            assert cfg[key] == value, key                  # nothing else moved: no width
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (6, 8, 19008, 7168)
    assert cfg["vocab_size"] * 8 >= row["config"]["vocab_size"]      # an eighth, the floor
    m = cfg["model"]
    assert (m["n_routed_experts_published"], m["expert_offset"]) == (256, 0)
    assert len(cfg["layer_types"]) == 46 and cfg["layer_types"][:6] == [
        "full_attention", "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert {"language_model_only", "mla_rescale", "gate", "indexer", "window"} <= set(
        cfg["assumed"])
    assert "INFERENCE FROM THE KEY'S NAME" in cfg["assumed"]["mla_rescale"]
    assert "13 of 46" in cfg["reduced_why"]["num_hidden_layers"]
    params = {p["name"]: p["value"]
              for p in cfg["deployment"]["predictors"][0]["graph"]["parameters"]}
    assert params["arch"] == "dots3_note"
    assert json.loads(params["arch_sizes"]) == {"experts_held": 8, "expert_offset": 0}
    assert json.loads(params["prompt_buckets"]) == cfg["engine"]["prompt_buckets"]
    assert (int(params["d_model"]), int(params["num_layers"]), int(params["num_heads"]),
            int(params["vocab_size"])) == (5120, 6, 128, 19008)
    engine = cfg["engine"]
    for key in ("max_len", "page_size", "max_slots", "steps_per_call"):
        assert int(params[key]) == engine[key]
    # every slot can reach max_len (the window pool is the engine's to size:
    # every slot's table full, 128 x 10 + 1 pages)
    assert int(params["num_pages"]) == 128 * 7168 // 64 + 1 == 14337
    assert "num_window_pages" not in params
    # the program's spec for this block is the published one but for the share
    from dataclasses import replace

    from reference import dots3_note as ref
    from seldon_core_tpu.models.spec import DOTS3_NOTE

    spec, sizes = ref.spec_and_config(cfg["model"])
    assert spec == replace(DOTS3_NOTE, experts_held=8, layer_kinds=DOTS3_NOTE.layer_kinds[:6])
    assert sizes == dict(vocab_size=19008, d_model=5120, num_layers=6, num_heads=128)
    assert spec.window_table_pages(64, 8) == 10


def test_the_byte_arithmetic_of_the_cut_is_the_engine_s():
    """``reduced_why``'s numbers against what the engine would hold at the
    cell's sizes: shapes only (the program's own declared tree and
    ``lane_report()``'s ``weight_bytes`` rule), no weight is made."""
    import jax

    from reference import dots3_note as ref
    from seldon_core_tpu.models.paged import paged_hbm_accounting
    from seldon_core_tpu.models.spec import declared_tree

    cfg = config()
    spec, sizes = ref.spec_and_config(cfg["model"])
    leaves = jax.tree_util.tree_leaves(declared_tree(spec, sizes))
    count = sum(int(leaf.size) for leaf in leaves)
    weight_bytes = sum(int(leaf.size) * leaf.dtype.itemsize for leaf in leaves)
    d = 5120
    full = (d * 1024 + 1024 * 128 * 192 + d * 576 + 512 * 128 * 256 + 128 * 128 * d
            + d * 128 + 1024 * 64 * 128 + d * 128 + d * 64)
    window = (d * 1024 + 1024 * 64 * 256 + d * 1088 + 1024 * 64 * 320 + 64 * 128 * d + d * 64)
    expert, router, dense = 3 * d * 1536, d * 256, 3 * d * 13824
    assert (round(full / 1e6, 2), round(window / 1e6, 1)) == (144.05, 90.8)   # "144.1 M"
    assert round(expert / 1e6, 2) == 23.59 and round(dense / 1e6, 1) == 212.3
    assert round((full + 257 * expert) * 2 / 1e9, 1) == 12.4         # a whole routed layer
    held = ((full + dense) + 2 * (full + expert + router + 8 * expert)
            + 3 * (window + expert + router + 8 * expert) + 2 * 19008 * d)
    assert round(held / 1e6) == 2180 and round(2 * held / 1e9, 2) == 4.36
    norms = count - held                                   # scales, biases, LayerNorm
    f32 = 5 * router + norms
    assert 0 < norms < 100_000
    assert weight_bytes == 2 * (count - f32) + 4 * f32 == 4_373_141_504
    assert "4,373,141,504" in cfg["reduced_why"]["num_hidden_layers"]
    # the pools: three full layers of 640 + 128 lanes, three window layers of 1,152
    kinds = spec.cache_kinds(6)
    assert kinds == (("full", 3, 640), ("index", 3, 128), ("window", 3, 1152))
    full_pool = 3 * 14337 * 64 * (640 + 128) * 2
    window_pool = 3 * 1281 * 64 * 1152 * 2
    assert round(full_pool / 1e9, 2) == 4.23 and round(window_pool / 1e9, 2) == 0.57
    assert round(3 * 14337 * 64 * 1152 * 2 / 1e9, 2) == 6.34       # the windows unreleased
    priced = paged_hbm_accounting(
        streams=128, ctx_len=7168, d_model=0, num_layers=0, cache_pools=1,
        chunk_impl="pool", steps_per_call=8, weight_bytes=weight_bytes,
        cache_kinds=[(layers, lanes, spec.window if name == "window" else 0)
                     for name, layers, lanes in kinds])
    trash = 3 * 64 * (640 + 128) * 2 + 3 * 64 * 1152 * 2
    assert priced["pool_bytes"] == full_pool + window_pool - trash
    assert priced["window_bytes"] == window_pool - 3 * 64 * 1152 * 2
    total = weight_bytes + full_pool + window_pool
    assert 9.1e9 < total < 9.3e9 and total > 0.25 * 16 * 2**30     # the driver's floor


def test_the_traffic_reaches_seven_programs_and_every_request_fits():
    m = manifest.load_json(manifest.MANIFEST)
    cellrow, cfg, traffic = manifest.cell(m, SHIPPED)
    work = lengths.multiset(traffic)
    assert len(work) == 192 and traffic["clients"] == 160 > cfg["engine"]["max_slots"] == 128
    assert all(2049 <= p <= 4096 and 512 <= a <= 3072 and p + a <= 7168 for p, a in work)
    assert {warmup.prefill_bucket(p, cfg["engine"]) for p, _a in work} == {3072, 4096}
    assert (traffic["max_total"], traffic["pairing_seed"], traffic["warm_group_max"],
            traffic["protocol"], traffic["loop"]) == (7168, 1, 2, "sse-generate", "closed")
    assert traffic["ramp"] == {"clients_per_step": 16, "step_s": 0.7,
                               "until_first_tokens": 128}
    targets = warmup.reachable(cfg["engine"], work, traffic["clients"],
                               traffic["warm_group_max"])
    assert targets["prefill"] == {(b, k) for b in (3072, 4096) for k in (1, 2)}
    assert targets["chunk"] == {((128, 64),), ((128, 112),), ((64, 64), (64, 112))}
    # every decode lane-step holds over index_topk rows and a slid window
    assert min(p for p, _a in work) > cfg["model"]["index_topk"] > cfg["model"][
        "sliding_window_size"]
    prompts, answers = sorted(p for p, _a in work), sorted(a for _p, a in work)
    assert 3000 <= prompts[len(prompts) // 2] <= 3150
    assert 1450 <= answers[len(answers) // 2] <= 1620
    # the checked sample: the median prompt and 256 tokens, a third of its
    # rows dropped by the selection at every judged position
    from harness.kinds import generation_share_sparse as kind

    assert prompts[len(prompts) // 2] + kind.SAMPLE_NEW == 3078 + 256
    assert cfg["model"]["index_topk"] / prompts[len(prompts) // 2] < 0.67
    assert cellrow["chips"] == 1 and len(cellrow["why"]) <= 200
    new = [x for x in m["per_layer"] if x["name"] in NEW]
    assert len(new) == 7 and all(x["workloads"] == [SHIPPED] and x["moves"] == "out_tok_s"
                                 and x["layer"] == "sparse + window attention" for x in new)
    for name in LEFT_OUT:
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        assert SHIPPED not in metric["workloads"], name
    assert m["workloads"][-1]["name"] == SHIPPED and m["configs"][-1]["name"] == "dots3-note-prev"


# operation names and seconds of my traced run of the cell (PR 38, seed
# 3800000102, bucket spec 64 x 64 + 64 x 112; the program then still looked
# each chosen row's page up in a gather of its own, ``fusion_s32_131072_``,
# which no rule takes and the sort's carried rows have since replaced)
RECORDED = {
    "fusion_bf16_131072_640_": 1.1612, "fusion_s32_131072_": 0.5247,
    "fusion_f32_64_128_": 0.1334, "fusion_bf16_7168_64_128_": 0.1116,
    "fusion_f32_128_": 0.1041, "pallas_kernel_f32_128_1536_": 0.1036,
    "fusion_bf16_64_128_512_": 0.0990, "pallas_kernel_f32_128_64_1024_": 0.0809,
    "sort_f32_64_7168_": 0.0746, "fusion_bf16_4096_64_128_": 0.0580,
    "pallas_kernel_f32_128_5120_": 0.0521, "fusion_f32_64_7168_": 0.0390,
    "sort_f32_64_4096_": 0.0308, "fusion_f32_128_19008_": 0.0243,
    "fusion_f32_64_4096_": 0.0062, "fusion_bf16_64_128_640_": 0.0057,
    "fusion_bf16_128_1_128_192_": 0.0185, "sort_s32_1024_": 0.0038,
    "sort_f32_128_256_": 0.0017, "fusion_f32_128_64_": 0.0147,
}


def recorded_ctx():
    before = {"index_keys_scored": 0, "sparse_rows_read": 0, "sparse_rows_cached": 0,
              "window_rows_read": 0}
    after = {"index_keys_scored": 120_000_000, "sparse_rows_read": 69_206_016,
             "sparse_rows_cached": 120_000_000, "window_rows_read": 17_301_504}
    samples = [{"full_pages_held": 7000, "window_pages_held": 1150},
               {"full_pages_held": 8000, "window_pages_held": 1170}, None]
    return {"engine": {"window": [before, after], "trace": [before, after], "samples": samples},
            "trace": {"busy_s": 3.0576, "ops": {k: {"count": 1, "seconds": v}
                                                for k, v in RECORDED.items()}},
            "config": config(), "peaks": {"hbm_bytes_per_s": 8.19e11, "bf16_flops": 1.97e14}}


def test_the_new_readers_on_a_recorded_trace():
    ctx = recorded_ctx()
    got = {name: manifest.reader("layer_metrics", name)(ctx) for name in NEW}
    scoring = 0.1116 + 0.0580 + 0.0390 + 0.0062
    topk = 0.0746 + 0.0308
    sparse = 1.1612 + 0.1334 + 0.0990
    window = 0.0809
    assert got["sparse_rows_read_pct"] == pytest.approx(100 * 69_206_016 / 120_000_000)
    assert got["window_pages_held_pct"] == pytest.approx(
        (100 * 1150 / 7000 + 100 * 1170 / 8000) / 2)
    assert got["index_time_share_pct"] == pytest.approx(100 * (scoring + topk) / 3.0576)
    assert got["sparse_window_time_share_pct"] == pytest.approx(
        100 * (scoring + topk + sparse + window) / 3.0576)
    # a key is 256 B and 16,512 FLOP: the bytes bind (64 FLOP a byte)
    assert got["index_score_roofline"] == pytest.approx(
        100 * (120_000_000 * 256 / 8.19e11) / scoring)
    # a chosen row is 1,152 B and 278,528 FLOP at 128 heads: level with the ridge
    least = max(69_206_016 * 1152 / 8.19e11, 69_206_016 * 278_528 / 1.97e14)
    assert got["sparse_mla_kernel_roofline"] == pytest.approx(100 * least / sparse)
    # a window row is 2,176 B and 270,336 FLOP at 64 heads: the bytes bind
    assert got["window_mla_kernel_roofline"] == pytest.approx(
        100 * (17_301_504 * 2176 / 8.19e11) / window)
    assert all(0 < v < 105 for v in got.values()), got
    # no rule takes another's operation, and none takes the q fold, the held
    # experts' kernels, the router's sort or the window layers' statistics
    from layer_metrics import dots3_work as work

    z = work.sizes(ctx["config"])
    rules = (work.is_index_score, work.is_topk, work.is_sparse_attention,
             work.is_window_kernel)
    taken = {k: [r.__name__ for r in rules if r(k, z)] for k in RECORDED}
    assert all(len(v) <= 1 for v in taken.values()), taken
    for key in ("fusion_s32_131072_", "fusion_f32_128_", "pallas_kernel_f32_128_1536_",
                "pallas_kernel_f32_128_5120_", "fusion_f32_128_19008_",
                "fusion_bf16_64_128_640_", "fusion_bf16_128_1_128_192_", "sort_s32_1024_",
                "sort_f32_128_256_", "fusion_f32_128_64_"):
        assert not taken[key], key


def test_the_merge_at_a_bucket_as_wide_as_the_heads_is_counted_when_that_bucket_ran():
    """``(128, 128)`` float32 is the flash merge of a 128-lane bucket and
    the indexed prefill's statistics of a block of 128 queries alike: it
    is the sparse attention's only in a trace that ran such a bucket."""
    from layer_metrics import dots3_work as work

    ctx = recorded_ctx()
    ctx["trace"]["ops"]["fusion_f32_128_128_"] = {"count": 1, "seconds": 0.064}
    reader = manifest.reader("layer_metrics", "sparse_window_time_share_pct")
    ctx["trace"]["modules"] = {"jit_paged_chunk_s8_64x64_64x112": {"count": 9, "seconds": 2.1},
                               "jit_paged_prefill_b3072_k1": {"count": 1, "seconds": 0.24}}
    two_buckets = reader(ctx)
    assert work.lanes_run(ctx["trace"]) == {64}
    ctx["trace"]["modules"] = {"jit_paged_chunk_s8_128x112": {"count": 9, "seconds": 2.1}}
    assert work.lanes_run(ctx["trace"]) == {128}
    assert reader(ctx) == pytest.approx(two_buckets + 100 * 0.064 / 3.0576)


def test_the_readings_do_not_know_what_did_the_work():
    """The same counters and the same seconds, once in PR 38's operations
    (XLA's gather of the chosen rows, the merge and statistics, the
    weighted rows) and once in ONE ``pallas_kernel`` whose output is the
    full layers' flash state beside the merge: the sparse readers read
    the same, and ``step_mfu_pct`` the same again on a trace whose
    operations are renamed wholesale."""
    from layer_metrics import dots3_work as work

    names = ("sparse_mla_kernel_roofline", "sparse_window_time_share_pct", "step_mfu_pct")
    readers = {name: manifest.reader("layer_metrics", name) for name in names}
    others = {"fusion_bf16_7168_64_128_": 0.106, "fusion_f32_64_7168_": 0.039,
              "sort_f32_64_7168_": 0.109, "pallas_kernel_f32_128_64_1024_": 0.074,
              "pallas_kernel_f32_128_1536_": 0.098, "fusion_f32_128_": 0.097}
    xla = dict(others, **{"fusion_bf16_131072_640_": 1.009, "fusion_f32_64_128_": 0.124,
                          "fusion_bf16_64_128_512_": 0.093})
    kernel = dict(others, **{"pallas_kernel_f32_64_128_512_": 1.216, "fusion_f32_64_128_": 0.010})
    assert sum(xla.values()) == pytest.approx(sum(kernel.values()))
    renamed = {f"op_{i}": v for i, v in enumerate(xla.values())}

    def ctx_of(ops):
        ctx = recorded_ctx()
        for snapshot, scale in zip(ctx["engine"]["trace"], (0, 1)):
            snapshot.update(prefill_tokens=9216 * scale, prefills=3 * scale,
                            decode_lane_steps=10_240 * scale,
                            moe_local_assignments=24_000 * scale)
        ctx["trace"] = {"busy_s": 3.061, "window_s": 3.065,
                        "modules": {"jit_paged_chunk_s8_64x64_64x112": {"count": 10,
                                                                        "seconds": 2.33}},
                        "ops": {k: {"count": 1, "seconds": v} for k, v in ops.items()}}
        return ctx

    got = {kind: {name: read(ctx_of(ops)) for name, read in readers.items()}
           for kind, ops in (("xla", xla), ("kernel", kernel), ("renamed", renamed))}
    assert got["kernel"] == pytest.approx(got["xla"])
    assert 0 < got["xla"]["sparse_mla_kernel_roofline"] < 100
    assert 0 < got["xla"]["step_mfu_pct"] < 100
    assert got["renamed"]["step_mfu_pct"] == got["xla"]["step_mfu_pct"]
    assert got["renamed"]["sparse_mla_kernel_roofline"] is None
    # the needed work is the chosen set's, whatever a kernel moved to find it
    least = max(69_206_016 * 1152 / 8.19e11, 69_206_016 * 278_528 / 1.97e14)
    assert got["kernel"]["sparse_mla_kernel_roofline"] == pytest.approx(100 * least / 1.226)
    # a scoring kernel is the indexer's, by its scores' shape; the kernels of
    # other layers stay theirs
    z = work.sizes(config())
    for key in ("pallas_kernel_f32_64_7168_", "pallas_kernel_f32_64_112_64_",
                "pallas_kernel_f32_128_4096_", "pallas_kernel_bf16_64_64_64_"):
        assert work.is_index_score(key, z) and not work.is_sparse_attention(key, z), key
    assert work.is_sparse_attention("pallas_kernel_f32_128_128_512_", z)
    for key in ("pallas_kernel_f32_128_64_1024_", "pallas_kernel_f32_128_1536_",
                "pallas_kernel_f32_128_5120_", "pallas_kernel_bf16_64_3072_128_",
                "pallas_kernel_f32_64_64_512_", "pallas_kernel_f32_32_128_512_",
                "pallas_kernel_f32_64_2048_"):
        assert not work.is_sparse_attention(key, z) and not work.is_index_score(key, z), key
    rules = (work.is_index_score, work.is_topk, work.is_sparse_attention,
             work.is_window_kernel)
    assert all(sum(r(k, z) for r in rules) <= 1 for k in list(xla) + list(kernel))


def test_the_kinds_three_limits():
    """``generation_share``'s two limits and the third, which a program
    that reads every row fails where the first two pass it."""
    from harness.kinds import generation_share_sparse as kind

    n = 256
    sound = [0.0] * n
    assert kind.verdict(sound)["ok"]
    few = [0.0] * (n - 3) + [0.012, 0.03, 0.17]           # a sound program's worst
    assert kind.verdict(few)["ok"] and kind.verdict(few)["near"] == 2
    every_row = [0.0] * (n - 14) + [0.03] * 12 + [0.1, 0.19]  # the selection left out
    v = kind.verdict(every_row)
    assert v["off"] <= kind.OFF_SHARE_MAX * n and v["worst_gap_stds"] <= kind.WORST_GAP_STDS
    assert v["near"] == 14 and not v["ok"]
    assert not kind.verdict([0.0] * (n - 1) + [2.5])["ok"]    # a token from a wrong row
    assert not kind.verdict([0.0] * (n - 9) + [0.1] * 9)["ok"]  # 3.5 % off
