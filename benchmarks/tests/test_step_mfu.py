"""``step_mfu_pct``: the FLOPs the tokens of an interval need, a family of
configurations at a time, held against numbers worked by hand here from
each shipped configuration's sizes (one decode lane-step at a stated
context, one prompt at a stated length); the reader on fixtures; and its
blindness to what did the work: it opens neither ``trace["ops"]`` nor
``trace["modules"]``."""

import os

import pytest

from harness import manifest
from layer_metrics import step_work

SATURATED = ("gpt2-large.chat-saturated", "olmoe-1b-7b.chat-saturated",
             "gigachat3.1-702b-a36b.long-answer-saturated",
             "longcat-flash-omni.think-saturated", "dots3-note-prev.long-doc-saturated")
PEAKS = {"hbm_bytes_per_s": 8.19e11, "bf16_flops": 1.97e14}


def config(name):
    return manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs", name + ".json"))


def mla(d, heads, q_rank, rank, nope, rope, v):
    """Multiply-adds of one latent attention's matrices a token: W_qa,
    W_qb, W_kva, W_uk and W_uv, W_o."""
    return (d * q_rank + q_rank * heads * (nope + rope) + d * (rank + rope)
            + heads * rank * (nope + v) + heads * v * d)


def gpt2_by_hand():
    d, layers, vocab = 1280, 36, 50257
    token = layers * 2 * (3 * d * d + d * d + 2 * d * 4 * d)          # 24 d^2 a layer
    head = 2 * d * vocab
    step = token + head + layers * 4 * d * 340                          # 340 cached tokens
    prompt = 300 * token + head + layers * 4 * d * 300 * 300 / 2      # 300 positions
    return ({"decode_lane_steps": 1, "decode_kv_tokens": 340}, step,
            {"prefill_tokens": 300, "prefills": 1}, prompt)


def olmoe_by_hand():
    d, layers, vocab, experts, width, top_k = 2048, 8, 50304, 64, 1024, 8
    token = layers * 2 * (4 * d * d + d * experts)
    expert = 2 * 3 * d * width                                          # an assignment
    head = 2 * d * vocab
    step = token + head + layers * top_k * expert + layers * 4 * d * 340
    prompt = (300 * token + head + 300 * layers * top_k * expert
              + layers * 4 * d * 300 * 300 / 2)
    return ({"decode_lane_steps": 1, "decode_kv_tokens": 340,
             "moe_assignments": layers * top_k}, step,
            {"prefill_tokens": 300, "prefills": 1, "moe_assignments": 300 * layers * top_k},
            prompt)


def gigachat_by_hand():
    d, layers, vocab, heads = 7168, 6, 16032, 64
    attention = mla(d, heads, 1536, 512, 128, 64, 192)
    assert round(attention / 1e6, 1) == 132.6                           # the file's "132.6 M"
    dense, shared, router, expert = 3 * d * 18432, 3 * d * 2048, d * 256, 3 * d * 2048
    token = 2 * (layers * attention + dense + 5 * (shared + router))
    head = 2 * d * vocab
    row = 2 * heads * (576 + 512)                                       # 139,264 a cached row
    # of 5 x 8 picks over 256 experts an even router sends 1.25 to the 8 held
    step = token + head + 2 * expert * 1.25 + row * layers * 1700
    pairs = 1024 * 1024 / 2
    prompt = (1024 * token + head + 2 * expert * 1280
              + layers * 2 * heads * (128 + 64 + 192) * pairs)
    return ({"decode_lane_steps": 1, "latent_kv_tokens": layers * 1700,
             "moe_local_assignments": 1.25}, step,
            {"prefill_tokens": 1024, "prefills": 1, "moe_local_assignments": 1280}, prompt)


def longcat_by_hand():
    d, layers, vocab, heads = 6144, 4, 16384, 64
    attention = mla(d, heads, 1536, 512, 128, 64, 128)
    assert round(attention / 1e6, 2) == 90.57                           # the file's "90.57 M"
    ffn, router, expert = 3 * d * 12288, d * 768, 3 * d * 2048
    token = 2 * layers * (2 * attention + 2 * ffn + router)
    head = 2 * d * vocab
    row = 2 * heads * (576 + 512)
    # 4 x 12 picks over 768 outputs, 16 real experts held: one assignment a token
    step = token + head + 2 * expert * 1.0 + row * 2 * layers * 1150
    pairs = 512 * 512 / 2
    prompt = (512 * token + head + 2 * expert * 512
              + 2 * layers * 2 * heads * (128 + 64 + 128) * pairs)
    return ({"decode_lane_steps": 1, "latent_kv_tokens": 2 * layers * 1150,
             "moe_local_assignments": 1.0}, step,
            {"prefill_tokens": 512, "prefills": 1, "moe_local_assignments": 512}, prompt)


def dots3_by_hand():
    d, vocab = 5120, 19008
    full = (mla(d, 128, 1024, 512, 128, 64, 128) + d * 128               # + the gate
            + 1024 * 64 * 128 + d * 128 + d * 64)                        # + the indexer
    window = mla(d, 64, 1024, 1024, 192, 64, 128) + d * 64
    assert (round(full / 1e6, 2), round(window / 1e6, 1)) == (144.05, 90.8)
    dense, shared, router, expert = 3 * d * 13824, 3 * d * 1536, d * 256, 3 * d * 1536
    token = 2 * (3 * full + 3 * window + dense + 5 * (shared + router))
    head = 2 * d * vocab
    row, wrow, key = 2 * 128 * (576 + 512), 2 * 64 * (1088 + 1024), 2 * 64 * 128 + 2 * 64
    assert (row, wrow, key) == (278_528, 270_336, 16_512)
    # a lane holding 3,600 rows: 3 full layers score 3,600 keys and read 2,048
    # rows each, 3 window layers read 512 rows each; 1.25 assignments served here
    step = (token + head + 2 * expert * 1.25 + 3 * 3600 * key + 3 * 2048 * row
            + 3 * 512 * wrow)
    n, topk, win = 3072, 2048, 513
    prompt = (n * token + head + 2 * expert * 1.25 * n
              + 3 * (2 * 128 * (128 + 64 + 128) * (topk * n - topk * topk / 2)
                     + key * (n * n - topk * topk) / 2)
              + 3 * 2 * 64 * (192 + 64 + 128) * (win * n - win * win / 2))
    assert 4.5e9 < step < 4.8e9 and 7.5e12 < prompt < 8.5e12             # ISSUE 39's reckoning
    return ({"decode_lane_steps": 1, "index_keys_scored": 3 * 3600,
             "sparse_rows_read": 3 * 2048, "window_rows_read": 3 * 512,
             "moe_local_assignments": 1.25}, step,
            {"prefill_tokens": n, "prefills": 1, "moe_local_assignments": 1.25 * n}, prompt)


BY_HAND = {"gpt2-large": ("gpt2", gpt2_by_hand), "olmoe-1b-7b": ("olmoe", olmoe_by_hand),
           "gigachat3.1-702b-a36b": ("latent_share", gigachat_by_hand),
           "longcat-flash-omni": ("double_layer", longcat_by_hand),
           "dots3-note-prev": ("dots3", dots3_by_hand)}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_a_decode_lane_step_and_a_prompt_need_what_the_sizes_say(name):
    kind, by_hand = BY_HAND[name]
    cfg = config(name)
    assert step_work.family(cfg) == kind
    step_counters, step, prompt_counters, prompt = by_hand()
    assert step_work.needed_flops(cfg, step_counters) == pytest.approx(step, rel=1e-12)
    assert step_work.needed_flops(cfg, prompt_counters) == pytest.approx(prompt, rel=1e-12)
    # the work adds up: both together, twice over
    both = {k: 2 * (step_counters.get(k, 0) + prompt_counters.get(k, 0))
            for k in set(step_counters) | set(prompt_counters)}
    assert step_work.needed_flops(cfg, both) == pytest.approx(2 * (step + prompt), rel=1e-12)


def test_a_prompt_is_taken_at_the_mean_length_and_never_over_the_true_work():
    """Two prompts of 2,100 and 4,000 positions counted as two of 3,050:
    every term is convex in the length, so the reckoning is a lower bound."""
    for name in sorted(BY_HAND):
        cfg = config(name)
        apart = sum(step_work.needed_flops(cfg, {"prefill_tokens": n, "prefills": 1})
                    for n in (2100, 4000))
        together = step_work.needed_flops(cfg, {"prefill_tokens": 6100, "prefills": 2})
        assert 0 < together <= apart, name
    assert step_work.causal_pairs(100) == 5000 and step_work.causal_pairs(100, 10) == 950
    # a window's keys: min(t + 1, 513) summed over a 3,072-position prompt, less its half-row
    exact = sum(min(t + 1, 513) for t in range(3072))
    assert 0 < exact - step_work.causal_pairs(3072, 513) <= 513 / 2
    assert step_work.needed_flops({"model": {"some": "sizes"}}, {"prefill_tokens": 1}) is None


def counters_after(kind, scale=1.0):
    return {name: scale * n for name, n in {
        "prefill_tokens": 9000, "prefills": 3, "decode_lane_steps": 10_000,
        "decode_kv_tokens": 3_400_000, "latent_kv_tokens": 100_000_000,
        "moe_assignments": 600_000, "moe_local_assignments": 24_000,
        "sparse_rows_read": 61_000_000, "window_rows_read": 15_000_000,
        "index_keys_scored": 108_000_000}.items() if name in step_work.COUNTERS[kind]}


def ctx_of(name, ops=None, modules=None):
    cfg = config(name)
    after = counters_after(step_work.family(cfg))
    before = dict.fromkeys(after, 0)
    trace = {"window_s": 3.065, "busy_s": 3.061}
    if ops is not None:
        trace["ops"] = ops
    if modules is not None:
        trace["modules"] = modules
    return {"engine": {"window": [before, after], "trace": [before, after], "samples": []},
            "trace": trace, "config": cfg, "peaks": dict(PEAKS),
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_the_reader_on_a_fixture(name):
    read = manifest.reader("layer_metrics", "step_mfu_pct")
    ctx = ctx_of(name)
    got = read(ctx)
    flops = step_work.needed_flops(ctx["config"], ctx["engine"]["trace"][1])
    assert got == pytest.approx(100 * flops / (1.97e14 * 3.065)) and 0 < got < 100
    # the interval, not the busy time: a host that leaves the device idle reads lower
    ctx["trace"]["window_s"] = 6.13
    assert read(ctx) == pytest.approx(got / 2)
    # over the chips used
    ctx["trace"]["window_s"], ctx["device"]["count"] = 3.065, 4
    assert read(ctx) == pytest.approx(got / 4)
    # nothing without a trace, a traced interval, peaks, a known family or the counters
    for broken in (dict(ctx, trace=None), dict(ctx, trace={"busy_s": 1.0, "ops": {}}),
                   dict(ctx, trace={"window_s": 0.0}), dict(ctx, peaks=None),
                   dict(ctx, config={"model": {}}),
                   dict(ctx, engine={"window": ctx["engine"]["window"]}),
                   dict(ctx, engine={"trace": [None, None]})):
        assert read(broken) is None
    for missing in step_work.COUNTERS[step_work.family(ctx["config"])]:
        after = {k: v for k, v in ctx["engine"]["trace"][1].items() if k != missing}
        assert read(dict(ctx, engine={"trace": [ctx["engine"]["trace"][0], after]})) is None


def test_the_reader_opens_neither_the_operations_nor_the_programs():
    class Sealed(dict):
        def __getitem__(self, key):
            assert key not in ("ops", "modules"), key
            return super().__getitem__(key)

        def get(self, key, default=None):
            assert key not in ("ops", "modules"), key
            return super().get(key, default)

    read = manifest.reader("layer_metrics", "step_mfu_pct")
    for name in sorted(BY_HAND):
        ctx = ctx_of(name, ops={"fusion_bf16_131072_640_": {"count": 1, "seconds": 1.0}},
                     modules={"jit_paged_chunk_s8_64x64_64x112": {"count": 9, "seconds": 2.1}})
        plain = read(ctx)
        ctx["trace"] = Sealed(ctx["trace"])
        assert read(ctx) == plain and plain > 0


def test_the_metric_is_declared_for_the_saturated_cells_alone():
    m = manifest.load_manifest()
    entry = next(e for e in m["per_layer"] if e["name"] == "step_mfu_pct")
    assert entry == {"name": "step_mfu_pct", "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels", "moves": "out_tok_s",
                     "workloads": list(SATURATED)}
    assert "gpt2-large.doc-prefill" not in entry["workloads"]
    # every cell it lists reports the metric it moves, and every family is a shipped one
    moved = next(e for e in m["end_to_end"] if e["name"] == "out_tok_s")
    assert set(entry["workloads"]) <= set(moved["workloads"])
    for cell in entry["workloads"]:
        _row, cfg, _traffic = manifest.cell(m, cell)
        assert step_work.family(cfg) in step_work.FLOPS
