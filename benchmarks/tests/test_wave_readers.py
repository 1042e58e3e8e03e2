"""The readers of the wave loop's own counters and program names, each
on a hand-made ``ctx``; each returns ``None`` where a program without
those counters or names (the parent of PR 24) gives it nothing."""

import pytest

from harness import manifest


def read(metric, ctx):
    return manifest.reader("layer_metrics", metric)(ctx)


def engine_ctx(before, after, span="window"):
    return {"window": (100.0, 150.0), "engine": {span: [before, after]},
            "trace": None, "config": {}}


COUNTER_CASES = [
    ("ingress_wait_mean_ms", {"ingress_wait_s": 1.0, "ingress_waits": 10},
     {"ingress_wait_s": 4.0, "ingress_waits": 40}, 100.0),
    ("queue_wait_mean_ms", {"queue_wait_s": 10.0, "queue_waits": 5},
     {"queue_wait_s": 310.0, "queue_waits": 55}, 6000.0),
    ("prefill_pad_pct", {"prefill_tokens": 1000, "prefill_padded_tokens": 2000},
     {"prefill_tokens": 8000, "prefill_padded_tokens": 12000}, 30.0),
    ("host_gap_pct", {"host_gap_s": 2.0}, {"host_gap_s": 3.5}, 3.0),
    ("decode_ctx_tokens_mean", {"decode_kv_tokens": 1000, "decode_lane_steps": 10},
     {"decode_kv_tokens": 401000, "decode_lane_steps": 1010}, 400.0),
]


@pytest.mark.parametrize("metric,before,after,want", COUNTER_CASES)
def test_counter_readers_take_deltas_over_the_window(metric, before, after, want):
    assert read(metric, engine_ctx(before, after)) == pytest.approx(want)


@pytest.mark.parametrize("metric,before,after,_want", COUNTER_CASES)
def test_counter_readers_give_none_without_their_keys(metric, before, after, _want):
    older = {"prefill_tokens": 5, "tokens": 9}  # an engine that predates the counters
    assert read(metric, engine_ctx(older, dict(older, tokens=19))) is None
    assert read(metric, engine_ctx(before, None)) is None
    assert read(metric, {"window": (100.0, 150.0), "engine": {}, "trace": None}) is None
    if metric != "host_gap_pct":  # nothing counted in the window: no mean, no share
        assert read(metric, engine_ctx(before, before)) is None


def test_padded_ktok_is_read_from_the_trace_alone():
    modules = {
        "jit_paged_prefill_b1024_k4": {"count": 2.0, "seconds": 3.2},
        "jit_paged_prefill_cached_b256_k2_r4": {"count": 1.0, "seconds": 0.1},
        "jit_paged_chunk_s8_32x16": {"count": 3.0, "seconds": 1.5},
        "jit_scatter": {"count": 9.0, "seconds": 0.01},
    }
    ctx = {"trace": {"modules": modules}, "engine": {}, "config": {}}
    positions = 2 * 1024 * 4 + 1 * 256 * 2
    assert read("prefill_ms_per_padded_ktok", ctx) == pytest.approx(
        1e3 * 3.3 / (positions / 1000.0))


@pytest.mark.parametrize("trace", [
    None, {}, {"modules": {}},
    # the parent's names carry no shape
    {"modules": {"jit_prefill": {"count": 2.0, "seconds": 3.2},
                 "jit__unknown": {"count": 3.0, "seconds": 1.5}}},
])
def test_padded_ktok_gives_none_without_shaped_names(trace):
    assert read("prefill_ms_per_padded_ktok", {"trace": trace, "engine": {}}) is None
