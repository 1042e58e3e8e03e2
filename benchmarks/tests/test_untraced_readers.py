"""The readers of the untraced stretch of a traced run (PR 35), each on
a hand-made ``ctx``: the stretch runs from the window's first snapshot
to the last sample whose ``clock_s`` lies before the trace's; a program
whose snapshots carry no clock gives every reader nothing."""

import pytest

from harness import manifest
from layer_metrics import untraced


def read(metric, ctx):
    return manifest.reader("layer_metrics", metric)(ctx)


FIRST = {"clock_s": 1000.0, "tokens": 5000, "host_work_s": 10.0, "host_wait_s": 30.0,
         "deliver_lag_s": 2.0, "deliveries": 1000, "deliveries_behind": 50,
         "decode_stream_s": 100.0, "decode_stream_tokens": 10000,
         "ttft_s": 20.0, "ttfts": 100, "first_token_s": 8.0, "first_tokens": 100}
# 14 s on: what the untraced stretch ends on
LAST = {"clock_s": 1014.0, "tokens": 54000, "host_work_s": 13.5, "host_wait_s": 40.5,
        "deliver_lag_s": 5.0, "deliveries": 7000, "deliveries_behind": 350,
        "decode_stream_s": 700.0, "decode_stream_tokens": 70000,
        "ttft_s": 70.0, "ttfts": 500, "first_token_s": 33.0, "first_tokens": 500}


def moved(snapshot, by, clock):
    """A later snapshot: every counter ``by`` times further from FIRST."""
    out = {k: FIRST[k] + by * (v - FIRST[k]) for k, v in snapshot.items()}
    out["clock_s"] = clock
    return out


def ctx_of(first=FIRST, samples=None, trace_at=1015.3, trace=True):
    if samples is None:
        samples = [moved(LAST, 0.5, 1007.0), LAST,
                   # taken while the trace ran, and after it
                   moved(LAST, 3.0, 1016.0), moved(LAST, 4.0, 1040.0)]
    engine = {"window": [first, moved(LAST, 5.0, 1051.0)], "samples": samples}
    if trace:
        engine["trace"] = [moved(LAST, 2.0, trace_at), moved(LAST, 2.5, trace_at + 3.0)]
    return {"window": (100.0, 151.0), "engine": engine, "trace": None, "config": {}}


CASES = [
    ("host_busy_pct", 100.0 * 3.5 / (3.5 + 10.5)),
    ("engine_out_tok_s", 49000 / 14.0),
    ("deliver_lag_mean_ms", 1e3 * 3.0 / 6000),
    ("deliver_behind_pct", 100.0 * 300 / 6000),
    ("engine_tpot_mean_ms", 1e3 * 600.0 / 60000),
    ("engine_ttft_mean_ms", 1e3 * 50.0 / 400),
    ("first_token_mean_ms", 1e3 * 25.0 / 400),
]


@pytest.mark.parametrize("metric,want", CASES)
def test_each_reader_takes_its_deltas_over_the_untraced_stretch(metric, want):
    assert read(metric, ctx_of()) == pytest.approx(want)
    # whatever order the samples come in, and with a failed poll among them
    shuffled = ctx_of()
    shuffled["engine"]["samples"] = [None] + shuffled["engine"]["samples"][::-1]
    assert read(metric, shuffled) == pytest.approx(want)


@pytest.mark.parametrize("metric,_want", CASES)
def test_each_reader_gives_none_without_a_clock_or_a_stretch(metric, _want):
    def strip(snapshot):
        return {k: v for k, v in snapshot.items() if k != "clock_s"}

    # the parent of PR 35: no snapshot carries a clock
    older = ctx_of(first=strip(FIRST), samples=[strip(LAST)])
    older["engine"]["trace"] = [strip(s) for s in older["engine"]["trace"]]
    assert read(metric, older) is None
    # an untraced run, a run with no sample before the trace, no engine
    assert read(metric, ctx_of(trace=False)) is None
    assert read(metric, ctx_of(samples=[moved(LAST, 3.0, 1016.0)])) is None
    assert read(metric, ctx_of(samples=[])) is None
    assert read(metric, {"window": (0.0, 1.0), "engine": {}, "trace": None}) is None
    # a stretch in which nothing was counted: no deliveries, no stream
    # finished, no first token, no wave
    still = ctx_of(samples=[dict(FIRST, clock_s=1014.0)])
    if metric == "engine_out_tok_s":
        assert read(metric, still) == 0.0
    else:
        assert read(metric, still) is None
    # the counters alone missing (a clock, but an older engine behind it)
    bare = ctx_of(first={"clock_s": 1000.0, "tokens": 1},
                  samples=[{"clock_s": 1014.0, "tokens": 2}])
    assert read(metric, bare) == (pytest.approx(1 / 14.0) if metric == "engine_out_tok_s"
                                  else None)


def test_the_helper_picks_the_last_sample_before_the_trace_and_none_inside_it():
    ctx = ctx_of()
    first, last = untraced.stretch(ctx)
    assert first is FIRST and last is LAST
    assert untraced.delta(ctx, "tokens") == 49000
    assert untraced.delta(ctx, "no_such_counter") is None
    # a sample a hair before the trace's first snapshot is the stretch's end
    near = moved(LAST, 1.5, 1015.29)
    assert untraced.stretch(ctx_of(samples=[LAST, near]))[1] is near
    # one AT the trace's snapshot, or later, is refused
    at = moved(LAST, 2.0, 1015.3)
    assert untraced.stretch(ctx_of(samples=[at])) is None
    assert untraced.stretch(ctx_of(samples=[LAST, at]))[1] is LAST
    # and so is one from before the window opened
    early = moved(LAST, -0.1, 999.0)
    assert untraced.stretch(ctx_of(samples=[early])) is None


def test_every_new_metric_is_declared_for_its_cells_and_moves_their_metric():
    m = manifest.load_manifest()
    by_name = {e["name"]: e for e in m["per_layer"]}
    cells = {w["name"] for w in m["workloads"]}
    for name, _want in CASES:
        entry = by_name[name]
        assert entry["source"] == "program_counter"
        doc = name in ("engine_ttft_mean_ms", "first_token_mean_ms")
        # each entry against its own list: a cell whose stretch holds nothing
        # for a reader to read is not on that reader's list (PERF.md section 3)
        assert entry["workloads"] and set(entry["workloads"]) <= cells
        assert ("gpt2-large.doc-prefill" in entry["workloads"]) == doc
        assert all(w.endswith("-saturated") for w in entry["workloads"]) != doc
        assert entry["moves"] == ("ttft_p50_ms" if doc else "out_tok_s")
