"""The readers of the device's idle time as the program stamps it
(PR 50), each on a hand-made ``ctx`` of two snapshots a span: the
untraced stretch (the window's first snapshot to the last sample before
the trace), the traced interval, the window."""

import pytest

from harness import manifest
from layer_metrics import idle_work


def read(metric, ctx):
    return manifest.reader("layer_metrics", metric)(ctx)


def snapshot(clock, busy, by, compile_s=0.0):
    return {"clock_s": clock, "device_busy_s": busy, "device_idle_s": sum(by.values()),
            "device_idle_by_s": dict(by), "device_programs": int(10 * busy),
            "xla_compiles": int(compile_s > 0), "xla_compile_s": compile_s}


ZERO = dict.fromkeys(
    ("no_work", "between", "admit", "prefill.pack", "prefill.call", "prefill.tail",
     "launch.plan", "launch.call", "launch.post", "wait", "harvest", "record"), 0.0)
FIRST = snapshot(1000.0, 50.0, dict(ZERO, no_work=7.0, harvest=1.0))
# 14 s on, 13.5 of them settled: 12.0 busy and 1.5 idle
LAST = snapshot(1014.0, 62.0, dict(
    ZERO, no_work=7.6, harvest=1.1, admit=0.05, **{
        "prefill.pack": 0.1, "prefill.tail": 0.15, "launch.plan": 0.2,
        "launch.call": 0.25, "launch.post": 0.05}))
# the traced interval: 3 s, 2.4 busy and 0.6 idle, a compile under it
TRACE = (snapshot(1015.3, 63.0, LAST["device_idle_by_s"]),
         snapshot(1018.3, 65.4, dict(LAST["device_idle_by_s"], **{
             "launch.call": 0.25 + 0.45, "harvest": 1.1 + 0.15}), compile_s=0.08))
END = snapshot(1051.0, 95.0, dict(TRACE[1]["device_idle_by_s"], record=0.5), compile_s=0.08)


def ctx_of(first=FIRST, last=LAST, trace=TRACE, end=END):
    engine = {"window": [first, end], "samples": [last, trace[1]], "trace": list(trace)}
    return {"window": (100.0, 151.0), "engine": engine, "trace": None, "config": {},
            "peaks": {"hbm_bytes_per_s": 819e9}}


CASES = [
    ("device_idle_pct", 100.0 * 1.5 / 13.5),
    ("device_idle_traced_pct", 100.0 * 0.6 / 3.0),
    ("device_idle_launch_pct", 100.0 * 0.5 / 13.5),
    ("device_idle_prefill_pct", 100.0 * 0.3 / 13.5),
    ("window_compile_ms", 80.0),
    ("ttft_device_idle_pct", 100.0 * 1.5 / 13.5),
    ("ttft_device_idle_traced_pct", 100.0 * 0.6 / 3.0),
    ("ttft_idle_no_work_pct", 100.0 * 0.6 / 13.5),
    ("ttft_idle_host_pct", 100.0 * 0.9 / 13.5),
]


@pytest.mark.parametrize("metric,want", CASES)
def test_each_reader_returns_its_quotient(metric, want):
    assert read(metric, ctx_of()) == pytest.approx(want)


@pytest.mark.parametrize("metric,_want", CASES)
def test_equal_counters_read_zero_and_not_nothing(metric, _want):
    """A listed metric a traced run lacks gets the line refused: where
    the counters are there and nothing was idle or compiled, 0.0."""
    still = dict(FIRST, clock_s=1014.0)
    ctx = ctx_of(last=still, trace=(dict(FIRST, clock_s=1015.3), dict(FIRST, clock_s=1018.3)),
                 end=dict(FIRST, clock_s=1051.0))
    value = read(metric, ctx)
    assert value == 0.0 and value is not None
    # busy and no idle at all: the same
    busy = ctx_of(last=dict(still, device_busy_s=60.0),
                  trace=(dict(FIRST, clock_s=1015.3),
                         dict(FIRST, clock_s=1018.3, device_busy_s=70.0)),
                  end=dict(FIRST, clock_s=1051.0, device_busy_s=99.0))
    assert read(metric, busy) == 0.0


@pytest.mark.parametrize("metric,_want", CASES)
def test_snapshots_without_the_keys_read_nothing(metric, _want):
    """The parent of PR 50: ``engine_stats()`` has a clock and no device
    clock, and counts no compile."""
    def older(s):
        return {"clock_s": s["clock_s"], "host_work_s": 1.0, "host_gap_s": 0.0}

    ctx = ctx_of(first=older(FIRST), last=older(LAST),
                 trace=(older(TRACE[0]), older(TRACE[1])), end=older(END))
    assert read(metric, ctx) is None
    # a failed poll at either end of the span, no engine at all
    assert read(metric, ctx_of(first=None, trace=(None, TRACE[1]), end=None)) is None
    assert read(metric, {"window": (0.0, 1.0), "engine": {}, "trace": None}) is None
    # a CPU rehearsal: the counters are there, the device is none
    assert read(metric, dict(ctx_of(), peaks=None)) is None


def test_the_parts_sum_to_the_whole():
    ctx = ctx_of()
    whole = read("ttft_device_idle_pct", ctx)
    assert read("ttft_idle_no_work_pct", ctx) + read("ttft_idle_host_pct", ctx) == \
        pytest.approx(whole)
    rest = idle_work.idle_pct(ctx, "untraced", other_than=idle_work.LAUNCH + idle_work.PREFILL)
    assert (read("device_idle_launch_pct", ctx) + read("device_idle_prefill_pct", ctx)
            + rest) == pytest.approx(read("device_idle_pct", ctx))


def test_the_untraced_readers_need_a_sample_before_the_trace():
    ctx = ctx_of()
    ctx["engine"]["samples"] = [TRACE[1]]
    assert read("device_idle_pct", ctx) is None
    assert read("device_idle_traced_pct", ctx) == pytest.approx(20.0)
    del ctx["engine"]["trace"]  # an untraced run
    assert read("device_idle_traced_pct", ctx) is None
    assert read("window_compile_ms", ctx) == pytest.approx(80.0)


def test_every_new_entry_lists_cells_that_report_what_it_moves():
    m = manifest.load_json(manifest.MANIFEST)
    assert manifest.check_manifest(m) == []
    e2e = {x["name"]: x for x in m["end_to_end"]}
    entries = {x["name"]: x for x in m["per_layer"]}
    for metric, _want in CASES:
        entry = entries[metric]
        assert entry["source"] == "program_counter" and entry["better"] == "lower"
        assert entry["layer"] == "wave scheduler + KV manager"
        assert entry["workloads"], metric
        for cell in entry["workloads"]:
            assert cell in e2e[entry["moves"]]["workloads"], (metric, cell)
    saturated = [w["name"] for w in m["workloads"] if w["name"].endswith("-saturated")]
    assert entries["device_idle_pct"]["workloads"] == saturated and len(saturated) == 8
    assert entries["ttft_idle_host_pct"]["workloads"] == ["gpt2-large.doc-prefill"]
    # appended: what the manifest had keeps its place
    assert [x["name"] for x in m["per_layer"]][-9:] == [c[0] for c in CASES]
