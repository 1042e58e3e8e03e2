"""The reduction from a profiler trace to numbers, on a small trace
recorded on a TPU v5e (``fixtures/record_trace.py``: four runs of one
named program with a 20 ms host sleep after each)."""

import os

import pytest

from harness import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace", "fixture.xplane.pb")


def test_union_length():
    assert tr.union_length([]) == 0
    assert tr.union_length([(0, 10), (5, 12), (20, 21)]) == 13
    assert tr.union_length([(3, 4), (0, 10)]) == 10  # nested counts once


@pytest.mark.parametrize("text, want", [
    ('%block_31.14 = (f32[16,1,1280]{2,1,0:T(1,128)S(1)}, f32[16,20,128]{2,1,0}) '
     'custom-call(s32[16,16]{1,0} %copy-done.522), custom_call_target="tpu_custom_call"',
     "pallas_kernel_f32_16_1_1280_"),
    ("%fusion.3423.remat2 = (bf16[1,513,64,20,64]{1,4,3,2,0:T(8,128)(2,1)}, bf16[1]{0}) "
     "fusion(bf16[] %a), kind=kLoop", "fusion_bf16_1_513_64_20_64_"),
    ("%dynamic_update_slice.46 = bf16[36,513,64,20,64]{1,4,3,2,0} "
     "dynamic-update-slice(bf16[36,513,64,20,64]{1,4} %p, bf16[] %q)",
     "dynamic-update-slice_bf16_36_513_64_20_64_"),
    ("%while.44 = (s32[]{:T(128)}, bf16[36,513]{1,0}) while((s32[]) %x), condition=%c",
     "while_s32__"),
    ("ThreadpoolListener::StartRegion", "ThreadpoolListener::StartRegion"),
])
def test_operation_names_survive_a_recompile(text, want):
    assert tr.stable_op_name(text) == want


def test_recorded_trace():
    got = tr.reduce_trace(FIXTURE)
    assert got["devices"] == 1
    assert got["modules"] == {"jit_fixture_step": {"count": 4.0, "seconds": pytest.approx(
        1.4307e-05, rel=1e-6)}}
    assert got["busy_s"] == pytest.approx(1.4284e-05, rel=1e-6)
    assert got["window_s"] == pytest.approx(0.065266387, rel=1e-6)
    summed = sum(v["seconds"] for v in got["ops"].values())  # no overlap here: equal
    assert summed == pytest.approx(got["busy_s"], rel=1e-6) and summed < got["window_s"]
    assert got["ops"]["fusion_bf16_512_512_"]["count"] == 4
    gaps = got["breakdown"]["idle_gaps"]
    assert len(gaps) <= 10 and [name for name, _s in gaps[:3]] == ["$time sleep"] * 3
    assert all(0.02 < s < 0.025 for _n, s in gaps[:3])
    assert len(got["breakdown"]["device_ops"]) <= 10
