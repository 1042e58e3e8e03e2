"""The stratified draw: one multiset of sizes for every seed."""

import glob
import os

import pytest

from harness import lengths, manifest

TRAFFIC = sorted(glob.glob(os.path.join(manifest.BENCH_DIR, "traffic", "*.json")))


@pytest.mark.parametrize("path", TRAFFIC, ids=[os.path.basename(p) for p in TRAFFIC])
def test_same_multiset_other_order(path):
    traffic = manifest.load_json(path)
    work = lengths.multiset(traffic)
    a = lengths.schedule(work, 7)
    b = lengths.schedule(work, 2**31 + 12345)  # the driver's seeds pass 32 signed bits
    assert sorted(a) == sorted(b) == sorted(work) == sorted(lengths.multiset(traffic))
    assert a != b
    assert a == lengths.schedule(work, 7)
    assert len(a) == traffic["requests"]


@pytest.mark.parametrize("path", TRAFFIC, ids=[os.path.basename(p) for p in TRAFFIC])
def test_lengths_stay_inside_their_limits(path):
    traffic = manifest.load_json(path)
    for prompt, answer in lengths.multiset(traffic):
        assert traffic["prompt_tokens"].get("min", prompt) <= prompt
        assert prompt <= traffic["prompt_tokens"].get("max", prompt)
        assert answer >= 1 and prompt + answer <= traffic["max_total"]


def test_quantiles_are_stratified():
    dist = {"dist": "lognormal", "median": 100, "sigma": 0.5, "min": 1, "max": 10_000}
    draws = [lengths.quantile(dist, (i + 0.5) / 101) for i in range(101)]
    assert draws == sorted(draws) and draws[50] == 100
    assert lengths.quantile({"dist": "uniform", "min": 10, "max": 20}, 0.5) == 15
    assert lengths.quantile({"dist": "fixed", "value": 8}, 0.9) == 8
