"""run.py end to end at a toy size on the CPU, in a temporary checkout
to which two cells were ADDED as new files and manifest entries
(``fixtures/build_tree``) without editing a file of the benchmark: one
of the benchmark's own kind and protocol, one of another kind (a
classifier behind the batcher) over another protocol (unary REST)."""

import json
import os
import subprocess
import sys

import pytest

import build_tree

CELL = "tiny-lm.tiny-chat"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return build_tree.build(str(tmp_path_factory.mktemp("checkout")))


def run_cell(tree, *extra, env=None, cell=CELL):
    return subprocess.run(
        [sys.executable, os.path.join(tree, "benchmarks", "run.py"), "--workload", cell,
         "--seed", str(2**31 + 77), "--seconds", "4", *extra],
        cwd=tree, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


@pytest.mark.parametrize("trace", ["0", "1"])
def test_a_cell_runs_and_its_last_line_parses(tree, trace):
    out = run_cell(tree, "--trace", trace, "--rehearse-cpu")
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert set(result) - {"rehearsal", "breakdown", "compared"} == RESULT_KEYS
    assert result["rehearsal"]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    # each number compared beside its limit: the line's last key, and said again by
    # the last lines on standard error
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {"worst_gap_stds", "failed_requests"}
    assert all(number <= limit for number, limit in result["compared"].values())
    said = out.stderr.strip().splitlines()[-len(result["compared"]):]
    assert [line.split()[2].rstrip(":") for line in said] == list(result["compared"]), said
    assert result["device"]["platform"] == "cpu"
    for name, metric in result["metrics"].items():
        # the share of token events that found the next one queued is a true 0 in
        # about half of the toy runs (and in every cell on the chip)
        assert set(metric) == {"value", "unit"}
        assert metric["value"] > 0 or name == "deliver_behind_pct", name
    earlier = "\n".join(lines[:-1])
    for said in ("set-up:", "samples behind each percentile", "generator busy share",
                 "reference:"):
        assert said in earlier, earlier
    # a toy chunk lasts milliseconds, so two warm-up requests meant to decode side by
    # side do not always overlap here: either way the count is said
    assert "window compiles: 0" in earlier or "COMPILED INSIDE THE WINDOW" in earlier, earlier
    if trace == "0":
        assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
    else:  # the added reader was found by its name; device readers found no device trace
        assert result["metrics"]["window_events"]["value"] > 0
        assert "chunk_tokens_mean" in result["metrics"]
        assert "decode_step_ms" not in result["metrics"]


@pytest.mark.parametrize("trace", ["0", "1"])
def test_a_cell_of_another_kind_over_another_protocol_arrives_as_files(tree, trace):
    out = run_cell(tree, "--trace", trace, "--rehearse-cpu", cell="tiny-mlp.tiny-rows")
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == ({"rows_s", "setup_s"} if trace == "0"
                                      else {"batch_rows_mean"})
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert "served score rows lie within" in "\n".join(lines[:-1])
    worst, limit = result["compared"]["worst_abs"]
    assert 0 <= worst <= limit and result["compared"]["failed_requests"] == [0, 0]


def test_without_a_chip_the_measurement_path_fails(tree):
    out = run_cell(tree, "--trace", "0")
    assert out.returncode != 0
    assert not out.stdout.strip().startswith("{") and '"metrics"' not in out.stdout
    assert "no accelerator" in out.stderr


def test_outside_a_checkout_of_the_program_it_fails(tree, tmp_path):
    os.symlink(os.path.join(tree, "benchmarks"), tmp_path / "benchmarks")
    os.symlink(os.path.join(tree, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--rehearse-cpu"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and '"metrics"' not in out.stdout
