"""BENCHMARK.json against the contract's limits a file can break, and
every name against the files it must find."""

import json
import os
import re

import pytest

from harness import manifest
from harness.peaks import UnknownDevice, peaks

M = manifest.load_json(manifest.MANIFEST)


def test_manifest_passes_its_own_check():
    assert manifest.check_manifest(M) == []
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(manifest.MANIFEST) <= 64 * 1024
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)


@pytest.mark.parametrize("bad", [
    {"unit": "tokens per s"}, {"unit": "µs"}, {"name": "a/b"}, {"name": "x" * 65},
    {"name": "has space"}, {"better": "faster"}, {"source": "guess"},
])
def test_check_refuses_forbidden_names_and_units(bad):
    m = json.loads(json.dumps(M))
    m["per_layer"][0].update(bad)
    assert manifest.check_manifest(m)


def test_entries_have_just_the_contract_keys():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1
    for x in M["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    for x in M["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}


def test_every_name_finds_its_file():
    for w in M["workloads"]:
        cell, config, traffic = manifest.cell(M, w["name"])
        assert config["name"] == cell["config"]
        assert traffic["loop"] == "closed" and traffic["clients"] >= 1
        assert callable(manifest.module("harness/protocols", traffic["protocol"]).call)
        assert callable(manifest.module("harness/kinds", config["kind"]).judge)
    for x in M["end_to_end"]:
        assert callable(manifest.reader("end_to_end", x["name"]))
    for x in M["per_layer"]:
        assert callable(manifest.reader("layer_metrics", x["name"]))
    with pytest.raises(manifest.ManifestError):
        manifest.reader("layer_metrics", "no_such_metric")


def test_files_under_paths_are_named_from_permitted_characters():
    for folder, _dirs, files in os.walk(manifest.BENCH_DIR):
        if "__pycache__" in folder or ".pytest_cache" in folder:
            continue
        for name in files:
            rel = os.path.relpath(os.path.join(folder, name), manifest.ROOT)
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_no_latency_is_judged_where_a_queue_sets_it():
    saturated = "gpt2-large.chat-saturated"
    judged = {x["name"] for x in manifest.metrics_of(M, saturated, "end_to_end")}
    assert judged == {"out_tok_s", "setup_s"}
    layered = {x["name"] for x in manifest.metrics_of(M, saturated, "per_layer")}
    assert "queue_ttft_p50_ms" in layered


def test_an_unknown_device_kind_is_an_error():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDevice):
        peaks("cpu")
