"""A temporary checkout for the rehearsal tests: a copy of the
benchmark to which two cells were ADDED as new files and new manifest
entries (no file of the benchmark is edited), beside links to the
program: one of the kind and protocol the benchmark has (a
configuration, a traffic mix, a per-layer metric), and one of another
kind over another protocol (those two modules, a plain reference, an
end-to-end metric and a per-layer one besides)."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(os.path.dirname(HERE))
ROOT = os.path.dirname(BENCH_DIR)


def build(dest: str) -> str:
    shutil.copytree(BENCH_DIR, os.path.join(dest, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", ".pytest_cache"))
    for name in ("seldon_core_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), os.path.join(dest, name))
    add = os.path.join(HERE, "add")
    for folder, _dirs, names in os.walk(add):
        for name in names:
            target = os.path.join(dest, "benchmarks", os.path.relpath(folder, add), name)
            assert not os.path.exists(target), f"{target} would be edited, not added"
            shutil.copy(os.path.join(folder, name), target)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny-lm", "source": "none: rehearsal", "reduced": [],
        "file": "benchmarks/configs/tiny-lm.json", "why": "rehearsal"})
    manifest["workloads"].append({
        "name": "tiny-lm.tiny-chat", "config": "tiny-lm", "traffic": "tiny-chat",
        "chips": 1, "why": "rehearsal"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "out_tok_s" in (metric["name"], metric.get("moves")):
            metric["workloads"].append("tiny-lm.tiny-chat")
    manifest["per_layer"].append({
        "name": "window_events", "unit": "events", "better": "higher",
        "source": "host_clock", "layer": "ingress + gateway", "moves": "out_tok_s",
        "workloads": ["tiny-lm.tiny-chat"]})
    manifest["configs"].append({
        "name": "tiny-mlp", "source": "none: rehearsal", "reduced": [],
        "file": "benchmarks/configs/tiny-mlp.json", "why": "rehearsal"})
    manifest["workloads"].append({
        "name": "tiny-mlp.tiny-rows", "config": "tiny-mlp", "traffic": "tiny-rows",
        "chips": 1, "why": "rehearsal"})
    manifest["end_to_end"].append({
        "name": "rows_s", "unit": "rows/s", "better": "higher", "bound": 0.1,
        "source": "host_clock", "workloads": ["tiny-mlp.tiny-rows"]})
    manifest["per_layer"].append({
        "name": "batch_rows_mean", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "batcher", "moves": "rows_s",
        "workloads": ["tiny-mlp.tiny-rows"]})
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return dest
