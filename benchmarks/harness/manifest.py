"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the NAME the manifest gives it:

* configuration ``c``  -> the manifest entry's ``file`` (``configs/c.json``)
* traffic mix ``t``    -> ``benchmarks/traffic/t.json``
* end-to-end metric ``m`` -> ``benchmarks/end_to_end/m.py`` and per-layer
  metric ``m`` -> ``benchmarks/layer_metrics/m.py`` (dots and dashes in
  ``m`` become underscores), each a module with ``read(ctx)``
* a traffic file's ``protocol`` ``p`` -> ``harness/protocols/p.py`` and a
  configuration's ``kind`` ``k`` -> ``harness/kinds/k.py``

so a later PR adds a cell by adding files and manifest entries and
edits no file that is here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(path: str = MANIFEST) -> dict:
    manifest = load_json(path)
    problems = check_manifest(manifest)
    if problems:
        raise ManifestError("; ".join(problems))
    return manifest


def check_manifest(m: dict) -> list:
    """The contract's rules a run depends on, as a list of problems."""
    bad = []

    def name(kind, value):
        if not isinstance(value, str) or not NAME_RE.match(value):
            bad.append(f"{kind} name {value!r} uses characters outside [A-Za-z0-9_.-] "
                       "or is longer than 64")

    def unique(kind, names):
        if len(set(names)) != len(names):
            bad.append(f"two {kind} share a name")

    configs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        name("configuration", c["name"])
        for key in c["reduced"]:
            name("reduced key", key)
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in m["paths"]):
            bad.append(f"configuration file {c['file']} lies outside paths")
    unique("configurations", [c["name"] for c in m["configs"]])
    cells = [w["name"] for w in m["workloads"]]
    unique("cells", cells)
    for w in m["workloads"]:
        name("cell", w["name"])
        name("traffic", w["traffic"])
        if w["config"] not in configs:
            bad.append(f"cell {w['name']} names an unknown configuration")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']} asks for {w['chips']} chips")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad.append(f"cell {w['name']}: why must be one line of 1..200 characters")
    for c in configs:
        if not any(w["config"] == c for w in m["workloads"]):
            bad.append(f"configuration {c} has no cell")
    metrics = m["end_to_end"] + m["per_layer"]
    unique("metrics", [x["name"] for x in metrics])
    e2e = {x["name"]: x for x in m["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("setup_s is not an end-to-end metric")
    for x in metrics:
        name("metric", x["name"])
        if not UNIT_RE.match(x["unit"]):
            bad.append(f"metric {x['name']}: unit {x['unit']!r} not permitted")
        if x["better"] not in ("lower", "higher"):
            bad.append(f"metric {x['name']}: better must be lower or higher")
        if x["source"] not in SOURCES:
            bad.append(f"metric {x['name']}: unknown source {x['source']!r}")
        for w in x.get("workloads", []):
            if w not in cells:
                bad.append(f"metric {x['name']} lists an unknown cell {w}")
    for x in m["end_to_end"]:
        if x["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end metric {x['name']} may not come from {x['source']}")
        if not 0 < x["bound"] <= 0.1:
            bad.append(f"metric {x['name']}: bound {x['bound']} outside (0, 0.1]")
    for x in m["per_layer"]:
        moved = e2e.get(x["moves"])
        if moved is None:
            bad.append(f"per-layer metric {x['name']} moves an unknown metric")
            continue
        for w in x.get("workloads", cells):
            if w not in moved.get("workloads", cells):
                bad.append(f"{x['name']} is reported in {w}, where {x['moves']} is not")
    for w in cells:
        for kind in ("end_to_end", "per_layer"):
            if not [x for x in metrics_of(m, w, kind) if x["name"] != "setup_s"]:
                bad.append(f"cell {w} reports no {kind} metric besides setup_s")
    return bad


def metrics_of(m: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries cell ``cell`` reports."""
    return [x for x in m[kind] if cell in x.get("workloads", [cell])]


def cell(m: dict, workload: str) -> tuple:
    """(cell, configuration file contents, traffic file contents)."""
    for w in m["workloads"]:
        if w["name"] == workload:
            break
    else:
        raise ManifestError(f"no cell named {workload!r}; known: "
                            f"{[w['name'] for w in m['workloads']]}")
    (entry,) = [c for c in m["configs"] if c["name"] == w["config"]]
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))
    return w, config, traffic


def module(folder: str, name: str):
    """The module ``<folder>/<name>.py`` under ``benchmarks/`` (dots and
    dashes in ``name`` become underscores in the file's): a metric's
    reader, a traffic file's protocol, a configuration's kind."""
    stem = re.sub(r"[.\-]", "_", name)
    path = os.path.join(BENCH_DIR, folder, stem + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"{name!r} has no module at {path}")
    spec = importlib.util.spec_from_file_location(f"{folder.replace('/', '_')}_{stem}", path)
    found = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(found)
    return found


def reader(folder: str, metric: str):
    """The ``read(ctx)`` of ``<folder>/<metric>.py`` (``folder`` is
    ``end_to_end`` or ``layer_metrics``)."""
    return module(folder, metric).read
