"""Checker: generation metrics contract (GL4xx).

Invariant (PR 3, extended by PRs 5-8): ``PagedEngine.engine_stats()``
is complete-by-contract — every key it emits maps to a canonical
Prometheus metric in ``GenerationPrometheusBridge``
(``ENGINE_STATS_METRICS``) or is explicitly excluded
(``ENGINE_STATS_EXCLUDED``), and the SLO counter keys the flight
recorder threads per-chunk (``_SLO_COUNTER_KEYS``) are real, mapped
counters.  The per-subsystem runtime contract tests asserted slices of
this; the checker generalizes them into one static pass that also
polices metric NAMING (``seldon_tpu_`` prefix, counters end
``_total``).

Rules:

* GL401 — engine_stats key neither bridge-mapped nor excluded.
* GL402 — bridge-mapped/excluded key that engine_stats never emits.
* GL403 — metric naming: prefix/suffix discipline in
  ``ENGINE_STATS_METRICS`` and ``TRANSPORT_METRICS``.
* GL404 — ``_SLO_COUNTER_KEYS`` entry that is not a mapped
  engine-stats counter (the flight-recorder threading contract).
* GL405 — ``record_transport_hop`` keyword parameter with no
  ``TRANSPORT_METRICS`` mapping and no ``TRANSPORT_RECORD_EXCLUDED``
  entry: a per-hop measurement (e.g. the r14 ``zero_copy_bytes``
  split) that would silently skip Prometheus export.
* GL406 — ``TelemetryAggregator.fleet_rollup()`` key neither mapped
  (``FLEET_METRICS``) nor excluded (``FLEET_EXCLUDED``): a fleet
  aggregate that would silently skip ``seldon_tpu_fleet_*`` export
  (the r20 fleet-telemetry contract, same shape as GL401).
* GL407 — ``FLEET_METRICS``/``FLEET_EXCLUDED`` key the rollup never
  emits — dead fleet mapping (the GL402 twin).

The GL403 naming pass also covers ``COST_LEDGER_METRICS`` (the
per-adapter cost-ledger export) and ``FLEET_METRICS``.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from tools.graftlint.core import LintContext, Source, Violation, str_const

NAME = "metrics-contract"

PAGED = "seldon_core_tpu/models/paged/engine.py"
METRICS = "seldon_core_tpu/utils/metrics.py"
FLEETVIEW = "seldon_core_tpu/controlplane/fleetview.py"


def _dict_literal_keys(node: ast.Dict) -> List[str]:
    out = []
    for k in node.keys:
        s = str_const(k) if k is not None else None
        if s is not None:
            out.append(s)
    return out


def _assigned_dict(tree: ast.AST, name: str, attr_of_self: bool = False) -> Optional[ast.Dict]:
    """First ``<name> = {...}`` (or ``self.<name> = {...}``) dict
    literal in the tree."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        t = node.targets[0]
        match = (
            isinstance(t, ast.Attribute) and t.attr == name
            if attr_of_self else
            isinstance(t, ast.Name) and t.id == name
        )
        if match and isinstance(node.value, ast.Dict):
            return node.value
    return None


def _metric_specs(tree: ast.AST, name: str) -> Dict[str, Tuple[str, str]]:
    """Parse ``NAME: Dict[...] = { "key": (kind, metric, doc), ... }``
    into {key: (kind, metric_name)}."""
    out: Dict[str, Tuple[str, str]] = {}
    for node in ast.walk(tree):
        target = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            target, value = node.targets[0], node.value
        elif isinstance(node, ast.AnnAssign):
            target, value = node.target, node.value
        else:
            continue
        if not (isinstance(target, ast.Name) and target.id == name):
            continue
        if not isinstance(value, ast.Dict):
            continue
        for k, v in zip(value.keys, value.values):
            key = str_const(k) if k is not None else None
            if key is None or not isinstance(v, ast.Tuple) or len(v.elts) < 2:
                continue
            kind = str_const(v.elts[0]) or ""
            metric = str_const(v.elts[1]) or ""
            out[key] = (kind, metric)
    return out


def _set_literal(tree: ast.AST, name: str) -> Optional[Set[str]]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if isinstance(t, ast.Name) and t.id == name \
                    and isinstance(node.value, (ast.Set, ast.Tuple, ast.List)):
                return {
                    s for e in node.value.elts
                    if (s := str_const(e)) is not None
                }
    return None


def _engine_stats_keys(paged: Source) -> Set[str]:
    """Keys engine_stats() emits: the ``self._counters`` init dict plus
    the literal keys of the dict built inside ``engine_stats``."""
    keys: Set[str] = set()
    counters = _assigned_dict(paged.tree, "_counters", attr_of_self=True)
    if counters is not None:
        keys |= set(_dict_literal_keys(counters))
    for node in ast.walk(paged.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == "engine_stats":
            for sub in ast.walk(node):
                if isinstance(sub, ast.Dict):
                    keys |= set(_dict_literal_keys(sub))
    # detail-mode additions (out["recorder"] = ...) are not part of the
    # DEFAULT contract; they only exist under detail=True
    keys.discard("records")
    return keys


def _fleet_rollup_keys(fleetview: Source) -> Set[str]:
    """Keys ``fleet_rollup()`` emits: the literal keys of every dict
    built inside the function (one return literal today; the walk keeps
    the contract honest if it grows helpers)."""
    keys: Set[str] = set()
    for node in ast.walk(fleetview.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == "fleet_rollup":
            for sub in ast.walk(node):
                if isinstance(sub, ast.Dict):
                    keys |= set(_dict_literal_keys(sub))
    return keys


def _hop_record_params(tree: ast.AST) -> List[Tuple[str, int]]:
    """The keyword parameters of ``record_transport_hop`` (the per-hop
    recording surface) with their line — every quantitative one must be
    bridge-mapped or excluded."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == "record_transport_hop":
            a = node.args
            return [(p.arg, node.lineno) for p in a.kwonlyargs]
    return []


class _Checker:
    name = NAME
    codes = ("GL401", "GL402", "GL403", "GL404", "GL405", "GL406", "GL407")
    doc = __doc__

    def run(self, ctx: LintContext) -> Iterable[Violation]:
        paged = ctx.source(PAGED)
        metrics = ctx.source(METRICS)
        if paged is None or metrics is None:
            return []
        out = self.check_pair(paged, metrics)
        fleetview = ctx.source(FLEETVIEW)
        if fleetview is not None:
            out += self.check_fleet(fleetview, metrics)
        return out

    def check_fleet(self, fleetview: Source, metrics: Source) -> List[Violation]:
        """The r20 fleet-rollup contract: every fleet_rollup() key is
        FLEET_METRICS-mapped or FLEET_EXCLUDED, and no dead mappings."""
        out: List[Violation] = []
        specs = _metric_specs(metrics.tree, "FLEET_METRICS")
        excluded = _set_literal(metrics.tree, "FLEET_EXCLUDED") or set()
        produced = _fleet_rollup_keys(fleetview)
        if not specs or not produced:
            out.append(Violation(
                checker=self.name, code="GL407", path=METRICS, line=1,
                symbol="FLEET_METRICS",
                message=(
                    "could not locate FLEET_METRICS / fleet_rollup keys — "
                    "the fleet contract anchor moved; update "
                    "tools/graftlint/checkers/metrics_contract.py"
                ),
            ))
            return out
        for key in sorted(produced - set(specs) - excluded):
            out.append(Violation(
                checker=self.name, code="GL406", path=FLEETVIEW, line=1,
                symbol=key,
                message=(
                    f"fleet_rollup() emits {key!r} but the fleet bridge "
                    "neither maps it (FLEET_METRICS) nor excludes it "
                    "(FLEET_EXCLUDED) — the aggregate would silently "
                    "skip seldon_tpu_fleet_* export"
                ),
            ))
        for key in sorted((set(specs) | excluded) - produced):
            out.append(Violation(
                checker=self.name, code="GL407", path=METRICS, line=1,
                symbol=key,
                message=(
                    f"{key!r} is fleet-mapped/excluded but fleet_rollup() "
                    "never emits it — dead mapping (or a renamed rollup)"
                ),
            ))
        return out

    def check_pair(self, paged: Source, metrics: Source) -> List[Violation]:
        out: List[Violation] = []
        specs = _metric_specs(metrics.tree, "ENGINE_STATS_METRICS")
        excluded = _set_literal(metrics.tree, "ENGINE_STATS_EXCLUDED") or set()
        produced = _engine_stats_keys(paged)
        slo_keys = _set_literal(paged.tree, "_SLO_COUNTER_KEYS") or set()

        if not specs or not produced:
            out.append(Violation(
                checker=self.name, code="GL402", path=METRICS, line=1,
                symbol="ENGINE_STATS_METRICS",
                message=(
                    "could not locate ENGINE_STATS_METRICS / engine_stats "
                    "keys — the contract anchor moved; update "
                    "tools/graftlint/checkers/metrics_contract.py"
                ),
            ))
            return out

        detail_only = {"recorder", "recorder_stats", "seq"}
        for key in sorted(produced - set(specs) - excluded - detail_only):
            out.append(Violation(
                checker=self.name, code="GL401", path=PAGED, line=1,
                symbol=key,
                message=(
                    f"engine_stats() emits {key!r} but the Prometheus bridge "
                    "neither maps it (ENGINE_STATS_METRICS) nor excludes it "
                    "(ENGINE_STATS_EXCLUDED) — the counter would silently "
                    "skip export"
                ),
            ))
        for key in sorted((set(specs) | excluded) - produced):
            out.append(Violation(
                checker=self.name, code="GL402", path=METRICS, line=1,
                symbol=key,
                message=(
                    f"{key!r} is bridge-mapped/excluded but engine_stats() "
                    "never emits it — dead mapping (or a renamed counter)"
                ),
            ))

        transport_specs = _metric_specs(metrics.tree, "TRANSPORT_METRICS")
        # the r20 additions ride the same naming discipline (fixtures
        # without them contribute nothing — _metric_specs returns {})
        cost_specs = _metric_specs(metrics.tree, "COST_LEDGER_METRICS")
        fleet_specs = _metric_specs(metrics.tree, "FLEET_METRICS")
        # iterate the spec maps SEPARATELY: cost-ledger keys reuse
        # engine-stats key names ("prefill_tokens"), and a dict merge
        # would shadow one mapping's metric name from the naming pass
        for spec_map in (specs, transport_specs, cost_specs, fleet_specs):
            for key, (kind, metric) in sorted(spec_map.items()):
                if not metric.startswith("seldon_tpu_"):
                    out.append(Violation(
                        checker=self.name, code="GL403", path=METRICS, line=1,
                        symbol=metric,
                        message=f"metric {metric!r} (key {key!r}) must carry "
                                "the seldon_tpu_ prefix",
                    ))
                if kind == "counter" and not metric.endswith("_total"):
                    out.append(Violation(
                        checker=self.name, code="GL403", path=METRICS, line=1,
                        symbol=metric,
                        message=f"counter {metric!r} (key {key!r}) must end "
                                "in _total (Prometheus naming)",
                    ))
                if kind == "gauge" and metric.endswith("_total"):
                    out.append(Violation(
                        checker=self.name, code="GL403", path=METRICS, line=1,
                        symbol=metric,
                        message=f"gauge {metric!r} (key {key!r}) must not "
                                "end in _total",
                    ))

        excluded_record = _set_literal(metrics.tree, "TRANSPORT_RECORD_EXCLUDED") or set()
        # internal plumbing kwargs of the recording call, not measurements
        record_plumbing = {"registry", "error"}
        # fields the recorder derives rather than receives (the seconds
        # pair maps the *_s internals) are already TRANSPORT_METRICS keys
        for param, line in _hop_record_params(metrics.tree):
            if param in record_plumbing or param in excluded_record:
                continue
            if param not in transport_specs:
                out.append(Violation(
                    checker=self.name, code="GL405", path=METRICS, line=line,
                    symbol=param,
                    message=(
                        f"record_transport_hop takes {param!r} but "
                        "TRANSPORT_METRICS neither maps it nor "
                        "TRANSPORT_RECORD_EXCLUDED excludes it — the "
                        "per-hop measurement would silently skip "
                        "Prometheus export"
                    ),
                ))

        for key in sorted(slo_keys):
            if key not in produced or specs.get(key, ("", ""))[0] != "counter":
                out.append(Violation(
                    checker=self.name, code="GL404", path=PAGED, line=1,
                    symbol=key,
                    message=(
                        f"_SLO_COUNTER_KEYS entry {key!r} must be an "
                        "engine_stats counter mapped by the bridge — the "
                        "flight recorder threads its per-chunk delta"
                    ),
                ))
        return out


CHECKER = _Checker()
