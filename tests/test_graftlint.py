"""graftlint: the static-invariant suite is itself under test.

Three layers:

* **seeded-fixture tests** — every checker must catch the known-bad
  snippets in tools/graftlint/fixtures/ (a checker that goes vacuous
  fails HERE, not silently on the tree);
* **real-tree gate** — the full suite over ``seldon_core_tpu/`` must
  be green (pragmas + allowlist are the only sanctioned suppressions).
  This is the tier-1 wiring: ``pytest tests/`` alone enforces the
  invariants;
* **suite plumbing** — checker registry meta-test, allowlist parsing
  and staleness, inline pragmas, CLI JSON contract, and the
  runtime/knobs.py registry the knob checker reads.
"""

import ast
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.graftlint.checkers import (  # noqa: E402
    ALL_CHECKERS,
    BY_NAME,
    capture_redaction,
    except_hygiene,
    jit_purity,
    knob_registry,
    lock_discipline,
    metrics_contract,
    propagation,
)
from tools.graftlint.core import (  # noqa: E402
    Source,
    load_allowlist,
    run_suite,
)

FIXTURES = os.path.join(REPO, "tools", "graftlint", "fixtures")


def _fixture(name: str) -> Source:
    path = os.path.join(FIXTURES, name)
    with open(path) as f:
        text = f.read()
    return Source(
        path=f"tools/graftlint/fixtures/{name}", abspath=path, text=text,
        lines=text.splitlines(), tree=ast.parse(text),
    )


def _src(text: str, path: str = "seldon_core_tpu/fake/mod.py") -> Source:
    return Source(path=path, abspath=path, text=text,
                  lines=text.splitlines(), tree=ast.parse(text))


# ---------------------------------------------------------------------------
# seeded fixtures: every checker catches its known-bad snippet
# ---------------------------------------------------------------------------

class TestSeededFixtures:
    def test_jit_purity_catches_all_seeds(self):
        vs = jit_purity.CHECKER.check_source(_fixture("bad_jit_purity.py"))
        codes = sorted(v.code for v in vs)
        # every rule fires at least once; the pure function fires nothing
        for code in ("GL101", "GL102", "GL103", "GL104", "GL105"):
            assert code in codes, f"{code} missing from {codes}"
        assert not [v for v in vs if v.symbol == "pure_ok"], \
            "shape/static host math must not be flagged"
        # the specific seeds: two casts, three pulls/branches, two mutations
        assert codes.count("GL101") == 2
        assert codes.count("GL103") == 2
        assert codes.count("GL104") == 2

    def test_knob_registry_catches_all_seeds(self):
        vs = knob_registry.CHECKER.check_source(_fixture("bad_knob_registry.py"))
        by_code = {}
        for v in vs:
            by_code.setdefault(v.code, set()).add(v.symbol)
        assert by_code["GL201"] >= {
            "SELDON_TPU_TP", "SELDON_TPU_PAGED_DEBUG", "SELDON_TPU_MAX_QUEUE",
            "SELDON_TPU_PREFIX_CACHE",  # via module-level constant
        }
        assert "SELDON_TPU_TOTALLY_UNDECLARED" in by_code["GL202"]
        assert "seldon.io/not-a-real-annotation" in by_code["GL202"]
        assert "X-Seldon-Mystery-Header" in by_code["GL202"]
        assert by_code["GL204"] == {"SELDON_TPU_GHOST_KNOB"}

    def test_direct_environ_read_of_knob_fails(self):
        # the acceptance criterion, minimal form: a fresh module doing a
        # direct os.environ read of a registered knob is a violation
        vs = knob_registry.CHECKER.check_source(_src(
            "import os\nTP = os.environ.get('SELDON_TPU_TP', '')\n"
        ))
        assert [v.code for v in vs] == ["GL201"]
        assert vs[0].symbol == "SELDON_TPU_TP"

    def test_lock_discipline_catches_all_seeds(self):
        vs = lock_discipline.CHECKER.check_source(
            _fixture("bad_lock_discipline.py"))
        syms = {(v.code, v.symbol) for v in vs}
        assert ("GL301", "BadEngine.bad_caller->_pop_locked") in syms
        # the allocator the engine's lock guards lives in self.cache
        assert ("GL301", "BadEngine.bad_cache_caller->cache.alloc") in syms
        assert not [v for v in vs if "good_cache_caller" in v.symbol]
        assert ("GL302", "BadEngine.bad_writer._count") in syms
        assert ("GL302", "BadEngine.bad_writer._queue") in syms
        # lock-held callers and __init__ writes are clean
        assert not [v for v in vs if "good_caller" in v.symbol]
        assert not [v for v in vs if "__init__" in v.symbol]
        assert not [v for v in vs if "good_locked_branch" in v.symbol]

    def test_metrics_contract_catches_all_seeds(self):
        vs = metrics_contract.CHECKER.check_pair(
            _fixture("bad_metrics_paged.py"),
            _fixture("bad_metrics_metrics.py"),
        )
        pairs = {(v.code, v.symbol) for v in vs}
        assert ("GL401", "unmapped_counter") in pairs
        assert ("GL402", "never_emitted") in pairs
        assert ("GL403", "seldon_tpu_engine_bad_name") in pairs
        assert ("GL403", "transport_requests_total") in pairs
        assert ("GL404", "ghost_slo_key") in pairs
        # a record_transport_hop measurement kwarg with no metric mapping
        assert ("GL405", "ghost_measurement") in pairs
        # mapped-and-emitted keys are clean
        assert not [v for v in vs if v.symbol in ("chunks", "shed",
                                                  "active_slots")]
        # mapped/excluded/plumbing recorder kwargs are clean
        assert not [v for v in vs if v.code == "GL405" and v.symbol in (
            "requests", "zero_copy_bytes", "error", "registry")]
        # r20: COST_LEDGER_METRICS / FLEET_METRICS ride the naming pass
        assert ("GL403", "seldon_tpu_engine_cost_adapter_page_seconds") \
            in pairs
        assert ("GL403", "seldon_tpu_fleet_bad_total") in pairs

    def test_metrics_contract_catches_fleet_seeds(self):
        vs = metrics_contract.CHECKER.check_fleet(
            _fixture("bad_metrics_fleet.py"),
            _fixture("bad_metrics_metrics.py"),
        )
        pairs = {(v.code, v.symbol) for v in vs}
        # rollup key with no FLEET_METRICS mapping and no exclusion
        assert ("GL406", "phantom_rollup") in pairs
        # fleet-mapped key the rollup never emits
        assert ("GL407", "never_rolled") in pairs
        # mapped and excluded keys are clean
        assert not [v for v in vs if v.symbol in (
            "replicas_ok", "fleet_queue_depth", "t")]

    def test_propagation_catches_all_seeds(self):
        src = _fixture("bad_propagation.py")
        vs = (propagation.CHECKER.check_ingress(src)
              + propagation.CHECKER.check_transport(src))
        pairs = {(v.code, v.symbol) for v in vs}
        assert ("GL501", "bad_handler") in pairs
        assert ("GL502", "bad_handler") in pairs
        assert ("GL503", "BadClient.transform_input") in pairs
        assert ("GL504", "BadClient.transform_input") in pairs
        assert ("GL505", "BadClient.transform_input") in pairs
        assert not [v for v in vs if "good" in v.symbol.lower()]

    def test_capture_redaction_catches_all_seeds(self):
        vs = capture_redaction.CHECKER.check_source(
            _fixture("bad_capture_redaction.py"))
        assert [(v.code, v.symbol) for v in vs] == [("GL408", "bad_writer")]
        # direct nesting AND unpack-side code are clean
        assert not [v for v in vs if "good" in v.symbol]

    def test_capture_redaction_module_level_write(self):
        vs = capture_redaction.CHECKER.check_source(_src(
            "from seldon_core_tpu.codec.bufview import pack_capture\n"
            "BLOB = pack_capture({'meta': {}})\n"
        ))
        assert [(v.code, v.symbol) for v in vs] == [("GL408", "<module>")]

    def test_except_hygiene_catches_all_seeds(self):
        vs = except_hygiene.CHECKER.check_source(
            _fixture("bad_except_hygiene.py"))
        codes = sorted(v.code for v in vs)
        assert codes == ["GL601", "GL601", "GL602", "GL603"]
        # re-raise / conversion / justified comment all pass
        lines = {v.line for v in vs}
        text = _fixture("bad_except_hygiene.py").lines
        for ln in lines:
            assert "fine" not in text[ln - 1]


# ---------------------------------------------------------------------------
# the real tree: tier-1 enforcement
# ---------------------------------------------------------------------------

def test_real_tree_is_green():
    """THE gate: the full suite over seldon_core_tpu/ passes with the
    committed allowlist.  A new invariant violation anywhere in the
    package fails tier-1 right here."""
    res = run_suite(REPO)
    assert res["files_scanned"] > 50
    assert len(res["checkers"]) >= 6
    msgs = "\n".join(
        f"{v['path']}:{v['line']}: {v['code']} [{v['symbol']}] {v['message']}"
        for v in res["violations"]
    )
    assert res["ok"], f"graftlint violations:\n{msgs}"


def test_real_tree_allowlist_entries_all_used():
    """Indirect but important: run_suite reports stale entries as
    GL001 violations, so a green tree also proves the burn-down file
    is minimal."""
    res = run_suite(REPO)
    assert not [v for v in res["violations"] if v["code"] == "GL001"]
    # the burn-down currently carries the documented keeps
    assert res["suppressed"], "expected the documented allowlisted keeps"
    for s in res["suppressed"]:
        assert s["reason"].strip()


# ---------------------------------------------------------------------------
# suite plumbing
# ---------------------------------------------------------------------------

def test_meta_every_checker_module_is_registered():
    """A checker module that exists but is not in ALL_CHECKERS would
    never run — the directory and the registry must agree."""
    checkers_dir = os.path.join(REPO, "tools", "graftlint", "checkers")
    modules = {
        name[:-3] for name in os.listdir(checkers_dir)
        if name.endswith(".py") and name != "__init__.py"
    }
    assert len(ALL_CHECKERS) == len(modules) >= 6
    registered_names = {c.name for c in ALL_CHECKERS}
    assert len(registered_names) == len(ALL_CHECKERS), "duplicate checker name"
    for c in ALL_CHECKERS:
        assert c.codes, f"{c.name} declares no codes"
        assert c.doc and c.doc.strip(), f"{c.name} has no doc"
        assert callable(c.run)
    assert BY_NAME == {c.name: c for c in ALL_CHECKERS}
    # code prefixes are disjoint per checker
    seen = {}
    for c in ALL_CHECKERS:
        for code in c.codes:
            assert code not in seen, f"{code} claimed by {seen.get(code)} and {c.name}"
            seen[code] = c.name


def test_inline_pragma_requires_reason():
    good = _src(
        "class C:\n"
        "    def _f_locked(self): self._x = 1\n"
        "    def g(self):\n"
        "        # graftlint: allow[lock-discipline] — single-writer window\n"
        "        self._x = 2\n"
    )
    bad = _src(
        "class C:\n"
        "    def _f_locked(self): self._x = 1\n"
        "    def g(self):\n"
        "        # graftlint: allow[lock-discipline]\n"
        "        self._x = 2\n"
    )
    v_good = [v for v in lock_discipline.CHECKER.check_source(good)
              if not good.pragma_allows(v.line, v.checker)]
    v_bad = [v for v in lock_discipline.CHECKER.check_source(bad)
             if not bad.pragma_allows(v.line, v.checker)]
    assert not v_good
    assert v_bad, "a reasonless pragma must not suppress"


def test_allowlist_parse_and_staleness(tmp_path):
    allow = tmp_path / "allowlist.toml"
    allow.write_text(
        '# comment\n[[allow]]\nchecker = "except-hygiene"\n'
        'path = "seldon_core_tpu/x.py"\nsymbol = "except@3"\n'
        'reason = "fixture"\n'
    )
    entries = load_allowlist(str(allow))
    assert len(entries) == 1 and entries[0].checker == "except-hygiene"

    # entry without reason is a hard error
    allow.write_text('[[allow]]\nchecker = "c"\npath = "p"\nsymbol = "s"\n')
    with pytest.raises(ValueError, match="reason"):
        load_allowlist(str(allow))

    # unparseable lines are hard errors, not silent widening
    allow.write_text('[[allow]]\nchecker = broken\n')
    with pytest.raises(ValueError, match="unparseable"):
        load_allowlist(str(allow))


def test_allowlist_suppresses_and_reports_stale(tmp_path):
    pkg = tmp_path / "seldon_core_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(
        "def f(fn):\n    try:\n        return fn()\n"
        "    except Exception:\n        return None\n"
    )
    allow = tmp_path / "allow.toml"
    allow.write_text(
        '[[allow]]\nchecker = "except-hygiene"\n'
        'path = "seldon_core_tpu/mod.py"\nsymbol = "except@4"\n'
        'reason = "test keep"\n'
        '[[allow]]\nchecker = "except-hygiene"\n'
        'path = "seldon_core_tpu/gone.py"\nsymbol = "except@9"\n'
        'reason = "stale entry"\n'
    )
    res = run_suite(
        str(tmp_path), checkers=[except_hygiene.CHECKER],
        allowlist_path=str(allow),
    )
    assert len(res["suppressed"]) == 1
    stale = [v for v in res["violations"] if v["code"] == "GL001"]
    assert len(stale) == 1 and "gone.py" in stale[0]["symbol"]
    assert not res["ok"]


def test_cli_json_contract():
    """python -m tools.graftlint --json exits 0 on the tree and emits
    the machine-readable schema bench's lint phase consumes."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(proc.stdout)
    assert data["ok"] is True
    assert data["violations"] == []
    assert data["files_scanned"] > 50
    assert set(data["checkers"]) == {c.name for c in ALL_CHECKERS}
    assert isinstance(data["counts"], dict)
    assert isinstance(data["suppressed"], list)


def test_cli_single_checker_and_list():
    out = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--list"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0
    for c in ALL_CHECKERS:
        assert c.name in out.stdout
    bad = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--checker", "nope"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert bad.returncode == 2


# ---------------------------------------------------------------------------
# runtime/knobs.py registry
# ---------------------------------------------------------------------------

class TestKnobRegistry:
    def test_raw_passthrough_and_undeclared_raises(self, monkeypatch):
        from seldon_core_tpu.runtime import knobs

        monkeypatch.setenv("SELDON_TPU_TP", "4")
        assert knobs.raw("SELDON_TPU_TP") == "4"
        monkeypatch.delenv("SELDON_TPU_TP")
        assert knobs.raw("SELDON_TPU_TP", "0") == "0"
        with pytest.raises(knobs.UndeclaredKnobError):
            knobs.raw("SELDON_TPU_NOT_A_KNOB")

    def test_flag_zero_off_semantics(self, monkeypatch):
        from seldon_core_tpu.runtime import knobs

        # default-on flag: unset -> on, "0" -> off, anything else -> on
        monkeypatch.delenv("SELDON_TPU_BREAKER", raising=False)
        assert knobs.flag("SELDON_TPU_BREAKER") is True
        monkeypatch.setenv("SELDON_TPU_BREAKER", "0")
        assert knobs.flag("SELDON_TPU_BREAKER") is False
        monkeypatch.setenv("SELDON_TPU_BREAKER", "yes")
        assert knobs.flag("SELDON_TPU_BREAKER") is True
        # default-off flag: unset -> off, "1" -> on
        monkeypatch.delenv("SELDON_TPU_PAGED_DEBUG", raising=False)
        assert knobs.flag("SELDON_TPU_PAGED_DEBUG") is False
        monkeypatch.setenv("SELDON_TPU_PAGED_DEBUG", "1")
        assert knobs.flag("SELDON_TPU_PAGED_DEBUG") is True
        # non-flag kinds refuse flag()
        with pytest.raises(knobs.UndeclaredKnobError):
            knobs.flag("SELDON_TPU_TP")

    def test_every_knob_declares_contract_fields(self):
        from seldon_core_tpu.runtime import knobs

        for k in knobs.ENV_KNOBS.values():
            assert k.name.startswith("SELDON_TPU_")
            assert k.kind in ("flag", "int", "float", "str", "path", "spec")
            assert k.doc.strip()
            assert k.anchor.strip()
        assert len(knobs.ENV_KNOBS) >= 25
        assert "X-Seldon-Deadline-Ms" in knobs.HEADERS
        assert "seldon.io/hedge-ms" in knobs.ANNOTATIONS
        assert knobs.declared("x-seldon-deadline-ms")  # case-insensitive

    def test_snapshot_reflects_environment(self):
        from seldon_core_tpu.runtime import knobs

        snap = knobs.snapshot(environ={"SELDON_TPU_TP": "2"})
        by_name = {row["name"]: row for row in snap}
        assert by_name["SELDON_TPU_TP"]["set"] is True
        assert by_name["SELDON_TPU_TP"]["value"] == "2"
        assert by_name["SELDON_TPU_BREAKER"]["set"] is False
        assert by_name["SELDON_TPU_BREAKER"]["default"] == "1"
        assert by_name["SELDON_TPU_BREAKER"]["zero_off"] is True

    def test_fault_knob_zero_spells_off(self, monkeypatch):
        """The =0-spells-OFF contract on the fault spec (the PR 7
        review catch, applied to SELDON_TPU_FAULT): '0' disarms instead
        of parsing as a point name."""
        from seldon_core_tpu.utils import faults

        faults.configure("0")
        assert not faults.enabled()
        faults.clear()

    def test_debug_knobs_endpoint(self, monkeypatch):
        import asyncio

        aiohttp = pytest.importorskip("aiohttp")  # noqa: F841
        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.graph import UnitSpec
        from seldon_core_tpu.engine.server import Gateway, build_gateway_app
        from seldon_core_tpu.engine.service import PredictorService
        from seldon_core_tpu.runtime.component import TPUComponent

        class M(TPUComponent):
            def predict(self, X, names, meta=None):
                return X

        monkeypatch.setenv("SELDON_TPU_MAX_QUEUE", "7")
        svc = PredictorService(
            UnitSpec(name="m", type="MODEL", component=M()), name="main")
        gw = Gateway([(svc, 1.0)])

        async def scenario():
            client = TestClient(TestServer(build_gateway_app(gw)))
            await client.start_server()
            data = await (await client.get("/debug/knobs")).json()
            await client.close()
            return data

        data = asyncio.run(scenario())
        by_name = {row["name"]: row for row in data["knobs"]}
        assert by_name["SELDON_TPU_MAX_QUEUE"]["value"] == "7"
        assert "SELDON_TPU_MAX_QUEUE" in data["set"]
        assert by_name["SELDON_TPU_BREAKER"]["zero_off"] is True
