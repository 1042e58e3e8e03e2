"""``seldon_core_tpu/models/paged/`` as a package: which module may import
which, what the package still exports, that importing it stays cheap, and
``cache.PagedCache`` on its own — built for every tiny spec of
``tests/paged_harness.py`` with no engine above it."""
import ast
import importlib
import os
import pathlib
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paged_harness as harness

ROOT = pathlib.Path(__file__).resolve().parents[1]
PACKAGE = ROOT / "seldon_core_tpu" / "models" / "paged"
NAME = "seldon_core_tpu.models.paged"

# a module imports only from those before it (docs/architecture.md section 5) ...
ORDER = ("lanes", "blocks", "capacity", "cache", "seam", "engine", "component")
# ... and, of the rest of seldon_core_tpu, the layers below the engine
# only from these
# (and the error type a refusal carries to the transport: the seam's
# profile window is refused with one)
BELOW = ("seldon_core_tpu.models.spec", "seldon_core_tpu.ops",
         "seldon_core_tpu.runtime.knobs", "seldon_core_tpu.utils",
         "seldon_core_tpu.runtime.component.MicroserviceError")


def _imports(path):
    """``(module, names)`` of every import in ``path``, function-level
    ones included, relative ones resolved against the package."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, ()
        elif isinstance(node, ast.ImportFrom):
            module = node.module or ""
            if node.level:
                assert node.level == 1, f"{path.name}: import from above the package"
                module = f"{NAME}.{module}" if module else NAME
            yield module, tuple(alias.name for alias in node.names)


def _siblings(path):
    """The package's own modules ``path`` imports."""
    out = set()
    for module, names in _imports(path):
        if module == NAME:  # from . import cache
            out.update(names)
        elif module.startswith(NAME + "."):
            out.add(module[len(NAME) + 1:].split(".")[0])
    return out


def test_the_package_holds_the_seven_modules():
    assert sorted(p.stem for p in PACKAGE.glob("*.py")) == sorted(
        ORDER + ("__init__",))
    assert not (PACKAGE.parent / "paged.py").exists()


@pytest.mark.parametrize("module", ORDER)
def test_arrows_point_down(module):
    """No module imports a sibling that stands after it in the table."""
    allowed = set(ORDER[:ORDER.index(module)])
    up = _siblings(PACKAGE / f"{module}.py") - allowed
    assert not up, f"paged/{module}.py imports from {sorted(up)}"


@pytest.mark.parametrize("module", ORDER[:ORDER.index("engine")])
def test_layers_below_the_engine_import_little(module):
    """The blocks, the cache, the arithmetic and the clocks know the
    spec, the ops, the knobs and the utilities — not the runtime, the
    codec, the control plane or the other generation stacks."""
    for source, names in _imports(PACKAGE / f"{module}.py"):
        if not source.startswith("seldon_core_tpu") or source.startswith(NAME):
            continue
        for name in [f"{source}.{n}" for n in names] or [source]:
            assert name.startswith(BELOW), f"paged/{module}.py imports {name}"


def test_the_engine_keeps_no_allocator_and_no_pool_format():
    """What moved behind ``PagedCache`` stays there: ``engine.py`` defines
    and names none of the allocator's, the window allocator's or the
    pool argument's former methods and attributes."""
    text = (PACKAGE / "engine.py").read_text()
    for name in ("_alloc_locked", "_free_locked", "_window_ensure_locked",
                 "_free_window_locked", "_evict_cached_locked",
                 "_ensure_pages_locked", "_match_prefix_locked",
                 "_register_prefix_locked", "_store_kv", "_write_kv",
                 "_delta_state", "_delta_conv", "write_kinds(", "write_kv("):
        assert name not in text, f"paged/engine.py names {name}"


def test_init_only_re_exports():
    tree = ast.parse((PACKAGE / "__init__.py").read_text())
    for node in tree.body:
        assert (isinstance(node, (ast.Import, ast.ImportFrom))
                or (isinstance(node, ast.Expr)
                    and isinstance(node.value, ast.Constant))  # the docstring
                or (isinstance(node, ast.Assign)
                    and node.targets[0].id == "__all__")), ast.dump(node)[:80]


def test_every_imported_name_resolves():
    """Every ``from seldon_core_tpu.models.paged import X`` of the code
    that is not a test — the benchmark's own tests included — still
    finds ``X``."""
    package = importlib.import_module(NAME)
    roots = [ROOT / "benchmarks", ROOT / "seldon_core_tpu", ROOT / "tools",
             ROOT / "bench.py", ROOT / "chip_smoke.py"]
    files = [p for r in roots for p in ([r] if r.is_file() else r.rglob("*.py"))]
    wanted = {}
    for path in files:
        if PACKAGE in path.parents:
            continue
        for module, names in _imports(path):
            if module == NAME:
                for name in names:
                    wanted.setdefault(name, path)
    assert {"PagedEngine", "StreamingLM", "get_paged_lm_class",
            "paged_hbm_accounting", "prefill_position_bytes",
            "prefill_positions_max", "journal_entry", "write_kv"} <= set(wanted)
    missing = {n: str(p) for n, p in wanted.items() if not hasattr(package, n)}
    assert not missing


def test_the_supervisors_default_component_resolves():
    text = (ROOT / "seldon_core_tpu" / "controlplane" / "supervisor.py").read_text()
    assert f'component: str = "{NAME}.StreamingLM"' in text
    module, _, cls = f"{NAME}.StreamingLM".rpartition(".")
    assert getattr(importlib.import_module(module), cls).__name__ == "StreamingLM"


def test_importing_the_package_imports_no_flax():
    """The blocks — and flax with them — load on the first
    ``get_paged_lm_class()``, not with the package."""
    code = (f"import sys, {NAME} as p\n"
            "assert 'flax' not in sys.modules, 'flax came with the package'\n"
            "assert p.__name__ + '.blocks' not in sys.modules\n"
            "p.get_paged_lm_class()\n"
            "assert 'flax' in sys.modules\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True,
                          capture_output=True, timeout=240,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]


# ---- PagedCache alone ----

ARCHS = ("gpt2",) + tuple(harness.MODELS)


def _cache(arch, **kw):
    from seldon_core_tpu.models.paged.cache import PagedCache

    spec, sizes = harness.spec_and_sizes(arch)
    kw = {"num_pages": 17, "page_size": harness.PAGE, "max_len": harness.MAX_LEN,
          "max_slots": 2, "max_steps": 2, "dtype": jnp.float32, **kw}
    return spec, PagedCache(spec, num_layers=sizes["num_layers"],
                            d_model=sizes["d_model"], **kw)


def _stream(slot, prompt):
    return types.SimpleNamespace(slot=slot, prompt=np.asarray(prompt, np.int32),
                                 pages=[], wpages=[], wfirst=0, req_id=slot)


@pytest.mark.parametrize("arch", ARCHS)
def test_args_are_what_store_takes_back(arch):
    """Whatever form the pool argument takes for a spec — an array, a
    dict a kind, a dict that carries the state a lane, no V at all —
    ``store`` takes ``args()`` back leaf for leaf."""
    spec, cache = _cache(arch)
    pk, pv = cache.args()
    assert (pv is None) == (spec.cache_pools == 1)
    assert isinstance(pk, dict) == bool(spec.kinds or spec.recurrent)
    assert len(cache.state) == len(cache.conv) == spec.state_layers(cache.num_layers)
    before = [id(x) for x in jax.tree_util.tree_leaves((pk, pv))]
    marked = jax.tree_util.tree_map(lambda x: x + 1, (pk, pv))
    cache.store(*marked)
    again = cache.args()
    assert (jax.tree_util.tree_structure(again)
            == jax.tree_util.tree_structure((pk, pv)))
    assert all(float(jnp.min(x)) == 1.0 for x in jax.tree_util.tree_leaves(again))
    cache.store(pk, pv)
    assert [id(x) for x in jax.tree_util.tree_leaves(cache.args())] == before


def test_int8_args_carry_the_scales():
    _, cache = _cache("gpt2", kv_dtype="int8")
    (pk, sk), (pv, sv) = cache.args()
    assert pk.dtype == pv.dtype == jnp.int8 and sk.dtype == sv.dtype == jnp.float32
    assert sk.shape == (cache.num_layers, cache.num_pages)
    cache.store((pk, sk + 2), (pv, sv))
    assert float(cache.scales_k[0, 0]) == 2.0
    assert cache.pool_shard_bytes == 2 * (pk.nbytes + sk.nbytes)


@pytest.mark.parametrize("arch", ARCHS)
def test_a_bare_cache_keeps_its_books(arch):
    """alloc / seat / ensure_pages / window_ensure / free with no engine:
    the audit is clean at every stop, and everything comes back."""
    spec, cache = _cache(arch)
    total = cache.num_pages - 1
    assert cache.check_invariants() == [] and cache.allocatable() == total
    a, b = _stream(0, range(11)), _stream(1, range(5))
    slots = [a, b]
    for s in slots:
        s.pages = cache.alloc(cache.pages_of(len(s.prompt)))
        cache.seat(s, len(s.prompt))
    assert cache.check_invariants(slots, slots) == []
    assert cache.pool_pages_used == len(a.pages) + len(b.pages)
    assert list(cache.tables[0, :len(a.pages)]) == a.pages
    length = len(a.prompt)
    for _ in range(3 * harness.PAGE):
        assert cache.ensure_pages(a, length, length + 2)
        length += 2
        assert cache.check_invariants(slots, slots) == []
        if length + 2 >= harness.MAX_LEN:
            break
    assert len(a.pages) == cache.pages_of(length)
    if spec.kinds:
        assert 0 < len(a.wpages) <= cache.window_pages
        assert cache.window_pages_held == len(a.wpages) + len(b.wpages)
        assert cache.full_pages_held == cache.pool_pages_used
        assert "window" in cache.chunk_tables()
    else:
        assert a.wpages == [] and cache.window_pages_total == 0
        assert cache.full_pages_held == 0 and cache.chunk_tables() == {}
    assert ("slots" in cache.prefill_tables([0, 1], 2)) == bool(spec.recurrent)
    assert cache.alloc(total) is None  # over capacity: refused, nothing taken
    for s in slots:
        cache.release(s, [None, None])
    assert cache.check_invariants([None, None], []) == []
    assert cache.allocatable() == total and cache.pool_pages_used == 0
    assert cache.window_pages_held == 0 and not cache.wtables.any()


def test_the_audit_names_a_page_both_free_and_mapped():
    _, cache = _cache("gpt2")
    s = _stream(0, range(9))
    s.pages = cache.alloc(2)
    cache.seat(s, 9)
    cache.free_pages.append(s.pages[0])
    found = cache.check_invariants([s, None], [s])
    assert any("free∩mapped" in p for p in found)


def test_a_bare_cache_shares_and_evicts_a_prefix():
    """register / match / map / free / evict over the index alone."""
    _, cache = _cache("gpt2", prefix_cache=True)
    evicted = []
    cache.on_evict = evicted.append
    prompt = list(range(2 * harness.PAGE + 3))
    a = _stream(0, prompt)
    a.pages = cache.alloc(cache.pages_of(len(prompt)))
    cache.seat(a, len(prompt))
    assert cache.match_prefix(a.prompt, 7) == []
    keys = cache.register_prefix(a, 7)
    assert len(keys) == 2 and cache.register_prefix(a, 7) == []
    matched = cache.match_prefix(a.prompt, 7)
    assert [e.page for e in matched] == a.pages[:2]
    assert cache.match_prefix(a.prompt, 8) == []  # another root: another chain
    cache.map_prefix(matched)
    assert all(int(cache.page_ref[p]) == 2 for p in a.pages[:2])
    cache.unmap_prefix(matched)
    cache.free(a.pages)
    a.pages = []
    assert cache.prefix_pages_cached == 2 and cache.pool_pages_used == 0
    assert cache.check_invariants([None, None], []) == []
    assert len(cache.alloc(cache.num_pages - 1)) == cache.num_pages - 1
    assert [e.key for e in evicted] == keys[::-1]  # the leaf before its parent
    assert cache.counters["prefix_evictions"] == 2 and not cache.prefix_index
