"""The KV pool does not move (PR 25): it rests ``(layers, pages,
page_size, d_model)`` everywhere (PR 28: the one layout), the decode
kernel addresses (layer, page) in the whole pool, and K/V writes land
in place.

Fast tier, CPU, the kernel forced (Pallas in interpret mode):

* the traced ``paged_chunk`` and ``paged_prefill`` programs, read as
  jaxprs: every ``pallas_call``'s K/V operands ARE the scan carry's pool
  variables, and the pools a program returns come from its arguments
  through ``dynamic_update_slice`` alone;
* the kernel against a float64 host oracle on a 3-layer pool whose
  layers differ, per layer and pool dtype (a wrong layer index is a
  wrong answer), the in-kernel LoRA fold and the zero-length lane;
* the decode lane, chosen from what the code can see (mode, mesh, dtype,
  backend, geometry) and reported by ``lane_report()``; the deleted
  impl knob; what a 5-d KV container meets at the pool.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from seldon_core_tpu.models import paged
from seldon_core_tpu.models.paged import PagedEngine
from seldon_core_tpu.models.transformer import TransformerLM
from seldon_core_tpu.ops import kernels
from seldon_core_tpu.ops.kernels import paged_attention_decode
from seldon_core_tpu.runtime import knobs
from seldon_core_tpu.runtime.component import MicroserviceError

CFG = dict(vocab_size=64, d_model=32, num_layers=3, num_heads=2, max_len=256)
LAYERS = CFG["num_layers"]


@pytest.fixture(scope="module")
def params():
    lm = TransformerLM(dtype=jnp.float32, **CFG)
    return lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(params, **kw):
    base = dict(dtype=jnp.float32, page_size=8, max_slots=4, steps_per_call=4)
    base.update(kw)
    return PagedEngine(params, **CFG, **base)


@pytest.fixture
def kernel_lane(monkeypatch):
    monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "force")
    monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "pool")
    monkeypatch.delenv("SELDON_TPU_KV_DTYPE", raising=False)


# ---------------------------------------------------------------------------
# (a) the programs, read as jaxprs
# ---------------------------------------------------------------------------

# primitives that hand their operands to a sub-jaxpr one for one
_CALLS = ("pjit", "jit", "closed_call", "core_call", "remat", "checkpoint",
          "custom_jvp_call", "custom_vjp_call")


def _sub_jaxpr(eqn):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is not None:
            return getattr(sub, "jaxpr", sub)
    return None


class _PoolAudit:
    """Follow the pool arguments of a traced program through it.

    A variable is *the pool* if it is a pool argument, the result of a
    ``dynamic_update_slice`` ON the pool, or either of those carried
    into or out of a scan / call.  Everything else that touches the
    pool is recorded by primitive name."""

    def __init__(self, pool_shape):
        self.pool_shape = tuple(pool_shape)
        self.kernel_reads = []   # per pool-shaped operand of a kernel: writes
        #                          since its scan step began; None = not the pool
        self.consumers = set()   # primitives that took the pool as an operand

    def walk(self, jaxpr, pool_in):
        """``pool_in``: {invar index: writes so far}.  Returns the same
        for the jaxpr's outvars."""
        tag = {jaxpr.invars[i]: n for i, n in pool_in.items()}

        def of(v):
            return None if isinstance(v, jex_core.Literal) else tag.get(v)

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            ins = [of(v) for v in eqn.invars]
            if name == "pallas_call":
                self.kernel_reads += [
                    n for v, n in zip(eqn.invars, ins)
                    if tuple(getattr(v.aval, "shape", ())) == self.pool_shape]
                self.consumers.add(name)
                continue
            if all(n is None for n in ins):
                continue
            self.consumers.add(name)
            if name == "dynamic_update_slice" and ins[0] is not None:
                tag[eqn.outvars[0]] = ins[0] + 1
            elif name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                # a step's reads see the pool as the step received it
                out = self.walk(body, {i: 0 for i, n in enumerate(ins)
                                       if n is not None})
                for i, n in out.items():
                    tag[eqn.outvars[i]] = n
            elif name in _CALLS and _sub_jaxpr(eqn) is not None:
                out = self.walk(_sub_jaxpr(eqn), {i: n for i, n in enumerate(ins)
                                                  if n is not None})
                for i, n in out.items():
                    tag[eqn.outvars[i]] = n
        return {i: of(v) for i, v in enumerate(jaxpr.outvars) if of(v) is not None}


def _traced(jitted, args):
    """``(jaxpr, {flat position of pk and of pv: 0})`` of a program
    whose arguments are ``(params, pk, pv, ...)``."""
    first = len(jax.tree.leaves(args[0]))
    return jitted.trace(*args).jaxpr.jaxpr, {first: 0, first + 1: 0}


def _unwrap(fn):  # the jit under the compile sentinel
    return fn if hasattr(fn, "trace") else fn.__wrapped__


class TestProgramsAddressThePoolInPlace:
    @pytest.mark.parametrize("buckets", [((4, 4),), ((2, 2), (2, 4))])
    def test_chunk_kernels_read_the_carry_and_writes_are_dus(
        self, params, kernel_lane, buckets
    ):
        eng = _engine(params)
        try:
            assert eng._kernel_active and eng.cache.pages_k.ndim == 4
            jaxpr, pools = _traced(eng._chunk_program(4, buckets),
                                   eng.chunk_example_args(buckets))
            audit = _PoolAudit(eng.cache.pages_k.shape)
            out = audit.walk(jaxpr, pools)
        finally:
            eng.close()
        # K and V of every kernel call (layers x buckets) are the carry's
        # own pool variables, read before the step's first write
        assert len(audit.kernel_reads) == 2 * LAYERS * len(buckets)
        assert audit.kernel_reads == [0] * len(audit.kernel_reads)
        # nothing slices, reshapes, copies or gathers the pool
        assert audit.consumers <= {"pallas_call", "dynamic_update_slice",
                                   "scan", *_CALLS}, audit.consumers
        # the returned pools (outputs 1, 2) are the arguments, written in
        # place: one DUS per lane per step
        assert out.get(1) == out.get(2) == eng.max_slots

    def test_prefill_gathers_the_whole_pool_and_writes_are_dus(
        self, params, kernel_lane
    ):
        eng = _engine(params)
        try:
            k, bucket = 2, 16
            kv_k, kv_v = eng._kv_args()
            jaxpr, pools = _traced(
                _unwrap(eng._build_prefill(bucket, k)),
                (eng.params, kv_k, kv_v, jnp.zeros((k, bucket), jnp.int32),
                 jnp.ones((k,), jnp.int32),
                 jnp.zeros((k, bucket // eng.page_size), jnp.int32)))
            audit = _PoolAudit(eng.cache.pages_k.shape)
            out = audit.walk(jaxpr, pools)
        finally:
            eng.close()
        # the read is ONE (layer, page) gather of the whole pool: no
        # layer is cut out of it first
        assert audit.consumers <= {"gather", "dynamic_update_slice", *_CALLS}
        assert out.get(1) == out.get(2) == k * (bucket // 8)

    def test_kernel_off_chunk_still_slices_per_layer(self, params, monkeypatch):
        """The contrast arm: the audit does see a pool that moves."""
        monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "0")
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "pool")
        eng = _engine(params)
        try:
            jaxpr, pools = _traced(eng._chunk_program(4, ((4, 4),)),
                                   eng.chunk_example_args(((4, 4),)))
            audit = _PoolAudit(eng.cache.pages_k.shape)
            audit.walk(jaxpr, pools)
        finally:
            eng.close()
        assert not audit.kernel_reads
        assert "slice" in audit.consumers


# ---------------------------------------------------------------------------
# (b) the kernel on a pool of distinct layers
# ---------------------------------------------------------------------------

B, H, HD, PS, P, NUM_PAGES = 4, 2, 64, 8, 4, 12
D = H * HD
LENGTHS = np.array([0, 5, 16, 32], np.int32)  # a dead lane, a partial page, full


def _pool(rng, pool, num_pages=NUM_PAGES, h=H, hd=HD):
    """``(what the pool stores, as float64, heads apart; the device
    pool; per-page scales or None)``."""
    raw = rng.normal(size=(LAYERS, num_pages, PS, h, hd)).astype(np.float32)
    scales = None
    if pool == "int8":
        scales = (np.abs(raw).max(axis=(2, 3, 4)) / 127.0).astype(np.float32)
        q = np.clip(np.round(raw / scales[:, :, None, None, None]), -127, 127)
        stored = q * scales[:, :, None, None, None].astype(np.float64)
        dev = jnp.asarray(q, jnp.int8)
    else:
        dev = jnp.asarray(raw, {"f32": jnp.float32, "bf16": jnp.bfloat16}[pool])
        stored = np.asarray(dev.astype(jnp.float32), np.float64)
    return stored, dev.reshape(LAYERS, num_pages, PS, h * hd), scales


def _oracle(q, pk, pv, tables, lengths):
    """float64, on the host.  A length past the table attends the whole
    table (a lane masked done may hold more than the slice it was given)."""
    b, p = tables.shape
    gk = pk[tables].reshape(b, p * PS, *pk.shape[2:])
    gv = pv[tables].reshape(b, p * PS, *pv.shape[2:])
    s = np.einsum("bhd,bkhd->bhk", q.astype(np.float64), gk)
    mask = np.arange(p * PS)[None, :] < lengths[:, None]
    s = np.where(mask[:, None, :], s, -np.inf)
    m = s.max(-1)
    with np.errstate(invalid="ignore"):
        w = np.where(mask[:, None, :], np.exp(s - m[..., None]), 0.0)
    l = w.sum(-1)
    return np.einsum("bhk,bkhd->bhd", w, gv) / np.where(l > 0, l, 1.0)[..., None]


def _check(outs, ref, lengths=LENGTHS):
    acc, l = np.asarray(outs[0], np.float64), np.asarray(outs[2], np.float64)
    live = lengths > 0
    got = acc / np.where(l > 0, l, 1.0)[..., None]
    assert float(np.max(np.abs(got[live] - ref[live]))) < 1e-4
    # a dead lane carries the neutral flash state, not NaN
    assert np.all(l[~live] == 0.0) and np.all(acc[~live] == 0.0)
    assert np.all(np.isinf(np.asarray(outs[1])[~live]))


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("pool", ["f32", "bf16", "int8"])
def test_kernel_reads_its_layer_of_the_whole_pool(pool, layer):
    rng = np.random.default_rng(7)
    q = rng.normal(size=(B, H, HD)).astype(np.float32)
    pkn, pk, sk = _pool(rng, pool)
    pvn, pv, sv = _pool(rng, pool)
    tables = rng.integers(1, NUM_PAGES, size=(B, P)).astype(np.int32)
    kw = {}
    if pool == "int8":
        kw["kv_scales"] = (jnp.asarray(sk), jnp.asarray(sv))
    # the layer is a traced scalar (one kernel for all layers)
    outs = jax.jit(
        lambda q, pk, pv, t, n, layer: paged_attention_decode(
            q, pk, pv, t, n, layer=layer, page_size=PS, **kw),
    )(jnp.asarray(q), pk, pv, jnp.asarray(tables), jnp.asarray(LENGTHS),
      jnp.int32(layer))
    _check(outs, _oracle(q, pkn[layer], pvn[layer], tables, LENGTHS))


def _lora_factors(rng, lanes, d, rank=4, slots=3):
    """``(x, a, b, idx)``: slot 0 holds zero factors (no adapter)."""
    x = rng.normal(size=(lanes, d)).astype(np.float32)
    a = rng.normal(size=(LAYERS, slots, d, rank)).astype(np.float32) * 0.05
    b = rng.normal(size=(LAYERS, slots, rank, 3 * d)).astype(np.float32) * 0.05
    a[:, 0] = 0.0
    b[:, 0] = 0.0
    return x, a, b, (np.arange(lanes) % slots).astype(np.int32)


@pytest.mark.parametrize("layer", range(LAYERS))
def test_lora_fold_indexes_layer_and_slot(layer):
    rng = np.random.default_rng(11)
    q = rng.normal(size=(B, H, HD)).astype(np.float32)
    pkn, pk, _ = _pool(rng, "f32")
    pvn, pv, _ = _pool(rng, "f32")
    tables = rng.integers(1, NUM_PAGES, size=(B, P)).astype(np.int32)
    x, a, b, idx = _lora_factors(rng, B, D)
    q_scale = HD ** -0.5
    outs = paged_attention_decode(
        jnp.asarray(q), pk, pv, jnp.asarray(tables), jnp.asarray(LENGTHS),
        layer=layer, page_size=PS,
        lora=(jnp.asarray(x), jnp.asarray(np.swapaxes(a, -1, -2)),
              jnp.asarray(b), jnp.asarray(idx), q_scale))
    delta = np.einsum("bd,bdr,bre->be", x, a[layer][idx], b[layer][idx])
    assert float(np.max(np.abs(np.asarray(outs[3]) - delta))) < 1e-4
    q_eff = q + q_scale * delta[:, :D].reshape(B, H, HD)
    _check(outs, _oracle(q_eff, pkn[layer], pvn[layer], tables, LENGTHS))


# the page loop's edges (PR 27): a lane pays for ceil(length / page_size)
# pages and a lane of length 0 for none.  name -> (table width, lengths)
_FULL = 4 * PS
LOOP_EDGES = {
    # the doc cell's shape: 4 callers on 32 lanes
    "mostly_dead": (4, np.where(np.arange(32) % 8 == 3, [13, 32, 7, 25] * 8, 0)),
    # exactly 1, a page, a page and one, the full table, none
    "page_edges": (4, np.array([1, PS, PS + 1, _FULL, 0, PS - 1, 2 * PS, _FULL - 1])),
    # a table wider than any lane needs (a long bucket's short lanes)
    "wide_table": (16, np.array([3, 2 * PS, 0, PS + 2])),
    # a lane masked done may hold more than its table slice: it attends
    # the slice, and never reads a table entry past it
    "past_the_table": (2, np.array([5 * PS, 2 * PS + 1, 1, 0])),
}


@pytest.mark.parametrize("variant", ["bf16", "int8", "lora"])
@pytest.mark.parametrize("heads,head_dim", [(20, 64), (16, 128)])
@pytest.mark.parametrize("edge", sorted(LOOP_EDGES))
def test_stream_kernel_pays_for_live_pages_only(edge, heads, head_dim, variant):
    """Both served geometries (GPT-2-large's 20 x 64: heads padded to 24
    projector rows; OLMoE's 16 x 128), with the int8 pool and with the
    in-kernel LoRA fold, against the float64 oracle at the tolerance the
    kernel has always had."""
    width, lengths = LOOP_EDGES[edge]
    lengths = lengths.astype(np.int32)
    lanes, d, layer = len(lengths), heads * head_dim, 1
    num_pages = 24
    rng = np.random.default_rng(27)
    q = rng.normal(size=(lanes, heads, head_dim)).astype(np.float32) * head_dim ** -0.5
    pool = "int8" if variant == "int8" else "bf16"
    pkn, pk, sk = _pool(rng, pool, num_pages, heads, head_dim)
    pvn, pv, sv = _pool(rng, pool, num_pages, heads, head_dim)
    tables = rng.integers(1, num_pages, size=(lanes, width)).astype(np.int32)
    kw, q_eff = {}, q
    if variant == "int8":
        kw["kv_scales"] = (jnp.asarray(sk), jnp.asarray(sv))
    if variant == "lora":
        x, a, b, idx = _lora_factors(rng, lanes, d)
        q_scale = head_dim ** -0.5
        kw["lora"] = (jnp.asarray(x), jnp.asarray(np.swapaxes(a, -1, -2)),
                      jnp.asarray(b), jnp.asarray(idx), q_scale)
        delta = np.einsum("bd,bdr,bre->be", x, a[layer][idx], b[layer][idx])
        q_eff = q + q_scale * delta[:, :d].reshape(lanes, heads, head_dim)
    outs = jax.jit(lambda *a_: paged_attention_decode(
        *a_, layer=jnp.int32(layer), page_size=PS, **kw))(
        jnp.asarray(q), pk, pv, jnp.asarray(tables), jnp.asarray(lengths))
    # the readers find the kernel by its first output: 3-D, f32
    assert outs[0].shape == (lanes, heads, head_dim) and outs[0].dtype == jnp.float32
    if variant == "lora":
        # every lane's delta, dead lanes' too: the caller's self term
        # and pool write read it (slot 0's is an exact 0.0)
        got = np.asarray(outs[3], np.float64)
        assert float(np.max(np.abs(got - delta))) < 1e-4
        assert np.all(got[idx == 0] == 0.0)
    _check(outs, _oracle(q_eff, pkn[layer], pvn[layer], tables, lengths), lengths)


def test_pool_with_its_heads_apart_is_refused():
    pool = jnp.zeros((LAYERS, NUM_PAGES, PS, H, HD), jnp.float32)
    with pytest.raises(ValueError, match="4-d"):
        paged_attention_decode(
            jnp.zeros((B, H, HD)), pool, pool, jnp.zeros((B, P), jnp.int32),
            jnp.asarray(LENGTHS), layer=0, page_size=PS)


def _tokens(eng, n=4, max_new=10):
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, CFG["vocab_size"], size=(14 + 3 * i,)).astype(np.int32)
               for i in range(n)]
    streams = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run()
    return np.stack([s.result for s in streams])


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_three_layer_engine_tokens_kernel_vs_gather(params, monkeypatch, kv):
    """End to end on three distinct layers: prefill's whole-pool gather,
    the kernel's (layer, page) reads and the in-place writes give the
    gather lane's tokens exactly (f32 engine; the int8 pool shares one
    quantised pool between two readers)."""
    monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "pool")
    monkeypatch.setenv("SELDON_TPU_KV_DTYPE", kv)
    out = {}
    for mode in ("0", "force"):
        monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", mode)
        eng = _engine(params)
        try:
            assert eng._kernel_active is (mode == "force")
            out[mode] = _tokens(eng)
        finally:
            eng.close()
    np.testing.assert_array_equal(out["0"], out["force"])


# ---------------------------------------------------------------------------
# (c) the lane, chosen from what the code can see
# ---------------------------------------------------------------------------

# heads * head_dim: CFG's 2 x 16 = 32 is not a multiple of 128 (Mosaic
# cannot DMA its pages; the interpreter can), 2 x 64 = 128 is
ALIGNED = dict(CFG, d_model=128)


@pytest.fixture(scope="module")
def aligned_params():
    lm = TransformerLM(dtype=jnp.float32, **ALIGNED)
    return lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]


class TestDecodeLane:
    @pytest.mark.parametrize("mode,backend,mesh,aligned,interpret,kernel", [
        # the CPU's lanes (an engine is built for each of these)
        ("force", "cpu", False, False, True, True),    # PARENT_SHA's toy lane
        ("force", "cpu", False, True, True, True),
        ("force", "cpu", False, True, False, True),    # the Mosaic tests' lane
        ("force", "cpu", False, False, False, False),  # compiled, unaligned
        ("force", "cpu", True, True, True, False),     # a TP mesh: the gather
        ("0", "cpu", False, True, True, False),
        ("1", "cpu", False, True, True, False),        # 1 does not interpret
        ("auto", "cpu", False, True, True, False),
        # a chip's (the predicate alone: nothing here can build for one)
        ("auto", "tpu", False, True, False, True),     # the benchmark's cells
        ("1", "tpu", False, True, False, True),
        ("auto", "tpu", False, False, False, False),   # unaligned -> gather
        ("1", "tpu", False, False, False, False),
        ("force", "tpu", False, False, False, False),
        ("auto", "tpu", True, True, False, False),
        ("0", "tpu", False, True, False, False),
    ])
    def test_decode_lane_is_chosen_from_what_the_code_sees(
        self, params, aligned_params, monkeypatch, caplog, mode, backend, mesh,
        aligned, interpret, kernel,
    ):
        monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", mode)
        monkeypatch.delenv("SELDON_TPU_CHUNK_IMPL", raising=False)
        monkeypatch.setattr(kernels, "interpret_mode", lambda: interpret)
        cfg = ALIGNED if aligned else CFG
        geometry = (cfg["num_heads"], cfg["d_model"] // cfg["num_heads"])
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: backend)
            assert paged.paged_kernel_static_eligible(
                mode, not mesh, jnp.float32, *geometry) is kernel
            # a term it has always had
            assert not paged.paged_kernel_static_eligible(
                mode, not mesh, jnp.int8, *geometry)
        if backend != jax.default_backend():
            return
        if mesh and len(jax.devices()) < 2:
            pytest.skip("needs two devices for a TP mesh")
        with caplog.at_level(logging.WARNING, logger=paged.__name__):
            eng = PagedEngine(
                aligned_params if aligned else params, **cfg, dtype=jnp.float32,
                page_size=8, max_slots=4, steps_per_call=4,
                **({"tp": 2} if mesh else {}))
        try:
            rep = eng.lane_report()
            assert rep["kernel_active"] is kernel
            assert rep["chunk_impl"] == ("pool" if kernel else "ring")
            assert rep["tp"] == (2 if mesh else 1)
            assert eng.engine_stats()["kernel_active"] == int(kernel)
            # one layout, and nothing left that reports one
            assert eng.cache.pages_k.shape == (
                LAYERS, eng.num_pages, eng.page_size, cfg["d_model"])
            assert set(rep) == {
                "tp", "dp", "chunk_impl", "kv_dtype", "kernel_active",
                "pool_shard_bytes", "arch", "weight_bytes",
                # PR 30: what the cache holds
                "attention", "cache_width", "experts_held",
                # PR 31: which grouped expert matmul each program traced
                "expert_matmul",
                # PR 32: the pool's leading axis (attention sub-layers)
                # and the cap on a prefill call's padded positions
                "cache_layers", "prefill_positions_max",
                # PR 33: what a from-zero prefill attends with
                "prefill_attention",
                # PR 37: the type the matrices rest in
                "weights"}
            assert rep["cache_layers"] == LAYERS
            assert (rep["attention"], rep["cache_width"], rep["experts_held"]) == (
                "mha", cfg["d_model"], 0)
            assert rep["expert_matmul"] == {}     # a dense model has none
        finally:
            eng.close()
        # a kernel asked for by name that cannot run says so, once
        warned = [r for r in caplog.records if "cannot run here" in r.message]
        assert len(warned) == int(mode in ("1", "force") and not kernel)

    def test_the_impl_knob_is_gone(self, params, monkeypatch):
        name = "SELDON_TPU_PAGED_KERNEL_IMPL"
        assert not knobs.declared(name)
        with pytest.raises(knobs.UndeclaredKnobError):
            knobs.raw(name)
        monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "force")
        reports = []
        for value in (None, "grid"):
            if value:
                monkeypatch.setenv(name, value)
            eng = _engine(params)
            try:
                reports.append((eng.lane_report(), eng.cache.pages_k.shape))
            finally:
                eng.close()
        assert reports[0] == reports[1] and reports[0][0]["kernel_active"]

    def test_explicit_ring_with_kernel_request_stays_flat(self, params, monkeypatch):
        """The ring chunk never calls the kernel: the request buys only
        the warning."""
        monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "force")
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "ring")
        eng = _engine(params)
        try:
            rep = eng.lane_report()
            assert eng.cache.pages_k.ndim == 4 and rep["kernel_active"] is False
        finally:
            eng.close()

    def test_mesh_engine_rests_flat_with_the_kernel_off(self, params, monkeypatch):
        if len(jax.devices()) < 2:
            pytest.skip("needs two devices for a TP mesh")
        monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "force")
        eng = _engine(params, tp=2)
        try:
            rep = eng.lane_report()
            assert rep["tp"] == 2 and eng.cache.pages_k.ndim == 4
            assert rep["kernel_active"] is False
        finally:
            eng.close()

    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    def test_container_with_its_heads_apart_is_refused_at_the_pool(
        self, params, monkeypatch, kv
    ):
        """A handoff container may hold rank-5 K/V (``codec/bufview.py``
        packs and unpacks rank 4 or 5: peers that rested their pool
        split wrote such frames).  Where it meets the pool it is a
        geometry mismatch, a 400 before anything is scattered — what
        this engine did with one before the split pool went (PR 28),
        plain and with the int8 pool's scale frames."""
        monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "force")
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "pool")
        monkeypatch.setenv("SELDON_TPU_KV_DTYPE", kv)
        eng = _engine(params)
        try:
            prompt = np.arange(1, 12, dtype=np.int32)
            good = eng.prefill_export(prompt)
            assert good["layout"] == "flat" and good["k"].ndim == 4
            assert ("k_scales" in good) is (kv == "int8")
            heads = CFG["num_heads"]
            split = dict(good, layout="split", **{
                n: good[n].reshape(*good[n].shape[:3], heads, -1) for n in "kv"})
            before = np.asarray(eng.cache.pages_k)
            with pytest.raises(MicroserviceError) as err:
                eng.submit_prefilled(split, max_new_tokens=2)
            assert err.value.reason == "KV_LAYOUT_MISMATCH"
            assert err.value.status_code == 400
            np.testing.assert_array_equal(np.asarray(eng.cache.pages_k), before)
            # the same pages as the pool holds them are taken
            stream = eng.submit_prefilled(good, max_new_tokens=2)
            eng.run()
            assert len(stream.result) == 2
        finally:
            eng.close()
