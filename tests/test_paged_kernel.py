"""Pallas paged-attention decode kernel (ops/kernels.py).

Parity against the XLA gather path at two levels: the raw flash state
(kernel vs dense reference math) and the full engine (kernel-forced vs
gather decode produce identical tokens).  Kernels run in interpret
mode off-TPU, so this tier needs no hardware.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from seldon_core_tpu.ops.kernels import paged_attention_decode  # noqa: E402

pytestmark = pytest.mark.slow  # compile-heavy: excluded from the default fast tier (make test-all)



LAYERS = 3  # distinct layers in one pool: a wrong layer index is a wrong answer


def _dense_reference(q, pk, pv, tables, lengths):
    """pk/pv: ONE layer, split (num_pages, ps, h, hd)."""
    B = q.shape[0]
    P, ps = tables.shape[1], pk.shape[1]
    gk = pk[tables].reshape(B, P * ps, *pk.shape[2:])
    gv = pv[tables].reshape(B, P * ps, *pv.shape[2:])
    s = jnp.einsum("bhd,bkhd->bhk", q, gk)
    mask = jnp.arange(P * ps)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    w = jnp.exp(s - m[..., None])
    return jnp.einsum("bhk,bkhd->bhd", w, gv), m, w.sum(-1)


def _whole_pool(rng, num_pages, ps, h, hd):
    """A LAYERS-deep pool as numpy (heads apart, for the oracle) and as
    the device array the kernel reads: (L, pages, ps, h*hd)."""
    pool = rng.normal(size=(LAYERS, num_pages, ps, h, hd)).astype(np.float32)
    return pool, jnp.asarray(pool).reshape(LAYERS, num_pages, ps, h * hd)


@pytest.mark.parametrize("layer", range(LAYERS))
def test_kernel_matches_dense_flash_state(layer):
    rng = np.random.default_rng(0)
    B, h, hd, ps, P, num_pages = 4, 8, 64, 16, 4, 32
    q = jnp.asarray(rng.normal(size=(B, h, hd)).astype(np.float32))
    pkn, pk = _whole_pool(rng, num_pages, ps, h, hd)
    pvn, pv = _whole_pool(rng, num_pages, ps, h, hd)
    tables = jnp.asarray(rng.integers(1, num_pages, size=(B, P)).astype(np.int32))
    # ragged lengths incl. partial pages and a full table
    lengths = jnp.asarray(np.array([5, 16, 37, 64], np.int32))

    # the layer rides as a TRACED scalar: one compiled kernel serves all
    acc, m, l = jax.jit(
        lambda *a, layer: paged_attention_decode(*a, layer=layer, page_size=ps)
    )(q, pk, pv, tables, lengths, layer=jnp.int32(layer))
    acc_ref, m_ref, l_ref = _dense_reference(
        q, jnp.asarray(pkn[layer]), jnp.asarray(pvn[layer]), tables, lengths)

    assert jnp.allclose(m, m_ref, atol=1e-5)
    assert jnp.allclose(l, l_ref, rtol=1e-5)
    assert jnp.allclose(
        acc / l[..., None], acc_ref / l_ref[..., None], rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("layer", range(LAYERS))
def test_kernel_zero_length_lane_is_finite(layer):
    rng = np.random.default_rng(1)
    B, h, hd, ps, P, num_pages = 2, 4, 32, 8, 2, 8
    q = jnp.asarray(rng.normal(size=(B, h, hd)).astype(np.float32))
    _, pk = _whole_pool(rng, num_pages, ps, h, hd)
    _, pv = _whole_pool(rng, num_pages, ps, h, hd)
    tables = jnp.zeros((B, P), jnp.int32)
    lengths = jnp.asarray(np.array([0, 3], np.int32))
    acc, m, l = paged_attention_decode(
        q, pk, pv, tables, lengths, layer=layer, page_size=ps)
    # lane 0 has no cache: flash state must be the neutral element the
    # self-token merge recovers from (acc 0, m -inf, l 0), not NaN
    assert float(l[0].sum()) == 0.0
    assert np.all(np.isinf(np.asarray(m[0])))
    assert np.all(np.asarray(acc[0]) == 0.0)
    assert np.all(np.isfinite(np.asarray(l[1])))


@pytest.mark.parametrize("lengths", [
    (5, 16, 37, 64),   # ragged: partial pages and a full table
    (1, 16, 17, 64),   # exactly one token, a page, a page and one, full
    (0, 0, 33, 0),     # mostly empty lanes (the doc cell's shape)
    (3, 9, 0, 14),     # a table wider than any lane needs
], ids=["ragged", "page_edges", "mostly_dead", "wide_table"])
@pytest.mark.parametrize("layer", range(LAYERS))
def test_kernel_matches_float64_host_oracle(layer, lengths):
    """Adjudicate numerics against a HOST float64 oracle, not another
    on-chip program: an on-TPU 'reference' einsum is itself bf16-rounded
    (default matmul precision), which masked a bf16-precision bug in
    the stream kernel's MXU dots on hardware (r4, docs/architecture.md
    'Decode-step cost, decomposed honestly')."""
    rng = np.random.default_rng(3)
    B, h, hd, ps, P, num_pages = 4, 8, 64, 16, 4, 32
    qn = rng.normal(size=(B, h, hd)).astype(np.float32)
    pkn, pk = _whole_pool(rng, num_pages, ps, h, hd)
    pvn, pv = _whole_pool(rng, num_pages, ps, h, hd)
    tn = rng.integers(1, num_pages, size=(B, P)).astype(np.int32)
    ln = np.array(lengths, np.int32)
    live = ln > 0

    gk = pkn[layer][tn].reshape(B, P * ps, h, hd).astype(np.float64)
    gv = pvn[layer][tn].reshape(B, P * ps, h, hd).astype(np.float64)
    s = np.einsum("bhd,bkhd->bhk", qn.astype(np.float64), gk)
    mask = np.arange(P * ps)[None, :] < ln[:, None]
    s = np.where(mask[:, None, :], s, -np.inf)
    m64 = s.max(-1)
    with np.errstate(invalid="ignore"):
        w = np.where(mask[:, None, :], np.exp(s - m64[..., None]), 0.0)
        ref = np.einsum("bhk,bkhd->bhd", w, gv) / w.sum(-1)[..., None]

    acc, m, l = jax.jit(
        lambda *a: paged_attention_decode(*a, layer=layer, page_size=ps)
    )(jnp.asarray(qn), pk, pv, jnp.asarray(tn), jnp.asarray(ln))
    out = np.asarray(acc / l[..., None], np.float64)
    assert float(np.max(np.abs(out[live] - ref[live]))) < 1e-4
    assert float(np.max(np.abs(np.asarray(m, np.float64)[live] - m64[live]))) < 1e-4
    # an empty lane: the neutral flash state
    assert np.all(np.asarray(l)[~live] == 0.0) and np.all(np.asarray(acc)[~live] == 0.0)


def _lm_fixture():
    from seldon_core_tpu.models.transformer import TransformerLM

    cfg = dict(vocab_size=256, d_model=64, num_layers=2, num_heads=4, max_len=256)
    module = TransformerLM(dtype=jnp.bfloat16, **cfg)
    params = module.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    prompts = [np.arange(5 + 7 * i, dtype=np.int32) % 256 for i in range(4)]
    return cfg, params, prompts


def _run_engine(cfg, params, prompts):
    from seldon_core_tpu.models.paged import PagedEngine

    eng = PagedEngine(
        params, dtype=jnp.bfloat16, page_size=32, max_slots=4,
        steps_per_call=8, **cfg,
    )
    streams = [eng.submit(p, max_new_tokens=24) for p in prompts]
    eng.run()
    return np.stack([s.result for s in streams]), eng


def test_engine_tokens_identical_kernel_vs_gather(monkeypatch):
    cfg, params, prompts = _lm_fixture()

    def run(mode):
        monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", mode)
        # the decode kernel lives in the POOL chunk's per-step
        # attention — the default ring chunk never reads the pool per
        # step, so without this the kernel gate was never reached and
        # the test compared the gather path to itself
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "pool")
        toks, eng = _run_engine(cfg, params, prompts)
        assert eng._chunk_impl == "pool"
        return toks

    monkeypatch.delenv("SELDON_TPU_CHUNK_IMPL", raising=False)
    monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "0")
    gather, _ = _run_engine(cfg, params, prompts)
    assert np.array_equal(gather, run("force"))  # interpret-mode pallas on CPU


def test_kernel_optin_autoselects_pool_chunk(monkeypatch):
    """The two env knobs are coupled: SELDON_TPU_PAGED_KERNEL opts into
    kernels that only the pool chunk invokes.  With CHUNK_IMPL unset the
    engine auto-selects the pool impl (otherwise the opt-in has zero
    speed effect); an explicit ring choice wins but is warned about."""
    cfg, params, prompts = _lm_fixture()
    monkeypatch.delenv("SELDON_TPU_CHUNK_IMPL", raising=False)
    monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "force")
    _, eng = _run_engine(cfg, params, prompts)
    assert eng._chunk_impl == "pool"
    monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "ring")
    _, eng = _run_engine(cfg, params, prompts)
    assert eng._chunk_impl == "ring"  # explicit choice respected


def test_ring_vs_pool_chunk_token_parity(monkeypatch):
    """A/B over the env-selectable chunk implementations (kernel OFF):
    the ring chunk (r5 default) and the legacy per-step pool gather
    must emit identical tokens — the fallback knob must be a pure
    performance choice."""
    cfg, params, prompts = _lm_fixture()
    monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "0")
    monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "ring")
    ring, eng_ring = _run_engine(cfg, params, prompts)
    assert eng_ring._chunk_impl == "ring"
    monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "pool")
    pool, eng_pool = _run_engine(cfg, params, prompts)
    assert eng_pool._chunk_impl == "pool"
    assert np.array_equal(ring, pool)
