"""``ops/hyper.py`` (PR 45): the mixing of a residual of several rows
round a sub-layer (manifold-constrained hyper-connections, as Xing4.0
configures them) against the plain equations, written here once more in
numpy float64; the Sinkhorn's own properties; the Pallas kernels under
the interpreter against XLA's form.  ``tests/test_paged_kernel_mosaic.py``
compiles the kernels for a described v5e at the published widths.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.spec import model_spec
from seldon_core_tpu.ops import hyper

N, C = 4, 64
K = hyper.coefficients(N)
KW = dict(iters=20, eps=1e-6, lo=-30.0, hi=30.0)


def _case(seed, t=40, n=N, c=C, spread=1.0):
    rng = np.random.default_rng(seed)
    k = hyper.coefficients(n)
    x = rng.normal(size=(n, t, c)).astype(np.float32)
    params = {"phi": (spread * rng.normal(size=(k, n * c)) / np.sqrt(n * c)
                      ).astype(np.float32),
              "bias": rng.uniform(-0.1, 0.1, size=(k,)).astype(np.float32),
              "scale": rng.uniform(0.5, 1.5, size=(3,)).astype(np.float32)}
    y = rng.normal(size=(t, c)).astype(np.float32)
    return x, params, y


def _plain(x, params, y, iters=20, eps=1e-6, lo=-30.0, hi=30.0):
    """The equations of the module's docstring, token-major, float64."""
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    phi, bias, scale = (np.asarray(params[k], np.float64)
                        for k in ("phi", "bias", "scale"))
    n, t, c = x.shape
    rows = np.moveaxis(x, 0, 1)                                   # (T, n, C)
    flat = rows.reshape(t, n * c)
    raw = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + eps) @ phi.T
    pre = 1 / (1 + np.exp(-(scale[0] * raw[:, :n] + bias[:n])))
    post = 2 / (1 + np.exp(-(scale[1] * raw[:, n:2 * n] + bias[n:2 * n])))
    m = np.exp(np.clip(scale[2] * raw[:, 2 * n:] + bias[2 * n:], lo, hi)
               ).reshape(t, n, n)
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    h = np.einsum("tn,tnc->tc", pre, rows)
    out = np.einsum("tij,tjc->tic", m, rows) + post[:, :, None] * y[:, None, :]
    return h, post, m, np.moveaxis(out, 1, 0)


@pytest.fixture
def interpreted(monkeypatch):
    """The kernels under the Pallas interpreter."""
    monkeypatch.setattr(hyper, "backend", lambda: "interpret")


# float32 against float64 over 256 products of unit terms and 20
# normalisations: the largest difference seen is 1.5e-6 (the read row,
# whose entries reach 4); 2e-5 is ten times that and a thousandth of
# what any of the wrong mixings of tests/test_xing4_paged.py moves
ATOL = 2e-5


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("t", [40, 128, 200])
def test_the_pair_matches_the_plain_equations(interpreted, impl, t):
    x, params, y = _case(1, t=t)
    h, post, res, out = _plain(x, params, y)
    got_h, got_post, got_res = hyper.hyper_pre(
        jnp.asarray(x), params, impl=impl, **KW)
    assert got_h.shape == (t, C) and got_res.shape == (t, N, N)
    assert np.abs(got_h - h).max() < ATOL
    assert np.abs(got_post - post).max() < ATOL
    assert np.abs(got_res - res).max() < ATOL
    got = hyper.hyper_post(jnp.asarray(x), jnp.asarray(y), got_post, got_res,
                           impl=impl)
    assert got.shape == x.shape and got.dtype == jnp.float32
    assert np.abs(got - out).max() < ATOL


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_sublayers_output_may_rest_in_bfloat16(interpreted, impl):
    """``y`` arrives in the compute type and is widened where it is
    used; the rows and the coefficients stay float32."""
    x, params, y = _case(2)
    y16 = jnp.asarray(y, jnp.bfloat16)
    _h, post, res, out = _plain(x, params, np.asarray(y16, np.float32))
    got = hyper.hyper_post(jnp.asarray(x), y16, jnp.asarray(post, jnp.float32),
                           jnp.asarray(res, jnp.float32), impl=impl)
    assert got.dtype == jnp.float32 and np.abs(got - out).max() < ATOL


def test_leading_dims_are_carried(interpreted):
    """``(n, B, L, C)``, as the LM hands the rows over."""
    x, params, y = _case(3, t=24)
    flat = hyper.hyper_pre(jnp.asarray(x), params, **KW)
    shaped = hyper.hyper_pre(jnp.asarray(x).reshape(N, 3, 8, C), params, **KW)
    assert shaped[0].shape == (3, 8, C) and shaped[2].shape == (3, 8, N, N)
    for a, b in zip(flat, shaped):
        assert np.array_equal(np.asarray(a).reshape(b.shape), b)
    out = hyper.hyper_post(jnp.asarray(x).reshape(N, 3, 8, C),
                           jnp.asarray(y).reshape(3, 8, C), shaped[1], shaped[2])
    assert out.shape == (N, 3, 8, C)


def test_the_sinkhorn_lands_on_the_doubly_stochastic_matrices():
    """Rows and columns sum to 1 within 1e-5 after 20 iterations, and
    not after 1 (logits of a spread of 0.7; 20 iterations leave a
    matrix of logits several units apart 1e-4 short of it)."""
    logits = jnp.asarray(np.random.default_rng(4).normal(size=(64, N, N)) * 0.7,
                         jnp.float32)
    twenty = np.asarray(hyper.sinkhorn(logits, 20, 1e-6, -30.0, 30.0))
    assert np.abs(twenty.sum(-1) - 1).max() < 1e-5
    assert np.abs(twenty.sum(-2) - 1).max() < 1e-5
    assert (twenty > 0).all()
    one = np.asarray(hyper.sinkhorn(logits, 1, 1e-6, -30.0, 30.0))
    assert np.abs(one.sum(-1) - 1).max() > 1e-3      # its rows are not there yet
    assert np.abs(one.sum(-2) - 1).max() < 1e-5      # (columns came last)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_clamp_keeps_the_exponential_finite(interpreted, impl):
    """Coefficient logits of hundreds: without the clamp at +-30
    ``exp`` overflows float32 and the matrix is NaN."""
    x, params, _y = _case(5, spread=400.0)
    _h, _post, res = hyper.hyper_pre(jnp.asarray(x), params, impl=impl, **KW)
    assert np.isfinite(np.asarray(res)).all()
    assert np.abs(np.asarray(res).sum(-2) - 1).max() < 1e-4
    unclamped = hyper.sinkhorn(
        jnp.full((1, N, N), 200.0).at[0, 0, 0].set(-200.0), 20, 1e-6, -1e9, 1e9)
    assert not np.isfinite(np.asarray(unclamped)).all()


def test_one_stream_is_refused_by_name():
    x, params, y = _case(6, n=2)
    with pytest.raises(ValueError, match="1 stream.*nothing to mix"):
        hyper.hyper_pre(jnp.asarray(x[:1]), params, **KW)
    with pytest.raises(ValueError, match="1 stream.*nothing to mix"):
        hyper.hyper_post(jnp.asarray(x[:1]), jnp.asarray(y), None, None)
    with pytest.raises(ValueError, match="hc_mult 1.*nothing to mix"):
        model_spec("xing4_0", hc_mult=1)


@pytest.mark.parametrize("n", [2, 3])
def test_other_stream_counts(interpreted, n):
    x, params, y = _case(7, n=n)
    h, post, res, out = _plain(x, params, y)
    for impl in ("xla", "pallas"):
        got_h, got_post, got_res = hyper.hyper_pre(
            jnp.asarray(x), params, impl=impl, **KW)
        assert np.abs(got_h - h).max() < ATOL and np.abs(got_res - res).max() < ATOL
        got = hyper.hyper_post(jnp.asarray(x), jnp.asarray(y), got_post, got_res,
                               impl=impl)
        assert np.abs(got - out).max() < ATOL


def test_the_rule_and_the_names():
    assert hyper.hyper_impl(4, "tpu") == hyper.hyper_impl(4, "interpret") == "pallas"
    assert hyper.hyper_impl(4, "cpu") == "xla"
    assert hyper.hyper_impl(11, "tpu") == "xla"  # 2 x 11 + 121 coefficients > 128 lanes
    assert (hyper.PRE_SCOPE, hyper.POST_SCOPE) == (
        "seldon.hyper.pre", "seldon.hyper.post")
    # float32: the rows read once, then read and written once beside the
    # sub-layer's row; phi, bias and alpha at rest
    assert hyper.position_bytes(4, 3584) == 4 * 3584 * 13 == 186_368
    assert hyper.weight_bytes(4, 3584) == 4 * (14_336 * 24 + 24 + 3)


def test_the_scopes_and_the_kernels_are_in_what_a_program_traces(interpreted):
    x, params, y = _case(8)

    def both(x, y):
        h, post, res = hyper.hyper_pre(x, params, **KW)
        return hyper.hyper_post(x, y + h, post, res)

    text = jax.jit(both).lower(jnp.asarray(x), jnp.asarray(y)).as_text(
        debug_info=True)
    assert "seldon.hyper.pre" in text and "seldon.hyper.post" in text
    import paged_harness as harness

    calls = harness.pallas_calls(both, jnp.asarray(x), jnp.asarray(y))
    assert [name for name, _shapes in calls] == ["hyper_pre_mix", "hyper_post_mix"]
    # three-dimensional first outputs: the readers' rule
    assert calls[0][1][0] == (1, 40, C) and calls[1][1][0] == (N, 40, C)
