"""``ops/ssm.py`` (CPU; the kernels under the Pallas interpreter): the
prefill's scan and the decode step of a selective state-space layer
against the recurrence position by position, the pad rule on ``Delta``,
the extremes of a decay, and the selection.

Tolerances: the two forms sum a state entry's two terms and a position's
16 products in the same order in float32; they read 1e-6 apart on values
of order 1 and are held to 2e-5.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paged_harness as harness
from seldon_core_tpu.ops import delta, ssm

N, E = 16, 256
TOL = 2e-5


def inputs(k, length, seed=0, dt_shift=-3.0):
    """Seeded inputs of a call: x, Delta (through its softplus), B, C and
    the layer's A (in (-16, -1]) and D."""
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (k, length, E))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (k, length, E)) + dt_shift)
    b = jax.random.normal(ks[2], (k, length, N))
    c = jax.random.normal(ks[3], (k, length, N))
    a = -jnp.exp(jax.random.uniform(ks[4], (N, E), minval=0.0, maxval=np.log(16.0)))
    d = jax.random.uniform(ks[5], (E,), minval=0.5, maxval=1.5)
    return x, dt, b, c, a, d


@pytest.fixture(params=["xla", "pallas"])
def form(request, monkeypatch):
    """Both forms of the step and of the scan: XLA's (the CPU's own) and
    the kernels under the interpreter."""
    if request.param == "pallas":
        monkeypatch.setattr(ssm, "backend", lambda: "interpret")
    assert ssm.step_impl(N, E) == ssm.scan_impl(N, E) == request.param
    return request.param


def scan(*args, **kw):
    # a jit of its own a call: ``jax.jit(ssm.scan)`` would keep the first
    # form's trace for the second (tests/test_delta_ops.py says why)
    return jax.jit(lambda *a: ssm.scan(*a, **kw))(*args)


@pytest.mark.parametrize("length", [16, 64, 37, 300])
def test_the_scan_is_the_recurrence(form, length):
    """Lengths on a bucket (16, 64), off one (37: padded to whole eights
    inside the kernel) and past one run of the kernel's positions (300)."""
    args = inputs(2, length, seed=length)
    y, state = scan(*args)
    want_y, want_state = ssm.recurrence(*args)
    np.testing.assert_allclose(y, want_y, atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL)
    assert y.shape == (2, length, E) and state.shape == (2, N, E)
    assert y.dtype == state.dtype == jnp.float32


def test_pad_positions_leave_each_row_its_own_state(form):
    """Two lengths in one padded call: each row's state is the one at its
    LAST REAL position, and its outputs before that are its own."""
    x, dt, b, c, a, d = inputs(3, 48, seed=2)
    lens = [48, 17, 33]
    y, state = scan(x, dt, b, c, a, d, true_lens=jnp.asarray(lens, jnp.int32))
    for i, n in enumerate(lens):
        row = slice(i, i + 1)
        want_y, want_state = ssm.recurrence(x[row, :n], dt[row, :n], b[row, :n],
                                            c[row, :n], a, d)
        np.testing.assert_allclose(y[row, :n], want_y, atol=TOL)
        np.testing.assert_allclose(state[row], want_state, atol=TOL)
    # the pad rule is on Delta AFTER its softplus: what a mask before it
    # leaves (softplus(0) = ln 2 a pad position) moves the state
    wrong = jnp.where((jnp.arange(48)[None, :] < jnp.asarray(lens)[:, None])[..., None],
                      dt, np.log(2.0))
    _y, scanned = ssm.recurrence(x, wrong, b, c, a, d)
    assert np.abs(np.asarray(scanned[1] - state[1])).max() > 100 * TOL


@pytest.mark.parametrize("dt_value", [200.0, 1e-9])
def test_a_decay_at_either_end_is_finite(form, dt_value):
    """A ``Delta`` large enough that ``exp(Delta A)`` underflows to 0 (the
    state forgets everything: it is ``Delta x B`` of the last position)
    and one small enough that it rounds to 1 (the state only adds)."""
    x, dt, b, c, a, d = inputs(1, 24, seed=3)
    dt = jnp.full_like(dt, dt_value)
    y, state = scan(x, dt, b, c, a, d)
    want_y, want_state = ssm.recurrence(x, dt, b, c, a, d)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(state)).all()
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=TOL)
    np.testing.assert_allclose(state, want_state, rtol=1e-5, atol=TOL)
    if dt_value > 1:
        assert float(jnp.exp(dt_value * a).max()) == 0.0
        last = (dt[0, -1] * x[0, -1])[None, :] * b[0, -1][:, None]
        np.testing.assert_allclose(state[0], last, rtol=1e-5)
    else:
        assert float(jnp.exp(dt_value * a).min()) == 1.0


def test_a_step_is_one_position_of_the_recurrence(form):
    x, dt, b, c, a, d = inputs(8, 1, seed=4)
    state = jax.random.normal(jax.random.key(9), (8, N, E))
    new, y = jax.jit(lambda *z: ssm.step(*z))(state, x[:, 0], dt[:, 0], b[:, 0],
                                              c[:, 0], a, d)
    want_y, want = ssm.recurrence(x, dt, b, c, a, d, state=state)
    np.testing.assert_allclose(new, want, atol=TOL)
    np.testing.assert_allclose(y, want_y[:, 0], atol=TOL)


@pytest.mark.parametrize("lanes", [8, 6])
def test_a_lane_that_is_not_running_keeps_its_state_bit_for_bit(form, lanes):
    """(six lanes: one block of all of them, where eight are a block of
    the kernel's own size)"""
    x, dt, b, c, a, d = inputs(lanes, 1, seed=5)
    state = jax.random.normal(jax.random.key(10), (lanes, N, E))
    active = jnp.asarray([True, False, True, True, False, True, True, False][:lanes])
    new, y = jax.jit(lambda *z: ssm.step(*z[:-1], active=z[-1]))(
        state, x[:, 0], dt[:, 0], b[:, 0], c[:, 0], a, d, active)
    idle = ~np.asarray(active)
    assert (np.asarray(new)[idle] == np.asarray(state)[idle]).all()
    assert not (np.asarray(new)[~idle] == np.asarray(state)[~idle]).all()
    want_y, want = ssm.recurrence(x, jnp.where(active[:, None, None], dt, 0.0), b, c,
                                  a, d, state=state)
    np.testing.assert_allclose(new, want, atol=TOL)
    np.testing.assert_allclose(y, want_y[:, 0], atol=TOL)


def test_a_prefill_of_n_then_m_steps_is_the_recurrence_over_n_plus_m(form):
    """The state a padded prefill leaves and the convolution's tail carry
    a stream on: n positions scanned in a bucket of 32, then m steps, the
    convolution (with its bias) in front of both."""
    n, m, k = 21, 9, 2
    ks = jax.random.split(jax.random.key(6), 3)
    raw = jax.random.normal(ks[0], (k, n + m, E))               # x~ before the taps
    taps = jax.random.normal(ks[1], (delta.TAPS, E)) * 0.5
    bias = jax.random.normal(ks[2], (E,)) * 0.1
    _x, dt, b, c, a, d = inputs(k, n + m, seed=7)
    whole, _tail = delta.conv(raw, taps, bias=bias)
    want_y, want_state = ssm.recurrence(whole, dt, b, c, a, d)

    lens = jnp.full((k,), n, jnp.int32)

    def padded(v):
        return jnp.pad(v[:, :n], [(0, 0), (0, 32 - n), (0, 0)])

    first, tail = delta.conv(padded(raw), taps, lens, bias=bias)
    y, state = scan(first, padded(dt), padded(b), padded(c), a, d, true_lens=lens)
    np.testing.assert_allclose(y[:, :n], want_y[:, :n], atol=TOL)
    # the tail is the last three INPUTS at the row's real length
    np.testing.assert_allclose(tail, raw[:, n - 3:n], atol=0)
    step = jax.jit(lambda *z: ssm.step(*z))
    for t in range(n, n + m):
        x_t, tail = delta.conv_step(tail, raw[:, t], taps, bias=bias)
        np.testing.assert_allclose(x_t, whole[:, t], atol=TOL)
        state, y_t = step(state, x_t, dt[:, t], b[:, t], c[:, t], a, d)
        np.testing.assert_allclose(y_t, want_y[:, t], atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL)


def test_the_convolution_s_bias_is_added_before_the_silu_and_none_traces_none():
    raw = jax.random.normal(jax.random.key(1), (1, 12, E))
    taps = jax.random.normal(jax.random.key(2), (delta.TAPS, E))
    bias = jnp.full((E,), 0.25)
    plain, _ = delta.conv(raw, taps)
    biased, _ = delta.conv(raw, taps, bias=bias)
    back = jnp.pad(raw, [(0, 0), (3, 0), (0, 0)])
    summed = sum(back[:, j:j + 12] * taps[j] for j in range(4))
    np.testing.assert_allclose(biased, jax.nn.silu(summed + 0.25), atol=1e-6)
    np.testing.assert_allclose(plain, jax.nn.silu(summed), atol=1e-6)
    # a call that passes no bias traces what it traced before there was one
    assert "0.25" not in str(jax.make_jaxpr(lambda r, t: delta.conv(r, t))(raw, taps))
    adds = [str(jax.make_jaxpr(f)(raw, taps)).count(" add ")
            for f in (lambda r, t: delta.conv(r, t),
                      lambda r, t: delta.conv(r, t, bias=bias))]
    assert adds[1] == adds[0] + 1


def test_the_selection_is_the_projection_three_norms_and_a_softplus():
    rank = 8
    ks = jax.random.split(jax.random.key(8), 7)
    x = jax.random.normal(ks[0], (2, 5, E))
    w_x = jax.random.normal(ks[1], (E, rank + 2 * N)) * E ** -0.5
    scales = [jax.random.uniform(k, (w,), minval=0.5, maxval=1.5)
              for k, w in zip(ks[2:5], (rank, N, N))]
    w_dt = jax.random.normal(ks[5], (rank, E)) * rank ** -0.5
    dt_bias = jax.random.uniform(ks[6], (E,), minval=-6.9, maxval=-2.25)
    dt, b, c = ssm.select(x, w_x, *scales, w_dt, dt_bias, eps=1e-6, dtype=jnp.float32)

    def norm(v, s):
        return v / jnp.sqrt((v * v).mean(-1, keepdims=True) + 1e-6) * s

    with jax.default_matmul_precision("highest"):
        low = x @ w_x
        want_dt = jax.nn.softplus(norm(low[..., :rank], scales[0]) @ w_dt + dt_bias)
    np.testing.assert_allclose(dt, want_dt, rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(b, norm(low[..., rank:rank + N], scales[1]), atol=2e-5)
    np.testing.assert_allclose(c, norm(low[..., rank + N:], scales[2]), atol=2e-5)
    assert float(dt.min()) > 0 and dt.dtype == b.dtype == c.dtype == jnp.float32


def test_which_form_serves_is_a_fact_of_the_backend_and_the_shapes():
    # the CPU traces XLA's form; a TPU the kernels, where the state is
    # whole (8, 128) tiles and the channels whole blocks of the scan
    assert ssm.step_impl(16, 5120, "cpu") == ssm.scan_impl(16, 5120, "cpu") == "xla"
    assert ssm.step_impl(16, 5120, "tpu") == ssm.scan_impl(16, 5120, "tpu") == "pallas"
    assert ssm.step_impl(12, 5120, "tpu") == ssm.scan_impl(16, 5000, "tpu") == "xla"
    assert ssm._scan_block(5120) == 512 and ssm._scan_block(128) == 128
    assert ssm._scan_block(640) == 128 and ssm._scan_block(96) == 0


def test_the_kernels_first_outputs_are_the_shapes_a_trace_names_them_by(monkeypatch):
    """``ssm_state_step``: the state ``(slots, N, E)``; ``ssm_scan``: ``y``
    ``(prompts, L, E)`` — the benchmark's readers find the calls by them
    (``layer_metrics/ssm_work.py``): do not reorder the outputs."""
    monkeypatch.setattr(ssm, "backend", lambda: "interpret")
    x, dt, b, c, a, d = inputs(2, 32, seed=11)
    state = jnp.zeros((8, N, E))
    x8, dt8, b8, c8, _a, _d = inputs(8, 1, seed=12)
    calls = harness.pallas_calls(lambda *z: ssm.scan(*z), x, dt, b, c, a, d)
    assert calls == [("ssm_scan", [(2, 32, E), (2, N, E)])]
    calls = harness.pallas_calls(lambda *z: ssm.step(*z), state, x8[:, 0], dt8[:, 0],
                                 b8[:, 0], c8[:, 0], a, d)
    assert calls == [("ssm_state_step", [(8, N, E), (8, E)])]
