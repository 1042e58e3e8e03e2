"""``ops/delta.py`` under a decay a key channel (Kimi Delta Attention): the
chunked scan, the decode step and the bounded gate, each held to the
recurrence written position by position (CPU, float32; the step kernel
under the Pallas interpreter).

The tolerances: every form is float32 at the highest matmul precision, so
what separates two of them is rounding in another order — 5e-6 here at
outputs of order 1 (read: 2e-7 at 200 positions, 7e-6 on a state).  The
case that shows why the scan is written as it is: **every channel's gate
at its floor, -5 a position, for 64 positions running**.  There ``e^{-g}``
reaches ``e^{320}`` inside one chunk; the block-wise form keeps every
exponent within ``+-8 x 5`` of a block's middle position, and a form
that referred a block to its START read 8e-5 at the block's last row
(``e^{-80}`` flushes a small component of k to zero where its partner's
``e^{+80}`` makes the pair matter).  A decay averaged over a head's
channels (Olmo-Hybrid's rule), beta times 2 and a state kept in bfloat16
move the same outputs by 1e-3 to tenths.

The prefill's scan has two forms (``delta.scan_impl``): XLA's — the CPU's
own answer — and the kernel ``delta_chunk_scan`` under the Pallas
interpreter (the ``form`` fixture's second case), which factors its decays
the same way and is held to float64 ``by_hand`` throughout.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.ops import delta

TOL = 5e-6
B, H, DK, DV = 2, 2, 16, 32
FLOOR = -5.0


def draw(length, seed=0, batch=B, heads=H, dk=DK, dv=DV, floor_run=None):
    """q and k normalised as a layer makes them, v of unit variance, a
    decay a key channel over (e^-5, 1) and beta in (0, 1);
    ``floor_run`` ``(start, n)``: every channel's gate at its floor for
    ``n`` positions from ``start``."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = delta.l2norm(jax.random.normal(ks[0], (batch, length, heads, dk))) * dk ** -0.5
    k = delta.l2norm(jax.random.normal(ks[1], (batch, length, heads, dk)))
    v = jax.random.normal(ks[2], (batch, length, heads, dv))
    log_alpha = FLOOR * jax.nn.sigmoid(
        2.0 * jax.random.normal(ks[3], (batch, length, heads, dk)) - 1.0)
    if floor_run:
        start, n = floor_run
        log_alpha = log_alpha.at[:, start:start + n].set(FLOOR)
    beta = jax.random.uniform(ks[4], (batch, length, heads))
    return q, k, v, log_alpha, beta


def by_hand(q, k, v, log_alpha, beta):
    """The module docstring's equations in numpy float64, a loop a
    position and a head, ``S' = Diag(alpha) S``: nothing of
    ``ops/delta.py``."""
    q, k, v, log_alpha, beta = (np.asarray(x, np.float64)
                                for x in (q, k, v, log_alpha, beta))
    batch, length, heads, dk = q.shape
    out = np.zeros(v.shape)
    state = np.zeros((batch, heads, dk, v.shape[-1]))
    for b in range(batch):
        for h in range(heads):
            s = state[b, h]
            for t in range(length):
                s = np.exp(log_alpha[b, t, h])[:, None] * s
                u = beta[b, t, h] * (v[b, t, h] - s.T @ k[b, t, h])
                s = s + np.outer(k[b, t, h], u)
                out[b, t, h] = s.T @ q[b, t, h]
            state[b, h] = s
    return out, state


@pytest.fixture(params=["xla", "kernel"])
def form(request, monkeypatch):
    """XLA's form of the scan, or the kernel under the interpreter."""
    if request.param == "kernel":
        monkeypatch.setattr(delta, "backend", lambda: "interpret")
    return request.param


def scan(*args):
    """``chunked_scan`` jitted under a function of its own: ``jax.jit`` of
    the same function at the same shapes would hand one form's case the
    trace the other left."""
    return jax.jit(lambda *a: delta.chunked_scan(*a))(*args)


def truth(form, *args):
    return by_hand(*args) if form == "kernel" else delta.recurrence(*args)


def test_the_recurrence_under_a_channel_decay_is_the_equations():
    args = draw(23, seed=4)
    out, state = delta.recurrence(*args)
    want_out, want_state = by_hand(*args)
    np.testing.assert_allclose(out, want_out, atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL)
    assert state.dtype == jnp.float32 and out.dtype == jnp.float32


@pytest.mark.parametrize("length", [5, 16, 17, 48, 64, 65, 100, 128, 200])
def test_the_chunked_scan_is_the_recurrence(form, length):
    """Lengths on and off multiples of the chunk (64) and of a diagonal
    block (16)."""
    args = draw(length, seed=length)
    want_out, want_state = truth(form, *args)
    out, state = scan(*args)
    np.testing.assert_allclose(out, want_out, atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL)


@pytest.mark.parametrize("length,start", [(100, 10), (128, 10), (128, 0), (200, 64),
                                          (160, 37)])
def test_every_gate_at_its_floor_for_64_positions_running(form, length, start):
    """Nothing overflows, nothing is NaN, and the outputs are the
    recurrence's: against float64 by hand, where neither form's own
    rounding hides the other's."""
    args = draw(length, seed=length + start, floor_run=(start, 64))
    assert float(args[3][:, start:start + 64].max()) == FLOOR
    out, state = scan(*args)
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(state).all())
    want_out, want_state = by_hand(*args)
    np.testing.assert_allclose(out, want_out, atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL)


def test_a_floor_the_blocks_cannot_hold_is_refused_by_the_spec():
    from seldon_core_tpu.models.spec import model_spec

    assert -FLOOR * delta.SUB <= delta.BLOCK_EXPONENT_MAX
    with pytest.raises(ValueError, match="blocks of 16"):
        model_spec("bailing_hybrid", lin_gate_floor=-6.0)


@pytest.mark.parametrize("lens", [(37, 90), (64, 5), (128, 1)])
def test_pad_positions_leave_each_row_its_own_state(form, lens):
    """Two lengths in one call, padded to the longer: each row's state is
    the state at its own last real position, and its outputs before
    that are its own."""
    longest = max(lens)
    q, k, v, la, beta = draw(longest, seed=sum(lens))
    real = (jnp.arange(longest)[None, :] < jnp.asarray(lens)[:, None])
    la = jnp.where(real[..., None, None], la, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)
    out, state = scan(q, k, v, la, beta)
    for row, n in enumerate(lens):
        one = tuple(x[row:row + 1, :n] for x in (q, k, v, la, beta))
        want_out, want_state = truth(form, *one)
        np.testing.assert_allclose(out[row, :n], want_out[0], atol=TOL)
        np.testing.assert_allclose(state[row], want_state[0], atol=TOL)


@pytest.mark.parametrize("dv,pack", [(32, 1), (64, 2), (128, 1)])
def test_a_decode_step_is_one_step_of_the_recurrence(dv, pack):
    q, k, v, la, beta = draw(7, seed=dv, dv=dv)
    _out, before = delta.recurrence(*(x[:, :6] for x in (q, k, v, la, beta)))
    want_out, want_state = delta.recurrence(q, k, v, la, beta)
    assert delta.pack_of(H, dv) == pack
    state, out = delta.step(delta.pack_state(before, pack), q[:, 6], k[:, 6], v[:, 6],
                            la[:, 6], beta[:, 6], pack=pack)
    np.testing.assert_allclose(out, want_out[:, 6], atol=TOL)
    np.testing.assert_allclose(delta.unpack_state(state, pack), want_state, atol=TOL)


@pytest.mark.parametrize("n,m", [(37, 20), (64, 3), (2, 9)])
def test_prefill_of_n_then_m_steps_is_the_recurrence_over_n_plus_m(n, m):
    q, k, v, la, beta = draw(n + m, seed=n * m)
    want_out, want_state = delta.recurrence(q, k, v, la, beta)
    out, state = jax.jit(delta.chunked_scan)(*(x[:, :n] for x in (q, k, v, la, beta)))
    np.testing.assert_allclose(out, want_out[:, :n], atol=TOL)
    step = jax.jit(delta.step)
    for t in range(n, n + m):
        state, o = step(state, q[:, t], k[:, t], v[:, t], la[:, t], beta[:, t])
        np.testing.assert_allclose(o, want_out[:, t], atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=2 * TOL)


def test_a_lane_that_does_not_run_keeps_its_state_bit_for_bit():
    q, k, v, la, beta = draw(1, seed=9, batch=3)
    state = jax.random.normal(jax.random.key(1), (3, H, DK, DV))
    active = jnp.asarray([True, False, True])
    new, _out = delta.step(state, q[:, 0], k[:, 0], v[:, 0], la[:, 0], beta[:, 0],
                           active=active)
    assert bool((new[1] == state[1]).all())
    assert not bool((new[0] == state[0]).all())


@pytest.mark.parametrize("length", [40, 100])
def test_a_decay_constant_over_a_head_s_channels_is_olmo_s_result(form, length):
    """The scalar form is the special case: the same decay given a head
    and given a channel (every channel alike).  To a stated rounding —
    the two forms sum the same terms in another order (read 8e-7)."""
    q, k, v, la, beta = draw(length, seed=length)
    scalar = la[..., 0]
    spread = jnp.broadcast_to(scalar[..., None], la.shape)
    for f in (delta.recurrence, scan):
        out_s, state_s = f(q, k, v, scalar, beta)
        out_c, state_c = f(q, k, v, spread, beta)
        np.testing.assert_allclose(out_c, out_s, atol=TOL)
        np.testing.assert_allclose(state_c, state_s, atol=TOL)
    # one step of the recurrence: the same products, bit for bit
    state = jax.random.normal(jax.random.key(2), (B, H, DK, DV))
    new_s, o_s = delta.step(state, q[:, 0], k[:, 0], v[:, 0], scalar[:, 0], beta[:, 0])
    new_c, o_c = delta.step(state, q[:, 0], k[:, 0], v[:, 0], spread[:, 0], beta[:, 0])
    assert bool((new_s == new_c).all()) and bool((o_s == o_c).all())


def test_the_bounded_gate_is_a_channel_s_and_lies_over_its_floor():
    ks = jax.random.split(jax.random.key(3), 4)
    a = 3.0 * jax.random.normal(ks[0], (2, 5, H * DK))
    b = jax.random.normal(ks[1], (2, 5, H))
    a_log = jax.random.uniform(ks[2], (H,), minval=-1.0, maxval=0.5)
    dt_bias = jax.random.uniform(ks[3], (H * DK,), minval=-6.0, maxval=-1.0)
    log_alpha, beta = delta.gates(a, b, a_log, dt_bias, False, floor=FLOOR)
    assert log_alpha.shape == (2, 5, H, DK) and beta.shape == (2, 5, H)
    assert float(log_alpha.min()) > FLOOR and float(log_alpha.max()) < 0.0
    assert 0.0 < float(beta.min()) and float(beta.max()) < 1.0  # no factor 2
    z = (np.asarray(a, np.float64) + np.asarray(dt_bias, np.float64)).reshape(2, 5, H, DK)
    want = FLOOR / (1.0 + np.exp(-np.exp(np.asarray(a_log, np.float64))[:, None] * z))
    np.testing.assert_allclose(log_alpha, want, atol=1e-5)
    # a head's channels differ: what Olmo-Hybrid's one gate a head cannot say
    assert float((log_alpha.max(-1) - log_alpha.min(-1)).min()) > 0.5


@pytest.mark.parametrize("wrong", ["decay_head", "beta_two", "state_bf16"])
def test_a_wrong_recurrence_is_not_within_the_tolerance(form, wrong):
    """(under ``form`` "kernel" the wrong programs that have a scan run
    the kernel's)"""
    q, k, v, la, beta = draw(100, seed=11)
    want, _state = truth(form, q, k, v, la, beta)
    run = scan if form == "kernel" else delta.recurrence
    if wrong == "decay_head":  # Olmo-Hybrid's rule: one decay a head
        mean = jnp.log(jnp.exp(la).mean(-1))
        got, _s = run(q, k, v, mean, beta)
    elif wrong == "beta_two":
        got, _s = run(q, k, v, la, 2.0 * beta)
    else:
        def one(s, xs):
            q_t, k_t, v_t, la_t, b_t = xs
            s, o = delta.step(s, q_t, k_t, v_t, la_t, b_t)
            return s.astype(jnp.bfloat16).astype(jnp.float32), o

        xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, la, beta))
        _s, got = jax.lax.scan(one, jnp.zeros((B, H, DK, DV)), xs)
        got = jnp.moveaxis(got, 0, 1)
    assert float(jnp.abs(got - want).max()) > 100 * TOL


# ---------------------------------------------------------------------------
# the step kernel (under the interpreter)
# ---------------------------------------------------------------------------

@pytest.fixture
def kernel(monkeypatch):
    monkeypatch.setattr(delta, "backend", lambda: "interpret")


@pytest.mark.parametrize("dk,dv,pack", [(8, 128, 1), (16, 64, 2), (128, 128, 1)])
def test_the_step_kernel_is_the_step_under_a_channel_decay(monkeypatch, dk, dv, pack):
    heads = 2
    ks = jax.random.split(jax.random.key(dk + dv), 6)
    state = jax.random.normal(ks[0], delta.state_shape(3, heads, dk, dv))
    q = jax.random.normal(ks[1], (3, heads, dk))
    k = jax.random.normal(ks[2], (3, heads, dk))
    v = jax.random.normal(ks[3], (3, heads, dv))
    la = FLOOR * jax.random.uniform(ks[4], (3, heads, dk))
    beta = jax.random.uniform(ks[5], (3, heads))
    active = jnp.asarray([True, False, True])
    want_state, want_out = delta.step(state, q, k, v, la, beta, pack=pack, active=active)
    monkeypatch.setattr(delta, "backend", lambda: "interpret")
    assert delta.step_impl(dk, pack * dv) == "pallas"
    got_state, got_out = delta.step(state, q, k, v, la, beta, pack=pack, active=active)
    np.testing.assert_allclose(got_state, want_state, atol=TOL)
    np.testing.assert_allclose(got_out, want_out, atol=TOL)
    assert bool((got_state[1] == state[1]).all())  # the lane left out: bit for bit


def test_the_kernel_s_call_carries_the_decay_with_the_rows(kernel):
    """A decay a channel rides ``(slots, G, 3 pack, d_k)`` beside q and k
    and the lanes' operand is ``(slots, G, 2, W)``; the scalar form's
    operands are what they were, ``(.., 2 pack, d_k)`` and ``(.., 3, W)``:
    which form a call takes is a fact of its structure."""
    def operands(la_shape):
        s = jnp.zeros((4, 2, 8, 128))
        args = (s, jnp.zeros((4, 2, 8)), jnp.zeros((4, 2, 8)), jnp.zeros((4, 2, 128)),
                jnp.zeros(la_shape), jnp.zeros((4, 2)))
        text = str(jax.make_jaxpr(delta.step)(*args))
        assert "delta_state_step" in text
        return text

    channel, scalar = operands((4, 2, 8)), operands((4, 2))
    assert "f32[4,2,3,8]" in channel and "f32[4,2,2,128]" in channel
    assert "f32[4,2,2,8]" in scalar and "f32[4,2,3,128]" in scalar
    assert "f32[4,2,3,8]" not in scalar


@pytest.mark.parametrize("length", [64, 150])
def test_the_scan_kernel_at_the_published_head(kernel, length):
    """Ling-3.0-flash's head, 128 x 128 (two heads: side by side in the
    inverse), a floor run across a chunk's edge, against float64."""
    args = draw(length, seed=length, batch=1, dk=128, dv=128, floor_run=(40, 64))
    want_out, want_state = by_hand(*args)
    out, state = scan(*args)
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(state).all())
    np.testing.assert_allclose(out, want_out, atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL)


def test_the_scan_kernel_continues_from_a_state_under_a_channel_decay(kernel):
    q, k, v, la, beta = draw(150, seed=5)
    _out, first = scan(*(x[:, :70] for x in (q, k, v, la, beta)))
    out, state = jax.jit(lambda *a: delta.chunked_scan(*a[:-1], state=a[-1]))(
        *(x[:, 70:] for x in (q, k, v, la, beta)), first)
    want_out, want_state = by_hand(q, k, v, la, beta)
    np.testing.assert_allclose(out, want_out[:, 70:], atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL)


def kernel_calls(jaxpr):
    """The ``pallas_call`` equations of a jaxpr, the scan's own jit looked
    into (``delta._scan_jit``: a program's layers share one trace of it)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += kernel_calls(sub)
    return found


def test_the_scan_kernel_s_call_carries_the_decay_like_the_keys(kernel):
    """One kernel, the variant a fact of the call's structure: a decay a
    channel rides ``(B, H, L, d_k)`` as k does, a decay a head ``(B, groups,
    chunks, heads, 64)`` beside beta; the output ``(prompts, H, L, d_v)``
    is the first result either way (``layer_metrics/kda_work.py is_scan``:
    four dims, H second, the first not the slots)."""
    def call(la_shape):
        args = (jnp.zeros((B, 128, H, DK)), jnp.zeros((B, 128, H, DK)),
                jnp.zeros((B, 128, H, DV)), jnp.zeros(la_shape), jnp.zeros((B, 128, H)))
        jaxpr = jax.make_jaxpr(lambda *a: delta.chunked_scan(*a))(*args)
        (eqn,) = kernel_calls(jaxpr.jaxpr)
        assert "delta_chunk_scan" in str(jaxpr)
        assert tuple(eqn.outvars[0].aval.shape) == (B, H, 128, DV)
        assert tuple(eqn.outvars[1].aval.shape) == (B, H, DK, DV)
        return [tuple(v.aval.shape) for v in eqn.invars]

    channel, scalar = call((B, 128, H, DK)), call((B, 128, H))
    assert channel[3] == (B, H, 128, DK) and channel[4] == (B, 1, 2, H, 64)
    assert scalar[3] == scalar[4] == (B, 1, 2, H, 64)


# the scalar forms as the tree before this model traced them (jaxpr text,
# sha256; taken on PR 51's tree with this test's own ``_traced``): a decay
# a channel is a second branch, not an edit of the first
OLMO_JAXPRS = {
    "step/xla": "32b8e9528024b36a", "step/pallas": "1b29eeae422156a9",
    "scan": "a26825cf66cabe28", "gates": "a3b923765c7b273d",
}


def _traced(what, monkeypatch):
    f32 = jnp.float32
    if what.startswith("step"):
        monkeypatch.setattr(delta, "backend",
                            lambda: "interpret" if what.endswith("pallas") else "cpu")
        args = (jnp.zeros((4, 2, 8, 128), f32), jnp.zeros((4, 4, 8), f32),
                jnp.zeros((4, 4, 8), f32), jnp.zeros((4, 4, 64), f32),
                jnp.zeros((4, 4), f32), jnp.zeros((4, 4), f32))
        text = str(jax.make_jaxpr(
            lambda *a: delta.step(*a, pack=2, active=jnp.ones((4,), bool)))(*args))
    elif what == "scan":
        args = (jnp.zeros((2, 100, 4, 8), f32), jnp.zeros((2, 100, 4, 8), f32),
                jnp.zeros((2, 100, 4, 16), f32), jnp.zeros((2, 100, 4), f32),
                jnp.zeros((2, 100, 4), f32))
        text = str(jax.make_jaxpr(delta.chunked_scan)(*args))
    else:
        args = (jnp.zeros((2, 5, 4), f32), jnp.zeros((2, 5, 4), f32),
                jnp.zeros((4,), f32), jnp.zeros((4,), f32))
        text = str(jax.make_jaxpr(delta.gates)(*args))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("what", sorted(OLMO_JAXPRS))
def test_a_decay_a_head_traces_what_it_traced(monkeypatch, what):
    assert _traced(what, monkeypatch) == OLMO_JAXPRS[what]


def test_the_scopes_name_the_forms():
    q, k, v, la, beta = draw(20)
    text = jax.jit(delta.chunked_scan).lower(q, k, v, la, beta).as_text(debug_info=True)
    assert "seldon.delta.scan" in text
    state = jnp.zeros((B, H, DK, DV))
    text = jax.jit(delta.step).lower(
        state, q[:, 0], k[:, 0], v[:, 0], la[:, 0], beta[:, 0]).as_text(debug_info=True)
    assert "seldon.delta.step" in text
