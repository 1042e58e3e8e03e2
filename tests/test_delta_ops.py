"""``ops/delta.py``: the chunked scan, the decode step and the
convolution of a Gated-DeltaNet layer, each held to the recurrence
written position by position (CPU, float32).

The tolerances: every form is float32 at the highest matmul precision, so
what separates two of them is rounding in another order — 2e-6 here at
outputs of order 1 (read: 1.3e-6 at 200 positions).  A state kept in
bfloat16 moves the same outputs by 1e-2, beta without its factor 2 or a
decay left at 1 by tenths: ``test_a_wrong_recurrence_is_not_within_the_tolerance``
holds the three against the tolerance the sound form passes.

The prefill's scan has two forms (``delta.scan_impl``): XLA's — the CPU's
own answer — held to the recurrence, and the kernel ``delta_chunk_scan``
under the Pallas interpreter (the ``form`` fixture's second case) held to
float64 ``by_hand``, where neither form's own rounding hides the other's.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.ops import delta

TOL = 5e-6
B, H, DK, DV = 2, 4, 8, 16


def draw(length, seed=0, batch=B, heads=H, dk=DK, dv=DV):
    """q and k normalised as a layer makes them, v of unit variance, a
    decay in (0.6, 1) and beta in (0, 2)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = delta.l2norm(jax.random.normal(ks[0], (batch, length, heads, dk))) * dk ** -0.5
    k = delta.l2norm(jax.random.normal(ks[1], (batch, length, heads, dk)))
    v = jax.random.normal(ks[2], (batch, length, heads, dv))
    log_alpha = -0.5 * jax.random.uniform(ks[3], (batch, length, heads))
    beta = 2.0 * jax.random.uniform(ks[4], (batch, length, heads))
    return q, k, v, log_alpha, beta


def by_hand(q, k, v, log_alpha, beta):
    """The equations of the module's docstring in numpy float64, a loop a
    position, a head and a row: nothing of ``ops/delta.py``."""
    q, k, v, log_alpha, beta = (np.asarray(x, np.float64)
                                for x in (q, k, v, log_alpha, beta))
    batch, length, heads, dk = q.shape
    out = np.zeros(v.shape)
    state = np.zeros((batch, heads, dk, v.shape[-1]))
    for b in range(batch):
        for h in range(heads):
            s = state[b, h]
            for t in range(length):
                s = np.exp(log_alpha[b, t, h]) * s
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


def scan(*args, **kw):
    """``chunked_scan`` jitted under a function of its own: ``jax.jit`` of
    the same function at the same shapes would hand one form's case the
    trace the other left."""
    return jax.jit(lambda *a: delta.chunked_scan(*a, **kw))(*args)


def traced(*args):
    """... and its jaxpr, under a function of its own for the same reason."""
    return jax.make_jaxpr(lambda *a: delta.chunked_scan(*a))(*args)


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


def truth(form, *args):
    return by_hand(*args) if form == "kernel" else delta.recurrence(*args)


def test_the_recurrence_is_the_equations():
    args = draw(23, seed=4)
    out, state = delta.recurrence(*args)
    want_out, want_state = by_hand(*args)
    np.testing.assert_allclose(out, want_out, atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL)
    assert state.dtype == jnp.float32 and out.dtype == jnp.float32


@pytest.mark.parametrize("length", [5, 16, 40, 64, 100, 128, 200])
def test_the_chunked_scan_is_the_recurrence(form, length):
    """Lengths that are and are not multiples of the chunk (64) and of a
    diagonal block (16); beta over (0, 2)."""
    args = draw(length, seed=length)
    assert float(args[4].max()) > 1.0  # beta past 1: the negative eigenvalue's side
    want_out, want_state = truth(form, *args)
    out, state = scan(*args)
    np.testing.assert_allclose(out, want_out, atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL)
    assert state.dtype == jnp.float32 and state.shape == (B, H, DK, DV)


@pytest.mark.parametrize("chunk", [16, 32])
def test_the_state_is_carried_chunk_to_chunk(chunk):
    """A small chunk makes many: the carried state meets every edge."""
    args = draw(90, seed=9)
    want_out, want_state = delta.recurrence(*args)
    out, state = delta.chunked_scan(*args, chunk=chunk)
    np.testing.assert_allclose(out, want_out, atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL)


def test_a_scan_continues_from_a_state(form):
    q, k, v, la, beta = draw(70, seed=2)
    _out, first = scan(q[:, :30], k[:, :30], v[:, :30], la[:, :30], beta[:, :30])
    out, state = jax.jit(lambda *a: delta.chunked_scan(*a[:-1], state=a[-1]))(
        q[:, 30:], k[:, 30:], v[:, 30:], la[:, 30:], beta[:, 30:], first)
    want_out, want_state = truth(form, q, k, v, la, beta)
    np.testing.assert_allclose(out, want_out[:, 30:], atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL)


@pytest.mark.parametrize("lens", [(37, 90), (64, 5), (128, 1)])
def test_pad_positions_leave_each_row_its_own_state(form, lens):
    """Two prompts of different lengths in one call padded to 128: with
    beta 0 and alpha 1 past a row's length, the state that comes back is
    the state at that row's LAST REAL position."""
    bucket = 128
    q, k, v, la, beta = draw(bucket, seed=11)
    real = jnp.arange(bucket)[None, :, None] < jnp.asarray(lens)[:, None, None]
    out, state = scan(q, k, v, jnp.where(real, la, 0.0), jnp.where(real, beta, 0.0))
    for row, n in enumerate(lens):
        want_out, want_state = truth(
            form, *(x[row:row + 1, :n] for x in (q, k, v, la, beta)))
        np.testing.assert_allclose(state[row:row + 1], want_state, atol=TOL)
        np.testing.assert_allclose(out[row:row + 1, :n], want_out, atol=TOL)


@pytest.mark.parametrize("dv,pack", [(16, 1), (64, 2), (128, 1)])
def test_a_decode_step_is_one_step_of_the_recurrence(dv, pack):
    assert delta.pack_of(H, dv) == pack
    q, k, v, la, beta = draw(12, seed=3, dv=dv)
    want_out, want_state = delta.recurrence(q, k, v, la, beta)
    state = jnp.zeros(delta.state_shape(B, H, DK, dv), jnp.float32)
    step = jax.jit(delta.step, static_argnames="pack")
    for t in range(12):
        state, out = step(state, q[:, t], k[:, t], v[:, t], la[:, t], beta[:, t],
                          pack=pack)
        np.testing.assert_allclose(out, want_out[:, t], atol=TOL)
    assert state.dtype == jnp.float32
    np.testing.assert_allclose(delta.unpack_state(state, pack), want_state, atol=TOL)


def test_the_published_widths_rest_two_heads_in_whole_tiles():
    """30 heads of 96 x 192: 15 pairs of 384 lanes (three whole tiles);
    an odd head count or a value width of whole tiles is left plain."""
    assert delta.state_shape(128, 30, 96, 192) == (128, 15, 96, 384)
    assert delta.pack_of(30, 128) == 1 and delta.pack_of(3, 192) == 1
    state = jax.random.normal(jax.random.key(0), (3, 4, 8, 64))
    packed = delta.pack_state(state, 2)
    assert packed.shape == (3, 2, 8, 128)
    np.testing.assert_array_equal(delta.unpack_state(packed, 2), state)
    # a pair's lanes: head 2g then head 2g + 1
    np.testing.assert_array_equal(packed[:, 1, :, :64], state[:, 2])
    np.testing.assert_array_equal(packed[:, 1, :, 64:], state[:, 3])


def test_a_lane_that_does_not_run_keeps_its_state_bit_for_bit():
    q, k, v, la, beta = draw(1, seed=5, batch=3, dv=64)
    state = jax.random.normal(jax.random.key(1), delta.state_shape(3, H, DK, 64))
    active = jnp.asarray([True, False, True])
    new, _out = delta.step(state, q[:, 0], k[:, 0], v[:, 0], la[:, 0], beta[:, 0],
                           pack=2, active=active)
    np.testing.assert_array_equal(new[1], state[1])
    assert not np.array_equal(new[0], state[0])
    tail = jax.random.normal(jax.random.key(2), (3, 3, 10))
    taps = jax.random.normal(jax.random.key(3), (4, 10))
    _mixed, kept = delta.conv_step(tail, jnp.ones((3, 10)), taps, active)
    np.testing.assert_array_equal(kept[1], tail[1])
    np.testing.assert_array_equal(kept[0, :2], tail[0, 1:])


def test_the_convolution_is_a_sum_of_four_taps_and_keeps_the_real_tail():
    x = jax.random.normal(jax.random.key(6), (3, 20, 10))
    taps = jax.random.normal(jax.random.key(7), (4, 10))
    lens = jnp.asarray([20, 7, 2])
    out, tail = delta.conv(x, taps, lens)
    xs = np.concatenate([np.zeros((3, 3, 10)), np.asarray(x)], axis=1)
    want = sum(xs[:, j:j + 20] * np.asarray(taps)[j] for j in range(4))
    np.testing.assert_allclose(out, want / (1 + np.exp(-want)), atol=TOL)
    np.testing.assert_array_equal(tail[0], x[0, 17:20])
    np.testing.assert_array_equal(tail[1], x[1, 4:7])
    # a row shorter than the taps: zeros before its first input
    np.testing.assert_array_equal(tail[2], np.concatenate(
        [np.zeros((1, 10)), np.asarray(x[2, :2])]))


@pytest.mark.parametrize("n,m", [(37, 20), (64, 3), (2, 9)])
def test_a_prefill_hands_state_and_tail_to_decode(n, m):
    """A prefill of n in a padded call, then m decode steps, is the
    convolution and the recurrence over n + m positions."""
    heads, dk, dv, bucket = 4, 8, 64, 64
    chans = heads * (2 * dk + dv)
    x = jax.random.normal(jax.random.key(n), (1, n + m, chans))
    taps = 0.5 * jax.random.normal(jax.random.key(m), (4, chans))
    gates = jax.random.normal(jax.random.key(n + m), (1, n + m, 2 * heads))
    la, beta = delta.gates(gates[..., :heads], gates[..., heads:],
                           jnp.full((heads,), -1.0), jnp.zeros((heads,)))
    assert 0.0 < float(beta.min()) and float(beta.max()) < 2.0

    def split(mixed):
        lead = mixed.shape[:-1]
        return (delta.l2norm(mixed[..., :heads * dk].reshape(*lead, heads, dk)) * dk ** -0.5,
                delta.l2norm(mixed[..., heads * dk:2 * heads * dk].reshape(*lead, heads, dk)),
                mixed[..., 2 * heads * dk:].reshape(*lead, heads, dv))

    whole, _tail = delta.conv(x, taps)
    want_out, want_state = delta.recurrence(*split(whole), la, beta)

    padded = jnp.pad(x[:, :n], [(0, 0), (0, bucket - n), (0, 0)])
    lens = jnp.asarray([n])
    mixed, tail = delta.conv(padded, taps, lens)
    real = (jnp.arange(bucket) < n)[None, :, None]
    pad = [(0, 0), (0, bucket - n), (0, 0)]
    out, state = delta.chunked_scan(
        *split(mixed), jnp.where(real, jnp.pad(la[:, :n], pad), 0.0),
        jnp.where(real, jnp.pad(beta[:, :n], pad), 0.0))
    np.testing.assert_allclose(out[:, :n], want_out[:, :n], atol=TOL)
    state = delta.pack_state(state, 2)
    for t in range(n, n + m):
        mixed, tail = delta.conv_step(tail, x[:, t], taps)
        q, k, v = split(mixed)
        state, out = delta.step(state, q, k, v, la[:, t], beta[:, t], pack=2)
        np.testing.assert_allclose(out, want_out[:, t], atol=TOL)
    np.testing.assert_allclose(delta.unpack_state(state, 2), want_state, atol=TOL)


@pytest.mark.parametrize("wrong", ["state_bf16", "beta_one", "alpha_one"])
def test_a_wrong_recurrence_is_not_within_the_tolerance(form, wrong):
    q, k, v, la, beta = draw(100, seed=8)
    want_out, _state = truth(form, q, k, v, la, beta)
    if wrong == "beta_one":
        out, _s = scan(q, k, v, la, 0.5 * beta)
    elif wrong == "alpha_one":
        out, _s = scan(q, k, v, jnp.zeros_like(la), beta)
    else:  # the state rounded to bfloat16 between chunks of 16
        state, outs = None, []
        for lo in range(0, 100, 16):
            sl = slice(lo, lo + 16)
            o, state = delta.chunked_scan(q[:, sl], k[:, sl], v[:, sl], la[:, sl],
                                          beta[:, sl], state=state)
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
            outs.append(o)
        out = jnp.concatenate(outs, axis=1)
    assert float(jnp.abs(out - want_out).max()) > 100 * TOL


@pytest.fixture
def kernel(monkeypatch):
    """The decode step's Pallas kernel under the interpreter (the CPU's
    own answer is XLA's form)."""
    monkeypatch.setattr(delta, "backend", lambda: "interpret")


@pytest.mark.parametrize("dk,dv,pack", [(8, 64, 2), (16, 128, 1), (96, 192, 2)])
def test_the_step_kernel_is_the_step(monkeypatch, dk, dv, pack):
    """``delta_state_step`` against XLA's form of the same step, a lane
    left out included; (96, 192) is the published head."""
    heads = 4
    q, k, v, la, beta = draw(1, seed=dk, batch=3, heads=heads, dk=dk, dv=dv)
    state = jax.random.normal(jax.random.key(dv), delta.state_shape(3, heads, dk, dv))
    active = jnp.asarray([True, False, True])
    args = (state, q[:, 0], k[:, 0], v[:, 0], la[:, 0], beta[:, 0])
    assert delta.step_impl(dk, pack * dv) == "xla"  # the CPU's answer
    want_state, want_out = delta.step(*args, pack=pack, active=active)
    monkeypatch.setattr(delta, "backend", lambda: "interpret")
    assert delta.step_impl(dk, pack * dv) == "pallas"
    got_state, got_out = jax.jit(
        lambda *a: delta.step(*a, pack=pack, active=active))(*args)
    np.testing.assert_allclose(got_state, want_state, atol=TOL)
    np.testing.assert_allclose(got_out, want_out, atol=TOL)
    np.testing.assert_array_equal(got_state[1], state[1])  # bit for bit
    assert got_state.dtype == jnp.float32


def test_the_kernel_is_asked_only_where_the_state_is_whole_tiles(kernel):
    assert delta.step_impl(96, 384) == "pallas" and delta.step_impl(8, 128) == "pallas"
    assert delta.step_impl(8, 16) == "xla" and delta.step_impl(12, 128) == "xla"
    assert delta.step_impl(96, 384, where="cpu") == "xla"
    assert delta.step_impl(96, 384, where="tpu") == "pallas"
    # a state that is not whole tiles takes XLA's form under the same knob
    q, k, v, la, beta = draw(1, seed=1)
    state = jnp.zeros(delta.state_shape(B, H, DK, DV))
    new, out = delta.step(state, q[:, 0], k[:, 0], v[:, 0], la[:, 0], beta[:, 0])
    assert new.shape == (B, H, DK, DV) and out.shape == (B, H, DV)


def test_the_kernel_s_call_is_named_by_the_state_it_writes(kernel):
    """The state is the call's FIRST output and aliases its input: a
    device trace keys the kernel by it, which is how the benchmark's
    reader finds the update (``layer_metrics/delta_work.py is_step``)."""
    q, k, v, la, beta = draw(1, seed=2, dv=64)
    state = jnp.zeros(delta.state_shape(B, H, DK, 64))
    jaxpr = jax.make_jaxpr(lambda *a: delta.step(*a, pack=2))(
        state, q[:, 0], k[:, 0], v[:, 0], la[:, 0], beta[:, 0])
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert tuple(calls[0].outvars[0].aval.shape) == (B, H // 2, DK, 128)
    assert dict(calls[0].params["input_output_aliases"]) == {0: 0}


@pytest.mark.parametrize("length", [64, 150])
def test_the_scan_kernel_at_the_published_head(kernel, length):
    """Olmo-Hybrid's head, 96 x 192 (two heads: side by side in the
    inverse), against float64."""
    args = draw(length, seed=length, batch=1, heads=2, dk=96, dv=192)
    want_out, want_state = by_hand(*args)
    out, state = scan(*args)
    np.testing.assert_allclose(out, want_out, atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL)
    assert out.dtype == jnp.float32 and state.dtype == jnp.float32


def test_an_odd_head_count_is_inverted_a_head_at_a_time(kernel):
    args = draw(70, seed=3, heads=3)
    want_out, want_state = by_hand(*args)
    out, state = scan(*args)
    np.testing.assert_allclose(out, want_out, atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL)


def test_the_cpu_takes_xla_s_scan():
    assert delta.scan_impl(96) == "xla" and delta.scan_impl(8) == "xla"
    assert "pallas_call" not in str(traced(*draw(70)))


def test_the_scan_kernel_is_asked_only_where_a_chunk_is_whole_tiles(kernel):
    assert delta.scan_impl(96) == "pallas" and delta.scan_impl(8) == "pallas"
    assert delta.scan_impl(12) == "xla"            # a key width off the sublanes
    assert delta.scan_impl(96, chunk=32) == "xla"  # a chunk that is not CHUNK
    assert delta.scan_impl(96, where="cpu") == "xla"
    assert delta.scan_impl(96, where="tpu") == "pallas"
    # a shape that is not whole tiles takes XLA's form under the same knob
    args = draw(70, seed=1, dk=12)
    assert "pallas_call" not in str(traced(*args))
    want_out, want_state = delta.recurrence(*args)
    out, state = scan(*args)
    np.testing.assert_allclose(out, want_out, atol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL)
    assert "pallas_call" in str(traced(*draw(70)))


def test_the_scan_kernel_s_call_is_named_and_laid_for_the_readers(kernel):
    """``delta_chunk_scan``'s FIRST output is the scan's output laid
    ``(prompts, H, L, d_v)`` — four dims, the heads second, the prompts
    (never the slots) first, no two heads packed into one — and the final
    state its second: a device trace keys the kernel by the first, which
    is how the benchmark's readers find the scan
    (``layer_metrics/delta_work.py is_scan``, ``kda_work.py is_scan``)."""
    args = draw(100, seed=2)
    jaxpr = traced(*args)
    calls = kernel_calls(jaxpr.jaxpr)
    assert len(calls) == 1 and "delta_chunk_scan" in str(jaxpr)
    out, state = (tuple(v.aval.shape) for v in calls[0].outvars)
    assert out == (B, H, 128, DV) and state == (B, H, DK, DV)
    text = jax.jit(lambda *a: delta.chunked_scan(*a)).lower(*args).as_text(
        debug_info=True)
    assert "seldon.delta.scan" in text


def test_the_scopes_name_the_three_operations():
    """``seldon.delta.conv`` / ``.scan`` / ``.step`` reach the lowered
    program's operation names (what a device trace shows)."""
    q, k, v, la, beta = draw(32)
    scan = jax.jit(delta.chunked_scan).lower(q, k, v, la, beta).as_text(debug_info=True)
    assert "seldon.delta.scan" in scan
    state = jnp.zeros(delta.state_shape(B, H, DK, DV))
    step = jax.jit(delta.step).lower(
        state, q[:, 0], k[:, 0], v[:, 0], la[:, 0], beta[:, 0]).as_text(debug_info=True)
    assert "seldon.delta.step" in step
    conv = jax.jit(delta.conv).lower(
        jnp.zeros((2, 8, 6)), jnp.zeros((4, 6))).as_text(debug_info=True)
    assert "seldon.delta.conv" in conv
