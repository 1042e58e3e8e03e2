"""ops/moe.py: the router and the grouped expert matmul against a dense
all-experts einsum (every expert applied to every row, then only the
routed ones kept): random routing, an expert that receives no row, one
that receives every row, a decode step's few rows and a prefill
group's many.

Every case runs on both sides of ``grouped_swiglu``'s rule:
``ragged_dot`` in float32 (what the CPU lanes trace) and the Pallas
kernels under the interpreter in bfloat16 — the streaming one under the
ridge, the tiled one at and over it (``lane``: the test answers
``"interpret"`` where the program asks for its backend)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.ops import moe

D, F, E = 32, 16, 8


def weights(seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (D, E), jnp.float32) * D ** -0.5,
            (jax.random.normal(ks[1], (E, D, F), jnp.float32) * D ** -0.5).astype(dtype),
            (jax.random.normal(ks[2], (E, D, F), jnp.float32) * D ** -0.5).astype(dtype),
            (jax.random.normal(ks[3], (E, F, D), jnp.float32) * F ** -0.5).astype(dtype))


# bf16 operands against the same bf16-rounded operands in float32: what
# is left is the rounding of each matmul's output, a few parts in a
# thousand of unit-spread values
TOL = {"ragged_dot": 1e-5, "stream": 0.02, "tiled": 0.02}


@pytest.fixture(params=["ragged_dot", "stream"])
def lane(request, monkeypatch):
    """``(implementation, operand type)``; on ``stream`` the grouped
    SwiGLU is told its backend is the Pallas interpreter."""
    if request.param == "stream":
        monkeypatch.setattr(moe, "matmul_backend", lambda: "interpret")
        return "stream", jnp.bfloat16
    return "ragged_dot", jnp.float32


def runs(impl, rows, groups=E, dtype=None):
    """The rule's answer for this test's widths, as the call asks it."""
    dtype = dtype or (jnp.bfloat16 if impl == "stream" else jnp.float32)
    return moe.expert_matmul_impl(rows, groups, D, F, dtype, moe.matmul_backend())


def dense(h, w_gate, w_up, w_down, gates, experts):
    """Every expert on every row, in float32; then the routed pairs."""
    h, w_gate, w_up, w_down = (a.astype(jnp.float32) for a in (h, w_gate, w_up, w_down))
    with jax.default_matmul_precision("highest"):
        act = jax.nn.silu(jnp.einsum("td,edf->tef", h, w_gate)) * jnp.einsum(
            "td,edf->tef", h, w_up)
        out = jnp.einsum("tef,efd->ted", act, w_down)            # (T, E, D)
    picked = jnp.take_along_axis(out, experts[:, :, None], axis=1)  # (T, k, D)
    return (picked * gates[:, :, None]).sum(axis=1)


# (700, 3) is a prefill group's: 262 rows an expert, over the rule's
# line, so the first lane runs ragged_dot there and the second the
# tiled kernel (2,100 rows: no multiple of its row block);
# (50, 3) and (25, 8) are no multiple of the kernel's row tile
@pytest.mark.parametrize("rows,top_k", [(1, 2), (4, 2), (32, 2), (200, 3), (64, 8),
                                        (50, 3), (25, 8), (700, 3)])
def test_routed_rows_equal_the_dense_einsum(lane, rows, top_k):
    impl, dtype = lane
    w_router, w_gate, w_up, w_down = weights(dtype=dtype)
    h = jax.random.normal(jax.random.key(rows), (rows, D), jnp.float32).astype(dtype)
    gates, experts = moe.route(h, w_router, top_k)
    over = rows * top_k >= moe.STREAM_MAX_MEAN_ROWS * E
    ran = {"stream": "tiled"}.get(impl, impl) if over else impl
    assert runs(impl, rows * top_k) == ran
    got = moe.expert_ffn(h, w_gate, w_up, w_down, gates, experts)
    want = dense(h, w_gate, w_up, w_down, gates, experts)
    assert got.shape == (rows, D) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got - want)).max() < TOL[ran]


@pytest.mark.parametrize("case", ["an_expert_with_no_row", "one_expert_takes_all",
                                  "first_and_last_only"])
def test_uneven_groups(lane, case):
    impl, dtype = lane
    _w_router, w_gate, w_up, w_down = weights(1, dtype)
    rows, top_k = 24, 2
    assert runs(impl, rows * top_k) == impl
    h = jax.random.normal(jax.random.key(7), (rows, D), jnp.float32).astype(dtype)
    rng = np.random.default_rng(3)
    if case == "an_expert_with_no_row":       # expert 3 never chosen
        pool = np.array([e for e in range(E) if e != 3])
        experts = np.stack([rng.choice(pool, top_k, replace=False) for _ in range(rows)])
    elif case == "one_expert_takes_all":      # every row's first choice is expert 5
        experts = np.stack([[5, rng.choice([e for e in range(E) if e != 5])]
                            for _ in range(rows)])
    else:
        experts = np.tile([0, E - 1], (rows, 1))
    experts = jnp.asarray(experts, jnp.int32)
    gates = jnp.asarray(rng.uniform(0.05, 0.5, size=(rows, top_k)), jnp.float32)
    got = moe.expert_ffn(h, w_gate, w_up, w_down, gates, experts)
    want = dense(h, w_gate, w_up, w_down, gates, experts)
    assert np.abs(np.asarray(got - want)).max() < TOL[impl]
    hist = np.asarray(moe.expert_histogram(experts, E))
    assert hist.sum() == rows * top_k
    assert hist.tolist() == np.bincount(np.asarray(experts).ravel(), minlength=E).tolist()
    if case == "an_expert_with_no_row":
        assert hist[3] == 0
    if case == "one_expert_takes_all":
        assert hist[5] == rows


def test_router_is_float32_softmax_topk_not_renormalised():
    w_router = weights(2)[0]
    h = jax.random.normal(jax.random.key(11), (16, D), jnp.float32)
    gates, experts = moe.route(h.astype(jnp.bfloat16), w_router, 3)
    assert gates.dtype == jnp.float32 and experts.dtype == jnp.int32
    probs = np.asarray(jax.nn.softmax(
        jnp.dot(h.astype(jnp.bfloat16).astype(jnp.float32), w_router,
                precision="highest"), axis=-1))
    order = np.argsort(-probs, axis=-1)[:, :3]
    assert np.asarray(experts).tolist() == order.tolist()
    assert np.allclose(np.asarray(gates), np.take_along_axis(probs, order, -1), atol=1e-6)
    assert (np.asarray(gates).sum(-1) < 0.999).all()  # the probabilities as they are


def test_histogram_counts_only_the_rows_the_mask_keeps():
    experts = jnp.asarray([[0, 1], [1, 2], [7, 0]], jnp.int32)
    assert np.asarray(moe.expert_histogram(experts, E)).tolist() == [2, 2, 1, 0, 0, 0, 0, 1]
    kept = moe.expert_histogram(experts, E, mask=jnp.asarray([True, False, True]))
    assert np.asarray(kept).tolist() == [2, 1, 0, 0, 0, 0, 0, 1]


def test_bf16_rows_accumulate_in_float32(lane):
    """bf16 operands, f32 accumulation (``TOL``), on ``ragged_dot``
    (gate and up leave it in bf16) and in the kernel (which keeps them
    in float32 until their product)."""
    impl, _dtype = lane
    w_router, w_gate, w_up, w_down = weights(4, jnp.bfloat16)
    h = jax.random.normal(jax.random.key(5), (48, D), jnp.float32).astype(jnp.bfloat16)
    gates, experts = moe.route(h, w_router, 2)
    assert runs(impl, 96, dtype=jnp.bfloat16) == impl
    got = moe.expert_ffn(h, w_gate, w_up, w_down, gates, experts)
    want = dense(h, w_gate, w_up, w_down, gates, experts)
    assert got.dtype == jnp.float32
    assert np.abs(np.asarray(got - want)).max() < 0.02


def test_inside_a_scan_as_the_decode_chunk_runs_it(lane):
    impl, dtype = lane
    w_router, w_gate, w_up, w_down = weights(6, dtype)
    hs = jax.random.normal(jax.random.key(9), (3, 4, D), jnp.float32).astype(dtype)

    def step(acc, h):
        gates, experts = moe.route(h, w_router, 2)
        out = moe.expert_ffn(h, w_gate, w_up, w_down, gates, experts)
        return acc + moe.expert_histogram(experts, E), out

    hist, outs = jax.jit(lambda x: jax.lax.scan(step, jnp.zeros((E,), jnp.int32), x))(hs)
    assert int(hist.sum()) == 3 * 4 * 2
    for i in range(3):
        gates, experts = moe.route(hs[i], w_router, 2)
        want = dense(hs[i], w_gate, w_up, w_down, gates, experts)
        assert np.abs(np.asarray(outs[i] - want)).max() < TOL[impl]


# ---------------------------------------------------------------------------
# a replica's share (expert_ffn_held): pad rows past the groups, a
# second pass, and the while_loop both run in
# ---------------------------------------------------------------------------

HELD_LOOP_CASES = ["one_pass_with_pad_rows", "a_second_pass", "nothing_local"]


def held_loop(*args):
    """Experts 2..5 of 32 on :func:`held_loop_inputs`' arguments."""
    return moe.expert_ffn_held(*args, 2, 32)


def traced_anew(fn):
    """``jax.jit`` of ``fn`` that shares no trace with another test's: the
    lane's backend and a wrapped ``grouped_swiglu`` are read as the pass
    is traced, and jit caches by the function it was handed."""
    return jax.jit(lambda *args: fn(*args))


def held_loop_inputs(case, dtype):
    """Experts 2..5 held, of 32 as the pass is sized, 40 tokens choosing
    two of eight: ``expert_ffn_held``'s arguments up to the gates and
    experts, and the dense reference with the absent experts' gates at 0."""
    _w_router, w_gate, w_up, w_down = weights(8, dtype)
    tokens, top_k, offset, held = 40, 2, 2, 4
    h = jax.random.normal(jax.random.key(21), (tokens, D), jnp.float32).astype(dtype)
    rng = np.random.default_rng(12)
    if case == "one_pass_with_pad_rows":
        experts = np.stack([rng.choice(E, top_k, replace=False) for _ in range(tokens)])
    elif case == "a_second_pass":
        experts = np.tile([2, 3], (tokens, 1))       # 80 local assignments
    else:
        experts = np.stack([rng.choice([0, 1, 6, 7], top_k, replace=False)
                            for _ in range(tokens)])
    experts = jnp.asarray(experts, jnp.int32)
    gates = jnp.asarray(rng.uniform(0.05, 0.5, size=(tokens, top_k)), jnp.float32)
    sl = slice(offset, offset + held)
    local = (experts >= offset) & (experts < offset + held)
    want = dense(h, w_gate, w_up, w_down, jnp.where(local, gates, 0.0), experts)
    return (h, w_gate[sl], w_up[sl], w_down[sl], gates, experts), want


@pytest.mark.parametrize("case", HELD_LOOP_CASES)
def test_held_experts_in_a_while_loop(lane, case):
    """Experts 2..5 held, of 32 as the pass is sized: 64 rows
    (``held_rows_cap``) of which random choices among the eight fill
    about 40 (the rest lie past the groups and come back as whatever
    the kernel left there); every token choosing two held experts is 80
    and needs two passes; no token choosing any leaves the loop unrun."""
    impl, dtype = lane
    assert moe.held_rows_cap(40, 2, 4, 32) == 64
    assert runs(impl, 64, groups=4) == impl
    args, want = held_loop_inputs(case, dtype)
    got = traced_anew(held_loop)(*args)
    assert got.shape == (40, D) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got - want)).max() < TOL[impl]
    if case == "nothing_local":
        assert not np.asarray(got).any()


# The four configurations that hold a share, at their published sizes:
# (top-k, held, router outputs), the decode pass's (tokens, rows) and the
# prefill programs' tokens with, where the pass stays under the ridge,
# its rows as they were before the rule told the regimes apart (PR 42).
HELD_SHARES = {
    "gigachat3.1": ((8, 8, 256), (128, 128), {
        16: 64, 128: 128, 1024: 1024, 2048: 2048, 4096: None, 8192: None}),
    "longcat-flash": ((12, 16, 768), (128, 128), {
        16: 64, 512: 512, 1024: 1024, 2048: 2048, 3072: 4096, 4096: 4096}),
    "dots3": ((8, 8, 256), (128, 128), {
        16: 64, 1024: 1024, 2048: 2048, 3072: None, 4096: None, 6144: None,
        7168: None, 8192: None}),
    "smallthinker": ((6, 16, 64), (64, 512), {
        16: 128, 128: 1024, 512: 4096, 1024: 4096, 2048: None, 3072: None,
        4096: None, 6144: None, 8192: None}),
}
# ... and the rows over the ridge, where the issue's table names them
OVER_THE_RIDGE = {
    "gigachat3.1": {4096: 2048, 8192: 3072},
    "dots3": {3072: 2048, 4096: 2048, 6144: 2560, 8192: 3072},
    "smallthinker": {2048: 4608, 3072: 7168, 4096: 9216, 6144: 13824, 8192: 18432},
}


@pytest.mark.parametrize("config", sorted(HELD_SHARES))
def test_a_pass_s_rows_follow_the_regime(config):
    """Under the ridge (fewer than 256 rows an expert at four even
    shares) a pass is the power of two it always was: every decode
    chunk, every small prefill.  At and over it: whole 512s, at least
    the ridge's own rows, at least the even share (one pass when routing
    is even), at most every assignment (the ridge's rows first), and
    never fewer for more tokens."""
    (top_k, held, outputs), (slots, decode_rows), prefills = HELD_SHARES[config]
    cap = lambda tokens: moe.held_rows_cap(tokens, top_k, held, outputs)  # noqa: E731
    ridge = moe.STREAM_MAX_MEAN_ROWS * held
    assert cap(slots) == decode_rows < ridge
    for tokens, before in prefills.items():
        rows, even = cap(tokens), tokens * top_k * held / outputs
        if before is not None:
            assert rows == before, tokens
            # the line itself (four shares are exactly the ridge) is
            # where the two halves meet
            assert rows < ridge or rows == ridge >= 1.5 * even, tokens
            continue
        assert rows == OVER_THE_RIDGE[config].get(tokens, rows), tokens
        assert rows % 512 == 0 and ridge <= rows, tokens
        assert even <= rows <= max(ridge, -(-tokens * top_k // 512) * 512), tokens
        # what the pass was: the power of two over four even shares
        assert rows < 4 * even or rows == ridge, tokens
    sizes = sorted(prefills)
    assert [cap(t) for t in sizes] == sorted(cap(t) for t in sizes)


@pytest.mark.parametrize("passes", [1, 2, 3])
def test_a_tight_pass_over_the_ridge_drops_nothing(passes):
    """4 of 16 experts held, 1,024 tokens choosing 4: four even shares
    are 4,096 rows (1,024 an expert: over the ridge), so a pass holds
    1,536.  Even routing fills one pass; piled onto this share it takes
    two and three, and the sum is ``expert_ffn``'s over the held experts
    either way.  The engine's three counters are this arithmetic
    (``held_pass_account``)."""
    tokens, top_k, held, of, offset = 1024, 4, 4, 16, 4
    cap = moe.held_rows_cap(tokens, top_k, held, of)
    assert cap == 1536 >= moe.STREAM_MAX_MEAN_ROWS * held
    ks = jax.random.split(jax.random.key(40 + passes), 4)
    w_gate = jax.random.normal(ks[0], (of, D, F), jnp.float32) * D ** -0.5
    w_up = jax.random.normal(ks[1], (of, D, F), jnp.float32) * D ** -0.5
    w_down = jax.random.normal(ks[2], (of, F, D), jnp.float32) * F ** -0.5
    h = jax.random.normal(ks[3], (tokens, D), jnp.float32)
    rng = np.random.default_rng(passes)
    # how many of a token's four picks fall on the held experts 4..7
    local_picks = {1: rng.choice([0, 1, 2], tokens, p=[0.3, 0.4, 0.3]),
                   2: rng.choice([2, 3], tokens),
                   3: np.full(tokens, 4)}[passes]
    inside, outside = np.arange(offset, offset + held), np.r_[0:offset, offset + held:of]
    experts = np.stack([
        np.r_[rng.choice(inside, n, replace=False),
              rng.choice(outside, top_k - n, replace=False)][rng.permutation(top_k)]
        for n in local_picks]).astype(np.int32)
    n_local = int(local_picks.sum())
    assert -(-n_local // cap) == passes
    experts = jnp.asarray(experts)
    gates = jnp.asarray(rng.uniform(0.05, 0.5, size=(tokens, top_k)), jnp.float32)
    sl = slice(offset, offset + held)
    got = jax.jit(lambda *a: moe.expert_ffn_held(*a, offset, of))(
        h, w_gate[sl], w_up[sl], w_down[sl], gates, experts)
    local = (experts >= offset) & (experts < offset + held)
    want = jax.jit(moe.expert_ffn)(
        h, w_gate, w_up, w_down, jnp.where(local, gates, 0.0), experts)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    assert np.abs(np.asarray(want)).max() > 0.1
    # two routed layers of one prefill call: this one and one nobody chose
    hist = np.asarray(moe.expert_histogram(experts, of))
    assert moe.held_pass_account([hist[sl].sum(), 0], cap) == (
        passes * cap, n_local, passes - 1)


# ---------------------------------------------------------------------------
# the streaming kernel by itself, and the rule that chooses it
# ---------------------------------------------------------------------------

def grouped_dense(x, matrices, sizes):
    """Every row through its own group's matrices, in float32."""
    owner = np.repeat(np.arange(len(sizes)), sizes)
    x = np.asarray(x.astype(jnp.float32))[: len(owner)]
    mats = [np.asarray(m.astype(jnp.float32)) for m in matrices]
    y = np.einsum("rk,rkn->rn", x, mats[0][owner])
    if len(mats) == 2:
        y = np.asarray(jax.nn.silu(jnp.asarray(y))) * np.einsum("rk,rkn->rn", x, mats[1][owner])
    return y


@pytest.mark.parametrize("row_tile", [8, 16, 128])
@pytest.mark.parametrize("sizes", [
    (5, 0, 7, 1, 0, 0, 19, 3),        # empty groups, none aligned to a tile
    (0, 0, 0, 35, 0, 0, 0, 0),        # every row on one group: several tiles
    (1, 1, 1, 1, 1, 1, 1, 1),
    (0, 0, 9, 4, 0, 0, 0, 0),         # rows past the groups: 22 of 35
    (0, 0, 0, 0, 0, 0, 0, 0),         # no group hit: nothing to compute
])
def test_the_streaming_kernel_against_each_rows_own_group(sizes, row_tile):
    """``stream_matmul`` with one matrix a group and with two (gate and
    up, ``silu(gate) * up`` in the kernel), in ``(K, 128)`` blocks of a
    256-wide matrix (two grid columns), 35 rows (no multiple of 8)."""
    k, n, rows = 32, 256, 35
    ks = jax.random.split(jax.random.key(sum(sizes) + row_tile), 3)
    x = jax.random.normal(ks[0], (rows, k), jnp.float32).astype(jnp.bfloat16)
    w1 = (jax.random.normal(ks[1], (E, k, n), jnp.float32) * k ** -0.5).astype(jnp.bfloat16)
    w2 = (jax.random.normal(ks[2], (E, k, n), jnp.float32) * k ** -0.5).astype(jnp.bfloat16)
    assert moe.stream_block(k, n, block_bytes=k * 128 * 2) == 128
    real = sum(sizes)
    for matrices in ((w1,), (w1, w2)):
        got = moe.stream_matmul(x, matrices, jnp.asarray(sizes, jnp.int32), interpret=True,
                                row_tile=row_tile, block_bytes=k * 128 * 2)
        assert got.shape == (rows, n) and got.dtype == jnp.float32
        want = grouped_dense(x, matrices, np.asarray(sizes))
        assert np.isfinite(np.asarray(got)).all()
        assert np.abs(np.asarray(got)[:real] - want).max(initial=0.0) < 2e-3


@pytest.mark.parametrize("real", [300, 200, 100])
def test_a_call_of_more_rows_than_a_segment_is_cut_by_rows(monkeypatch, real):
    """300 sorted rows in segments of 128: a group that straddles a cut
    is computed in both segments, part by part; with 200 real rows the
    third segment holds rows past the groups only, with 100 the second
    too, and runs nothing."""
    monkeypatch.setattr(moe, "STREAM_SEGMENT_BYTES", 128 * 4 * D)
    assert moe.stream_segment_rows(D) == 128
    _w_router, w_gate, w_up, w_down = weights(13, jnp.bfloat16)
    rng = np.random.default_rng(real)
    sizes = rng.multinomial(real, [0.3, 0.0, 0.1, 0.25, 0.0, 0.05, 0.2, 0.1])
    x = jax.random.normal(jax.random.key(real), (300, D), jnp.float32).astype(jnp.bfloat16)
    got = jax.jit(lambda *a: moe.stream_swiglu(*a, interpret=True))(
        x, w_gate, w_up, w_down, jnp.asarray(sizes, jnp.int32))
    assert got.shape == (300, D) and got.dtype == jnp.float32
    act = grouped_dense(x, (w_gate, w_up), sizes)
    want = grouped_dense(jnp.asarray(act).astype(jnp.bfloat16), (w_down,), sizes)
    assert np.abs(np.asarray(got)[:real] - want).max() < 0.02
    if real <= 256:     # a segment of no group's rows comes back as zeros
        assert not np.asarray(got)[256:].any()


# ---------------------------------------------------------------------------
# the tiled kernel by itself: the lane at and over the ridge
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("row_block,row_tile", [(64, 32), (64, 16), (32, 32), (160, 64)])
@pytest.mark.parametrize("sizes", [
    (5, 0, 7, 1, 0, 0, 19, 3),        # empty groups, each smaller than a tile
    (40, 0, 37, 1, 0, 0, 50, 22),     # groups that straddle a row block's edge
    (0, 0, 0, 150, 0, 0, 0, 0),       # every row on one group: every block
    (1, 1, 1, 1, 1, 1, 1, 1),
    (0, 0, 70, 4, 0, 0, 0, 0),        # rows past the groups: 76 of 150
    (64, 0, 0, 0, 64, 0, 0, 22),      # groups that end where a block does
    (0, 0, 0, 0, 0, 0, 0, 0),         # no group hit: nothing to compute
])
def test_the_tiled_kernel_against_each_rows_own_group(sizes, row_block, row_tile):
    """``tiled_matmul`` with one matrix a group and with two (gate and
    up, ``silu(gate) * up`` in the kernel), in ``(K, 128)`` blocks of a
    256-wide matrix (two grid columns), 150 rows — no multiple of a row
    block or of a tile, so the last block is partial: every row before
    the groups' end is its own group's, whatever lies past it."""
    k, n, rows = 32, 256, 150
    ks = jax.random.split(jax.random.key(sum(sizes) + row_tile), 3)
    x = jax.random.normal(ks[0], (rows, k), jnp.float32).astype(jnp.bfloat16)
    w1 = (jax.random.normal(ks[1], (E, k, n), jnp.float32) * k ** -0.5).astype(jnp.bfloat16)
    w2 = (jax.random.normal(ks[2], (E, k, n), jnp.float32) * k ** -0.5).astype(jnp.bfloat16)
    real = sum(sizes)
    visits = moe.tiled_visits(jnp.asarray(sizes, jnp.int32), rows, row_block)
    for matrices in ((w1,), (w1, w2)):
        got = moe.tiled_matmul(x, matrices, visits, interpret=True, row_block=row_block,
                               row_tile=row_tile, block_bytes=k * 128 * 2)
        assert got.shape == (rows, n) and got.dtype == jnp.float32
        want = grouped_dense(x, matrices, np.asarray(sizes))
        assert np.abs(np.asarray(got)[:real] - want).max(initial=0.0) < 2e-3
    # the gate and up's result leaves in the matrices' type where asked
    hidden = moe.tiled_matmul(x, (w1, w2), visits, interpret=True, row_block=row_block,
                              row_tile=row_tile, out_dtype=jnp.bfloat16)
    assert hidden.dtype == jnp.bfloat16     # rounded once: half a unit in the last of 8 bits
    np.testing.assert_allclose(np.asarray(hidden.astype(jnp.float32))[:real], want,
                               rtol=2.0 ** -8, atol=2e-3)


@pytest.mark.parametrize("sizes,row_block,visits", [
    # one group a block; a group over three blocks; groups sharing one
    ((64, 64, 64), 64, [(0, 0), (1, 1), (2, 2)]),
    ((0, 150, 0), 64, [(1, 0), (1, 1), (1, 2)]),
    ((40, 0, 37, 1, 50, 22), 64, [(0, 0), (2, 0), (2, 1), (3, 1), (4, 1), (5, 2)]),
    ((40, 0, 37, 1, 51, 21), 64, [(0, 0), (2, 0), (2, 1), (3, 1), (4, 1), (4, 2), (5, 2)]),
    ((0, 0, 9), 64, [(2, 0)]),
    ((0, 0, 0), 64, []),
])
def test_a_visit_is_a_group_and_a_row_block_that_share_rows(sizes, row_block, visits):
    """The tiled kernel's grid: (group, block) pairs in ascending order,
    a group's matrices fetched once however many blocks it spans (the
    pairs of one group are adjacent), the list's tail repeating the last
    real visit so nothing more is fetched."""
    rows = 150
    group, block, starts, ends, count = (
        np.asarray(a) for a in moe.tiled_visits(jnp.asarray(sizes, jnp.int32), rows, row_block))
    most = -(-rows // row_block) + len(sizes) - 1
    assert group.shape == block.shape == (most,) and count.tolist() == [len(visits)]
    assert list(zip(group.tolist(), block.tolist()))[:len(visits)] == visits
    assert ends.tolist() == np.cumsum(sizes).tolist()
    assert (ends - starts).tolist() == list(sizes)
    if visits:
        assert set(zip(group[len(visits):].tolist(),
                       block[len(visits):].tolist())) <= {visits[-1]}
    assert ((0 <= block) & (block < -(-rows // row_block))).all()


@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("real", [700, 523, 0])
def test_the_tiled_swiglu_is_float32_ragged_dot_s_inside_the_streams_tolerance(real, act):
    """The whole lane as ``grouped_swiglu`` runs it over the ridge (the
    rule's own tiles: 700 rows are a partial second block of 512) against ``ragged_dot`` on the same bf16-rounded
    operands in float32: every real row inside the streaming kernel's
    tolerance, SwiGLU and ReGLU; with 523 real rows the rest lie past the
    groups and come back as whatever was there."""
    rows = 700
    _w_router, w_gate, w_up, w_down = weights(17, jnp.bfloat16)
    rng = np.random.default_rng(real)
    sizes = rng.multinomial(real, [0.3, 0.0, 0.1, 0.25, 0.0, 0.05, 0.2, 0.1])
    x = jax.random.normal(jax.random.key(real), (rows, D), jnp.float32).astype(jnp.bfloat16)
    assert moe.expert_matmul_impl(rows, 2, D, F, jnp.bfloat16, "interpret") == "tiled"
    got = moe.tiled_swiglu(x, w_gate, w_up, w_down, jnp.asarray(sizes, jnp.int32),
                           interpret=True, act=act)
    assert got.shape == (rows, D) and got.dtype == jnp.float32
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    want = moe.ragged_swiglu(f32(x), f32(w_gate), f32(w_up), f32(w_down),
                             jnp.asarray(sizes, jnp.int32), jnp.float32, act)
    assert np.abs(np.asarray(got)[:real] - np.asarray(want)[:real]).max(
        initial=0.0) < TOL["stream"]
    other = moe.ragged_swiglu(f32(x), f32(w_gate), f32(w_up), f32(w_down),
                              jnp.asarray(sizes, jnp.int32), jnp.float32,
                              "relu" if act == "silu" else "silu")
    if real:
        assert np.abs(np.asarray(got)[:real] - np.asarray(other)[:real]).max() > 0.05


def over_the_ridge_inputs(passes):
    """4 of 16 experts held, 1,024 tokens choosing 4, in bf16: a pass of
    1,536 rows, filled by one pass's local picks or two's;
    ``expert_ffn``'s arguments over all sixteen, the absent experts'
    gates at 0."""
    tokens, top_k, held, of, offset = 1024, 4, 4, 16, 4
    cap = moe.held_rows_cap(tokens, top_k, held, of)
    assert cap == 1536
    ks = jax.random.split(jax.random.key(60 + passes), 4)
    bf16 = jnp.bfloat16
    w_gate = (jax.random.normal(ks[0], (of, D, F), jnp.float32) * D ** -0.5).astype(bf16)
    w_up = (jax.random.normal(ks[1], (of, D, F), jnp.float32) * D ** -0.5).astype(bf16)
    w_down = (jax.random.normal(ks[2], (of, F, D), jnp.float32) * F ** -0.5).astype(bf16)
    h = jax.random.normal(ks[3], (tokens, D), jnp.float32).astype(bf16)
    rng = np.random.default_rng(passes)
    local_picks = {1: rng.choice([0, 1, 2], tokens, p=[0.3, 0.4, 0.3]),
                   2: rng.choice([2, 3], tokens)}[passes]
    inside, outside = np.arange(offset, offset + held), np.r_[0:offset, offset + held:of]
    experts = jnp.asarray(np.stack([
        np.r_[rng.choice(inside, n, replace=False),
              rng.choice(outside, top_k - n, replace=False)][rng.permutation(top_k)]
        for n in local_picks]).astype(np.int32))
    assert -(-int(local_picks.sum()) // cap) == passes
    gates = jnp.asarray(rng.uniform(0.05, 0.5, size=(tokens, top_k)), jnp.float32)
    local = (experts >= offset) & (experts < offset + held)
    return h, w_gate, w_up, w_down, jnp.where(local, gates, 0.0), experts


def held_ridge(h, w_gate, w_up, w_down, gates, experts):
    """Experts 4..7 of 16 on :func:`over_the_ridge_inputs`' arguments."""
    return moe.expert_ffn_held(h, w_gate[4:8], w_up[4:8], w_down[4:8], gates, experts, 4, 16)


@pytest.mark.parametrize("passes", [1, 2])
def test_held_experts_over_the_ridge_through_the_tiled_kernel(monkeypatch, passes):
    """``expert_ffn_held`` end to end where a pass is over the ridge, in
    bf16 under the interpreter: 4 of 16 experts held, 1,024 tokens
    choosing 4, a pass of 1,536 rows on the tiled kernel — against
    ``expert_ffn`` over all sixteen with the absent experts' gates at 0
    (16,384 / 16 = 256 rows an expert: the tiled kernel too)."""
    monkeypatch.setattr(moe, "matmul_backend", lambda: "interpret")
    bf16 = jnp.bfloat16
    assert moe.expert_matmul_impl(1536, 4, D, F, bf16, "interpret") == "tiled"
    args = over_the_ridge_inputs(passes)
    got = traced_anew(held_ridge)(*args)
    assert moe.expert_matmul_impl(1024 * 4, 16, D, F, bf16, "interpret") == "tiled"
    whole = jax.jit(moe.expert_ffn)(*args)
    want = dense(*args)
    assert got.shape == (1024, D) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got - want)).max() < TOL["tiled"]
    assert np.abs(np.asarray(whole - want)).max() < TOL["tiled"]
    assert np.abs(np.asarray(want)).max() > 0.1


@pytest.mark.parametrize("case", [f"loop-{c}" for c in HELD_LOOP_CASES]
                         + ["ridge-1", "ridge-2"])
def test_rows_past_the_groups_never_reach_a_token(monkeypatch, case):
    """A grouped matmul owes nothing past its last group (the tiled
    kernel never writes there): with every such row NaN the held pass is
    finite and the dense reference's, under the ridge (one pass with pad
    rows, a second pass, none) and over it (one pass, two).  An absent
    assignment is selected to zero, not multiplied to it."""
    monkeypatch.setattr(moe, "matmul_backend", lambda: "interpret")
    real, passes = moe.grouped_swiglu, []

    def poisoned(rows, w_gate, w_up, w_down, sizes, **kw):
        out = real(rows, w_gate, w_up, w_down, sizes, **kw)
        past = jnp.arange(out.shape[0]) >= sizes.sum()
        passes.append(out.shape)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(moe, "grouped_swiglu", poisoned)
    kind, which = case.split("-")
    if kind == "loop":
        args, want = held_loop_inputs(which, jnp.bfloat16)
        got, rows, tol = traced_anew(held_loop)(*args), 64, TOL["stream"]
    else:
        args = over_the_ridge_inputs(int(which))
        got, want, rows, tol = traced_anew(held_ridge)(*args), dense(*args), 1536, TOL["tiled"]
    assert passes == [(rows, D)]  # the pass the loop traced was the poisoned one
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got - want)).max() < tol


def eqns_of(fn, *args):
    """Every equation ``fn`` traces, loop and call bodies included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("regime", ["under_the_ridge", "over_the_ridge"])
def test_no_copy_of_a_pass_s_result_stands_before_the_gathers(monkeypatch, regime):
    """Between the grouped matmul and the ``top_k`` gathers nothing
    rewrites the pass's ``(rows, d_model)`` float32 result: no mask
    (``select_n``), no zero row under it (``concatenate`` / ``pad``: 235
    MB each way a layer at Xing4's 16,384 x 3,584).  The select is over
    the gathered ``(tokens, d_model)`` rows, once an assignment — the
    shapes here have ``tokens != rows`` so the two cannot be confused."""
    monkeypatch.setattr(moe, "matmul_backend", lambda: "interpret")
    if regime == "under_the_ridge":
        fn, (args, _want), tokens, top_k, rows = (
            held_loop, held_loop_inputs("a_second_pass", jnp.bfloat16), 40, 2, 64)
    else:
        fn, args, tokens, top_k, rows = held_ridge, over_the_ridge_inputs(1), 1024, 4, 1536
    shaped = [(eqn.primitive.name, tuple(out.aval.shape))
              for eqn in eqns_of(fn, *args) for out in eqn.outvars]
    assert (rows, D) in [shape for _name, shape in shaped]     # the pass is in there
    copies = [(name, shape) for name, shape in shaped
              if name in ("select_n", "concatenate", "pad")
              and shape in ((rows, D), (rows + 1, D))]
    assert copies == []
    assert shaped.count(("select_n", (tokens, D))) == top_k
    assert shaped.count(("gather", (tokens, D))) == top_k


def test_the_rule_is_a_function_of_shape_type_and_backend():
    bf16, f32 = jnp.bfloat16, jnp.float32
    rule = moe.expert_matmul_impl
    # OLMoE: a decode step's 32 lanes x top-8 over 64 experts of 2048 x 1024
    assert rule(256, 64, 2048, 1024, bf16, "tpu") == "stream"
    assert rule(256, 64, 2048, 1024, bf16, "interpret") == "stream"
    # GigaChat: a decode pass's 128 rows over 8 held experts of 7168 x 2048
    assert rule(moe.held_rows_cap(128, 8, 8, 256), 8, 7168, 2048, bf16, "tpu") == "stream"
    # ... and its prefill passes: a 1,024-token prompt's 128 rows an
    # expert stream, 256 and more (the ridge) take the tiled kernel
    # (at the ridge four even shares, which are the tokens; past it one
    # and a half: 8,192 tokens' 2,048 local assignments in 3,072 rows)
    for tokens, rows_, runs_ in ((1024, 1024, "stream"), (2048, 2048, "tiled"),
                                 (8192, 3072, "tiled")):
        rows = moe.held_rows_cap(tokens, 8, 8, 256)
        assert rows == rows_ and rule(rows, 8, 7168, 2048, bf16, "tpu") == runs_
    # OLMoE's prefill groups: 32 to 128 rows an expert stream, the
    # largest (512 x 4 prompts: 256 rows an expert) does not
    for rows in (2048, 4096, 8192):
        assert rule(rows, 64, 2048, 1024, bf16, "tpu") == "stream"
    assert rule(16384, 64, 2048, 1024, bf16, "tpu") == "tiled"
    assert rule(16384, 64, 2048, 1024, bf16, "interpret") == "tiled"
    # Xing4's two prefill programs hold 16,384 rows over 64 whole experts
    # of 3584 x 1024 (the ridge's own line), its decode chunk 2,048
    for tokens in (3072, 4096):
        assert moe.layer_expert_matmul(
            tokens, 4, 64, 64, 3584, 1024, bf16, held_pass=True, backend="tpu") == "tiled"
    assert moe.layer_expert_matmul(
        128, 4, 64, 64, 3584, 1024, bf16, held_pass=True, backend="tpu") == "stream"
    # its tiles: 128 rows at a time, in blocks of rows that fit the
    # pipeline beside a block of whole K
    assert moe.TILED_ROW_TILE == 128
    assert moe.tiled_row_block(16384, 3584, 1024) == 1024
    assert moe.tiled_row_block(2048, 7168, 2048) == 1024
    assert moe.tiled_row_block(2048, 16384, 2048) == 512        # 1,024 rows of d: 32 MiB
    assert moe.tiled_row_block(16384, 1024, 16384) == 512       # ... or of the hidden rows
    assert moe.tiled_row_block(300, 2048, 1024) == 256          # never over the rows
    assert moe.tiled_row_block(16384, 40960, 1024) == 0         # not 256 rows of whole K
    # widths neither kernel's VMEM rule takes stay on ragged_dot, on
    # both sides of the ridge
    assert rule(256, 64, 40960, 1024, bf16, "tpu") == "ragged_dot"
    assert rule(16384, 64, 40960, 1024, bf16, "tpu") == "ragged_dot"
    assert rule(16384, 64, 1024, 40960, bf16, "tpu") == "ragged_dot"
    assert [moe.stream_row_tile(r, 64) for r in (256, 2048, 8192)] == [16, 32, 128]
    assert moe.stream_segment_rows(2048) == 2048 and moe.stream_segment_rows(7168) == 512
    # the CPU exactness lanes, and every backend that is neither
    assert rule(256, 64, 2048, 1024, f32, "tpu") == "ragged_dot"
    assert rule(256, 64, 2048, 1024, bf16, "cpu") == "ragged_dot"
    assert rule(256, 64, 2048, 1024, bf16, "gpu") == "ragged_dot"
    # ... over the ridge as under it
    assert rule(16384, 64, 2048, 1024, f32, "tpu") == "ragged_dot"
    assert rule(16384, 64, 2048, 1024, bf16, "cpu") == "ragged_dot"
    assert rule(16384, 64, 2048, 1024, bf16, "gpu") == "ragged_dot"
    assert moe.matmul_backend() == "cpu"
    # the layer's rows are the rule's: every assignment, or one pass's
    assert moe.layer_expert_matmul(
        32, 8, 64, 64, 2048, 1024, bf16, held_pass=False, backend="tpu") == "stream"
    assert moe.layer_expert_matmul(
        2048, 8, 8, 256, 7168, 2048, bf16, held_pass=True, backend="tpu") == "tiled"
    assert moe.layer_expert_matmul(
        2048, 8, 8, 256, 7168, 2048, bf16, held_pass=True, backend="cpu") == "ragged_dot"
    # a block is whole K and the widest N that divides and fits
    assert moe.stream_block(2048, 1024) == 1024 and moe.stream_block(1024, 2048) == 2048
    assert moe.stream_block(7168, 2048) * 7168 * 2 <= moe.STREAM_BLOCK_BYTES
    assert 7168 % moe.stream_block(2048, 7168) == 0
    assert moe.stream_block(32, 16) == 16         # no multiple of 128: whole


def small_olmoe_engine(monkeypatch, backend):
    """A small OLMoE engine (8 experts top-2, two layers, 4 slots,
    buckets 16 / 32 / 64) whose grouped matmuls are told they run on
    ``backend``."""
    import os
    import sys

    from seldon_core_tpu.models.paged import PagedEngine
    from seldon_core_tpu.models.spec import init_params

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))
    from reference import olmoe as ref

    spec, sizes = ref.spec_and_config(dict(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_experts=8,
        num_experts_per_tok=2, intermediate_size=32, rms_norm_eps=1e-5,
        rope_theta=10000, vocab_size=97))
    monkeypatch.setattr(moe, "matmul_backend", lambda: backend)
    return PagedEngine(init_params(spec, sizes, 3, dtype=jnp.bfloat16), **sizes,
                       max_len=64, page_size=8, max_slots=4, steps_per_call=2,
                       dtype=jnp.bfloat16, spec=spec)


@pytest.mark.parametrize("backend", ["cpu", "interpret"])
def test_lane_report_says_what_the_programs_traced(monkeypatch, backend):
    """The small OLMoE engine: ``lane_report()["expert_matmul"]`` names
    every chunk and prefill program, and each program's trace asked the rule the same
    question and got the same answer."""
    # the rule's line drawn where this engine's programs straddle it
    monkeypatch.setattr(moe, "STREAM_MAX_MEAN_ROWS", 32)
    asked, rule = [], moe.expert_matmul_impl
    monkeypatch.setattr(moe, "expert_matmul_impl",
                        lambda *a: asked.append(rule(*a)) or asked[-1])
    eng = small_olmoe_engine(monkeypatch, backend)
    try:
        report = eng.lane_report()["expert_matmul"]
        assert sorted(report) == ["chunk"] + [
            f"prefill_b{b}_k{k}" for b in (16, 32, 64) for k in (1, 2, 4)]
        if backend == "cpu":
            assert set(report.values()) == {"ragged_dot"}
        else:   # under 32 rows an expert: 8 x 32 = 256 assignment rows
            assert {k for k, v in report.items() if v == "stream"} == {
                "chunk", "prefill_b16_k1", "prefill_b16_k2", "prefill_b16_k4",
                "prefill_b32_k1", "prefill_b32_k2", "prefill_b64_k1"}
            assert {k for k, v in report.items() if v == "tiled"} == {
                "prefill_b32_k4", "prefill_b64_k2", "prefill_b64_k4"}
        i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
        unwrap = lambda fn: fn if hasattr(fn, "lower") else fn.__wrapped__  # noqa: E731
        pools = eng._kv_args()
        for name in ("chunk", "prefill_b16_k2", "prefill_b64_k2"):
            del asked[:]
            if name == "chunk":
                eng.lower_chunk(2, ((4, 4),))
            else:
                bucket, k = int(name.split("_")[1][1:]), int(name.split("_")[2][1:])
                unwrap(eng._build_prefill(bucket, k)).lower(
                    eng.params, *pools, i32(k, bucket), i32(k), i32(k, bucket // 8))
            assert asked and set(asked) == {report[name]}, name
    finally:
        eng.close()


@pytest.mark.parametrize("backend", ["cpu", "interpret"])
def test_the_engine_counts_how_often_the_tiled_lane_engages(monkeypatch, backend):
    """The same small OLMoE engine with the line drawn at 16 rows an
    expert: a 40-token prompt's ``b64_k1`` program (128 assignment rows
    over 8 experts) is over it, a 10-token prompt's ``b16_k1`` under.
    ``prefill_expert_layer_calls`` counts both calls' routed layers at
    dispatch, ``prefill_expert_layer_calls_tiled`` the first's alone
    where the backend takes the kernels, none on the CPU."""
    monkeypatch.setattr(moe, "STREAM_MAX_MEAN_ROWS", 16)
    eng = small_olmoe_engine(monkeypatch, backend)
    try:
        report = eng.lane_report()["expert_matmul"]
        over = "tiled" if backend == "interpret" else "ragged_dot"
        assert report["prefill_b64_k1"] == over
        assert report["prefill_b16_k1"] == ("stream" if backend == "interpret"
                                            else "ragged_dot")
        stats = eng.engine_stats()
        assert stats["prefill_expert_layer_calls"] == 0
        assert stats["prefill_expert_layer_calls_tiled"] == 0
        rng = np.random.default_rng(5)
        for n in (40, 10):
            done = eng.submit(rng.integers(1, 97, n).astype(np.int32), max_new_tokens=2)
            eng.run()
            assert len(done.result) == 2
        stats = eng.engine_stats()
        assert stats["prefill_expert_layer_calls"] == 2 * 2
        assert stats["prefill_expert_layer_calls_tiled"] == (
            2 if backend == "interpret" else 0)
    finally:
        eng.close()
