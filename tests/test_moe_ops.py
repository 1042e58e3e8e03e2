"""ops/moe.py: the router and the grouped expert matmul against a dense
all-experts einsum (every expert applied to every row, then only the
routed ones kept): random routing, an expert that receives no row, one
that receives every row, a decode step's few rows and a prefill
group's many."""

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


def dense(h, w_gate, w_up, w_down, gates, experts):
    """Every expert on every row, in float32; then the routed pairs."""
    h, w_gate, w_up, w_down = (a.astype(jnp.float32) for a in (h, w_gate, w_up, w_down))
    with jax.default_matmul_precision("highest"):
        act = jax.nn.silu(jnp.einsum("td,edf->tef", h, w_gate)) * jnp.einsum(
            "td,edf->tef", h, w_up)
        out = jnp.einsum("tef,efd->ted", act, w_down)            # (T, E, D)
    picked = jnp.take_along_axis(out, experts[:, :, None], axis=1)  # (T, k, D)
    return (picked * gates[:, :, None]).sum(axis=1)


@pytest.mark.parametrize("rows,top_k", [(1, 2), (4, 2), (32, 2), (200, 3), (64, 8)])
def test_routed_rows_equal_the_dense_einsum(rows, top_k):
    w_router, w_gate, w_up, w_down = weights()
    h = jax.random.normal(jax.random.key(rows), (rows, D), jnp.float32)
    gates, experts = moe.route(h, w_router, top_k)
    got = moe.expert_ffn(h, w_gate, w_up, w_down, gates, experts)
    want = dense(h, w_gate, w_up, w_down, gates, experts)
    assert got.shape == (rows, D) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got - want)).max() < 1e-5


@pytest.mark.parametrize("case", ["an_expert_with_no_row", "one_expert_takes_all",
                                  "first_and_last_only"])
def test_uneven_groups(case):
    _w_router, w_gate, w_up, w_down = weights(1)
    rows, top_k = 24, 2
    h = jax.random.normal(jax.random.key(7), (rows, D), jnp.float32)
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
    assert np.abs(np.asarray(got - want)).max() < 1e-5
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


def test_bf16_rows_accumulate_in_float32():
    """bf16 operands, f32 accumulation: against the same bf16-rounded
    operands in float32 what is left is the rounding of each matmul's
    output, a few parts in a thousand of unit-spread values."""
    w_router, w_gate, w_up, w_down = weights(4, jnp.bfloat16)
    h = jax.random.normal(jax.random.key(5), (48, D), jnp.float32).astype(jnp.bfloat16)
    gates, experts = moe.route(h, w_router, 2)
    got = moe.expert_ffn(h, w_gate, w_up, w_down, gates, experts)
    want = dense(h, w_gate, w_up, w_down, gates, experts)
    assert got.dtype == jnp.float32
    assert np.abs(np.asarray(got - want)).max() < 0.02


def test_inside_a_scan_as_the_decode_chunk_runs_it():
    w_router, w_gate, w_up, w_down = weights(6)
    hs = jax.random.normal(jax.random.key(9), (3, 4, D), jnp.float32)

    def step(acc, h):
        gates, experts = moe.route(h, w_router, 2)
        out = moe.expert_ffn(h, w_gate, w_up, w_down, gates, experts)
        return acc + moe.expert_histogram(experts, E), out

    hist, outs = jax.jit(lambda x: jax.lax.scan(step, jnp.zeros((E,), jnp.int32), x))(hs)
    assert int(hist.sum()) == 3 * 4 * 2
    for i in range(3):
        gates, experts = moe.route(hs[i], w_router, 2)
        want = dense(hs[i], w_gate, w_up, w_down, gates, experts)
        assert np.abs(np.asarray(outs[i] - want)).max() < 1e-5
