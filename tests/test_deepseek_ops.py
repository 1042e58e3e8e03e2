"""DeepSeek-V3 off the module's engines (PR 30; a part of
``test_deepseek_paged.py`` until PR 44): the two attention paths, the
latent decode kernel under the interpreter, the router against the
source's loop, YaRN's closed form and the share of the experts a replica
holds (guide section 4); then the engine's front door — served streams
and their counters, eviction, the cap on a prefill call's padded
positions — and what it refuses by name (LongCat-Flash's latent pool by
the same cases), the published sizes and how the weights rest.  Every
engine here is a case's own: each counts from zero, is refused, or is
changed under the case.  Sizes as in that file."""

import math
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paged_harness as harness
from paged_harness import MAX_LEN, PAGE, PROMPT, SLOTS
from seldon_core_tpu.models.paged import PagedEngine, StreamingLM
from seldon_core_tpu.models.spec import DEEPSEEK_V3, init_params, model_spec, yarn_inv_freq
from seldon_core_tpu.ops import kernels, mla, moe

ref, MODEL = harness.MODELS["gigachat"]
SPEC, SIZES = harness.spec_and_sizes("gigachat")


def test_absorbed_attention_is_naive_attention():
    """One query over cached rows and its own: folding W_uk into q and
    W_uv into the output (a decode step) gives what making K and V per
    head (a prefill) gives."""
    rng = np.random.default_rng(1)
    b, c, h, n, r, rank, v, lanes = 3, 24, 4, 8, 4, 16, 12, 32
    rows = np.zeros((b, c + 1, lanes), np.float32)
    rows[..., :rank + r] = rng.normal(size=(b, c + 1, rank + r))
    rows = jnp.asarray(rows)
    lens = jnp.asarray([24, 7, 0], jnp.int32)
    q_nope = jnp.asarray(rng.normal(size=(b, 1, h, n)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(b, 1, h, r)), jnp.float32)
    w_uk = jnp.asarray(rng.normal(size=(h, rank, n)), jnp.float32)
    w_uv = jnp.asarray(rng.normal(size=(h, rank, v)), jnp.float32)
    scale = 0.21
    with jax.default_matmul_precision("highest"):
        naive = mla.naive_attention(q_nope, q_rope, rows[:, :c], lens, rows[:, c:],
                                    w_uk, w_uv, scale, jnp.float32)[:, 0]
        q_abs = jnp.einsum("bhn,hrn->bhr", q_nope[:, 0], w_uk)
        q_full = jnp.concatenate(
            [q_abs, q_rope[:, 0], jnp.zeros((b, h, lanes - rank - r))], -1) * scale
        valid = jnp.arange(c)[None] < lens[:, None]
        latent = mla.merge(
            mla.ctx_state(q_full, rows[:, :c], valid, rank),
            mla.ctx_state(q_full, rows[:, c:], jnp.ones((b, 1), bool), rank))
        absorbed = jnp.einsum("bhr,hrv->bhv", latent, w_uv)
    assert np.abs(np.asarray(naive - absorbed)).max() < 1e-5


def test_naive_attention_in_query_blocks_is_naive_attention(monkeypatch):
    rng = np.random.default_rng(2)
    b, c, length, h, n, r, rank, v = 2, 8, 16, 2, 4, 2, 8, 6
    args = [jnp.asarray(rng.normal(size=s), jnp.float32) for s in (
        (b, length, h, n), (b, length, h, r), (b, c, rank + r))]
    seg = jnp.asarray(rng.normal(size=(b, length, rank + r)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=s), jnp.float32)
         for s in ((h, rank, n), (h, rank, v))]
    lens = jnp.asarray([8, 3], jnp.int32)
    whole = mla.naive_attention(*args, lens, seg, *w, 0.3, jnp.float32)
    monkeypatch.setattr(mla, "QUERY_BLOCK", 4)
    blocked = mla.naive_attention(*args, lens, seg, *w, 0.3, jnp.float32)
    assert np.abs(np.asarray(whole - blocked)).max() < 1e-5


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 1e-5)])
@pytest.mark.parametrize("table_pages,step_tokens", [
    (1, 1024), (2, 16), (4, 16), (8, 16), (8, 24), (8, 1024)])
def test_latent_kernel_is_the_gather_and_einsums(monkeypatch, dtype, tol, table_pages,
                                                 step_tokens):
    """Interpret mode against ``ops/mla.py ctx_state`` on the gathered
    rows: ragged lengths, empty lanes, a full table, a lane longer than
    the table it was handed, every layer of the pool; the page loop two
    or three pages a step (several steps a lane, the last one partly
    blank) and the whole table in one step."""
    monkeypatch.setattr(kernels, "interpret_mode", lambda: True)
    monkeypatch.setattr(kernels, "LATENT_STEP_TOKENS", step_tokens)
    rng = np.random.default_rng(table_pages)
    layers, pages, ps, lanes_w, rank, h, lanes = 2, 64, 8, 128, 16, 4, 6
    pool = np.zeros((layers, pages, ps, lanes_w), np.float32)
    pool[..., :20] = rng.normal(size=(layers, pages, ps, 20))
    pool = jnp.asarray(pool, dtype)
    q = np.zeros((lanes, h, lanes_w), np.float32)
    q[..., :20] = rng.normal(size=(lanes, h, 20)) * 0.4
    q = jnp.asarray(q, dtype)
    tables = jnp.asarray(rng.permutation(np.arange(1, pages))[
        :lanes * table_pages].reshape(lanes, table_pages), jnp.int32)
    full = table_pages * ps
    lengths = jnp.asarray([0, 1, full, max(1, full - 3), 0, full + 9], jnp.int32)
    for layer in range(layers):
        acc, m, l = kernels.latent_attention_decode(
            q, pool, tables, lengths, layer=layer, page_size=ps, rank=rank)
        rows = pool[layer][tables].reshape(lanes, full, lanes_w)
        valid = jnp.arange(full)[None] < lengths[:, None]
        want_acc, want_m, want_l = mla.ctx_state(q, rows, valid, rank)
        assert np.array_equal(np.isfinite(m), np.isfinite(want_m))
        live = np.isfinite(np.asarray(m))
        assert np.abs(np.asarray(m - want_m)[live]).max() < tol
        # the two sum a page at a time and all at once: compare at a
        # common maximum
        assert np.abs(np.asarray(acc - want_acc)).max() < 5e-2 * (dtype == jnp.bfloat16) + 1e-4
        assert np.abs(np.asarray(l - want_l)).max() < 5e-2 * (dtype == jnp.bfloat16) + 1e-4
        assert float(jnp.abs(acc[0]).max()) == 0.0 and float(l[4].max()) == 0.0


def _route_loop(scores, bias, k, groups, keep, norm, scale):
    """HF's routing, one token and one comparison at a time."""
    out_w, out_e = [], []
    for s in scores:
        choice = s + bias
        size = len(s) // groups
        group_score = []
        for g in range(groups):
            best = sorted(choice[g * size:(g + 1) * size], reverse=True)[:2]
            group_score.append(np.float32(best[0]) + np.float32(best[1]))
        kept = sorted(range(groups), key=lambda g: (-group_score[g], g))[:keep]
        masked = [choice[e] if e // size in kept else np.float32(0.0)
                  for e in range(len(s))]
        chosen = sorted(range(len(s)), key=lambda e: (-masked[e], e))[:k]
        w = np.asarray([s[e] for e in chosen], np.float32)
        if norm:
            w = w / (w.sum() + np.float32(1e-20))
        out_w.append(w * np.float32(scale))
        out_e.append(chosen)
    return np.asarray(out_w), np.asarray(out_e)


def test_routing_is_the_source_s_loop_ties_and_bias_included():
    rng = np.random.default_rng(3)
    tokens, d, e, k, groups, keep = 40, 16, 16, 4, 4, 2
    h = rng.normal(size=(tokens, d)).astype(np.float32)
    w = rng.normal(size=(d, e)).astype(np.float32) * 0.3
    h[5] = 0.0   # every score 0.5: the bias alone decides, ties by index
    h[6] = 0.0
    bias = rng.uniform(-0.1, 0.1, size=e).astype(np.float32)
    bias[3] = bias[7] = bias[11] = 0.09  # equal best biases in three groups
    with jax.default_matmul_precision("highest"):
        scores = np.asarray(jax.nn.sigmoid(jnp.asarray(h) @ jnp.asarray(w)))
    gates, experts = moe.route_grouped(
        jnp.asarray(h), jnp.asarray(w), jnp.asarray(bias), k, groups, keep, True, 2.5)
    want_w, want_e = _route_loop(scores, bias, k, groups, keep, True, 2.5)
    assert np.array_equal(np.asarray(experts), want_e)
    assert np.abs(np.asarray(gates) - want_w).max() < 1e-6
    ref_w, ref_e = ref.route(dict(n_group=groups, topk_group=keep, num_experts_per_tok=k,
                                  norm_topk_prob=True, routed_scaling_factor=2.5),
                             scores, bias)
    assert np.array_equal(ref_e, want_e) and np.abs(ref_w - want_w).max() < 1e-6
    # the bias selects and never weighs: a token's gates are its chosen
    # experts' sigmoid scores over their sum
    picked = np.take_along_axis(scores, want_e, axis=-1)
    assert np.abs(np.asarray(gates) - 2.5 * picked / picked.sum(-1, keepdims=True)).max() < 1e-6
    assert np.abs(np.asarray(gates).sum(-1) - 2.5).max() < 1e-5
    # and it does select: without it other experts are chosen somewhere
    _g, plain = moe.route_grouped(jnp.asarray(h), jnp.asarray(w), jnp.zeros(e), k,
                                  groups, keep, True, 2.5)
    assert not np.array_equal(np.asarray(plain), want_e)
    # a chosen expert's group is one of the kept
    assert all(len({x // (e // groups) for x in row}) <= keep for row in want_e)


def _route_sorted(h, w_router, bias, top_k, n_group, topk_group, norm, scale):
    """``moe.route_grouped`` as it stood before PR 57, verbatim: a group's
    two largest by a sort of the group, the kept groups by a second
    ``top_k`` and a ``one_hot``, whatever ``n_group``."""
    with jax.named_scope(moe.ROUTER_SCOPE):
        logits = jnp.dot(
            h.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        scores = jax.nn.sigmoid(logits)                       # (T, E)
        choice = scores + bias.astype(jnp.float32)
        tokens, num_experts = choice.shape
        grouped = choice.reshape(tokens, n_group, num_experts // n_group)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)   # (T, G)
        _, kept = jax.lax.top_k(group_score, topk_group)          # (T, g)
        keep = jax.nn.one_hot(kept, n_group, dtype=jnp.int32).sum(axis=1) > 0
        choice = jnp.where(keep[:, :, None], grouped, 0.0).reshape(
            tokens, num_experts)
        _, experts = jax.lax.top_k(choice, top_k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
        if norm:
            gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
        gates = gates * scale
    return gates, experts.astype(jnp.int32)


def _two_best(grouped):
    """``(T, G, 2)``: a group's largest and second largest, by numpy."""
    return np.sort(grouped, axis=-1)[..., :-3:-1]


def _tied_logits(kind, rng, tokens, e, groups, keep):
    """``(logits (T, E), bias (E,))`` with ties of one kind in every row;
    the router's weight is the identity, so a token's logits are its
    ``h`` and equal logits are equal scores."""
    size = e // groups
    logits = rng.normal(size=(tokens, e)).astype(np.float32)
    bias = np.zeros(e, np.float32)
    if kind == "group_pair":        # a group's two largest are equal
        grouped = logits.reshape(tokens, groups, size)
        order = np.argsort(grouped, axis=-1)
        best = np.take_along_axis(grouped, order[..., -1:], axis=-1)
        np.put_along_axis(grouped, order[..., -2:-1], best, axis=-1)
        top = _two_best(grouped)
        assert np.array_equal(top[..., 0], top[..., 1])
    elif kind == "boundary_groups":  # the last kept and the first dropped group tie
        grouped = logits.reshape(tokens, groups, size)
        rank = np.argsort(-_two_best(grouped).sum(-1), axis=-1, kind="stable")
        if keep < groups:
            rows = np.arange(tokens)
            grouped[rows, rank[:, keep]] = grouped[rows, rank[:, keep - 1]]
            score = _two_best(grouped).sum(-1)
            assert np.array_equal(score[rows, rank[:, keep]], score[rows, rank[:, keep - 1]])
    elif kind == "kth_expert":      # five levels: the k-th and the next are equal
        logits = rng.integers(-2, 3, size=(tokens, e)).astype(np.float32) * 0.5
    elif kind == "constant_row":    # every score 0.5: the index alone decides
        logits[:] = 0.0
    elif kind == "constant_row_biased":  # ... and a bias of few levels does
        logits[:] = 0.0
        bias = np.round(rng.uniform(-0.2, 0.2, size=e), 1).astype(np.float32)
    elif kind == "negative_bias":   # every selection score under the masked groups' 0.0
        bias = rng.uniform(-2.0, -1.0, size=e).astype(np.float32)
    elif kind == "two_decimals":    # hundreds of ties a row, of every kind at once
        logits = np.round(logits, 2)
        bias = np.round(rng.uniform(-0.6, 0.1, size=e), 1).astype(np.float32)
    else:
        raise AssertionError(kind)
    return logits, bias


ROUTER_SHAPES = [(512, 8, 4, 8), (256, 8, 4, 8), (256, 1, 1, 8), (64, 1, 1, 4), (16, 4, 2, 4)]
ROUTER_TIES = ["group_pair", "boundary_groups", "kth_expert", "constant_row",
               "constant_row_biased", "negative_bias", "two_decimals"]


@pytest.mark.parametrize("kind", ROUTER_TIES)
@pytest.mark.parametrize("e,groups,keep,k", ROUTER_SHAPES,
                         ids=["ling", "gigachat", "dots3", "xing4", "tiny"])
def test_the_group_step_by_maxima_is_the_sort_s_bit_for_bit(e, groups, keep, k, kind):
    """The gates' bits and the experts in their order, against the three
    ``top_k`` form, at each cell's router and on ties of each kind."""
    rng = np.random.default_rng(ROUTER_TIES.index(kind) * 7 + e)
    logits, bias = _tied_logits(kind, rng, 96, e, groups, keep)
    args = (jnp.asarray(logits), jnp.eye(e, dtype=jnp.float32), jnp.asarray(bias))
    static = (k, groups, keep, True, 2.5)
    want_w, want_e = jax.jit(lambda *a: _route_sorted(*a, *static))(*args)
    got_w, got_e = jax.jit(lambda *a: moe.route_grouped(*a, *static))(*args)
    assert got_e.dtype == jnp.int32 and got_w.dtype == jnp.float32
    assert np.array_equal(np.asarray(got_e), np.asarray(want_e))
    assert np.array_equal(np.asarray(got_w).view(np.int32), np.asarray(want_w).view(np.int32))
    # the identity weight kept the ties: a token's scores are its logits'
    scores = np.asarray(jax.nn.sigmoid(jnp.asarray(logits)))
    picked = np.take_along_axis(scores, np.asarray(got_e), axis=-1)
    assert np.abs(np.asarray(got_w) - 2.5 * picked / picked.sum(-1, keepdims=True)).max() < 1e-6
    if kind == "negative_bias" and keep < groups:
        # HF masks with 0.0, not -inf: under such a bias a dropped group's
        # 0.0 outranks every kept expert, and the picks are dropped groups'
        choice = (scores + bias).reshape(len(scores), groups, e // groups)
        dropped = np.argsort(-_two_best(choice).sum(-1), axis=-1, kind="stable")[:, keep:]
        assert all(x // (e // groups) in row for row, xs in zip(dropped, np.asarray(got_e))
                   for x in xs)


def test_yarn_frequencies_and_scale_are_the_closed_form():
    spec = DEEPSEEK_V3
    dim, base, factor, orig = 64, 100_000.0, 64.0, 4096
    low = math.floor(dim * math.log(orig / (32 * 2 * math.pi)) / (2 * math.log(base)))
    high = math.ceil(dim * math.log(orig / (1 * 2 * math.pi)) / (2 * math.log(base)))
    assert (low, high) == (8, 19)
    got = yarn_inv_freq(spec)
    for i in range(dim // 2):
        plain = base ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = plain * (1 - ramp) + plain / factor * ramp
        assert abs(got[i] - want) <= 1e-6 * want
    assert got[0] == 1.0 and abs(got[31] * factor - base ** (-62 / 64)) < 1e-9
    m = 0.1 * math.log(64.0) + 1.0
    assert abs(m - 1.4159) < 1e-4
    assert abs(spec.softmax_scale - 192 ** -0.5 * m * m) < 1e-12
    assert np.allclose(ref.inv_freq(dict(
        qk_rope_head_dim=64, rope_theta=100000, rope_scaling=dict(
            factor=64, original_max_position_embeddings=4096, beta_fast=32,
            beta_slow=1))), got, rtol=1e-6)
    assert abs(ref.softmax_scale(MODEL) - SPEC.softmax_scale) < 1e-12
    # factor 1 is plain RoPE
    plain = yarn_inv_freq(replace(spec, rope_factor=1.0))
    assert np.allclose(plain, base ** (-np.arange(0, dim, 2) / dim))


def _share_model(held, offset):
    return dict(MODEL, n_routed_experts=held, expert_offset=offset)


def test_the_shares_add_up_to_the_uncut_layer():
    """The parts all shares give, what every replica computes alike (the
    shared expert) counted once, add up to the uncut layer — in the
    program's layer and in the reference's, which agree share by share."""
    rng = np.random.default_rng(4)
    d, e, f, k = 64, 8, 32, 2
    uncut = init_params(model_spec(
        "deepseek_v3", **{**_spec_sizes(), "experts_held": 0}), SIZES, 9,
        dtype=jnp.float32)["block_1"]
    h = jnp.asarray(rng.normal(size=(50, d)), jnp.float32)
    gates, experts = moe.route_grouped(
        h, uncut["router"], uncut["score_bias"], k, 4, 2, True, 2.5)
    with jax.default_matmul_precision("highest"):
        whole = moe.expert_ffn(h, uncut["experts_gate"], uncut["experts_up"],
                               uncut["experts_down"], gates, experts)
        shared = moe.swiglu(h, uncut["shared_gate"], uncut["shared_up"],
                            uncut["shared_down"])
        parts = []
        for offset in range(0, e, 2):  # four replicas of two experts each
            sl = slice(offset, offset + 2)
            parts.append(moe.expert_ffn_held(
                h, uncut["experts_gate"][sl], uncut["experts_up"][sl],
                uncut["experts_down"][sl], gates, experts, offset, e))
    assert np.abs(np.asarray(sum(parts) - whole)).max() < 1e-4
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    # an assignment to an absent expert adds nothing: a replica whose
    # experts nobody chose returns zeros, whatever it holds
    nobody = jnp.full_like(experts, 7)
    assert float(jnp.abs(moe.expert_ffn_held(
        h, uncut["experts_gate"][:2], uncut["experts_up"][:2],
        uncut["experts_down"][:2], gates, nobody, 0, e)).max()) == 0.0

    # the same through the reference's whole forward pass of one layer:
    # logits are not additive, the layer's output before the residual is
    def ffn_part(model, block):
        weights, chosen = ref.route(model, np.asarray(jax.nn.sigmoid(
            h @ block["router"])), block["score_bias"])
        y = jnp.zeros_like(h)
        for i in range(model["n_routed_experts"]):
            rows, slot = np.nonzero(chosen == i + model["expert_offset"])
            if rows.size:
                out = (jax.nn.silu(h[rows] @ block["experts_gate"][i])
                       * (h[rows] @ block["experts_up"][i])) @ block["experts_down"][i]
                y = y.at[rows].add(out * weights[rows, slot][:, None])
        return y

    with jax.default_matmul_precision("highest"):
        ref_parts = []
        for offset in range(0, e, 2):
            sl = slice(offset, offset + 2)
            block = {**uncut, **{n: uncut[n][sl] for n in
                                 ("experts_gate", "experts_up", "experts_down")}}
            ref_parts.append(ffn_part(_share_model(2, offset), block))
        ref_whole = ffn_part(_share_model(8, 0), uncut)
    assert np.abs(np.asarray(sum(ref_parts) - ref_whole)).max() < 1e-4
    assert np.abs(np.asarray(ref_whole - whole)).max() < 1e-4
    for mine, theirs in zip(parts, ref_parts):
        assert np.abs(np.asarray(mine - theirs)).max() < 1e-4
    assert float(jnp.abs(shared).max()) > 0  # counted once, beside the sum


def _spec_sizes():
    return dict(
        num_experts=8, experts_per_tok=2, expert_width=32, dense_layers=1,
        dense_width=96, shared_experts=1, n_group=4, topk_group=2, q_rank=24,
        kv_rank=16, nope_dim=8, rope_dim=4, v_dim=12, rope_orig_len=16)


def test_more_local_assignments_than_a_pass_holds_are_all_computed():
    """Every token sends both its experts here: 100 assignments against
    a pass of 64 rows; the while loop runs twice and drops none."""
    rng = np.random.default_rng(6)
    d, f, tokens = 16, 8, 50
    h = jnp.asarray(rng.normal(size=(tokens, d)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=s), jnp.float32)
         for s in ((4, d, f), (4, d, f), (4, f, d))]
    experts = jnp.asarray(rng.integers(0, 4, size=(tokens, 2)), jnp.int32)
    gates = jnp.asarray(rng.uniform(size=(tokens, 2)), jnp.float32)
    # four experts of 32 held: an even router sends 12.5 of the 100 here
    assert moe.held_rows_cap(tokens, 2, 4, 32) == 64 < tokens * 2
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: moe.expert_ffn_held(*a, 0, 32))(h, *w, gates, experts)
        want = moe.expert_ffn(h, *w, gates, experts)
    assert np.abs(np.asarray(got - want)).max() < 1e-4


# ---- the front door, the fences, the sizes, the cap ----

_engines, own_engine = harness.fixtures(SPEC, SIZES)


def test_engine_serves_and_counts_routing_and_latent_rows(own_engine):
    """Through submit/step: greedy tokens equal the reference's
    teacher-forced argmax (f32), a repeat is admitted on the prefix
    cache and answers the same, and the counters add up — from zero: an
    engine of its own (the only one of four steps a call on this lane)."""
    eng, params = own_engine("kernel", steps_per_call=4)
    first = eng.submit(np.asarray(PROMPT, np.int32), max_new_tokens=8)
    eng.run()
    toks = [int(t) for t in first.result]
    routing = []
    # (the chunk runs the last token forward too, for logits nobody
    # asked for yet: its routing is counted with the rest)
    want = np.asarray(ref.logits(params, MODEL, PROMPT + toks,
                                 routing=routing))[len(PROMPT) - 1:-1]
    assert toks == want.argmax(-1).tolist()
    stats = eng.engine_stats(detail=True)
    expert_layers, k = 2, MODEL["num_experts_per_tok"]
    n = len(PROMPT) + 8
    # every prompt token and every fed-back token, top-k each, per
    # EXPERT layer; of them those that fell to experts 2..5
    assert stats["moe_assignments"] == n * k * expert_layers
    assert sum(stats["moe_expert_hits"]) == stats["moe_assignments"]
    local = sum(int(((c >= 2) & (c < 6)).sum()) for c in routing)
    assert stats["moe_local_assignments"] == local
    assert 0 < local < stats["moe_assignments"]
    # the prompt's one prefill call: a held pass an expert layer at
    # its bucket's rows, for the prompt's own local assignments
    bucket = next(b for b in eng.prompt_buckets if b >= len(PROMPT))
    assert stats["prefill_held_rows"] == expert_layers * moe.held_rows_cap(
        bucket, k, 4, MODEL["n_routed_experts_published"])
    assert stats["prefill_held_local"] == sum(
        int(((c[:len(PROMPT)] >= 2) & (c[:len(PROMPT)] < 6)).sum())
        for c in routing)
    assert stats["prefill_held_extra_passes"] == 0
    # one lane decoding: k experts hit per (expert layer, step), and
    # of the held ones those the reference chose there
    assert stats["moe_layer_steps"] == 8 * expert_layers
    assert stats["moe_active_expert_steps"] == 8 * expert_layers * k
    held_hits = sum(int(((c[len(PROMPT):] >= 2) & (c[len(PROMPT):] < 6)).sum())
                    for c in routing)
    assert stats["moe_held_active_expert_steps"] == held_hits
    assert stats["moe_load_max"] >= stats["moe_load_mean"] > 0
    # step t of the lane read the 29 + t rows cached before it, in
    # each of the three layers
    assert stats["decode_kv_tokens"] == sum(len(PROMPT) + t for t in range(8))
    assert stats["latent_kv_tokens"] == 3 * stats["decode_kv_tokens"]
    report = eng.lane_report()
    assert (report["arch"], report["attention"], report["cache_width"],
            report["experts_held"]) == ("deepseek_v3", "mla", 128, 4)
    assert report["pool_shard_bytes"] == eng.cache.pages_k.nbytes

    again = eng.submit(np.asarray(PROMPT, np.int32), max_new_tokens=8)
    eng.run()
    assert [int(t) for t in again.result] == toks
    assert eng.engine_stats()["prefix_hits"] == 1


def test_stream_survives_evict_and_restore(own_engine):
    """(An engine of its own: it counts evictions from zero.)"""
    eng, _params = own_engine("gather", steps_per_call=2)
    stream = eng.submit(np.asarray(PROMPT[::-1], np.int32), max_new_tokens=10)
    eng.step()
    eng.step()
    with eng._lock:
        eng._evict_locked(stream)
    eng.run()
    fresh = eng.submit(np.asarray(PROMPT[::-1], np.int32), max_new_tokens=10)
    eng.run()
    assert eng.engine_stats()["evictions"] == 1
    assert stream.result.tolist() == fresh.result.tolist()


@pytest.mark.parametrize("arch", ["deepseek_v3", "longcat_flash", "xing4_0"])
class TestFences:
    def _make(self, arch, **kw):
        # (the small LongCat-Flash of PR 32 and the small Xing4.0 of PR
        # 45: the other latent pools, which what refuses one refuses by
        # the same cases)
        spec, sizes = harness.spec_and_sizes(
            {"deepseek_v3": "gigachat", "longcat_flash": "longcat",
             "xing4_0": "xing4"}[arch])
        return PagedEngine(
            init_params(spec, sizes, 0, dtype=jnp.float32), **sizes,
            max_len=MAX_LEN, page_size=PAGE, max_slots=SLOTS, spec=spec, **kw)

    def test_int8_kv(self, monkeypatch, arch):
        monkeypatch.setenv("SELDON_TPU_KV_DTYPE", "int8")
        with pytest.raises(ValueError, match=f"{arch}.*latent row.*int8 KV pool"):
            self._make(arch)

    def test_adapters(self, arch):
        with pytest.raises(ValueError, match=f"{arch}.*latent row.*adapters"):
            self._make(arch, max_adapters=2)

    @pytest.mark.parametrize("kw", [{"tp": 2}, {"dp": 2}])
    def test_a_mesh(self, kw, arch):
        with pytest.raises(ValueError, match=f"{arch}.*one chip"):
            self._make(arch, **kw)

    @pytest.mark.parametrize("kw", [{"quantize": "int8"}, {"precision": "w8a8"}])
    def test_int8_weights(self, kw, arch):
        with pytest.raises(ValueError, match=f"{arch}.*expert matrices"):
            self._make(arch, **kw)

    def test_the_speculative_lane(self, arch):
        with pytest.raises(ValueError, match=f"{arch}.*latent row.*speculative lane"):
            self._make(arch, speculative={"draft": "ngram"})

    def test_the_kv_tier(self, monkeypatch, arch):
        monkeypatch.setenv("SELDON_TPU_KV_OFFLOAD", "1")
        with pytest.raises(ValueError, match=f"{arch}.*latent row.*host KV tier"):
            self._make(arch)

    def test_the_ring_chunk(self, monkeypatch, arch):
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "ring")
        with pytest.raises(ValueError, match=f"{arch}.*latent row.*ring chunk"):
            self._make(arch)

    def test_disaggregated_prefill_and_migration(self, monkeypatch, arch):
        monkeypatch.delenv("SELDON_TPU_CHUNK_IMPL", raising=False)
        eng = self._make(arch)
        try:
            with pytest.raises(ValueError, match="latent row.*prefill export"):
                eng.prefill_export(np.asarray(PROMPT, np.int32))
            with pytest.raises(ValueError, match="latent row.*prefill import"):
                eng.submit_prefilled({"prompt": PROMPT})
            with pytest.raises(ValueError, match="latent row.*migration import"):
                eng.migrate_import({"prompt": PROMPT})
            # its streams are the drain journal's, as a speculative engine's
            stream = eng.submit(np.asarray(PROMPT, np.int32), max_new_tokens=30)
            eng.step()
            assert eng.migrate_export() == []
            assert [e["prompt"] for e in eng.drain()] == [PROMPT]
            assert stream.error is not None
        finally:
            eng.close()

    def test_sizes_an_arch_does_not_have(self, arch):
        with pytest.raises(ValueError, match="has no"):
            model_spec("olmoe", kv_rank=16)
        with pytest.raises(ValueError, match="has no"):
            model_spec(arch, **({"n_group": 4} if arch == "longcat_flash"
                                else {"zero_experts": 4}))
        with pytest.raises(ValueError, match="experts_held"):
            model_spec("deepseek_v3", experts_held=8, expert_offset=250)
        with pytest.raises(ValueError, match="groups"):
            model_spec("deepseek_v3", n_group=7)


def test_the_published_sizes_are_the_defaults_and_streaminglm_takes_a_share():
    spec = model_spec("deepseek_v3")
    assert spec is DEEPSEEK_V3
    assert (spec.num_experts, spec.experts_per_tok, spec.expert_width, spec.n_group,
            spec.topk_group, spec.dense_layers, spec.dense_width) == (
                256, 8, 2048, 8, 4, 3, 18432)
    assert (spec.q_rank, spec.kv_rank, spec.nope_dim, spec.rope_dim, spec.v_dim) == (
        1536, 512, 128, 64, 192)
    assert spec.cache_values == 576 and spec.cache_width(7168) == 640
    assert spec.cache_pools == 1 and spec.held == 256
    lm = StreamingLM(arch="deepseek_v3",
                     arch_sizes='{"experts_held": 8, "dense_layers": 1}')
    assert (lm.spec.held, lm.spec.expert_offset, lm.spec.dense_layers) == (8, 0, 1)
    assert lm.spec.layer_routed(1) and not lm.spec.layer_routed(0)
    assert lm.spec.num_experts == 256  # the router keeps its width


def test_weights_rest_as_the_spec_says():
    params = init_params(SPEC, SIZES, 1)
    dense, routed = params["block_0"], params["block_1"]
    assert {"mlp_gate", "mlp_up", "mlp_down"} <= set(dense)
    assert not {"router", "experts_gate", "shared_gate"} & set(dense)
    assert routed["experts_gate"].shape == (4, 64, 32)      # held, not 8
    assert routed["router"].shape == (64, 8)                # the router's width
    assert routed["kv_b_k"].shape == (4, 16, 8) and routed["kv_b_v"].shape == (4, 16, 12)
    assert routed["kv_a"]["kernel"].shape == (64, 20)
    assert routed["attn_proj"]["kernel"].shape == (48, 64)
    bias = np.asarray(routed["score_bias"])
    assert bias.dtype == np.float32 and 0 < np.abs(bias).max() <= 0.1
    f32 = {k for k, v in routed.items()
           if jax.tree_util.tree_leaves(v)[0].dtype == jnp.float32}
    assert f32 == {"attn_norm", "ffn_norm", "q_a_norm", "kv_a_norm", "router",
                   "score_bias"}


@pytest.mark.parametrize("held, experts", [(4, 8), (8, 256)])
def test_a_share_s_correction_bias_is_ordered_by_the_seed_not_redrawn(held, experts):
    """Every replica's block of every seed holds the same values within
    +-0.1: a seed that drew the held experts' biases low would give the
    chip less to stream and the benchmark a faster run (PR 30)."""
    from seldon_core_tpu.models.spec import share_bias

    grid = 0.1 * (2 * (np.arange(held) + 0.5) / held - 1)
    drawn = [np.asarray(share_bias(jax.random.key(seed), experts, held, 0.1))
             for seed in (1, 2, 3_000_004_157)]
    for bias in drawn:
        assert bias.shape == (experts,) and bias.dtype == np.float32
        assert np.allclose(np.sort(bias.reshape(-1, held), axis=1), grid, atol=1e-7)
        assert 0 < np.abs(bias).max() <= 0.1 and abs(float(bias[:held].sum())) < 1e-6
    assert not np.array_equal(drawn[0], drawn[1])          # the seed orders them
    if experts > held:                                      # and each block apart
        assert len({tuple(b) for b in drawn[0].reshape(-1, held)}) > 1
    # it is what a share's tree holds; a replica that holds every expert keeps the plain draw
    if (held, experts) == (4, 8):
        for seed in (1, 2):
            got = np.asarray(init_params(SPEC, SIZES, seed)["block_1"]["score_bias"])
            assert np.allclose(np.sort(got.reshape(-1, held), axis=1), grid, atol=1e-7)
        whole = np.asarray(init_params(
            model_spec("deepseek_v3", **{**_spec_sizes(), "experts_held": 0}), SIZES, 1
        )["block_1"]["score_bias"])
        assert not np.allclose(np.sort(whole), np.sort(np.tile(grid, 2)), atol=1e-4)


@pytest.mark.parametrize("name, arch, sizes, d, vocab, heads, resting, pool, want", [
    # one v5e chip (15.75 GiB), each served configuration's weights as they rest
    # and its pool (PERF.md section 4).  GPT-2's tree rests cast to bf16 since
    # PR 37 and nothing is added for a program's own copy; until then a float32
    # tree and its cast inside a running program were both counted
    ("gpt2-large", "gpt2", {}, 1280, 50257, 20, 1.68e9, 6.05e9, 16384),
    ("gpt2-large, float32 at rest + a program's cast", "gpt2", {}, 1280, 50257, 20,
     3.35e9 + 1.68e9, 6.05e9, 8192),
    ("olmoe-1b-7b", "olmoe", {}, 2048, 50304, 16, 7.13e9, 2.15e9, 8192),
    ("gigachat3.1 at one chip of 32", "deepseek_v3", {"experts_held": 8, "dense_layers": 1},
     7168, 16032, 64, 6.84e9, 4.03e9, 8192),
    # twice the chip takes twice the call; a model twice as wide takes half
    ("gigachat3.1 on 32 GiB", "deepseek_v3", {"experts_held": 8, "dense_layers": 1},
     7168, 16032, 64, 6.84e9 - 15.75 * 2**30, 4.03e9, 32768),
    ("gpt2 twice as wide", "gpt2", {}, 2560, 100514, 40, 3.35e9 + 1.68e9, 6.05e9, 4096),
    ("gpt2 twice as wide, cast", "gpt2", {}, 2560, 100514, 40, 1.68e9, 6.05e9, 8192),
])
def test_the_prefill_cap_follows_the_widths_and_the_hbm_left(name, arch, sizes, d, vocab,
                                                             heads, resting, pool, want):
    from seldon_core_tpu.models import paged

    per_position = paged.prefill_position_bytes(model_spec(arch, **sizes), d, vocab, heads)
    free = int(15.75 * 2**30 - resting - pool)
    assert paged.prefill_positions_max(free, per_position) == want, (name, per_position)
    assert paged.prefill_positions_max(None, per_position) is None
    assert paged.prefill_positions_max(0, per_position) == 1


def test_the_prefill_cap_s_count_is_within_a_third_of_the_compiler_s():
    """8,192 positions of GigaChat3.1 (``b2048_k4``): 2.8 GiB of
    temporaries by the chip compiler's count (PERF.md section 4)."""
    from seldon_core_tpu.models import paged

    spec = model_spec("deepseek_v3", experts_held=8, dense_layers=1)
    counted = 8192 * paged.prefill_position_bytes(spec, 7168, 16032, 64)
    assert 2 / 3 < counted / (2.8 * 2**30) < 4 / 3


def test_a_prefill_call_s_padded_positions_are_capped(own_engine):
    """Admission groups only merge, so a burst used to be ONE call: 16
    prompts of 2,048 needed 20.42 GB on the chip and failed all 16 (PR
    30).  A group past ``prefill_positions_max`` is served as several
    calls in the same wave, and answers as it would alone."""
    from seldon_core_tpu.models import paged

    assert [paged.prefill_group_max(b, 8192) for b in (256, 512, 1024, 2048, 4096, 16384)] == [
        32, 16, 8, 4, 2, 1]
    assert paged.prefill_group_max(2048, None) > 1 << 20  # the CPU names no limit: no cap
    # (an engine of its own: its cap is changed under it)
    eng, _params = own_engine("gather", steps_per_call=4)
    assert eng.prefill_positions_max is None
    eng.prefill_positions_max = 64  # two prompts of 32
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 97, size=20 + i).tolist() for i in range(4)]
    alone = []
    for prompt in prompts[:2]:
        stream = eng.submit(np.asarray(prompt, np.int32), max_new_tokens=6)
        eng.run()
        alone.append(stream.result.tolist())
    before = eng.engine_stats()
    streams = [eng.submit(np.asarray(p[::-1] if i >= 2 else p, np.int32),
                          max_new_tokens=6) for i, p in enumerate(prompts)]
    eng.run()
    after = eng.engine_stats()
    # four joiners of bucket 32 in one wave (two of them on the prefix
    # cache's pages: the cached-suffix program), two a call at most
    assert after["prefills"] - before["prefills"] == 4
    assert after["prefill_chunks"] - before["prefill_chunks"] >= 2
    assert (after["prefill_padded_tokens"] - before["prefill_padded_tokens"]) <= 4 * 32
    assert [s.result.tolist() for s in streams[:2]] == alone
    assert all(s.error is None and len(s.result) == 6 for s in streams)
