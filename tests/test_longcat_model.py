"""LongCat-Flash off the module's engines (PR 32; a part of
``test_longcat_paged.py`` until PR 44): the wrong programs the float32
tolerance tells apart, served streams and their counters, eviction, the
router against the source's rule, the share, tokens of only identity
experts, the published sizes and how the weights rest.  Every engine
here is a case's own: each is traced under a patch, serves other
weights, or counts from zero.  Sizes as in that file."""

from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paged_harness as harness
from paged_harness import PROMPT, SLOTS, run_program
from seldon_core_tpu.models import paged
from seldon_core_tpu.models.paged import StreamingLM
from seldon_core_tpu.models.spec import LONGCAT_FLASH, init_params, model_spec
from seldon_core_tpu.ops import moe

ref, MODEL = harness.MODELS["longcat"]
SPEC, SIZES = harness.spec_and_sizes("longcat")
F32_ATOL = 1e-4  # test_longcat_paged.py argues it


_engines, own_engine = harness.fixtures(SPEC, SIZES)


def _reference(params, tokens, model=MODEL):
    return np.asarray(ref.logits(params, model, tokens))


def _bf16_router(h, w, bias, top_k, scale):
    return _ROUTE(h.astype(jnp.bfloat16), w.astype(jnp.bfloat16), bias, top_k, scale)


def _renormalised(h, w, bias, top_k, scale):
    gates, experts = _ROUTE(h, w, bias, top_k, scale)
    return gates / gates.sum(-1, keepdims=True) * scale, experts


def _unscaled(h, w, bias, top_k, scale):
    return _ROUTE(h, w, bias, top_k, 1.0)


def _no_bias(h, w, bias, top_k, scale):
    return _ROUTE(h, w, jnp.zeros_like(bias), top_k, scale)


def _bias_in_weights(h, w, bias, top_k, scale):
    gates, experts = _ROUTE(h, w, bias, top_k, scale)
    return gates + scale * bias[experts], experts


_ROUTE = moe.route_zero


@pytest.mark.parametrize("wrong", [
    "bf16_router", "renormalised", "unscaled", "no_bias", "bias_in_weights",
    "no_q_scale", "no_kv_scale", "no_identity_experts", "experts_after_second_half",
    "one_cache_row_a_layer"])
def test_the_tolerance_fails_a_wrong_program(monkeypatch, own_engine, wrong):
    """What the f32 tolerance is for: each of these computes something
    else than the source defines, and none stays inside it.  A program
    that is wrong is traced anew, on an engine of its own."""
    served = None
    routes = {"bf16_router": _bf16_router, "renormalised": _renormalised,
              "unscaled": _unscaled, "no_bias": _no_bias,
              "bias_in_weights": _bias_in_weights}
    truth = init_params(SPEC, SIZES, 3, dtype=jnp.float32)
    model = MODEL
    if wrong in routes:
        monkeypatch.setattr(moe, "route_zero", routes[wrong])
    elif wrong == "no_identity_experts":
        monkeypatch.setattr(moe, "identity_experts",
                            lambda h, *_a: jnp.zeros(h.shape, jnp.float32))
    elif wrong == "no_q_scale":
        monkeypatch.setattr(type(SPEC), "lora_scales",
                            lambda self, d: (1.0, (d / self.kv_rank) ** 0.5))
    elif wrong == "no_kv_scale":
        monkeypatch.setattr(type(SPEC), "lora_scales",
                            lambda self, d: ((d / self.q_rank) ** 0.5, 1.0))
    elif wrong == "experts_after_second_half":
        # the shortcut taken from the SECOND half's norm: the reference's
        # error this time (its router and norm swapped between halves)
        truth = {**truth, **{name: {**truth[name],
                                    "ffn_norm_0": truth[name]["ffn_norm_1"],
                                    "ffn_norm_1": truth[name]["ffn_norm_0"]}
                             for name in ("block_0", "block_1")}}
    elif wrong == "one_cache_row_a_layer":
        # both attentions of a layer reading the first one's row
        served = jax.tree.map(lambda x: x, truth)
        for name in ("block_0", "block_1"):
            for leaf in ("kv_a_1", "kv_a_norm_1"):
                served[name][leaf] = served[name][leaf.replace("_1", "_0")]
    eng, _served = own_engine("gather", params=served)
    rows, tokens, at = run_program(eng, "decode")
    want = _reference(truth, tokens, model)[at: at + len(rows)]
    assert np.abs(rows - want).max() > 10 * F32_ATOL


def test_engine_serves_and_counts_routing_and_latent_rows(own_engine):
    """Through submit/step: greedy tokens equal the reference's
    teacher-forced argmax (f32), a repeat is admitted on the prefix
    cache and answers the same, and the counters add up: per LAYER the
    histogram over every router output, per ATTENTION the cache rows —
    from zero: an engine of its own (the only one of four steps a call)."""
    eng, params = own_engine("kernel", steps_per_call=4)
    first = eng.submit(np.asarray(PROMPT, np.int32), max_new_tokens=8)
    eng.run()
    toks = [int(t) for t in first.result]
    routing = []
    want = np.asarray(ref.logits(params, MODEL, PROMPT + toks,
                                 routing=routing))[len(PROMPT) - 1:-1]
    assert toks == want.argmax(-1).tolist()
    stats = eng.engine_stats(detail=True)
    layers, k, real = 2, MODEL["moe_topk"], 8
    n = len(PROMPT) + 8
    assert len(routing) == layers
    assert stats["moe_assignments"] == n * k * layers
    assert len(stats["moe_expert_hits"]) == 12                 # 8 real + 4 identity
    assert sum(stats["moe_expert_hits"]) == stats["moe_assignments"]
    assert np.asarray(stats["moe_layer_expert_hits"]).shape == (layers, 12)
    for layer, chosen in enumerate(routing):
        assert stats["moe_layer_expert_hits"][layer] == np.bincount(
            chosen.reshape(-1), minlength=12).tolist()
    zero = sum(int((c >= real).sum()) for c in routing)
    assert stats["moe_zero_assignments"] == zero
    assert 0 < zero < stats["moe_assignments"]
    local = sum(int(((c >= 2) & (c < 6)).sum()) for c in routing)
    assert stats["moe_local_assignments"] == local
    # tokens by their number of real picks: compute per token varies
    by_real = np.bincount(np.concatenate(
        [(c < real).sum(-1) for c in routing]), minlength=k + 1)
    assert stats["moe_real_picks_hist"] == by_real.tolist()
    assert stats["moe_routed_tokens"] == n * layers == by_real.sum()
    assert stats["moe_few_real_tokens"] == by_real[:k // 3 + 1].sum()
    assert stats["moe_many_real_tokens"] == by_real[k - 1:].sum()
    # one lane decoding: real experts hit per (layer, step), and of
    # the held ones those the reference chose there
    assert stats["moe_layer_steps"] == 8 * layers
    tail = [c[len(PROMPT):] for c in routing]
    assert stats["moe_active_expert_steps"] == sum(int((c < real).sum()) for c in tail)
    assert stats["moe_held_active_expert_steps"] == sum(
        int(((c >= 2) & (c < 6)).sum()) for c in tail)
    assert stats["moe_load_max"] >= stats["moe_load_mean"] > 0
    # step t of the lane read the 29 + t rows cached before it, in
    # each of the FOUR attentions
    assert stats["decode_kv_tokens"] == sum(len(PROMPT) + t for t in range(8))
    assert stats["latent_kv_tokens"] == 4 * stats["decode_kv_tokens"]
    report = eng.lane_report()
    assert (report["arch"], report["attention"], report["cache_width"],
            report["cache_layers"], report["experts_held"]) == (
                "longcat_flash", "mla", 128, 4, 4)
    assert report["pool_shard_bytes"] == eng.cache.pages_k.nbytes
    assert stats["moe_held_pass_rows"] == moe.held_rows_cap(SLOTS, k, 4, 12)

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


def test_routing_is_the_source_s_rule_ties_and_bias_included():
    rng = np.random.default_rng(3)
    tokens, d, real, zero, k = 40, 16, 12, 6, 5
    e = real + zero
    h = rng.normal(size=(tokens, d)).astype(np.float32)
    w = rng.normal(size=(d, e)).astype(np.float32) * 0.3
    h[5] = 0.0   # every probability 1 / e: the bias alone decides, ties by index
    bias = rng.uniform(-1 / e, 1 / e, size=e).astype(np.float32)
    bias[3] = bias[7] = bias[14] = 1 / e  # equal best biases, a real and an identity one
    with jax.default_matmul_precision("highest"):
        probs = np.asarray(jax.nn.softmax(jnp.asarray(h) @ jnp.asarray(w), axis=-1))
    gates, experts = moe.route_zero(jnp.asarray(h), jnp.asarray(w), jnp.asarray(bias), k, 6.0)
    want_e = np.asarray([sorted(range(e), key=lambda i: (-(p[i] + bias[i]), i))[:k]
                         for p in probs])
    want_w = 6.0 * np.take_along_axis(probs, want_e, axis=-1)
    assert np.array_equal(np.asarray(experts), want_e)
    assert np.abs(np.asarray(gates) - want_w).max() < 1e-6
    ref_w, ref_e = ref.route(dict(moe_topk=k, routed_scaling_factor=6), probs, bias)
    assert np.array_equal(ref_e, want_e) and np.abs(ref_w - want_w).max() < 1e-6
    assert want_e[5].tolist()[:3] == [3, 7, 14]
    # the bias selects and never weighs; the gates are not renormalised
    assert np.abs(np.asarray(gates).sum(-1) - 6.0).min() > 0.5
    _g, plain = moe.route_zero(jnp.asarray(h), jnp.asarray(w), jnp.zeros(e), k, 6.0)
    assert not np.array_equal(np.asarray(plain), want_e)
    # a pick past the real experts is an identity expert: gate * h, nothing else
    ident = moe.identity_experts(jnp.asarray(h), gates, experts, real)
    zero_gate = np.where(want_e >= real, want_w, 0).sum(-1)
    assert np.abs(np.asarray(ident) - zero_gate[:, None] * h).max() < 1e-6
    assert np.asarray(moe.real_pick_histogram(experts, real)).tolist() == np.bincount(
        (want_e < real).sum(-1), minlength=k + 1).tolist()


def _uncut():
    spec = replace(SPEC, experts_held=0, expert_offset=0)
    return spec, init_params(spec, SIZES, 9, dtype=jnp.float32)["block_1"]


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts all shares give (``expert_offset`` 0, 2, 4, 6),
    plus what every chip computes alike — the identity experts' part and
    the dense pair — counted once, add up to the uncut reference's layer
    output; in the program's layer and in the reference's, which agree
    share by share."""
    rng = np.random.default_rng(4)
    _spec, uncut = _uncut()
    n, d, real = 40, 64, 8
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    pos = jnp.arange(n)
    whole_model = dict(MODEL, n_routed_experts=8, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        whole, whole_parts = ref.layer(uncut, whole_model, x, pos)
        shares = []
        for offset in range(0, real, 2):
            sl = slice(offset, offset + 2)
            block = {**uncut, **{name: uncut[name][sl] for name in
                                 ("experts_gate", "experts_up", "experts_down")}}
            out, parts = ref.layer(block, dict(MODEL, n_routed_experts=2,
                                               expert_offset=offset), x, pos)
            shares.append((out, parts))
        # every share computes the same dense pair and identity part
        alike = shares[0][0] - shares[0][1]["routed"]
        for out, parts in shares:
            assert np.abs(np.asarray(out - parts["routed"] - alike)).max() < 1e-5
            assert np.abs(np.asarray(parts["identity"] - whole_parts["identity"])).max() == 0
        total = alike + sum(parts["routed"] for _out, parts in shares)
        assert np.abs(np.asarray(total - whole)).max() < 1e-4
        assert all(float(jnp.abs(parts["routed"]).max()) > 0 for _o, parts in shares)
        assert float(jnp.abs(whole_parts["identity"]).max()) > 0

        # the program's expert layer, share by share, on the reference's g
        g = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        gates, experts = moe.route_zero(g, uncut["router"], uncut["score_bias"], 4, 6.0)
        mine = [moe.expert_ffn_held(
            g, uncut["experts_gate"][o:o + 2], uncut["experts_up"][o:o + 2],
            uncut["experts_down"][o:o + 2], gates, experts, o, 12)
            for o in range(0, real, 2)]
        every = moe.expert_ffn_held(g, uncut["experts_gate"], uncut["experts_up"],
                                    uncut["experts_down"], gates, experts, 0, 12)
    assert np.abs(np.asarray(sum(mine) - every)).max() < 1e-4


@pytest.mark.parametrize("picks", ["all_identity", "none_identity"])
def test_a_token_of_only_identity_experts_and_one_of_none_are_both_right(own_engine, picks):
    """The bias alone can send every pick of every token to identity
    experts (no expert matrices are touched: the held experts' part is
    exactly zero) or to real ones (no identity part); the engine's
    logits agree with the reference either way (other weights under the
    same program: an engine of its own)."""
    params = init_params(SPEC, SIZES, 3, dtype=jnp.float32)
    bias = np.zeros((12,), np.float32)
    bias[8:] = 1.0 if picks == "all_identity" else -1.0
    for name in ("block_0", "block_1"):
        params[name]["score_bias"] = jnp.asarray(bias)
    eng, _ = own_engine("gather", params=params)
    routing = []
    rows, tokens, at = run_program(eng, "decode")
    want = np.asarray(ref.logits(params, MODEL, tokens, routing=routing))
    assert np.abs(rows - want[at: at + len(rows)]).max() < F32_ATOL
    chosen = np.concatenate(routing)
    assert (chosen >= 8).all() if picks == "all_identity" else (chosen < 8).all()
    rng = np.random.default_rng(8)
    h = jnp.asarray(rng.normal(size=(6, 64)), jnp.float32)
    block = params["block_1"]
    gates, experts = moe.route_zero(h, block["router"], block["score_bias"], 4, 6.0)
    held = moe.expert_ffn_held(h, block["experts_gate"], block["experts_up"],
                               block["experts_down"], gates, experts, 2, 12)
    ident = moe.identity_experts(h, gates, experts, 8)
    if picks == "all_identity":
        assert float(jnp.abs(held).max()) == 0.0 and float(jnp.abs(ident).min()) > 0
    else:
        assert float(jnp.abs(ident).max()) == 0.0


def test_the_published_sizes_are_the_defaults_and_streaminglm_takes_a_share():
    spec = model_spec("longcat_flash")
    assert spec is LONGCAT_FLASH
    assert (spec.num_experts, spec.zero_experts, spec.router_outputs, spec.experts_per_tok,
            spec.expert_width, spec.dense_width, spec.routed_scale) == (
                512, 256, 768, 12, 2048, 12288, 6.0)
    assert (spec.q_rank, spec.kv_rank, spec.nope_dim, spec.rope_dim, spec.v_dim) == (
        1536, 512, 128, 64, 128)
    assert spec.lora_scales(6144) == (2.0, 12 ** 0.5)
    assert spec.softmax_scale == 192 ** -0.5 and spec.rope_theta == 1e7
    assert spec.cache_values == 576 and spec.cache_width(6144) == 640
    assert spec.cache_pools == 1 and spec.cache_layers(28) == 56 and spec.held == 512
    assert spec.hist_width == 768 + 13
    assert all(spec.layer_routed(i) for i in range(4)) and spec.dense_layers == 0
    lm = StreamingLM(arch="longcat_flash",
                     arch_sizes='{"experts_held": 16, "expert_offset": 32}')
    assert (lm.spec.held, lm.spec.expert_offset, lm.spec.router_outputs) == (16, 32, 768)
    # the other blocks' pools keep one row a layer
    assert model_spec("deepseek_v3").cache_layers(6) == 6
    assert model_spec("olmoe").hist_width == 64


@pytest.mark.parametrize("sizes, match", [
    ({"n_group": 4}, "has no"), ({"shared_experts": 1}, "has no"),
    ({"dense_layers": 1}, "has no"), ({"rope_factor": 8.0}, "has no"),
    ({"zero_expert_num": 4}, "unknown sizes"), ({"experts_held": 16, "expert_offset": 500},
                                                "experts_held")])
def test_sizes_the_arch_does_not_have_are_refused(sizes, match):
    with pytest.raises(ValueError, match=match):
        model_spec("longcat_flash", **sizes)
    with pytest.raises(ValueError, match=match):
        StreamingLM(arch="longcat_flash", arch_sizes=sizes)


def test_zero_experts_is_longcat_s_alone():
    for arch in ("olmoe", "deepseek_v3"):
        with pytest.raises(ValueError, match="has no"):
            model_spec(arch, zero_experts=4)


def test_weights_rest_as_the_spec_says():
    params = init_params(SPEC, SIZES, 1)
    block = params["block_1"]
    for i in (0, 1):
        assert block[f"kv_b_k_{i}"].shape == (4, 16, 8)
        assert block[f"kv_b_v_{i}"].shape == (4, 16, 8)
        assert block[f"kv_a_{i}"]["kernel"].shape == (64, 20)
        assert block[f"attn_proj_{i}"]["kernel"].shape == (32, 64)
        assert block[f"mlp_gate_{i}"].shape == (64, 96)
        assert block[f"mlp_down_{i}"].shape == (96, 64)
    assert block["experts_gate"].shape == (4, 64, 32)       # held, not 8
    assert block["router"].shape == (64, 12)                # real + identity
    assert "shared_gate" not in block and "mlp_gate" not in block
    bias = np.asarray(block["score_bias"])
    # within +- the mean probability, the same 4 values in every block of 4
    assert bias.dtype == np.float32 and 0 < np.abs(bias).max() <= 1 / 12
    grid = (2 * (np.arange(4) + 0.5) / 4 - 1) / 12
    assert np.allclose(np.sort(bias.reshape(-1, 4), axis=1), grid, atol=1e-8)
    f32 = {k for k, v in block.items()
           if jax.tree_util.tree_leaves(v)[0].dtype == jnp.float32}
    assert f32 == {f"{n}_{i}" for i in (0, 1) for n in (
        "attn_norm", "ffn_norm", "q_a_norm", "kv_a_norm")} | {"router", "score_bias"}


def test_the_prefill_cap_and_the_accounting_count_the_double_layer():
    """One v5e chip (15.75 GiB), the cell's weights and pool: a call of
    8,192 positions fits, as for the other configurations; the pool's
    bytes count two rows a layer."""
    spec = model_spec("longcat_flash", experts_held=16)
    per_position = paged.prefill_position_bytes(spec, 6144, 16384, 64)
    free = int(15.75 * 2**30 - 10_383_495_168 - 4_027_187_200)
    # 13.42 GiB of arguments leave 2.33: the compiler counts 1.73 GiB of
    # temporaries for 4,096 positions (b1024_k4) and 0.89 for 2,048, and a
    # chunk enqueued behind the call holds 0.4 more
    assert per_position == 331_776
    assert paged.prefill_positions_max(free, per_position) == 2048
    priced = paged.paged_hbm_accounting(
        streams=128, ctx_len=3072, d_model=spec.cache_width(6144),
        num_layers=spec.cache_layers(4), cache_pools=spec.cache_pools,
        chunk_impl="pool")
    assert priced["pool_bytes"] == 128 * 48 * 64 * 640 * 2 * 8
