"""2-D (data x model) serving mesh + sequence-sharded long-context
paged decode (r19).

Correctness bar, same discipline as the r11 TP round: greedy decode is
BIT-EXACT (dp=2, tp=2) vs tp-only vs single-chip in the f32 exactness
regime.  The page-dim sharding of the KV pool is exact by construction
— the per-step gather reads one page's rows, so each data shard
contributes either the real rows or zeros and the all-reduce sums one
nonzero term — and the tests pin that, not approximate it.

The no-regression bar: ``dp=1`` resolves through the EXACT
:func:`tp_mesh` path, so the 1-D ``{model: N}`` lowering and the
``mesh=None`` single-chip lowering are byte-identical to the r11
programs (lowering-text asserted below).

Fast tier: resolve_dp/resolve_mesh precedence + degrade order,
create_mesh/mesh_shape round-trips, shard_decode_state page-dim
coverage, dp=1 byte-identity, one (2,2) parity smoke, accounting and
the ring-attention-over-``data`` oracle (conftest forces 8 CPU host
devices, so (2,2) runs everywhere).  The full (2,2) parity matrix and
the scaled long-context admit/decode point are @slow.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.paged import (
    PagedEngine,
    StreamingLM,
    paged_hbm_accounting,
    paged_max_context,
)
from seldon_core_tpu.models.transformer import TransformerLM
from seldon_core_tpu.parallel.mesh import (
    create_mesh,
    mesh_shape,
    resolve_dp,
    resolve_mesh,
    tp_mesh,
)
from seldon_core_tpu.parallel.sharding import shard_decode_state

CFG = dict(vocab_size=64, d_model=32, num_layers=1, num_heads=4, max_len=64)


@pytest.fixture(scope="module")
def params():
    lm = TransformerLM(dtype=jnp.float32, **CFG)
    return lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(params, **kw):
    base = dict(dtype=jnp.float32, page_size=8, max_slots=2, steps_per_call=4)
    base.update(kw)
    return PagedEngine(params, **CFG, **base)


def _prompts(n=2, seed=3):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, CFG["vocab_size"], size=(5 + 3 * i,)).astype(np.int32)
        for i in range(n)
    ]


def _serve(eng, prompts, max_new=6):
    streams = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run()
    for s in streams:
        assert s.error is None, s.error
    return [s.result for s in streams]


class TestDpKnob:
    """resolve_dp: resolve_tp's twin over SELDON_TPU_DP."""

    def test_explicit_beats_env(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_DP", "4")
        assert resolve_dp(2) == 2
        # an explicit 1 FORCES one replica group over the env
        assert resolve_dp(1) == 1

    def test_env_fallback_and_default_off(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_DP", "2")
        assert resolve_dp(None) == 2
        assert resolve_dp(0) == 2
        monkeypatch.delenv("SELDON_TPU_DP")
        assert resolve_dp(None) == 1

    def test_env_zero_spells_off(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_DP", "0")
        assert resolve_dp(None) == 1

    def test_degree_below_one_rejected(self):
        with pytest.raises(ValueError):
            resolve_dp(-2)


class TestResolveMesh:
    """resolve_mesh: THE precedence home for the 2-D serving mesh."""

    def test_explicit_mesh_wins(self):
        mesh = create_mesh({"model": 2}, devices=jax.devices()[:2])
        assert resolve_mesh(mesh=mesh, tp=4, dp=2) is mesh

    def test_mesh_axes_beat_knobs(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_TP", "4")
        monkeypatch.setenv("SELDON_TPU_DP", "2")
        mesh = resolve_mesh(mesh_axes={"model": 2})
        assert mesh_shape(mesh) == {"model": 2}

    def test_dp1_delegates_to_tp_mesh(self, monkeypatch):
        monkeypatch.delenv("SELDON_TPU_DP", raising=False)
        mesh = resolve_mesh(tp=2)
        want = tp_mesh(2)
        assert mesh_shape(mesh) == mesh_shape(want) == {"model": 2}
        # and the same devices in the same order — the byte-identity
        # precondition for the 1-D program
        assert list(mesh.devices.flat) == list(want.devices.flat)

    def test_all_ones_is_single_chip(self, monkeypatch):
        monkeypatch.delenv("SELDON_TPU_TP", raising=False)
        monkeypatch.delenv("SELDON_TPU_DP", raising=False)
        assert resolve_mesh() is None
        assert resolve_mesh(tp=1, dp=1) is None

    def test_two_d_mesh_is_data_major(self):
        mesh = resolve_mesh(tp=2, dp=2)
        assert mesh.axis_names == ("data", "model")
        assert mesh_shape(mesh) == {"data": 2, "model": 2}
        # data-major grid: each model group spans ADJACENT device ids
        # (fast ICI neighbours for the per-layer all-reduces)
        ids = [[d.id for d in row] for row in mesh.devices]
        assert ids == [[0, 1], [2, 3]]

    def test_dp_only_mesh_drops_model_axis(self):
        mesh = resolve_mesh(tp=1, dp=2)
        assert mesh_shape(mesh) == {"data": 2}

    def test_env_knobs_build_the_mesh(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_TP", "2")
        monkeypatch.setenv("SELDON_TPU_DP", "2")
        assert mesh_shape(resolve_mesh()) == {"data": 2, "model": 2}

    def test_degrade_shrinks_data_axis_first(self, caplog):
        # 8 virtual devices: dp=8 x tp=2 = 16 cannot fit; the model
        # degree survives and data shrinks to 8 // 2 = 4
        with caplog.at_level(
            logging.WARNING, logger="seldon_core_tpu.parallel.mesh"
        ):
            mesh = resolve_mesh(tp=2, dp=8)
        assert mesh_shape(mesh) == {"data": 4, "model": 2}
        msgs = [r.message for r in caplog.records]
        assert any(
            "shrinking the data axis first" in m
            and "data=8" in m and "model=2" in m
            for m in msgs
        ), msgs

    def test_degrade_to_single_chip_names_both_axes(self, caplog):
        with caplog.at_level(
            logging.WARNING, logger="seldon_core_tpu.parallel.mesh"
        ):
            assert resolve_mesh(tp=4096, dp=2) is None
        assert any(
            "data=2" in r.message and "model=4096" in r.message
            and "single-chip" in r.message
            for r in caplog.records
        )

    def test_strict_raises_instead_of_degrading(self):
        with pytest.raises(ValueError, match="shrinking the data axis"):
            resolve_mesh(tp=2, dp=8, strict=True)
        with pytest.raises(ValueError, match="single-chip"):
            resolve_mesh(tp=4096, dp=2, strict=True)


class TestCreateMeshRoundTrip:
    """Satellite 2: create_mesh's docstring/default drift fixed and the
    2-D round-trip pinned."""

    def test_two_d_round_trip_preserves_order(self):
        axes = {"data": 2, "model": 2}
        mesh = create_mesh(axes, devices=jax.devices()[:4])
        assert mesh_shape(mesh) == axes
        assert mesh.axis_names == ("data", "model")

    def test_default_is_all_data(self):
        # the trainer's pure replica mesh — the documented default
        assert mesh_shape(create_mesh()) == {"data": len(jax.devices())}

    def test_wildcard_fills_remaining(self):
        mesh = create_mesh({"data": -1, "model": 2},
                           devices=jax.devices()[:8])
        assert mesh_shape(mesh) == {"data": 4, "model": 2}


class TestSeqShardUnits:
    """shard_decode_state: the pool's page dim over `data`, heads dim
    over `model`."""

    @pytest.fixture(scope="class")
    def mesh(self):
        return create_mesh({"data": 2, "model": 2},
                           devices=jax.devices()[:4])

    def test_pool_sharded_on_both_axes(self, mesh):
        pool_shape = (1, 6, 8, 32)
        _, pk, pv = shard_decode_state(
            {}, mesh, pool_shape=pool_shape, dtype=jnp.float32, num_heads=4,
        )
        assert tuple(pk.sharding.spec) == (None, "data", None, "model")
        # one device holds pages/2 x heads/2
        assert pk.addressable_shards[0].data.shape == (1, 3, 8, 16)
        np.testing.assert_array_equal(np.asarray(pv), np.zeros(pool_shape))

    def test_indivisible_pages_replicate_page_dim_with_warn(
        self, mesh, caplog
    ):
        with caplog.at_level(
            logging.WARNING, logger="seldon_core_tpu.parallel.sharding"
        ):
            _, pk, _ = shard_decode_state(
                {}, mesh, pool_shape=(1, 5, 8, 32), dtype=jnp.float32,
                num_heads=4,
            )
        assert any("num_pages=5" in r.message for r in caplog.records)
        # heads sharding survives; only the page dim replicates
        assert tuple(pk.sharding.spec)[3] == "model"
        assert pk.addressable_shards[0].data.shape[1] == 5

    def test_seq_shard_off_replicates_page_dim_silently(self, mesh, caplog):
        with caplog.at_level(
            logging.WARNING, logger="seldon_core_tpu.parallel.sharding"
        ):
            _, pk, _ = shard_decode_state(
                {}, mesh, pool_shape=(1, 6, 8, 32), dtype=jnp.float32,
                num_heads=4, seq_shard=False,
            )
        # an explicit opt-out is not a degrade: no WARN
        assert not any("num_pages" in r.message for r in caplog.records)
        assert pk.addressable_shards[0].data.shape[1] == 6
        assert tuple(pk.sharding.spec)[3] == "model"

    def test_one_d_model_mesh_keeps_historical_spec(self):
        mesh1d = create_mesh({"model": 2}, devices=jax.devices()[:2])
        _, pk, _ = shard_decode_state(
            {}, mesh1d, pool_shape=(1, 6, 8, 32), dtype=jnp.float32,
            num_heads=4,
        )
        assert tuple(pk.sharding.spec) == (None, None, None, "model")


class TestDp1ByteIdentical:
    """The r11 no-regression bar carried forward: dp=1 lowers the EXACT
    1-D program, and dp=tp=1 the EXACT single-chip program."""

    @staticmethod
    def _lower_chunk(eng, steps=2, horizon=4):
        return eng.lower_chunk(steps, ((eng.max_slots, horizon),)).as_text()

    def test_dp1_tp2_program_byte_identical_to_tp_mesh(self, params):
        via_knob = _engine(params, tp=2, dp=1, shard_min_weight_size=0)
        via_mesh = _engine(
            params, mesh=tp_mesh(2), shard_min_weight_size=0
        )
        try:
            assert via_knob.dp_degree == 1
            a = self._lower_chunk(via_knob)
            b = self._lower_chunk(via_mesh)
        finally:
            via_knob.close()
            via_mesh.close()
        assert a == b

    def test_dp1_tp1_program_byte_identical_to_meshless(
        self, params, monkeypatch
    ):
        monkeypatch.delenv("SELDON_TPU_TP", raising=False)
        monkeypatch.delenv("SELDON_TPU_DP", raising=False)
        plain = _engine(params)
        knob = _engine(params, tp=1, dp=1)
        try:
            assert knob._mesh is None and knob.dp_degree == 1
            a = self._lower_chunk(plain)
            b = self._lower_chunk(knob)
        finally:
            plain.close()
            knob.close()
        assert a == b


class TestMeshParitySmoke:
    """Fast-tier (2,2) coverage: bit-exact greedy vs tp-only vs
    single-chip, plus the sharding bookkeeping."""

    def test_mesh22_greedy_bit_exact_three_ways(self, params):
        single = _engine(params, tp=1)
        outs_single = _serve(single, _prompts())
        s_single = single.engine_stats()
        single.close()

        tponly = _engine(params, tp=2, shard_min_weight_size=0)
        outs_tp = _serve(tponly, _prompts())
        tponly.close()

        mesh = _engine(params, tp=2, dp=2, shard_min_weight_size=0)
        assert mesh.tp_degree == 2 and mesh.dp_degree == 2
        outs_mesh = _serve(mesh, _prompts())
        s_mesh = mesh.engine_stats()
        mesh.close()

        for a, b, c in zip(outs_mesh, outs_tp, outs_single):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)
        assert s_mesh["dp_degree"] == 2 and s_single["dp_degree"] == 1
        # pool sharded over BOTH axes: one device holds at most a
        # quarter of the single-chip bytes (pool may round up to a dp
        # multiple of pages first, hence <=)
        assert s_mesh["pool_shard_bytes"] * 4 <= (
            s_single["pool_shard_bytes"] + s_single["pool_shard_bytes"] // 2
        )

    def test_pool_pages_round_up_to_dp_multiple(self, params):
        eng = _engine(params, tp=2, dp=2, shard_min_weight_size=0)
        try:
            assert eng.num_pages % 2 == 0
            assert tuple(eng.cache.pages_k.sharding.spec) == (
                None, "data", None, "model",
            )
        finally:
            eng.close()

    def test_env_knobs_reach_engine(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_TP", "2")
        monkeypatch.setenv("SELDON_TPU_DP", "2")
        eng = _engine(params, shard_min_weight_size=0)
        try:
            assert eng.tp_degree == 2 and eng.dp_degree == 2
        finally:
            eng.close()

    def test_seq_shard_off_still_bit_exact(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_SEQ_SHARD", "0")
        eng = _engine(params, tp=2, dp=2, shard_min_weight_size=0)
        try:
            # pure throughput replicas: page dim replicated, decode
            # unchanged
            assert tuple(eng.cache.pages_k.sharding.spec)[1] is None
            outs = _serve(eng, _prompts())
        finally:
            eng.close()
        monkeypatch.delenv("SELDON_TPU_SEQ_SHARD")
        ref = _engine(params, tp=1)
        try:
            ref_outs = _serve(ref, _prompts())
        finally:
            ref.close()
        for a, b in zip(outs, ref_outs):
            np.testing.assert_array_equal(a, b)

    def test_indivisible_slots_fall_back_with_warn(self, params, caplog):
        with caplog.at_level(
            logging.WARNING, logger="seldon_core_tpu.models.paged"
        ):
            eng = _engine(
                params, tp=2, dp=2, max_slots=3, shard_min_weight_size=0
            )
        try:
            assert eng.dp_degree == 2 and not eng._lane_sharded
            outs = _serve(eng, _prompts(3))
        finally:
            eng.close()
        ref = _engine(params, tp=1, max_slots=3)
        try:
            ref_outs = _serve(ref, _prompts(3))
        finally:
            ref.close()
        for a, b in zip(outs, ref_outs):
            np.testing.assert_array_equal(a, b)
        assert any("max_slots" in r.message for r in caplog.records)

    def test_speculative_mesh22_bit_exact(self, params):
        prompt = np.array([5, 9, 5, 9, 5, 9, 5], np.int32)
        ref = _engine(params, tp=1)
        want = ref.generate(prompt, max_new_tokens=8).tolist()
        ref.close()
        eng = _engine(
            params, tp=2, dp=2, shard_min_weight_size=0,
            speculative={"draft_k": 3, "ngram": 2},
        )
        try:
            got = eng.generate(prompt, max_new_tokens=8).tolist()
        finally:
            eng.close()
        assert got == want


class TestGeneratorLaneDp:
    """dp knob threading through the contiguous + speculative lanes."""

    def test_generator_dp_mesh_parity(self, params):
        from seldon_core_tpu.models.generate import Generator

        base = dict(dtype=jnp.float32, quantize="", **CFG)
        plain = Generator(params, tp=1, **base)
        prompt = np.array([[3, 1, 4, 1, 5]], np.int32)
        want = plain.generate(prompt, max_new_tokens=8)
        mesh_gen = Generator(params, tp=1, dp=2, **base)
        assert mesh_gen.dp_degree == 2 and mesh_gen.tp_degree == 1
        got = mesh_gen.generate(prompt, max_new_tokens=8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_speculative_generator_mesh22_bit_exact(self, params):
        from seldon_core_tpu.models.speculative import SpeculativeGenerator

        prompt = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)

        def run(**kw):
            g = SpeculativeGenerator(
                params, dtype=jnp.float32, page_size=8,
                shard_min_weight_size=0, **CFG, **kw,
            )
            return g.generate(prompt, max_new_tokens=8).tolist()

        assert run(tp=2, dp=2) == run(tp=1)

    def test_speculative_pool_rounds_and_shards(self, params):
        from seldon_core_tpu.models.speculative import SpeculativeGenerator

        g = SpeculativeGenerator(
            params, dtype=jnp.float32, page_size=8,
            shard_min_weight_size=0, tp=2, dp=2, **CFG,
        )
        # max_len 64 / page 8 + trash = 9 pages, rounded to 10 for dp=2
        assert g.target.pk.shape[1] == 10
        assert tuple(g.target.pk.sharding.spec)[1] == "data"


class TestAccountingDp:
    """paged_hbm_accounting dp_degree + paged_max_context."""

    KW = dict(d_model=256, num_layers=4, dtype_bytes=2,
              chunk_impl="ring")

    def test_dp_divides_kv_terms_and_keys_stay_separate(self):
        full = paged_hbm_accounting(streams=4, ctx_len=2048, **self.KW)
        both = paged_hbm_accounting(
            streams=4, ctx_len=2048, tp_degree=2, dp_degree=2, **self.KW
        )
        assert both["tp_degree"] == 2 and both["dp_degree"] == 2
        assert both["pool_bytes"] == full["pool_bytes"] // 4
        assert both["working_set_bytes"] == full["working_set_bytes"] // 4
        # dp alone divides by 2 and must NOT inflate the tp key
        dp_only = paged_hbm_accounting(
            streams=4, ctx_len=2048, dp_degree=2, **self.KW
        )
        assert dp_only["tp_degree"] == 1 and dp_only["dp_degree"] == 2
        assert dp_only["pool_bytes"] == full["pool_bytes"] // 2

    def test_indivisible_pool_pages_price_full_bytes(self):
        full = paged_hbm_accounting(streams=1, ctx_len=2048, **self.KW)
        fb = paged_hbm_accounting(
            streams=1, ctx_len=2048, dp_degree=2, num_pool_pages=33,
            **self.KW
        )
        # mirror shard_decode_state's WARN fallback: replicated page dim
        assert fb["dp_degree"] == 1
        assert fb["pool_bytes"] == full["pool_bytes"]
        ok = paged_hbm_accounting(
            streams=1, ctx_len=2048, dp_degree=2, num_pool_pages=34,
            **self.KW
        )
        assert ok["dp_degree"] == 2

    def test_max_context_scales_with_data_axis(self):
        budget = 64 << 20
        single = paged_max_context(budget, **self.KW)
        mesh = paged_max_context(budget, tp_degree=2, dp_degree=2, **self.KW)
        assert single > 0 and single % 64 == 0
        assert mesh > single
        assert mesh % 64 == 0

    def test_max_context_zero_when_one_page_overflows(self):
        assert paged_max_context(16, **self.KW) == 0

    def test_long_context_certificate(self):
        """The bench's admit certificate as arithmetic: per-shard bytes
        < budget < full bytes at 32k, so the 2-D mesh admits a context
        no single chip can hold."""
        ctx = 32 * 1024
        full = paged_hbm_accounting(streams=1, ctx_len=ctx, **self.KW)
        shard = paged_hbm_accounting(
            streams=1, ctx_len=ctx, tp_degree=2, dp_degree=2, **self.KW
        )
        budget = (shard["peak_bytes"] + full["peak_bytes"]) // 2
        assert shard["peak_bytes"] < budget < full["peak_bytes"]
        assert paged_max_context(budget, **self.KW) < ctx
        assert paged_max_context(
            budget, tp_degree=2, dp_degree=2, **self.KW
        ) >= ctx


class TestRingOracleOverDataAxis:
    """Satellite 1: ring attention runs over the SERVING mesh's `data`
    axis — the same axis that page-shards the paged pool — and matches
    the plain_attention oracle (the long-context numerics pin)."""

    def test_ring_over_serving_data_axis_matches_oracle(self):
        from seldon_core_tpu.parallel.ring_attention import (
            plain_attention,
            ring_attention,
        )

        mesh = resolve_mesh(tp=2, dp=2)
        rng = np.random.default_rng(11)
        q, k, v = (
            jnp.asarray(rng.normal(size=(1, 32, 4, 8)).astype(np.float32))
            for _ in range(3)
        )
        want = plain_attention(q, k, v, causal=True)
        got = ring_attention(q, k, v, mesh=mesh, seq_axis="data", causal=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )


class TestMeshObservability:
    """dp_degree threads engine_stats -> Prometheus bridge ->
    StreamingLM gauges -> chunk records."""

    def test_bridge_exports_dp_gauge(self, params):
        import prometheus_client as prom

        from seldon_core_tpu.utils.metrics import GenerationPrometheusBridge

        registry = prom.CollectorRegistry()
        eng = _engine(params, tp=2, dp=2, shard_min_weight_size=0)
        try:
            GenerationPrometheusBridge(
                eng, deployment_name="d", predictor_name="p",
                model_name="m", registry=registry,
            ).collect()
            labels = {"deployment_name": "d", "predictor_name": "p",
                      "model_name": "m"}
            assert registry.get_sample_value(
                "seldon_tpu_engine_dp_degree", labels) == 2.0
        finally:
            eng.close()

    def test_streaminglm_dp_knob_and_gauge(self):
        comp = StreamingLM(max_slots=2, steps_per_call=2, tp=2, dp=2, **CFG)
        comp.load()
        try:
            assert comp.engine.dp_degree == 2
            by_key = {m["key"]: m["value"] for m in comp.metrics()}
            assert by_key["paged_dp_degree"] == 2
        finally:
            comp.shutdown()

    def test_chunk_records_carry_dp_degree(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_FLIGHT_RECORDER", "64")
        eng = _engine(params, tp=2, dp=2, shard_min_weight_size=0)
        try:
            _serve(eng, _prompts())
            recs = eng.recorder.snapshot()
            assert recs and all(r["dp_degree"] == 2 for r in recs
                                if r.get("phase") == "decode")
        finally:
            eng.close()


@pytest.mark.slow
class TestMeshParityMatrix:
    """Satellite 4: the (2,2) parity matrix on the forced-8-device CPU
    host — greedy bit-exactness (dp=2, tp=2) vs single-chip across
    chunk impls x w8a8 x speculative x prefix-cache."""

    MCFG = dict(vocab_size=64, d_model=32, num_layers=2, num_heads=4,
                max_len=64)

    @pytest.fixture(scope="class")
    def mparams(self):
        lm = TransformerLM(dtype=jnp.float32, **self.MCFG)
        return lm.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))["params"]

    def _mprompts(self):
        rng = np.random.default_rng(3)
        shared = rng.integers(0, 64, size=(17,)).astype(np.int32)
        return [
            np.concatenate(
                [shared, rng.integers(0, 64, size=(2 + i,)).astype(np.int32)]
            )
            for i in range(3)
        ]

    def _run(self, params, monkeypatch, *, dp, tp, impl, precision,
             speculative, prefix_cache):
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", impl)
        eng = PagedEngine(
            params, dtype=jnp.float32, page_size=8, max_slots=2,
            steps_per_call=4, precision=precision, speculative=speculative,
            prefix_cache=prefix_cache, tp=tp, dp=dp,
            shard_min_weight_size=0, **self.MCFG,
        )
        assert eng.tp_degree == tp and eng.dp_degree == dp
        outs = []
        try:
            for p in self._mprompts():
                stream = eng.submit(p, max_new_tokens=8)
                eng.run()
                outs.append(stream.result)
        finally:
            eng.close()
        return outs

    @pytest.mark.parametrize("impl", ["ring", "pool"])
    @pytest.mark.parametrize("precision", ["", "w8a8"])
    @pytest.mark.parametrize("spec", [None, {"draft": "ngram", "draft_k": 3}])
    @pytest.mark.parametrize("prefix_cache", [True, False])
    def test_mesh22_bit_exact_vs_single_chip(
        self, mparams, monkeypatch, impl, precision, spec, prefix_cache
    ):
        kw = dict(impl=impl, precision=precision, speculative=spec,
                  prefix_cache=prefix_cache)
        off = self._run(mparams, monkeypatch, dp=1, tp=1, **kw)
        on = self._run(mparams, monkeypatch, dp=2, tp=2, **kw)
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a, b)


@pytest.mark.slow
class TestLongContextAdmit:
    """The scaled long-context point: the accounting says a single
    chip's budget cannot admit the context but the (2,2) mesh can, and
    the decode under that mesh is bit-exact vs an unconstrained
    single-chip replay."""

    LCFG = dict(vocab_size=64, d_model=32, num_layers=1, num_heads=4,
                max_len=512)

    def test_admit_and_decode_under_mesh(self):
        lm = TransformerLM(dtype=jnp.float32, **self.LCFG)
        params = lm.init(jax.random.key(2), jnp.zeros((1, 8), jnp.int32))["params"]
        ctx = 384
        acct_kw = dict(
            d_model=self.LCFG["d_model"],
            num_layers=self.LCFG["num_layers"],
            page_size=8, dtype_bytes=4, chunk_impl="ring",
        )
        full = paged_hbm_accounting(streams=1, ctx_len=ctx, **acct_kw)
        shard = paged_hbm_accounting(
            streams=1, ctx_len=ctx, tp_degree=2, dp_degree=2, **acct_kw
        )
        budget = (shard["peak_bytes"] + full["peak_bytes"]) // 2
        # the certificate: only the mesh admits this context
        assert paged_max_context(budget, page_size=8, **{
            k: v for k, v in acct_kw.items() if k != "page_size"
        }) < ctx
        assert paged_max_context(budget, page_size=8, tp_degree=2,
                                 dp_degree=2, **{
            k: v for k, v in acct_kw.items() if k != "page_size"
        }) >= ctx

        prompt = np.random.default_rng(5).integers(
            0, self.LCFG["vocab_size"], size=(ctx - 16,)
        ).astype(np.int32)

        def decode(**kw):
            eng = PagedEngine(
                params, dtype=jnp.float32, page_size=8, max_slots=2,
                steps_per_call=4, shard_min_weight_size=0,
                **self.LCFG, **kw,
            )
            try:
                return _serve(eng, [prompt], max_new=8)[0]
            finally:
                eng.close()

        on = decode(tp=2, dp=2)
        off = decode(tp=1)
        np.testing.assert_array_equal(on, off)
