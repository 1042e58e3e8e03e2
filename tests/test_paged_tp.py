"""Tensor-parallel paged generation (r11): GSPMD `model`-axis sharding
of the KV pool, the (w8a8) projections, and every engine program.

Correctness bar, same discipline as the prefix cache / bucket PRs:
greedy decode is BIT-EXACT TP=1 vs TP=N in the f32 exactness regime —
the TP program computes the same einsums over head shards and
all-reduces the partial sums, and f32 addition over the same operand
partitioning is the venue where that must reproduce exactly.  The TP=1
program must stay byte-identical to the pre-TP engine (mesh=None takes
the EXACT historical jit path), so single-chip deployments carry zero
regression risk.

Fast tier: knob/mesh semantics, `parallel/sharding.py` unit coverage,
the TP=1 byte-identical lowering, one tp=2 parity smoke, and the
monitoring surface (engine_stats -> Prometheus bridge -> StreamingLM
gauges) — conftest forces 8 CPU host devices, so tp=2 runs everywhere.
The full parity matrix (ring|pool × w8a8 × speculative × prefix-cache)
and the promoted MULTICHIP dry-run are @slow.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.paged import PagedEngine, StreamingLM
from seldon_core_tpu.models.transformer import TransformerLM
from seldon_core_tpu.parallel.mesh import create_mesh, resolve_tp, tp_mesh
from seldon_core_tpu.parallel.sharding import (
    infer_param_specs,
    shard_decode_state,
    shard_params,
)

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


def _serve(eng, prompts, max_new=6, hints=None):
    streams = [
        eng.submit(
            p, max_new_tokens=max_new,
            draft_hint=None if hints is None else hints[i],
        )
        for i, p in enumerate(prompts)
    ]
    eng.run()
    for s in streams:
        assert s.error is None, s.error
    return [s.result for s in streams]


class TestTpKnob:
    """resolve_tp / tp_mesh: the ONE place the knob's precedence lives."""

    def test_explicit_beats_env(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_TP", "4")
        assert resolve_tp(2) == 2

    def test_env_fallback_and_default_off(self, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_TP", "2")
        assert resolve_tp(None) == 2
        assert resolve_tp(0) == 2
        # an explicit 1 FORCES single-chip over the env
        assert resolve_tp(1) == 1
        monkeypatch.delenv("SELDON_TPU_TP")
        assert resolve_tp(None) == 1

    def test_env_zero_spells_off(self, monkeypatch):
        # SELDON_TPU_TP=0 disables, matching every other =0 knob —
        # it must never crash engine load
        monkeypatch.setenv("SELDON_TPU_TP", "0")
        assert resolve_tp(None) == 1
        assert tp_mesh(None) is None

    def test_degree_below_one_rejected(self):
        with pytest.raises(ValueError):
            resolve_tp(-1)

    def test_tp_one_means_no_mesh(self, monkeypatch):
        monkeypatch.delenv("SELDON_TPU_TP", raising=False)
        assert tp_mesh(1) is None
        assert tp_mesh(None) is None

    def test_builds_model_mesh_when_devices_allow(self):
        mesh = tp_mesh(2)
        assert mesh is not None
        assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {"model": 2}

    def test_degrades_to_single_chip_with_warn(self, caplog):
        with caplog.at_level(
            logging.WARNING, logger="seldon_core_tpu.parallel.mesh"
        ):
            assert tp_mesh(4096) is None
        assert any("degrading to single-chip" in r.message
                   for r in caplog.records)

    def test_strict_raises_instead_of_degrading(self):
        with pytest.raises(ValueError, match="degrading"):
            tp_mesh(4096, strict=True)


class TestShardingUnits:
    """infer_param_specs / shard_params / shard_decode_state coverage."""

    @pytest.fixture(scope="class")
    def mesh(self):
        return create_mesh({"model": 2}, devices=jax.devices()[:2])

    def test_spec_choices_dense_conv_bias_scale(self, mesh):
        from jax.sharding import PartitionSpec as P

        tree = {
            "dense": np.zeros((256, 128), np.float32),
            "conv": np.zeros((3, 3, 16, 64), np.float32),
            "bias": np.zeros((128,), np.float32),
            "scale": np.zeros((8,), np.float32),
        }
        specs = infer_param_specs(tree, mesh, min_weight_size=1024)
        # dense: largest divisible dim carries the model axis
        assert specs["dense"] == P("model", None)
        # conv: the output-channel dim (largest) shards
        assert specs["conv"] == P(None, None, None, "model")
        # small weights replicate
        assert specs["bias"] == P()
        assert specs["scale"] == P()

    def test_shard_decode_state_round_trip(self, mesh):
        tree = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
        pool_shape = (1, 5, 8, 32)
        p2, pk, pv = shard_decode_state(
            tree, mesh, pool_shape=pool_shape, dtype=jnp.float32,
            min_weight_size=0, num_heads=4,
        )
        # pools: created ALREADY sharded on the heads dim, zeros
        assert pk.shape == pool_shape and pv.shape == pool_shape
        assert pk.sharding.spec[3] == "model"
        assert pk.addressable_shards[0].data.shape[3] == 16  # 4 heads of 8 / 2
        np.testing.assert_array_equal(np.asarray(pk), np.zeros(pool_shape))
        # params: values survive the sharded placement bit-exactly
        np.testing.assert_array_equal(np.asarray(p2["w"]), tree["w"])
        assert p2["w"].sharding.spec == ("model", None)

    def test_indivisible_heads_replicate_pool_with_warn(self, mesh, caplog):
        with caplog.at_level(
            logging.WARNING, logger="seldon_core_tpu.parallel.sharding"
        ):
            _, pk, _ = shard_decode_state(
                {}, mesh, pool_shape=(1, 5, 8, 24), dtype=jnp.float32,
                num_heads=3,
            )
        assert any("NOT sharded" in r.message for r in caplog.records)
        # replicated: one device holds the full pool shape
        assert pk.addressable_shards[0].data.shape == (1, 5, 8, 24)

    def test_unannotatable_leaf_degrades_replicated_with_warn(
        self, mesh, caplog
    ):
        """Satellite guard: a leaf whose spec device_put rejects falls
        back to replicated with a WARN; a leaf that cannot be placed at
        all passes through host-side — engine load NEVER crashes on one
        odd checkpoint leaf."""
        from jax.sharding import PartitionSpec as P

        tree = {"good": np.zeros((4, 4), np.float32),
                "bad": np.zeros((6,), np.float32),
                "alien": "not-an-array"}
        specs = {"good": P(), "bad": P(None, "model"),  # rank mismatch
                 "alien": P()}
        with caplog.at_level(
            logging.WARNING, logger="seldon_core_tpu.parallel.sharding"
        ):
            out = shard_params(tree, mesh, specs=specs)
        msgs = [r.message for r in caplog.records]
        assert any("falling back to replicated" in m for m in msgs)
        assert any("not device-placeable" in m for m in msgs)
        np.testing.assert_array_equal(np.asarray(out["bad"]), tree["bad"])
        assert out["alien"] == "not-an-array"  # host-side pass-through


class TestTpOneByteIdentical:
    """The no-regression bar for single-chip hosts: tp=1 resolves to
    mesh=None, which takes the EXACT historical jit path — the lowered
    chunk program is byte-identical and carries no collectives."""

    @staticmethod
    def _lower_chunk(eng, steps=2, horizon=4):
        # the engine's shared audit surface: same body selection and
        # _tp_jit annotation as the serving path, so this can't drift
        return eng.lower_chunk(steps, ((eng.max_slots, horizon),)).as_text()

    def test_tp1_knob_program_byte_identical_to_meshless(
        self, params, monkeypatch
    ):
        monkeypatch.delenv("SELDON_TPU_TP", raising=False)
        plain = _engine(params)
        knob = _engine(params, tp=1)
        try:
            assert knob._mesh is None and knob.tp_degree == 1
            a = self._lower_chunk(plain)
            b = self._lower_chunk(knob)
        finally:
            plain.close()
            knob.close()
        assert a == b

    def test_tp1_program_carries_no_collectives(self, params):
        eng = _engine(params)
        try:
            text = self._lower_chunk(eng)
        finally:
            eng.close()
        for op in ("all-reduce", "all-gather", "reduce-scatter",
                   "collective-permute"):
            assert op not in text


class TestTpParitySmoke:
    """Fast-tier tp=2 coverage: one ring/f32 combo decodes bit-exactly
    vs TP=1, and the TP bookkeeping surfaces honestly."""

    def test_tp2_greedy_bit_exact_and_stats(self, params):
        off = _engine(params, tp=1)
        outs_off = _serve(off, _prompts())
        s_off = off.engine_stats()
        off.close()

        on = _engine(params, tp=2, shard_min_weight_size=0)
        assert on.tp_degree == 2
        outs_on = _serve(on, _prompts())
        s_on = on.engine_stats()
        on.close()

        for a, b in zip(outs_on, outs_off):
            np.testing.assert_array_equal(a, b)
        assert s_on["tp_degree"] == 2 and s_off["tp_degree"] == 1
        # heads-sharded pool: one device holds HALF the K+V bytes
        assert s_on["pool_shard_bytes"] == s_off["pool_shard_bytes"] // 2

    def test_env_knob_reaches_engine(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_TP", "2")
        eng = _engine(params, shard_min_weight_size=0)
        try:
            assert eng.tp_degree == 2
        finally:
            eng.close()

    def test_oversized_tp_degrades_engine_to_single_chip(
        self, params, caplog
    ):
        with caplog.at_level(
            logging.WARNING, logger="seldon_core_tpu.parallel.mesh"
        ):
            eng = _engine(params, tp=4096)
        try:
            assert eng.tp_degree == 1 and eng._mesh is None
        finally:
            eng.close()
        assert any("degrading to single-chip" in r.message
                   for r in caplog.records)


class TestTpObservability:
    """tp_degree + per-shard pool bytes thread engine_stats -> the
    Prometheus bridge -> StreamingLM's component gauges."""

    def test_bridge_exports_tp_gauges(self, params):
        import prometheus_client as prom

        from seldon_core_tpu.utils.metrics import GenerationPrometheusBridge

        registry = prom.CollectorRegistry()
        eng = _engine(params, tp=2, shard_min_weight_size=0)
        try:
            GenerationPrometheusBridge(
                eng, deployment_name="d", predictor_name="p",
                model_name="m", registry=registry,
            ).collect()
            labels = {"deployment_name": "d", "predictor_name": "p",
                      "model_name": "m"}
            assert registry.get_sample_value(
                "seldon_tpu_engine_tp_degree", labels) == 2.0
            assert registry.get_sample_value(
                "seldon_tpu_engine_pool_shard_bytes", labels
            ) == float(eng.engine_stats()["pool_shard_bytes"])
        finally:
            eng.close()

    def test_streaminglm_tp_knob_and_gauge(self):
        comp = StreamingLM(max_slots=2, steps_per_call=2, tp=2, **CFG)
        comp.load()
        try:
            assert comp.engine.tp_degree == 2
            by_key = {m["key"]: m["value"] for m in comp.metrics()}
            assert by_key["paged_tp_degree"] == 2
            # /health/status says where and how it is served: the device
            # as jax reports it, the degrees the engine GOT, the lane
            status = comp.health_status()
            devices = jax.devices()
            assert status["device"] == {
                "platform": "cpu", "kind": devices[0].device_kind,
                "count": len(devices), "pallas_interpret": True,
            }
            assert (status["tp"], status["dp"]) == (2, 1)
            assert status["kernel_active"] is False
            assert status["chunk_impl"] == "ring"
            assert status["pool_shard_bytes"] == comp.engine.engine_stats()["pool_shard_bytes"]
        finally:
            comp.shutdown()

    def test_chunk_records_carry_tp_degree(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_FLIGHT_RECORDER", "64")
        eng = _engine(params, tp=2, shard_min_weight_size=0)
        try:
            _serve(eng, _prompts())
            recs = eng.recorder.snapshot()
            assert recs and all(r["tp_degree"] == 2 for r in recs
                                if r.get("phase") == "decode")
        finally:
            eng.close()


@pytest.mark.slow
class TestTpParityMatrix:
    """The tentpole correctness bar: greedy bit-exactness TP=1 vs TP=2
    across chunk impls × w8a8 × speculative × prefix-cache on/off, in
    the f32 exactness regime."""

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

    def _run(self, params, monkeypatch, *, tp, impl, precision, speculative,
             prefix_cache):
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", impl)
        # tp passed EXPLICITLY (1 forces single-chip): the TP-off
        # baseline must stay off even with SELDON_TPU_TP in the env,
        # or the parity check degenerates to TP-vs-TP
        eng = PagedEngine(
            params, dtype=jnp.float32, page_size=8, max_slots=2,
            steps_per_call=4, precision=precision, speculative=speculative,
            prefix_cache=prefix_cache, tp=tp,
            shard_min_weight_size=0, **self.MCFG,
        )
        assert eng.tp_degree == tp
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
    def test_tp2_bit_exact_vs_tp1(
        self, mparams, monkeypatch, impl, precision, spec, prefix_cache
    ):
        kw = dict(impl=impl, precision=precision, speculative=spec,
                  prefix_cache=prefix_cache)
        off = self._run(mparams, monkeypatch, tp=1, **kw)
        on = self._run(mparams, monkeypatch, tp=2, **kw)
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a, b)


@pytest.mark.slow
class TestMultichipDryrunPromotion:
    """The MULTICHIP `paged_tp` dry-run as a real test: TP-on vs TP-off
    greedy token equality on whatever mesh the host exposes, DEGRADING
    to tp=1 on single-device hosts instead of skipping silently (the
    parity assert then pins the meshless path against itself — still a
    real decode, never a skip)."""

    def test_tp_on_vs_off_on_host_mesh(self):
        n_dev = len(jax.devices())
        tp = max(d for d in (4, 2, 1) if d <= n_dev)
        lm_cfg = dict(vocab_size=64, d_model=32, num_layers=1, num_heads=4,
                      max_len=32)
        lm_params = TransformerLM(dtype=jnp.float32, **lm_cfg).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]

        def build(tp_n, **kw):
            # tp passed EXPLICITLY (1 forces single-chip even with
            # SELDON_TPU_TP exported) — the off arm must really be off
            return PagedEngine(
                lm_params, dtype=jnp.float32, page_size=8, max_slots=2,
                steps_per_call=2, tp=tp_n,
                shard_min_weight_size=0, **lm_cfg, **kw,
            )

        prompts = [np.array([5, 9, 13], np.int32), np.array([1, 2], np.int32)]

        on = build(tp)
        assert on.tp_degree == tp  # strict: a degrade here is a failure
        outs_on = _serve(on, prompts, max_new=4)
        on.close()

        off = build(1)
        outs_off = _serve(off, prompts, max_new=4)
        off.close()

        for a, b in zip(outs_on, outs_off):
            np.testing.assert_array_equal(a, b)

        # the speculative verify lane on the same mesh stays bit-exact
        spec = build(tp, speculative={"draft_k": 2, "ngram": 2})
        spec_out = spec.generate(prompts[0], max_new_tokens=4)
        spec.close()
        np.testing.assert_array_equal(spec_out, outs_off[0])
