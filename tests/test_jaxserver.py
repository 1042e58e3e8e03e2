"""Dynamic batcher + jaxserver + model zoo tests."""

import threading
import time

import numpy as np
import pytest

from seldon_core_tpu.batching import (
    DynamicBatcher,
    MultiSignatureBatcher,
    bucket_for,
    default_buckets,
)
from seldon_core_tpu.runtime import InternalMessage, MicroserviceError
from seldon_core_tpu.runtime import dispatch


class TestBuckets:
    def test_default_buckets(self):
        assert default_buckets(64) == [1, 2, 4, 8, 16, 32, 64]
        assert default_buckets(48) == [1, 2, 4, 8, 16, 32, 48]
        assert default_buckets(1) == [1]

    def test_bucket_for(self):
        buckets = [1, 2, 4, 8]
        assert bucket_for(1, buckets) == 1
        assert bucket_for(3, buckets) == 4
        assert bucket_for(8, buckets) == 8
        assert bucket_for(100, buckets) == 8

    def test_normalize_buckets(self):
        from seldon_core_tpu.batching import normalize_buckets

        # force-appends max_batch_size when the user list stops short
        assert normalize_buckets([1, 4, 16], 32) == [1, 4, 16, 32]
        # caps over-max buckets
        assert normalize_buckets([1, 4, 64], 32) == [1, 4, 32]
        assert normalize_buckets(None, 4) == [1, 2, 4]
        with pytest.raises(ValueError):
            normalize_buckets([1], 0)

    def test_multi_signature_batcher_normalizes_and_validates(self):
        from seldon_core_tpu.batching import MultiSignatureBatcher

        b = MultiSignatureBatcher(lambda x: x, max_batch_size=32, buckets=[1, 4, 16])
        assert b.buckets == [1, 4, 16, 32]
        with pytest.raises(ValueError):
            MultiSignatureBatcher(lambda x: x, max_batch_size=0)


class TestDynamicBatcher:
    def test_single_request(self):
        calls = []

        def fn(batch):
            calls.append(batch.shape)
            return batch * 2

        with DynamicBatcher(fn, max_batch_size=8, max_wait_ms=1.0) as b:
            out = b.submit(np.ones((3, 2)))
        np.testing.assert_array_equal(out, np.ones((3, 2)) * 2)
        # 3 rows padded to bucket 4
        assert calls == [(4, 2)]

    def test_concurrent_requests_coalesce(self):
        calls = []
        release = threading.Event()

        def fn(batch):
            calls.append(batch.shape[0])
            return batch + 1

        b = DynamicBatcher(fn, max_batch_size=32, max_wait_ms=20.0)
        b.start()
        results = {}

        def worker(i):
            release.wait()
            results[i] = b.submit(np.full((1, 4), float(i)))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        release.set()
        for t in threads:
            t.join()
        b.stop()
        # every caller got its own row back
        for i in range(8):
            np.testing.assert_array_equal(results[i], np.full((1, 4), float(i) + 1))
        # fewer device calls than requests (coalesced)
        assert sum(calls) >= 8
        assert len(calls) < 8

    def test_row_order_preserved(self):
        def fn(batch):
            return batch

        with DynamicBatcher(fn, max_batch_size=16, max_wait_ms=5.0) as b:
            out = b.submit(np.arange(12, dtype=np.float64).reshape(6, 2))
        np.testing.assert_array_equal(out, np.arange(12).reshape(6, 2))

    def test_padding_never_leaks(self):
        def fn(batch):
            return batch.sum(axis=1, keepdims=True)

        with DynamicBatcher(fn, max_batch_size=8, max_wait_ms=0.5) as b:
            out = b.submit(np.ones((5, 3)))
        assert out.shape == (5, 1)
        np.testing.assert_array_equal(out, np.full((5, 1), 3.0))

    def test_error_propagates_to_caller(self):
        def fn(batch):
            raise RuntimeError("device on fire")

        with DynamicBatcher(fn, max_batch_size=4, max_wait_ms=0.5) as b:
            with pytest.raises(RuntimeError, match="device on fire"):
                b.submit(np.ones((1, 2)))

    def test_oversized_request_served_whole(self):
        shapes = []

        def fn(batch):
            shapes.append(batch.shape[0])
            return batch

        with DynamicBatcher(fn, max_batch_size=4, max_wait_ms=0.5) as b:
            out = b.submit(np.ones((10, 2)))
        assert out.shape == (10, 2)
        assert shapes == [10]


class TestMultiSignatureBatcher:
    def test_routes_by_trailing_shape(self):
        shapes = []

        def fn(batch):
            shapes.append(batch.shape)
            return batch.sum(axis=tuple(range(1, batch.ndim)), keepdims=False)[:, None]

        with MultiSignatureBatcher(fn, max_batch_size=8, max_wait_ms=0.5) as b:
            out_a = b.submit(np.ones((3, 4)))
            out_b = b.submit(np.ones((2, 6)))
        np.testing.assert_array_equal(out_a, np.full((3, 1), 4.0))
        np.testing.assert_array_equal(out_b, np.full((2, 1), 6.0))
        assert sorted(b.signatures) == [("<f8", (4,)), ("<f8", (6,))]
        # each signature got its own padded device call
        assert sorted(shapes) == [(2, 6), (4, 4)]

    def test_routes_by_dtype(self):
        dtypes = []

        def fn(batch):
            dtypes.append(batch.dtype.name)
            return batch

        with MultiSignatureBatcher(fn, max_batch_size=4, max_wait_ms=0.5) as b:
            b.submit(np.ones((1, 2), np.float32))
            b.submit(np.ones((1, 2), np.uint8))
        assert sorted(dtypes) == ["float32", "uint8"]

    def test_concurrent_mixed_shapes(self):
        def fn(batch):
            return batch * 2

        b = MultiSignatureBatcher(fn, max_batch_size=16, max_wait_ms=5.0)
        b.start()
        results = {}
        release = threading.Event()

        def worker(i):
            release.wait()
            width = 3 if i % 2 else 5
            results[i] = b.submit(np.full((1, width), float(i)))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        release.set()
        for t in threads:
            t.join()
        b.stop()
        for i in range(8):
            width = 3 if i % 2 else 5
            np.testing.assert_array_equal(results[i], np.full((1, width), 2.0 * i))
        assert b.stats.requests == 8

    def test_signature_cap(self):
        with MultiSignatureBatcher(lambda b: b, max_wait_ms=0.1, max_signatures=2) as b:
            b.submit(np.ones((1, 1)))
            b.submit(np.ones((1, 2)))
            with pytest.raises(ValueError, match="max_signatures"):
                b.submit(np.ones((1, 3)))

    def test_not_started_rejects(self):
        b = MultiSignatureBatcher(lambda x: x)
        with pytest.raises(RuntimeError, match="not started"):
            b.submit(np.ones((1, 2)))


@pytest.fixture(scope="module")
def mlp_server():
    from seldon_core_tpu.models.jaxserver import JaxServer

    server = JaxServer(
        model="mlp", num_classes=3, input_shape=(4,), dtype="float32",
        max_batch_size=8, max_wait_ms=1.0, warmup_dtypes=("float32",),
    )
    server.load()
    yield server
    server.unload()


class TestJaxServer:
    def test_predict_shapes(self, mlp_server):
        out = mlp_server.predict(np.ones((2, 4), np.float32), [])
        assert out.shape == (2, 3)

    def test_single_example_auto_batched(self, mlp_server):
        out = mlp_server.predict(np.ones(4, np.float32), [])
        assert out.shape == (3,)

    def test_deterministic(self, mlp_server):
        x = np.random.default_rng(0).normal(size=(3, 4)).astype(np.float32)
        a = mlp_server.predict(x, [])
        b = mlp_server.predict(x, [])
        np.testing.assert_allclose(a, b, rtol=1e-6)

    def test_bad_shape_rejected(self, mlp_server):
        with pytest.raises(MicroserviceError):
            mlp_server.predict(np.ones((2, 7), np.float32), [])

    def test_through_dispatch(self, mlp_server):
        msg = InternalMessage(payload=np.ones((1, 4), np.float32), kind="rawTensor")
        out = dispatch.predict(mlp_server, msg)
        assert np.asarray(out.payload).shape == (1, 3)
        assert out.names == ["t:0", "t:1", "t:2"]
        assert any(m["key"] == "jaxserver_mean_batch_rows" for m in out.meta.metrics)

    def test_softmax_option(self):
        from seldon_core_tpu.models.jaxserver import JaxServer

        server = JaxServer(
            model="mlp", num_classes=3, input_shape=(4,), dtype="float32",
            softmax_outputs=True, max_batch_size=4,
        )
        server.load()
        out = server.predict(np.ones((2, 4), np.float32), [])
        np.testing.assert_allclose(out.sum(axis=-1), 1.0, rtol=1e-5)
        server.unload()

    def test_checkpoint_roundtrip(self, tmp_path):
        import jax
        from flax import serialization

        from seldon_core_tpu.models.jaxserver import JaxServer
        from seldon_core_tpu.models.mlp import MLPClassifier

        # train-side: init and save a checkpoint
        module = MLPClassifier(num_classes=3)
        variables = module.init(jax.random.key(42), np.zeros((1, 4), np.float32))
        ckpt = tmp_path / "model.msgpack"
        ckpt.write_bytes(serialization.to_bytes(variables))

        server = JaxServer(
            model="mlp", model_uri=str(ckpt), num_classes=3, input_shape=(4,),
            dtype="float32", max_batch_size=4, warmup=False,
        )
        server.load()
        x = np.ones((1, 4), np.float32)
        expected = module.apply(variables, x)
        np.testing.assert_allclose(server.predict(x, []), np.asarray(expected), rtol=1e-5)
        server.unload()

    def test_warmup_covers_normalized_buckets(self):
        """ADVICE r1: user buckets not ending at max_batch_size must
        still pre-compile the forced final bucket — no request pays a
        trace mid-traffic."""
        from seldon_core_tpu.models.jaxserver import JaxServer

        server = JaxServer(
            model="mlp", num_classes=3, input_shape=(4,), dtype="float32",
            max_batch_size=8, buckets=[1, 2], warmup_dtypes=("float32",),
        )
        server.load()
        try:
            assert server.batcher.buckets == [1, 2, 8]
            # warmup compiled exactly one program per (bucket, dtype)
            assert server._predict_jit._cache_size() == 3
            server.predict(np.ones((8, 4), np.float32), [])
            assert server._predict_jit._cache_size() == 3  # no new trace
        finally:
            server.unload()

    def test_builtin_registration(self):
        import seldon_core_tpu.models  # noqa: F401 — triggers registration
        from seldon_core_tpu.engine.units import BUILTIN_IMPLEMENTATIONS

        assert "JAX_SERVER" in BUILTIN_IMPLEMENTATIONS


class TestMultiSignatureServing:
    def test_transformer_two_context_lengths(self):
        """One server, two context-length signatures, one weight set."""
        from seldon_core_tpu.models.jaxserver import JaxServer

        server = JaxServer(
            model="transformer_encoder", num_classes=3, dtype="float32",
            input_shape=(16,), extra_input_shapes=[(32,)],
            max_batch_size=4, max_wait_ms=0.5, warmup=False,
            warmup_dtypes=("int32",),
            model_kwargs={"vocab_size": 64, "d_model": 32, "num_layers": 1,
                          "num_heads": 2, "max_len": 64},
        )
        server.load()
        rng = np.random.default_rng(0)
        short = rng.integers(0, 64, size=(2, 16)).astype(np.int32)
        long = rng.integers(0, 64, size=(2, 32)).astype(np.int32)
        out_short = server.predict(short, [])
        out_long = server.predict(long, [])
        assert out_short.shape == (2, 3) and out_long.shape == (2, 3)
        assert sorted(server.batcher.signatures) == [("<i4", (16,)), ("<i4", (32,))]
        # parity with a direct module apply at the longer signature
        direct = np.asarray(server.module.apply(server.variables, long))
        np.testing.assert_allclose(out_long, direct, rtol=2e-4, atol=2e-4)
        # a length outside the served signatures is rejected, not retraced
        with pytest.raises(MicroserviceError):
            server.predict(rng.integers(0, 64, size=(2, 24)).astype(np.int32), [])
        status = server.health_status()
        assert status["signatures"] == [[16], [32]]
        # ... and where it ran, and what the batcher did: two 2-row
        # requests, each padded into the 2-bucket of its own signature
        import jax

        devices = jax.devices()
        assert status["device"] == {
            "platform": "cpu", "kind": devices[0].device_kind,
            "count": len(devices), "pallas_interpret": True,
        }
        assert status["batcher"] == {"batches": 2, "rows": 4, "padded_rows": 0}
        server.unload()


class TestModelZoo:
    def test_resnet_tiny_forward(self):
        import jax

        from seldon_core_tpu.models.resnet import ResNetTiny

        module = ResNetTiny(num_classes=10, dtype=np.float32)
        variables = module.init(jax.random.key(0), np.zeros((1, 32, 32, 3), np.float32))
        out = module.apply(variables, np.ones((2, 32, 32, 3), np.float32))
        assert out.shape == (2, 10)

    def test_resnet50_param_count(self):
        """ResNet-50 structure check without running the full forward."""
        import jax

        from seldon_core_tpu.models.resnet import ResNet50

        module = ResNet50(num_classes=1000)
        variables = jax.eval_shape(
            lambda: module.init(jax.random.key(0), np.zeros((1, 224, 224, 3), np.float32))
        )
        n_params = sum(np.prod(x.shape) for x in jax.tree.leaves(variables["params"]))
        # canonical ResNet-50 has ~25.5M parameters
        assert 25_000_000 < n_params < 26_000_000


class TestTopK:
    def test_topk_output_layout(self):
        from seldon_core_tpu.models.jaxserver import JaxServer

        server = JaxServer(model="mlp", num_classes=10, input_shape=(4,), dtype="float32",
                           softmax_outputs=True, top_k=3, max_batch_size=4,
                           warmup=False, warmup_dtypes=("float32",))
        server.load()
        x = np.random.default_rng(0).normal(size=(2, 4)).astype(np.float32)
        out = server.predict(x, [])
        assert out.shape == (2, 2, 3)  # [batch, (indices, scores), k]
        indices, scores = out[:, 0, :], out[:, 1, :]
        # scores sorted descending, indices are valid classes
        assert (np.diff(scores, axis=1) <= 1e-6).all()
        assert ((indices >= 0) & (indices < 10)).all()
        # parity with full logits top-k
        full = JaxServer(model="mlp", num_classes=10, input_shape=(4,), dtype="float32",
                         softmax_outputs=True, max_batch_size=4, warmup=False,
                         warmup_dtypes=("float32",), seed=0)
        full.load()
        logits = full.predict(x, [])
        np.testing.assert_allclose(np.sort(logits, axis=1)[:, -3:][:, ::-1], scores, rtol=1e-5)
        server.unload(); full.unload()


class TestViT:
    def test_vit_tiny_serves_images(self):
        from seldon_core_tpu.models.jaxserver import JaxServer

        server = JaxServer(
            model="vit_tiny", num_classes=10, input_shape=(32, 32, 3),
            dtype="float32", max_batch_size=4, warmup=False,
            warmup_dtypes=("float32",),
        )
        server.load()
        out = server.predict(np.zeros((2, 32, 32, 3), np.float32), [])
        arr = np.asarray(out)
        assert arr.shape == (2, 10)
        assert np.isfinite(arr).all()
        server.unload()

    def test_vit_patch_and_cls_shapes(self):
        import jax
        import jax.numpy as jnp

        from seldon_core_tpu.models.vit import ViTTiny

        m = ViTTiny(num_classes=5, dtype=jnp.float32)
        variables = m.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
        # 32/8 = 4 -> 16 patches + CLS = 17 positions
        assert variables["params"]["pos_embed"].shape == (1, 17, 64)
        logits = m.apply(variables, jnp.ones((3, 32, 32, 3)))
        assert logits.shape == (3, 5)

    def test_position_interpolation_serves_multiple_resolutions(self):
        """One ViT checkpoint, several input resolutions: pos_embed is
        anchored at pos_grid and bicubically resized at trace time."""
        import jax
        import jax.numpy as jnp

        from seldon_core_tpu.models.vit import ViTTiny

        m = ViTTiny(num_classes=5, dtype=jnp.float32)
        variables = m.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
        for res in (48, 64):
            logits = m.apply(variables, jnp.ones((2, res, res, 3)))
            assert logits.shape == (2, 5)
            assert np.isfinite(np.asarray(logits)).all()
        # still rejects non-multiples of patch_size
        with pytest.raises(ValueError):
            m.apply(variables, jnp.ones((1, 33, 33, 3)))

    def test_interpolation_is_identity_at_native_resolution(self):
        """pos_grid must not perturb the native path: a legacy
        (pos_grid=0) module with the same params produces bitwise-equal
        logits at the anchor resolution."""
        import jax
        import jax.numpy as jnp

        from seldon_core_tpu.models.vit import ViTTiny

        anchored = ViTTiny(num_classes=5, dtype=jnp.float32)
        legacy = ViTTiny(num_classes=5, dtype=jnp.float32, pos_grid=0)
        variables = anchored.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
        x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 32, 32, 3)), jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(anchored.apply(variables, x)),
            np.asarray(legacy.apply(variables, x)),
        )

    def test_multi_resolution_through_jaxserver_signatures(self):
        """Serving-side: extra_input_shapes + pos_grid = one server, one
        checkpoint, several resolutions (MultiSignatureBatcher path)."""
        from seldon_core_tpu.models.jaxserver import JaxServer

        server = JaxServer(
            model="vit_tiny", num_classes=10, input_shape=(32, 32, 3),
            extra_input_shapes=[(48, 48, 3)],
            dtype="float32", max_batch_size=4, warmup=False,
            warmup_dtypes=("float32",),
        )
        server.load()
        small = np.asarray(server.predict(np.zeros((2, 32, 32, 3), np.float32), []))
        large = np.asarray(server.predict(np.zeros((2, 48, 48, 3), np.float32), []))
        assert small.shape == (2, 10) and large.shape == (2, 10)
        assert np.isfinite(small).all() and np.isfinite(large).all()
        server.unload()


class TestFlashAttentionServing:
    def test_transformer_served_with_flash_attention(self):
        from seldon_core_tpu.models.jaxserver import JaxServer

        server = JaxServer(
            model="transformer_encoder", num_classes=3, input_shape=(32,),
            dtype="float32", max_batch_size=2, warmup=False,
            warmup_dtypes=("int32",),
            model_kwargs={"vocab_size": 64, "d_model": 32, "num_layers": 1,
                          "num_heads": 2, "max_len": 32, "attention": "flash"},
        )
        server.load()
        out = np.asarray(server.predict(np.zeros((2, 32), np.int32), []))
        assert out.shape == (2, 3) and np.isfinite(out).all()
        server.unload()

    def test_unknown_attention_rejected(self):
        from seldon_core_tpu.models.jaxserver import JaxServer
        from seldon_core_tpu.runtime.component import MicroserviceError

        server = JaxServer(
            model="transformer_encoder", num_classes=3, input_shape=(32,),
            dtype="float32", warmup=False,
            model_kwargs={"vocab_size": 64, "max_len": 32, "attention": "nope"},
        )
        with pytest.raises(MicroserviceError):
            server.load()

    def test_vit_accepts_flash_attention(self):
        from seldon_core_tpu.models.jaxserver import JaxServer

        server = JaxServer(
            model="vit_tiny", num_classes=10, input_shape=(32, 32, 3),
            dtype="float32", max_batch_size=2, warmup=False,
            warmup_dtypes=("float32",),
            model_kwargs={"attention": "flash"},
        )
        server.load()
        out = np.asarray(server.predict(np.zeros((2, 32, 32, 3), np.float32), []))
        assert out.shape == (2, 10) and np.isfinite(out).all()
        server.unload()
