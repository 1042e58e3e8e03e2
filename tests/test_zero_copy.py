"""Zero-copy buffer-view data plane (r14, docs §9a).

Covers the whole lane: BufferView semantics + the SRT1 framing
agreement (Python vs the C ABI table), the native ingress frame lanes
(HTTP + h2c gRPC PredictRaw), by-reference transport telemetry, the
engines' batched view submission (jaxserver + paged — bit-exact vs
per-request), and the SELDON_TPU_ZERO_COPY=0 parity gate.
"""

import asyncio
import base64
import json
import threading

import numpy as np
import pytest

from seldon_core_tpu import codec
from seldon_core_tpu.codec import bufview
from seldon_core_tpu.codec.bufview import BufferView


# ---------------------------------------------------------------------------
# BufferView semantics
# ---------------------------------------------------------------------------


class TestBufferView:
    def test_from_array_is_zero_copy_and_shares_memory(self):
        arr = np.arange(12, dtype=np.float32).reshape(3, 4)
        view = BufferView.from_array(arr)
        assert not view.copied
        got = view.array()
        assert got is arr  # the exact array, not even a new view object
        # np.asarray interop resolves through __array__, still the view
        assert np.asarray(view) is arr

    def test_from_array_non_contiguous_compacts_once_and_flags_it(self):
        strided = np.arange(24, dtype=np.float32).reshape(4, 6)[:, ::2]
        view = BufferView.from_array(strided)
        assert view.copied
        np.testing.assert_array_equal(view.array(), strided)

    def test_from_bytes_is_view_over_the_buffer(self):
        payload = np.arange(8, dtype=np.int32).tobytes()
        view = BufferView.from_bytes(payload, "int32", (2, 4))
        arr = view.array()
        assert not arr.flags.writeable
        root = arr
        while getattr(root, "base", None) is not None:
            root = root.base
        # rooted in the ingress buffer -> no copy between wire and array
        assert bytes(root) == payload

    def test_from_bytes_misaligned_names_offset_and_dtype(self):
        with pytest.raises(codec.PayloadError) as e:
            BufferView.from_bytes(b"\x00" * 10, "float32", (3,), offset=1)
        msg = str(e.value)
        assert "offset 1" in msg and "float32" in msg

    def test_buffer_too_small_is_payload_error(self):
        with pytest.raises(codec.PayloadError):
            BufferView("float32", (4, 4), b"\x00" * 8)

    def test_zero_d_and_empty(self):
        scalar = BufferView.from_bytes(
            np.float32(2.5).tobytes(), "float32", ()
        )
        assert scalar.shape == () and float(scalar.array()) == 2.5
        empty = BufferView.from_array(np.empty((0, 7), np.int8))
        assert empty.nbytes == 0 and empty.array().shape == (0, 7)


# ---------------------------------------------------------------------------
# SRT1 framing: round-trips + the C ABI agreement
# ---------------------------------------------------------------------------


class TestFraming:
    @pytest.mark.parametrize("dtype", ["float32", "int8", "bfloat16", "uint8",
                                       "int64", "float16"])
    @pytest.mark.parametrize("shape", [(), (0,), (5,), (2, 3, 4), (1, 4096)])
    def test_frame_roundtrip_bit_exact(self, dtype, shape):
        dt = codec.np_dtype(dtype)
        n = int(np.prod(shape)) if shape else 1
        src = (np.arange(n) % 100 + 1).astype(dt).reshape(shape)
        view = bufview.unpack_frame(bufview.pack_frame(src))
        assert view.dtype == dt and view.shape == tuple(shape)
        assert view.tobytes() == src.tobytes()
        assert not view.copied

    def test_payload_is_8_byte_aligned_in_frame(self):
        for ndim in range(0, 9):
            shape = (1,) * ndim
            frame = bufview.pack_frame(np.zeros(shape, np.float64))
            # header = 8 + 8*ndim: always a multiple of 8
            assert (len(frame) - 8) % 8 == 0
            assert bufview.frame_header(np.dtype(np.float64), shape) == \
                frame[: 8 + 8 * ndim]

    def test_frame_is_little_endian(self):
        frame = bufview.pack_frame(np.array([1], dtype="<i4"))
        assert frame[:4] == b"SRT1"
        assert frame[-4:] == (1).to_bytes(4, "little")

    def test_big_endian_source_is_byteswapped_not_corrupted(self):
        # dtype('>f4').name drops the byte order, so without the
        # encode-side swap the payload would decode as garbage
        be = np.array([1.0, 2.0], dtype=">f4")
        out = bufview.unpack_frame(bufview.pack_frame(be)).array()
        np.testing.assert_array_equal(out, [1.0, 2.0])
        assert out.dtype == np.dtype("<f4")

    def test_multi_frame_container_roundtrip_and_alignment(self):
        payloads = [
            np.arange(3, dtype=np.int8),           # 3-byte payload: pad needed
            np.arange(4, dtype=np.float32).reshape(2, 2),
            np.array([7], dtype=np.int64),
        ]
        blob = bufview.pack_frames(payloads)
        views = bufview.unpack_frames(blob)
        assert len(views) == 3
        for src, v in zip(payloads, views):
            assert v.tobytes() == src.tobytes() and v.shape == src.shape
            assert not v.copied  # views over the container, zero copy
        # single frame: container == plain frame, both decoders agree
        one = bufview.pack_frames([payloads[1]])
        assert one == bufview.pack_frame(payloads[1])
        assert len(bufview.unpack_frames(one)) == 1

    def test_multi_frame_bad_padding_raises(self):
        blob = bytearray(bufview.pack_frames(
            [np.arange(3, dtype=np.int8), np.arange(2, dtype=np.int8)]
        ))
        # corrupt an inter-frame pad byte: frame 1 = 8 header + 8 shape
        # + 3 payload = 19 bytes, padded to 24 — offsets 19-23 are pad
        blob[20] = 0xFF
        with pytest.raises(codec.PayloadError) as e:
            bufview.unpack_frames(bytes(blob))
        assert "padding" in str(e.value)

    @pytest.mark.parametrize("mutate,needle", [
        (lambda f: b"XXXX" + f[4:], "magic"),
        (lambda f: f[:4] + bytes([99]) + f[5:], "dtype code 99"),
        (lambda f: f[:16], "shape"),
        (lambda f: f[:6], "truncated"),
        (lambda f: f + b"\x00", "carries"),
    ])
    def test_malformed_frames_raise_named_payload_errors(self, mutate, needle):
        frame = bufview.pack_frame(np.arange(6, dtype=np.float32).reshape(2, 3))
        with pytest.raises(codec.PayloadError) as e:
            bufview.unpack_frame(mutate(frame))
        assert needle in str(e.value)

    def test_overflow_crafted_shape_fails_validation_like_cpp(self):
        # shape [2**32, 2**32] wraps an int64 product to 0: must be a
        # NAMED validation error at unpack (parity with srt1_payload_
        # bytes' kMaxElems guard), never a later numpy reshape error
        import struct as _struct

        frame = (_struct.pack("<IBBH", bufview.SRT1_MAGIC, 0, 2, 0)
                 + _struct.pack("<2q", 1 << 32, 1 << 32))
        with pytest.raises(codec.PayloadError) as e:
            bufview.unpack_frame(frame)
        assert "ceiling" in str(e.value)
        # the C++ validator rejects the identical bytes
        import ctypes

        from seldon_core_tpu.native import get_lib

        lib = get_lib()
        if lib is not None and hasattr(lib, "srt1_payload_bytes"):
            buf = (ctypes.c_uint8 * len(frame)).from_buffer_copy(frame)
            assert lib.srt1_payload_bytes(buf, len(frame)) == -1

    def test_c_abi_agreement(self):
        """The three SRT1 implementations cannot drift: the C table
        (native/codec.cc srt1_*) must agree with SRT1_DTYPES, header
        sizing and full-frame validation byte-for-byte."""
        import ctypes

        from seldon_core_tpu.native import get_lib

        lib = get_lib()
        if lib is None or not hasattr(lib, "srt1_item_size"):
            pytest.skip("native library not built")
        assert lib.srt1_magic() == bufview.SRT1_MAGIC
        for code, name in enumerate(bufview.SRT1_DTYPES):
            assert lib.srt1_item_size(code) == codec.np_dtype(name).itemsize, name
        assert lib.srt1_item_size(len(bufview.SRT1_DTYPES)) == -1
        for ndim in range(0, 9):
            assert lib.srt1_header_bytes(ndim) == 8 + 8 * ndim
        assert lib.srt1_header_bytes(9) == -1
        # full-frame validation parity on good and bad frames
        good = bufview.pack_frame(np.arange(10, dtype=np.int8).reshape(2, 5))
        bad = good[:4] + bytes([99]) + good[5:]

        def c_payload_bytes(frame):
            buf = (ctypes.c_uint8 * len(frame)).from_buffer_copy(frame)
            return lib.srt1_payload_bytes(buf, len(frame))

        assert c_payload_bytes(good) == 10
        assert c_payload_bytes(bad) == -1

    def test_stack_views_single_view_is_passthrough(self):
        arr = np.arange(8, dtype=np.float32).reshape(2, 4)
        batch, offsets = bufview.stack_views([BufferView.from_array(arr)])
        assert batch is arr  # NO copy for a lone full batch
        assert offsets == [0, 2]

    def test_stack_views_many_one_allocation(self):
        views = [
            BufferView.from_array(np.full((r, 3), r, np.float32))
            for r in (1, 2, 3)
        ]
        batch, offsets = bufview.stack_views(views)
        assert batch.shape == (6, 3) and offsets == [0, 1, 3, 6]
        for i, r in enumerate((1, 2, 3)):
            assert (batch[offsets[i]:offsets[i + 1]] == r).all()

    def test_stack_views_shape_mismatch_names_the_culprit(self):
        with pytest.raises(codec.PayloadError) as e:
            bufview.stack_views([np.zeros((1, 3), np.float32),
                                 np.zeros((1, 4), np.float32)])
        assert "view 1" in str(e.value)


# ---------------------------------------------------------------------------
# message + transport integration
# ---------------------------------------------------------------------------


class TestMessageIntegration:
    def test_internal_message_view_payload_degrades_to_proto(self):
        from seldon_core_tpu.runtime.message import InternalMessage

        arr = np.arange(6, dtype=np.float32).reshape(2, 3)
        msg = InternalMessage(payload=BufferView.from_array(arr), kind="rawTensor")
        # host_payload materialises the VIEW (no copy)
        assert msg.host_payload() is arr
        # remote boundaries degrade cleanly to the ordinary rawTensor
        proto = msg.to_proto()
        assert proto.data.WhichOneof("data_oneof") == "rawTensor"
        assert proto.data.rawTensor.data == arr.tobytes()
        body = msg.to_json()
        assert base64.b64decode(body["data"]["rawTensor"]["data"]) == arr.tobytes()

    def test_local_client_meters_zero_copy_bytes(self):
        import prometheus_client as prom

        from seldon_core_tpu.engine.graph import UnitSpec
        from seldon_core_tpu.engine.transport import LocalClient
        from seldon_core_tpu.runtime.message import InternalMessage

        class Echo:
            def predict(self, X, names, meta=None):
                return np.asarray(X)

        unit = UnitSpec(name="zc-meter", type="MODEL", component=Echo())
        client = LocalClient(unit, Echo())
        arr = np.arange(100, dtype=np.float32)
        msg = InternalMessage(payload=BufferView.from_array(arr), kind="rawTensor")

        asyncio.new_event_loop().run_until_complete(client.transform_input(msg))
        got = prom.REGISTRY.get_sample_value(
            "seldon_tpu_transport_zero_copy_bytes_total",
            {"unit": "zc-meter", "method": "predict", "transport": "local"},
        )
        assert got is not None and got >= arr.nbytes

    def test_plain_ndarray_payload_does_not_count_as_zero_copy(self):
        from seldon_core_tpu.engine.transport import LocalClient

        assert LocalClient._ref_bytes(
            type("M", (), {"payload": np.zeros(4)})()
        ) == 0
        view_msg = type("M", (), {"payload": BufferView.from_array(np.zeros(4))})()
        assert LocalClient._ref_bytes(view_msg) == 32


# ---------------------------------------------------------------------------
# engines: batched view submission, bit-exact vs per-request
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mlp_server():
    from seldon_core_tpu.models.jaxserver import JaxServer

    server = JaxServer(
        model="mlp", num_classes=5, input_shape=(8,), dtype="float32",
        warmup_dtypes=("float32",), max_batch_size=16, warmup=True,
    )
    server.load()
    yield server
    server.unload()


def _assert_batched_contract(server, arrays, outs):
    """What a batched submission promises of its rows: bit-equal to the
    same rows of a batched call in the same bucket (here the stacked
    rows through ``predict``), and equal to a per-request call within
    1e-6 — that one is ANOTHER XLA program (a single request pads to a
    narrower bucket), and two programs of one model may differ by an
    f32 ulp of a logit's terms (1.19e-07 on a logit near 1 has been
    seen here since the seed).  Relative to the request's largest
    logit: a sum's rounding error follows its terms, so a logit near 0
    misses a purely relative bound by as little (2.8e-08 on -0.0108)."""
    assert len(outs) == len(arrays)
    stacked = np.asarray(server.predict(np.concatenate(arrays), []))
    off = 0
    for a, o in zip(arrays, outs):
        rows = a.shape[0]
        np.testing.assert_array_equal(
            np.asarray(o).reshape(rows, -1), stacked[off:off + rows])
        ref = np.asarray(server.predict(a, []))
        np.testing.assert_allclose(
            np.asarray(o).reshape(ref.shape), ref, rtol=1e-6,
            atol=1e-6 * float(np.abs(ref).max()))
        off += rows
    assert off == stacked.shape[0]


class TestJaxServerViews:
    def test_raw_batch_views_matches_per_request_predict(self, mlp_server):
        rng = np.random.default_rng(3)
        arrays = [rng.normal(size=(r, 8)).astype(np.float32) for r in (1, 3, 2)]
        views = [BufferView.from_array(a) for a in arrays]
        outs = mlp_server.raw_batch_views(views)
        assert [o.shape[0] for o in outs] == [1, 3, 2]
        _assert_batched_contract(mlp_server, arrays, outs)

    def test_raw_batch_views_accepts_frames_end_to_end(self, mlp_server):
        x = np.ones((2, 8), np.float32)
        view = bufview.unpack_frame(bufview.pack_frame(x))
        (out,) = mlp_server.raw_batch_views([view])
        ref = np.asarray(mlp_server.predict(x, []))
        np.testing.assert_array_equal(out.reshape(ref.shape), ref)

    def test_mixed_dtype_wave_canonicalises(self, mlp_server):
        outs = mlp_server.raw_batch_views([
            np.ones((1, 8), np.float32),
            np.ones((1, 8), np.float64),  # not warmed: canonicalises
        ])
        np.testing.assert_array_equal(outs[0], outs[1])


def test_paged_submit_views_rolls_back_on_partial_admission():
    """All-or-nothing admission: when a later view's admission fails,
    the already-admitted streams are cancelled — not left decoding
    tokens nobody holds a handle to."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.paged import PagedEngine
    from seldon_core_tpu.models.transformer import TransformerLM
    from seldon_core_tpu.runtime.component import MicroserviceError

    cfg = dict(vocab_size=64, d_model=32, num_layers=1, num_heads=2, max_len=64)
    lm = TransformerLM(dtype=jnp.float32, **cfg)
    params = lm.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    eng = PagedEngine(params, dtype=jnp.float32, page_size=8, max_slots=2,
                      steps_per_call=4, **cfg)
    try:
        ok = np.arange(5, dtype=np.int32) % 64
        too_long = np.arange(80, dtype=np.int32) % 64  # > max_len
        with pytest.raises(MicroserviceError):
            eng.submit_views([ok, ok, too_long], max_new_tokens=4)
        # both admitted streams rolled back: nothing left queued
        assert eng.engine_stats()["queued_streams"] == 0
    finally:
        eng.close()


def test_paged_submit_views_bit_exact_vs_submit():
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.paged import PagedEngine
    from seldon_core_tpu.models.transformer import TransformerLM

    cfg = dict(vocab_size=64, d_model=32, num_layers=1, num_heads=2, max_len=128)
    lm = TransformerLM(dtype=jnp.float32, **cfg)
    params = lm.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    eng = PagedEngine(params, dtype=jnp.float32, page_size=8, max_slots=2,
                      steps_per_call=4, **cfg)
    try:
        prompts = [
            np.arange(5, dtype=np.int32) % 64,
            (np.arange(9, dtype=np.int32) * 3) % 64,
        ]
        views = [
            bufview.unpack_frame(bufview.pack_frame(p)) for p in prompts
        ]
        batched = eng.submit_views(views, max_new_tokens=6)
        eng.run()
        ref = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run()
        for b, r in zip(batched, ref):
            assert b.error is None and r.error is None
            # greedy decode bit-exact: view-submitted == array-submitted
            np.testing.assert_array_equal(np.asarray(b.result),
                                          np.asarray(r.result))
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# ingress lanes (HTTP frame lane, gRPC PredictRaw, knob-off parity)
# ---------------------------------------------------------------------------


@pytest.fixture()
def loop_thread():
    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    yield loop
    loop.call_soon_threadsafe(loop.stop)
    thread.join(timeout=5)


def _gateway(component, two_node=False):
    from seldon_core_tpu.engine import PredictorService, UnitSpec
    from seldon_core_tpu.engine.server import Gateway

    model = UnitSpec(name="m", type="MODEL", component=component)
    if two_node:
        class Identity:
            def transform_input(self, X, names, meta=None):
                return np.asarray(X)

        root = UnitSpec(name="pre", type="TRANSFORMER", component=Identity(),
                        children=[model])
    else:
        root = model
    return Gateway([(PredictorService(root, name="p"), 1.0)])


class Doubler:
    def predict(self, X, names, meta=None):
        return np.asarray(X) * 2


class TestIngressFrameLane:
    def _handler(self, loop, two_node=True):
        from seldon_core_tpu.native.frontserver import GatewayRawHandler

        return GatewayRawHandler(_gateway(Doubler(), two_node=two_node), loop)

    def test_http_frame_lane_roundtrip(self, loop_thread):
        handler = self._handler(loop_thread)
        x = np.arange(8, dtype=np.float32).reshape(2, 4)
        status, ctype, body = handler(
            "POST", "/api/v0.1/predictions", bufview.pack_frame(x)
        )
        assert status == 200 and ctype == "application/x-seldon-raw"
        np.testing.assert_array_equal(
            bufview.unpack_frame(body).array(), x * 2
        )

    def test_frame_lane_bit_exact_vs_json_lane(self, loop_thread):
        handler = self._handler(loop_thread)
        x = np.linspace(-1, 1, 12, dtype=np.float32).reshape(3, 4)
        _, _, frame_body = handler(
            "POST", "/api/v0.1/predictions", bufview.pack_frame(x)
        )
        out_on = bufview.unpack_frame(frame_body).array()
        jreq = json.dumps({"data": {"rawTensor": {
            "shape": [3, 4], "dtype": "float32",
            "data": base64.b64encode(x.tobytes()).decode(),
        }}}).encode()
        status, _, jbody = handler("POST", "/api/v0.1/predictions", jreq)
        assert status == 200
        rt = json.loads(jbody)["data"]["rawTensor"]
        out_off = np.frombuffer(
            base64.b64decode(rt["data"]), dtype=rt["dtype"]
        ).reshape(3, 4)
        assert out_on.tobytes() == out_off.tobytes()  # bit-exact lanes

    def test_multi_frame_container_serves_batched(self, loop_thread, mlp_server):
        # the batched-submission surface: N frames in one body -> ONE
        # raw_batch_views micro-batch -> a response container
        from seldon_core_tpu.native.frontserver import GatewayRawHandler

        handler = GatewayRawHandler(_gateway(mlp_server, two_node=False),
                                    loop_thread)
        xs = [np.full((r, 8), r, np.float32) for r in (1, 2)]
        status, ctype, body = handler(
            "POST", "/predict", bufview.pack_frames(xs)
        )
        assert status == 200 and ctype == "application/x-seldon-raw"
        outs = bufview.unpack_frames(body)
        _assert_batched_contract(mlp_server, xs, [o.array() for o in outs])

    def test_multi_frame_needs_single_local_model(self, loop_thread):
        # a 2-node graph cannot serve the bookkeeping-bypassing batched
        # container: clear 400, not a wrong answer
        handler = self._handler(loop_thread, two_node=True)
        status, ctype, body = handler(
            "POST", "/predict",
            bufview.pack_frames([np.ones((1, 4), np.float32)] * 2),
        )
        assert status == 400
        assert "single-local-MODEL" in json.loads(body)["status"]["info"]

    def test_single_model_gateway_takes_predict_sync_path(self, loop_thread):
        # single local MODEL: the frame lane runs on the calling thread
        # (predict_sync) — the response must still be correct even
        # though the loop never sees the request
        handler = self._handler(loop_thread, two_node=False)
        x = np.ones((1, 4), np.float32)
        status, ctype, body = handler(
            "POST", "/predict", bufview.pack_frame(x)
        )
        assert status == 200 and ctype == "application/x-seldon-raw"
        np.testing.assert_array_equal(
            bufview.unpack_frame(body).array(), x * 2
        )

    def test_malformed_frame_is_400_json(self, loop_thread):
        handler = self._handler(loop_thread)
        bad = bufview.pack_frame(np.ones(4, np.float32))[:-2]
        status, ctype, body = handler("POST", "/predict", b"SRT1" + bad[4:])
        assert status == 400 and ctype == "application/json"
        assert json.loads(body)["status"]["reason"] == "BAD_REQUEST"

    def test_lane_off_rejects_frames_with_remedy(self, loop_thread, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_ZERO_COPY", "0")
        handler = self._handler(loop_thread)
        status, _, body = handler(
            "POST", "/predict", bufview.pack_frame(np.ones(4, np.float32))
        )
        assert status == 400
        assert "SELDON_TPU_ZERO_COPY" in json.loads(body)["status"]["info"]

    def test_lane_off_json_path_is_untouched(self, loop_thread, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_ZERO_COPY", "0")
        handler = self._handler(loop_thread)
        status, _, body = handler(
            "POST", "/api/v0.1/predictions",
            json.dumps({"data": {"ndarray": [[1.0, 2.0, 3.0, 4.0]]}}).encode(),
        )
        assert status == 200
        out = json.loads(body)
        assert out["data"]["ndarray"] == [[2.0, 4.0, 6.0, 8.0]]


class TestGrpcPredictRaw:
    def _handler(self, loop):
        from seldon_core_tpu.engine.native_ingress import _DeploymentGrpcHandler

        return _DeploymentGrpcHandler(_gateway(Doubler(), two_node=True), loop)

    def test_predict_raw_roundtrip(self, loop_thread):
        handler = self._handler(loop_thread)
        x = np.arange(6, dtype=np.float32).reshape(2, 3)
        status, msg, payload = handler(
            "/seldon.protos.Seldon/PredictRaw", bufview.pack_frame(x)
        )
        assert status == 0, msg
        np.testing.assert_array_equal(
            bufview.unpack_frame(payload).array(), x * 2
        )

    def test_predict_raw_malformed_is_invalid_argument(self, loop_thread):
        handler = self._handler(loop_thread)
        status, msg, _ = handler("/seldon.protos.Seldon/PredictRaw", b"SRT1xx")
        assert status == 3 and "SRT1" in msg

    def test_predict_raw_gated_off_is_unimplemented(self, loop_thread, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_ZERO_COPY", "0")
        handler = self._handler(loop_thread)
        status, msg, _ = handler(
            "/seldon.protos.Seldon/PredictRaw",
            bufview.pack_frame(np.ones(3, np.float32)),
        )
        assert status == 12 and "SELDON_TPU_ZERO_COPY" in msg

    def test_predict_raw_multi_frame_batched(self, loop_thread, mlp_server):
        from seldon_core_tpu.engine.native_ingress import _DeploymentGrpcHandler

        handler = _DeploymentGrpcHandler(
            _gateway(mlp_server, two_node=False), loop_thread
        )
        xs = [np.full((r, 8), 0.5 * r, np.float32) for r in (2, 1)]
        status, msg, payload = handler(
            "/seldon.protos.Seldon/PredictRaw", bufview.pack_frames(xs)
        )
        assert status == 0, msg
        outs = bufview.unpack_frames(payload)
        _assert_batched_contract(mlp_server, xs, [o.array() for o in outs])

    def test_predict_raw_multi_frame_unstackable_is_client_fault(
            self, loop_thread, mlp_server):
        # frames that don't stack (mismatched widths) are the CLIENT's
        # mistake: INVALID_ARGUMENT (3), matching the HTTP lane's 400 —
        # never INTERNAL
        from seldon_core_tpu.engine.native_ingress import _DeploymentGrpcHandler

        handler = _DeploymentGrpcHandler(
            _gateway(mlp_server, two_node=False), loop_thread
        )
        status, msg, _ = handler(
            "/seldon.protos.Seldon/PredictRaw",
            bufview.pack_frames([np.ones((1, 8), np.float32),
                                 np.ones((1, 4), np.float32)]),
        )
        assert status == 3 and "stack" in msg

    def test_predict_raw_multi_frame_ineligible_graph(self, loop_thread):
        handler = self._handler(loop_thread)  # 2-node graph
        status, msg, _ = handler(
            "/seldon.protos.Seldon/PredictRaw",
            bufview.pack_frames([np.ones((1, 4), np.float32)] * 2),
        )
        assert status == 3 and "single-local-MODEL" in msg

    def test_proto_predict_path_unchanged(self, loop_thread):
        from seldon_core_tpu.proto import pb

        handler = self._handler(loop_thread)
        req = pb.SeldonMessage()
        req.data.rawTensor.dtype = "float32"
        req.data.rawTensor.shape.extend([1, 3])
        req.data.rawTensor.data = np.ones((1, 3), np.float32).tobytes()
        status, _, payload = handler(
            "/seldon.protos.Seldon/Predict", req.SerializeToString()
        )
        assert status == 0
        out = pb.SeldonMessage.FromString(payload)
        np.testing.assert_array_equal(
            codec.get_data_from_proto(out), np.full((1, 3), 2.0, np.float32)
        )


class TestNativeServerE2E:
    """Through the REAL C++ ingress: an SRT1 frame posted to a
    fallback-only deployment (no in-C++ model) must fall through to the
    Python buffer-view lane — the r14 C++ fix; it previously 500'd out
    of an armless fast lane."""

    def test_frame_falls_through_to_python_lane(self, loop_thread):
        import socket

        from seldon_core_tpu.native import frontserver as fsmod
        from seldon_core_tpu.native.frontserver import (
            GatewayRawHandler,
            NativeFrontServer,
            read_http_response,
        )

        if not fsmod.available():
            pytest.skip("native front server library not built")
        handler = GatewayRawHandler(_gateway(Doubler(), two_node=True),
                                    loop_thread)
        x = np.arange(8, dtype=np.float32).reshape(2, 4)
        frame = bufview.pack_frame(x)
        with NativeFrontServer(raw_handler=handler) as srv:
            req = (b"POST /api/v0.1/predictions HTTP/1.1\r\nHost: t\r\n"
                   b"Content-Type: application/x-seldon-raw\r\n"
                   b"Content-Length: " + str(len(frame)).encode()
                   + b"\r\n\r\n" + frame)
            s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
            try:
                s.sendall(req)
                status, body, _ = read_http_response(s, b"", timeout_s=20)
            finally:
                s.close()
        assert status == 200
        np.testing.assert_array_equal(bufview.unpack_frame(body).array(), x * 2)


# ---------------------------------------------------------------------------
# codec/device satellites
# ---------------------------------------------------------------------------


class TestDeviceHelpers:
    def test_from_device_many_single_fetch_matches_individual(self):
        import jax.numpy as jnp

        xs = [jnp.arange(4) + i for i in range(3)]
        many = codec.from_device_many(xs)
        for m, x in zip(many, xs):
            np.testing.assert_array_equal(m, np.asarray(x))
        # host arrays pass through
        host = codec.from_device_many([np.ones(2)])
        np.testing.assert_array_equal(host[0], np.ones(2))

    def test_to_device_skips_cast_when_dtype_matches(self):
        arr = np.arange(4, dtype=np.float32)
        x = codec.to_device(arr, dtype="float32")
        assert str(x.dtype) == "float32"
        np.testing.assert_array_equal(np.asarray(x), arr)

    def test_to_device_still_casts_when_needed(self):
        import jax.numpy as jnp

        x = codec.to_device(np.arange(4, dtype=np.float32), dtype=jnp.bfloat16)
        assert str(x.dtype) == "bfloat16"


def test_knob_is_registered_and_default_on(monkeypatch):
    from seldon_core_tpu.runtime import knobs

    assert "SELDON_TPU_ZERO_COPY" in knobs.ENV_KNOBS
    assert knobs.ENV_KNOBS["SELDON_TPU_ZERO_COPY"].zero_off
    monkeypatch.delenv("SELDON_TPU_ZERO_COPY", raising=False)
    assert bufview.zero_copy_enabled()
    monkeypatch.setenv("SELDON_TPU_ZERO_COPY", "0")
    assert not bufview.zero_copy_enabled()
