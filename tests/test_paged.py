"""Paged KV-cache + continuous batching: exact parity with the
contiguous-cache Generator, page accounting, mixed-length admission.

Correctness criterion is the same exact one test_generate.py uses:
greedy decoding through the paged pool must emit the same tokens as
re-running the full uncached TransformerLM forward every step.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.generate import Generator
from seldon_core_tpu.models.paged import PagedEngine, StreamingLM, get_paged_lm_class
from seldon_core_tpu.models.transformer import TransformerLM
from seldon_core_tpu.runtime.component import MicroserviceError

pytestmark = pytest.mark.slow  # compile-heavy: excluded from the default fast tier (make test-all)


CFG = dict(vocab_size=64, d_model=32, num_layers=2, num_heads=4, max_len=64)


@pytest.fixture(scope="module")
def lm():
    module = TransformerLM(dtype=jnp.float32, **CFG)
    params = module.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return module, params


def _greedy_uncached(module, params, prompt, n):
    tokens = np.asarray(prompt, np.int32).copy()
    out = []
    for _ in range(n):
        logits = module.apply({"params": params}, jnp.asarray(tokens))
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        tokens = np.concatenate([tokens, [[nxt]]], axis=1)
    return out


def _engine(params, **kw):
    base = dict(dtype=jnp.float32, page_size=8, max_slots=4, steps_per_call=4)
    base.update(kw)
    return PagedEngine(params, **CFG, **base)


class TestParamCompatibility:
    def test_paged_module_shares_transformerlm_tree(self, lm):
        """A TransformerLM checkpoint must drive PagedTransformerLM as-is."""
        module, params = lm
        paged = get_paged_lm_class()(dtype=jnp.float32, **CFG)
        pool = jnp.zeros((CFG["num_layers"], 3, 8, CFG["num_heads"], 8), jnp.float32)
        got = paged.init(
            jax.random.key(1), jnp.zeros((1, 4), jnp.int32),
            jnp.zeros((1, 4), jnp.int32), pool, pool,
            jnp.zeros((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32),
        )["params"]
        want_tree = jax.tree_util.tree_structure(params)
        got_tree = jax.tree_util.tree_structure(got)
        assert want_tree == got_tree
        for (pw, w), (pg, g) in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree_util.tree_leaves_with_path(got),
        ):
            assert pw == pg and w.shape == g.shape


class TestPagedParity:
    def test_greedy_matches_full_recompute(self, lm):
        module, params = lm
        eng = _engine(params)
        prompt = np.array([5, 9, 13, 2, 30], np.int32)
        got = eng.generate(prompt, max_new_tokens=8).tolist()
        want = _greedy_uncached(module, params, prompt[None], 8)
        assert got == want

    def test_matches_contiguous_generator(self, lm):
        _, params = lm
        eng = _engine(params)
        gen = Generator(params, dtype=jnp.float32, **CFG)
        prompt = np.array([7, 3, 1, 11], np.int32)
        paged = eng.generate(prompt, max_new_tokens=10, eos_id=-1)
        contiguous = gen.generate(prompt[None], max_new_tokens=10)[0]
        np.testing.assert_array_equal(paged, contiguous)

    def test_mixed_prompt_lengths_share_one_chunk_program(self, lm):
        """The restriction GenerativeLM has (uniform prompt lengths per
        batch) does not exist here: streams of different lengths decode
        together and each matches its solo generation."""
        module, params = lm
        eng = _engine(params)
        prompts = [
            np.array([5, 9, 13, 2, 30], np.int32),
            np.array([1, 2], np.int32),
            np.arange(17, dtype=np.int32) % CFG["vocab_size"],
        ]
        streams = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run()
        for p, s in zip(prompts, streams):
            want = _greedy_uncached(module, params, p[None], 6)
            assert s.result.tolist() == want

    def test_eos_frees_slot_early(self, lm):
        module, params = lm
        eng = _engine(params)
        prompt = np.array([5, 9, 13, 2, 30], np.int32)
        first = _greedy_uncached(module, params, prompt[None], 1)[0]
        out = eng.generate(prompt, max_new_tokens=6, eos_id=first)
        assert out[0] == first and (out[1:] == first).all()
        assert all(s is None for s in eng._slots)
        # pool whole again: freed outright or parked on the prefix
        # cache's LRU (refcount 0, reclaimable) — nothing leaked
        assert len(eng.cache.free_pages) + len(eng.cache.lru) == eng.num_pages - 1

    def test_streams_join_mid_flight(self, lm):
        module, params = lm
        eng = _engine(params, steps_per_call=2)
        a = eng.submit(np.array([5, 9, 13], np.int32), max_new_tokens=8)
        eng.step()  # a decodes alone for one chunk
        b = eng.submit(np.array([4, 4, 4, 4, 4, 4], np.int32), max_new_tokens=4)
        eng.run()
        assert a.result.tolist() == _greedy_uncached(
            module, params, np.array([[5, 9, 13]]), 8
        )
        assert b.result.tolist() == _greedy_uncached(
            module, params, np.array([[4, 4, 4, 4, 4, 4]]), 4
        )

    def test_sampling_seeded_per_stream(self, lm):
        _, params = lm
        eng = _engine(params)
        prompt = np.array([5, 9, 13], np.int32)
        a = eng.generate(prompt, max_new_tokens=8, temperature=1.5, seed=1)
        b = eng.generate(prompt, max_new_tokens=8, temperature=1.5, seed=1)
        c = eng.generate(prompt, max_new_tokens=8, temperature=1.5, seed=2)
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)


class TestSpeculativeEngine:
    """Speculative draft/verify composed WITH continuous batching:
    per-slot ngram drafts verified in one batched forward per chunk
    (VERDICT r2 weak #8 — previously two mutually exclusive lanes)."""

    def test_greedy_bit_exact_vs_plain_engine(self, lm):
        module, params = lm
        plain = _engine(params)
        spec = _engine(params, speculative={"draft_k": 4, "ngram": 2})
        # repetitive prompt: ngram drafting accepts well
        prompt = np.array([5, 9, 5, 9, 5, 9, 5], np.int32)
        want = plain.generate(prompt, max_new_tokens=12).tolist()
        got = spec.generate(prompt, max_new_tokens=12).tolist()
        assert got == want
        assert want == _greedy_uncached(module, params, prompt[None], 12)

    def test_chunks_per_token_reduction(self, lm):
        """With accepting drafts, the speculative engine must need fewer
        compiled-program invocations (verify forwards) than the plain
        engine needs decode chunks for the same output."""
        _, params = lm
        plain = _engine(params, steps_per_call=1)  # 1 forward per token
        spec = _engine(params, speculative={"draft_k": 4, "ngram": 2})
        prompt = np.array([5, 9, 5, 9, 5, 9, 5], np.int32)
        a = plain.generate(prompt, max_new_tokens=12)
        b = spec.generate(prompt, max_new_tokens=12)
        np.testing.assert_array_equal(a, b)
        plain_chunks = plain.engine_stats()["chunks"]
        spec_chunks = spec.engine_stats()["chunks"]
        assert spec_chunks < plain_chunks
        stats = spec.engine_stats()
        assert stats["spec_drafted"] > 0
        assert stats["spec_accepted"] > 0

    def test_concurrent_mixed_length_streams_bit_exact(self, lm):
        module, params = lm
        spec = _engine(params, speculative={"draft_k": 3, "ngram": 2})
        prompts = [
            np.array([5, 9, 5, 9, 5], np.int32),
            np.array([1, 2], np.int32),
            np.arange(11, dtype=np.int32) % CFG["vocab_size"],
        ]
        streams = [spec.submit(p, max_new_tokens=6) for p in prompts]
        spec.run()
        for p, s in zip(prompts, streams):
            want = _greedy_uncached(module, params, p[None], 6)
            assert s.result.tolist() == want

    def test_eos_inside_accepted_run_truncates(self, lm):
        module, params = lm
        prompt = np.array([5, 9, 5, 9, 5], np.int32)
        first = _greedy_uncached(module, params, prompt[None], 1)[0]
        spec = _engine(params, speculative={"draft_k": 4, "ngram": 2})
        out = spec.generate(prompt, max_new_tokens=6, eos_id=first)
        assert out[0] == first and (out[1:] == first).all()
        # slot + pages released
        assert all(s is None for s in spec._slots)
        assert len(spec.cache.free_pages) + len(spec.cache.lru) == spec.num_pages - 1

    def test_oracle_drafts_full_acceptance(self, lm):
        """draft='oracle' with the known continuation accepts every
        draft (the acceptance-ceiling benchmarking lane) and stays
        bit-exact."""
        _, params = lm
        plain = _engine(params)
        prompt = np.array([5, 9, 13, 2, 30], np.int32)
        want = plain.generate(prompt, max_new_tokens=12)
        spec = _engine(params, speculative={"draft": "oracle", "draft_k": 4})
        s = spec.submit(prompt, max_new_tokens=12, draft_hint=want)
        spec.run()
        np.testing.assert_array_equal(s.result, want)
        stats = spec.engine_stats()
        assert stats["spec_accepted"] == stats["spec_drafted"] > 0
        # full acceptance: 12 tokens in 1 prefill-emit + ceil(11/5) rounds
        assert stats["chunks"] <= 3

    def test_sampling_rejected_with_400(self, lm):
        _, params = lm
        spec = _engine(params, speculative={"draft_k": 2})
        with pytest.raises(MicroserviceError) as exc:
            spec.submit(np.array([1, 2, 3], np.int32), max_new_tokens=4,
                        temperature=0.9)
        assert exc.value.status_code == 400

    def test_streaminglm_speculative_component(self, lm):
        """StreamingLM(speculative=...) end-to-end: identical tokens to
        the plain component + acceptance metrics exported."""
        _, params = lm
        import tempfile

        from flax import serialization

        with tempfile.NamedTemporaryFile(suffix=".msgpack", delete=False) as f:
            path = f.name
            f.write(serialization.to_bytes(params))
        kwargs = dict(
            model_uri=f"file://{path}", page_size=8, max_slots=4,
            max_new_tokens=10, **CFG,
        )
        plain = StreamingLM(**kwargs)
        spec = StreamingLM(speculative={"draft_k": 4}, **kwargs)
        X = np.array([[5, 9, 5, 9, 5, 9, 5]], np.int32)
        try:
            a = plain.predict(X, [])
            b = spec.predict(X, [])
            np.testing.assert_array_equal(a, b)
            keys = {m["key"] for m in spec.metrics()}
            assert "speculative_acceptance_rate" in keys
            assert "speculative_rounds" in keys
            assert "speculative_acceptance_rate" not in {
                m["key"] for m in plain.metrics()
            }
        finally:
            plain.shutdown()
            spec.shutdown()


class TestTokenStreaming:
    """Incremental token delivery from the continuous-batching engine
    (additive to the reference contract — it predates generation)."""

    def test_streamed_tokens_equal_batch_result(self, lm):
        _, params = lm
        eng = _engine(params, steps_per_call=2)
        prompt = np.array([5, 9, 13, 2, 30], np.int32)
        want = eng.generate(prompt, max_new_tokens=8)
        s = eng.submit(prompt, max_new_tokens=8, stream_tokens=True)
        chunks = []
        import threading as _t

        runner = _t.Thread(target=eng.run)
        runner.start()
        while True:
            got = s.token_queue.get(timeout=30)
            if got is None:
                break
            chunks.append(got)
        runner.join()
        streamed = [t for c in chunks for t in c]
        # several incremental chunks, concatenating to the exact result
        assert len(chunks) >= 2
        assert streamed == want.tolist()
        np.testing.assert_array_equal(s.result, want)

    def test_streaming_clamps_at_eos_and_budget(self, lm):
        module, params = lm
        prompt = np.array([5, 9, 13, 2, 30], np.int32)
        first = _greedy_uncached(module, params, prompt[None], 1)[0]
        eng = _engine(params, steps_per_call=4)
        s = eng.submit(prompt, max_new_tokens=6, eos_id=first, stream_tokens=True)
        eng.run()
        chunks = []
        while True:
            got = s.token_queue.get(timeout=10)
            if got is None:
                break
            chunks.append(got)
        streamed = [t for c in chunks for t in c]
        # stream ends at eos (inclusive), matching the padded result's cut
        assert streamed == [first]

    def test_streaminglm_predict_stream_component(self, lm):
        _, params = lm
        import tempfile

        from flax import serialization

        with tempfile.NamedTemporaryFile(suffix=".msgpack", delete=False) as f:
            path = f.name
            f.write(serialization.to_bytes(params))
        comp = StreamingLM(model_uri=f"file://{path}", page_size=8,
                           max_slots=4, max_new_tokens=8, **CFG)
        try:
            X = np.array([[5, 9, 13, 2, 30]], np.int32)
            want = comp.predict(X, [])[0]
            streamed = np.concatenate(list(comp.predict_stream(X, [])))
            np.testing.assert_array_equal(streamed, want)
            # multi-row predict_stream is a 400
            with pytest.raises(MicroserviceError):
                list(comp.predict_stream(np.ones((2, 3), np.int32), []))
        finally:
            comp.shutdown()

    def test_abandoned_stream_frees_slot(self, lm):
        """A consumer that stops reading must not leave the stream
        decoding into an unread queue holding a slot/pages."""
        _, params = lm
        import tempfile

        from flax import serialization

        with tempfile.NamedTemporaryFile(suffix=".msgpack", delete=False) as f:
            path = f.name
            f.write(serialization.to_bytes(params))
        comp = StreamingLM(model_uri=f"file://{path}", page_size=8,
                           max_slots=2, max_new_tokens=30, steps_per_call=1,
                           **CFG)
        try:
            gen = comp.predict_stream(np.array([[5, 9, 13]], np.int32), [])
            first = next(gen)
            assert len(first) >= 1
            gen.close()  # consumer walks away
            # the engine retires the stream at its next bookkeeping
            # point: slot + pages free, loop goes idle
            import time as _time

            deadline = _time.time() + 20
            while _time.time() < deadline:
                stats = comp.engine.engine_stats()
                if stats["active_slots"] == 0 and stats["queued_streams"] == 0:
                    break
                _time.sleep(0.1)
            stats = comp.engine.engine_stats()
            assert stats["active_slots"] == 0 and stats["queued_streams"] == 0
            assert stats["pool_pages_used"] == 0
            # far fewer tokens decoded than the abandoned budget
            assert stats["tokens"] < 25
            # the engine still serves new work afterwards
            out = comp.predict(np.array([[1, 2]], np.int32), [],
                               meta={"tags": {"max_new_tokens": 4}})
            assert out.shape == (1, 4)
        finally:
            comp.shutdown()

    def test_cancel_queued_stream_resolves_immediately(self, lm):
        _, params = lm
        eng = _engine(params)
        s = eng.submit(np.array([1, 2, 3], np.int32), max_new_tokens=4,
                       stream_tokens=True)
        eng.cancel(s)  # never stepped: still queued
        assert s.event.is_set()
        assert s.token_queue.get(timeout=1) is None
        assert not eng.has_work()

    def test_grpc_generate_stream_end_to_end(self, lm):
        """Seldon/GenerateStream over a real socket through the sync
        server + client SDK."""
        import asyncio
        import tempfile

        from flax import serialization

        from seldon_core_tpu.client.client import SeldonTpuClient
        from seldon_core_tpu.engine import PredictorService, UnitSpec
        from seldon_core_tpu.engine.server import Gateway
        from seldon_core_tpu.engine.sync_server import build_sync_seldon_server

        _, params = lm
        with tempfile.NamedTemporaryFile(suffix=".msgpack", delete=False) as f:
            path = f.name
            f.write(serialization.to_bytes(params))
        comp = StreamingLM(model_uri=f"file://{path}", page_size=8,
                           max_slots=4, max_new_tokens=8, steps_per_call=2,
                           **CFG)

        async def scenario():
            svc = PredictorService(
                UnitSpec(name="lm", type="MODEL", component=comp), name="main"
            )
            gw = Gateway([(svc, 1.0)])
            server = build_sync_seldon_server(gw, asyncio.get_running_loop())
            port = server.add_insecure_port("127.0.0.1:0")
            server.start()

            def client_work():
                client = SeldonTpuClient(grpc_port=port, transport="grpc")
                chunks = list(client.generate_stream(
                    [5, 9, 13, 2, 30],
                    meta={"tags": {"max_new_tokens": 6}},
                ))
                batch = client.predict(
                    np.array([[5, 9, 13, 2, 30]], np.int32),
                    meta={"tags": {"max_new_tokens": 6}},
                )
                client.close()
                return chunks, batch

            chunks, batch = await asyncio.to_thread(client_work)
            await asyncio.to_thread(server.stop(0).wait)
            return chunks, batch

        chunks, batch = asyncio.run(scenario())
        try:
            streamed = np.concatenate(chunks)
            assert len(chunks) >= 2  # genuinely incremental
            np.testing.assert_array_equal(streamed, np.asarray(batch.data).reshape(-1))
        finally:
            comp.shutdown()


    def test_rest_sse_generate_stream_end_to_end(self, lm):
        """Token streaming over REST: /api/v0.1/generate/stream emits
        SSE events, the client SDK parses them, tokens match the unary
        predict of the same request."""
        import asyncio
        import tempfile

        from flax import serialization

        from seldon_core_tpu.client.client import SeldonTpuClient
        from seldon_core_tpu.engine import PredictorService, UnitSpec
        from seldon_core_tpu.engine.server import Gateway, build_gateway_app

        _, params = lm
        with tempfile.NamedTemporaryFile(suffix=".msgpack", delete=False) as f:
            path = f.name
            f.write(serialization.to_bytes(params))
        comp = StreamingLM(model_uri=f"file://{path}", page_size=8,
                           max_slots=4, max_new_tokens=8, steps_per_call=2,
                           **CFG)

        async def scenario():
            from aiohttp.test_utils import TestServer as AioTestServer

            svc = PredictorService(
                UnitSpec(name="lm", type="MODEL", component=comp), name="main"
            )
            gw = Gateway([(svc, 1.0)])
            server = AioTestServer(build_gateway_app(gw))
            await server.start_server()
            port = server.port

            def client_work():
                client = SeldonTpuClient(http_port=port, transport="rest")
                chunks = list(client.generate_stream(
                    [5, 9, 13, 2, 30], meta={"tags": {"max_new_tokens": 6}}
                ))
                batch = client.predict(
                    np.array([[5, 9, 13, 2, 30]], np.int32),
                    meta={"tags": {"max_new_tokens": 6}},
                )
                client.close()
                return chunks, batch

            chunks, batch = await asyncio.to_thread(client_work)
            await server.close()
            return chunks, batch

        chunks, batch = asyncio.run(scenario())
        try:
            assert len(chunks) >= 2
            np.testing.assert_array_equal(
                np.concatenate(chunks), np.asarray(batch.data).reshape(-1)
            )
        finally:
            comp.shutdown()

    def test_rest_sse_bad_prompt_is_http_error_not_stream(self, lm):
        """Rejections surface BEFORE headers: a bad prompt gets a JSON
        error status, never an abruptly-closed 200 stream."""
        import asyncio
        import tempfile

        from flax import serialization

        from seldon_core_tpu.engine import PredictorService, UnitSpec
        from seldon_core_tpu.engine.server import Gateway, build_gateway_app

        _, params = lm
        with tempfile.NamedTemporaryFile(suffix=".msgpack", delete=False) as f:
            path = f.name
            f.write(serialization.to_bytes(params))
        comp = StreamingLM(model_uri=f"file://{path}", page_size=8,
                           max_slots=2, max_new_tokens=4, **CFG)

        async def scenario():
            from aiohttp.test_utils import TestClient, TestServer

            svc = PredictorService(
                UnitSpec(name="lm", type="MODEL", component=comp), name="main"
            )
            client = TestClient(TestServer(build_gateway_app(Gateway([(svc, 1.0)]))))
            await client.start_server()
            # two prompt rows: the streaming lane serves one per stream
            resp = await client.post(
                "/api/v0.1/generate/stream",
                json={"data": {"ndarray": [[1, 2], [3, 4]]}},
            )
            body = await resp.json()
            await client.close()
            return resp.status, body

        status, body = asyncio.run(scenario())
        try:
            assert status == 400
            assert body["status"]["status"] == "FAILURE"
        finally:
            comp.shutdown()

    def test_rest_sse_not_implemented_for_non_generation(self):
        """A non-generation predictor answers 501 with guidance."""
        import asyncio

        from seldon_core_tpu.engine import PredictorService, UnitSpec
        from seldon_core_tpu.engine.server import Gateway, build_gateway_app

        async def scenario():
            from aiohttp.test_utils import TestClient, TestServer

            svc = PredictorService(
                UnitSpec(name="stub", type="MODEL", implementation="SIMPLE_MODEL"),
                name="main",
            )
            client = TestClient(TestServer(build_gateway_app(Gateway([(svc, 1.0)]))))
            await client.start_server()
            resp = await client.post(
                "/api/v0.1/generate/stream",
                json={"data": {"ndarray": [[1, 2]]}},
            )
            body = await resp.json()
            await client.close()
            return resp.status, body

        status, body = asyncio.run(scenario())
        assert status == 501
        assert body["status"]["reason"] == "NOT_IMPLEMENTED"

    def test_aio_server_generate_stream(self, lm):
        """The grpc.aio lane serves GenerateStream too (feature parity
        across both gRPC server modes)."""
        import asyncio
        import tempfile

        import grpc
        from flax import serialization

        from seldon_core_tpu.engine import PredictorService, UnitSpec
        from seldon_core_tpu.engine.server import Gateway, add_seldon_service
        from seldon_core_tpu.proto import services as proto_services
        from seldon_core_tpu.runtime.message import InternalMessage

        _, params = lm
        with tempfile.NamedTemporaryFile(suffix=".msgpack", delete=False) as f:
            path = f.name
            f.write(serialization.to_bytes(params))
        comp = StreamingLM(model_uri=f"file://{path}", page_size=8,
                           max_slots=2, max_new_tokens=6, steps_per_call=2,
                           **CFG)

        async def scenario():
            gw = Gateway([(PredictorService(
                UnitSpec(name="lm", type="MODEL", component=comp), name="main"), 1.0)])
            server = grpc.aio.server()
            add_seldon_service(server, gw)
            port = server.add_insecure_port("127.0.0.1:0")
            await server.start()
            channel = grpc.aio.insecure_channel(f"127.0.0.1:{port}")
            call = proto_services.unary_stream_callable(
                channel, "Seldon", "GenerateStream"
            )
            req = InternalMessage(
                payload=__import__("numpy").array([[5, 9, 13]], "int32"),
                kind="ndarray",
            ).to_proto()
            chunks = []
            async for msg in call(req):
                chunks.append(InternalMessage.from_proto(msg).array().reshape(-1))
            await channel.close()
            await server.stop(grace=None)
            return chunks

        chunks = asyncio.run(scenario())
        try:
            total = np.concatenate(chunks)
            assert total.shape == (6,)
            assert len(chunks) >= 2
        finally:
            comp.shutdown()


class TestPageAccounting:
    def test_pages_are_reused_across_requests(self, lm):
        _, params = lm
        eng = _engine(params, num_pages=9)  # 8 usable pages, 4 slots
        total = eng.num_pages - 1
        for _ in range(3):
            eng.generate(np.arange(10, dtype=np.int32), max_new_tokens=5)
            # all returned: free or LRU-cached (reclaimable), none leaked
            assert len(eng.cache.free_pages) + len(eng.cache.lru) == total

    def test_pool_smaller_than_worst_case_still_serves(self, lm):
        module, params = lm
        # worst case for 4 slots is 4 * (64/8) = 32 pages; give it 10
        eng = _engine(params, num_pages=11)
        prompts = [np.array([i + 1, i + 2, i + 3], np.int32) for i in range(4)]
        streams = [eng.submit(p, max_new_tokens=12) for p in prompts]
        eng.run()
        for p, s in zip(prompts, streams):
            want = _greedy_uncached(module, params, p[None], 12)
            assert s.result.tolist() == want

    def test_oversized_request_rejected_up_front(self, lm):
        _, params = lm
        eng = _engine(params, num_pages=3)  # 2 usable pages = 16 positions
        with pytest.raises(MicroserviceError):
            eng.submit(np.arange(12, dtype=np.int32), max_new_tokens=8)
        with pytest.raises(MicroserviceError):
            eng.submit(np.zeros(4, np.int32), max_new_tokens=100)  # > max_len

    def test_empty_prompt_rejected(self, lm):
        _, params = lm
        eng = _engine(params)
        with pytest.raises(MicroserviceError):
            eng.submit(np.zeros((0,), np.int32), max_new_tokens=4)

    def test_fail_all_frees_pages_and_unblocks(self, lm):
        """After an engine-level failure the pool is whole again and the
        engine keeps serving (regression: the old error path leaked the
        dead streams' pages, wedging every later allocation)."""
        module, params = lm
        eng = _engine(params)
        a = eng.submit(np.array([5, 9, 13], np.int32), max_new_tokens=8)
        eng.step()  # a is mid-flight holding pages
        boom = RuntimeError("injected")
        eng.fail_all(boom)
        assert a.event.is_set() and a.error is boom
        # pool whole again: freed outright or parked on the prefix
        # cache's LRU (refcount 0, reclaimable) — nothing leaked
        assert len(eng.cache.free_pages) + len(eng.cache.lru) == eng.num_pages - 1
        out = eng.generate(np.array([5, 9, 13], np.int32), max_new_tokens=4)
        want = _greedy_uncached(module, params, np.array([[5, 9, 13]]), 4)
        assert out.tolist() == want

    def test_stalled_stream_resumes_with_preserved_state(self, lm):
        """A stream stalled on pool pressure must resume from exactly the
        logits it stalled with (regression: the chunk scan used to
        overwrite inactive lanes' carries with a fake-EOS forward)."""
        module, params = lm
        # 3 usable pages: A (8+4 -> 2 pages) takes the pool first, B
        # (8+14 -> 3 pages) stalls holding its prefill logits, then A
        # finishes, frees pages, and B must resume losslessly
        eng = _engine(params, max_slots=2, num_pages=4, steps_per_call=4)
        pa = (np.arange(8) + 1).astype(np.int32)
        pb = (np.arange(8) + 20).astype(np.int32)
        a = eng.submit(pa, max_new_tokens=4)
        b = eng.submit(pb, max_new_tokens=14)
        eng.run()
        assert a.result.tolist() == _greedy_uncached(module, params, pa[None], 4)
        assert b.result.tolist() == _greedy_uncached(module, params, pb[None], 14)
        # pool whole again: freed outright or parked on the prefix
        # cache's LRU (refcount 0, reclaimable) — nothing leaked
        assert len(eng.cache.free_pages) + len(eng.cache.lru) == eng.num_pages - 1

    def test_pool_wedge_evicts_victim_not_everyone(self, lm):
        """When every active stream stalls, the engine evicts the one
        with least progress back to the queue and the rest run; the
        victim re-runs later and still returns correct tokens
        (regression: this used to 507 every in-flight request)."""
        module, params = lm
        eng = _engine(params, max_slots=2, num_pages=4, steps_per_call=4)
        pa = (np.arange(8) + 1).astype(np.int32)
        pb = (np.arange(8) + 30).astype(np.int32)
        a = eng.submit(pa, max_new_tokens=14)  # grows to 3 pages
        b = eng.submit(pb, max_new_tokens=4)   # needs 2, starves, evicted
        eng.run()
        assert a.result.tolist() == _greedy_uncached(module, params, pa[None], 14)
        assert b.result.tolist() == _greedy_uncached(module, params, pb[None], 4)
        # pool whole again: freed outright or parked on the prefix
        # cache's LRU (refcount 0, reclaimable) — nothing leaked
        assert len(eng.cache.free_pages) + len(eng.cache.lru) == eng.num_pages - 1

    def test_queue_waits_for_free_slot(self, lm):
        module, params = lm
        eng = _engine(params, max_slots=2)
        prompts = [np.array([i + 1, i + 5], np.int32) for i in range(5)]
        streams = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run()
        for p, s in zip(prompts, streams):
            want = _greedy_uncached(module, params, p[None], 4)
            assert s.result.tolist() == want

    def test_one_decode_program_for_everything(self, lm):
        _, params = lm
        eng = _engine(params)
        eng.generate(np.array([1, 2, 3], np.int32), max_new_tokens=4)
        eng.generate(np.arange(20, dtype=np.int32), max_new_tokens=9)
        # prefill ladder: (bucket, k) programs with buckets from the
        # ladder; decode: one chunk program per (ladder size, bucket
        # spec) pair actually used — steps has no ladder here (no
        # max_steps_per_call -> only the base size), each bucket's ctx
        # horizon is a power-of-two page count, and lane counts sum to
        # max_slots, so the compile count stays log-bounded in both axes
        assert {b for (b, _k) in eng._prefill_jit} <= set(eng.prompt_buckets)
        assert {s for (s, _spec) in eng._chunk_jit} == {eng.steps_per_call}
        for (_s, spec) in eng._chunk_jit:
            assert sum(nb for (nb, _h) in spec) == eng.max_slots
            assert all(h >= 1 and (h & (h - 1)) == 0 for (_nb, h) in spec)


class TestMeshShardedDecode:
    """Tensor-parallel continuous batching on the virtual 8-device mesh:
    params megatron-sharded, the KV pool sharded on its heads axis, XLA
    inserting the collectives inside the one compiled chunk program."""

    def test_sharded_engine_matches_unsharded(self, lm):
        from seldon_core_tpu.parallel.mesh import create_mesh

        module, params = lm
        mesh = create_mesh({"model": 4})
        plain = _engine(params)
        # min_weight_size=0: ALL weights get megatron specs, so the
        # sharded-matmul path really executes (the test config's weights
        # are below the production threshold)
        sharded = _engine(params, mesh=mesh, shard_min_weight_size=0)
        assert any(
            "model" in [ax for ax in leaf.sharding.spec if ax]
            for leaf in jax.tree_util.tree_leaves(sharded.params)
            if hasattr(leaf, "sharding") and leaf.ndim >= 1
        )
        prompts = [
            np.array([5, 9, 13], np.int32),
            np.array([1, 2, 3, 4, 5, 6], np.int32),
        ]
        for p in prompts:
            a = plain.generate(p, max_new_tokens=8)
            b = sharded.generate(p, max_new_tokens=8)
            np.testing.assert_array_equal(a, b)
            want = _greedy_uncached(module, params, p[None], 8)
            assert b.tolist() == want

    def test_pool_is_actually_sharded(self, lm):
        from seldon_core_tpu.parallel.mesh import create_mesh

        _, params = lm
        mesh = create_mesh({"model": 4})  # 4 heads over 4 devices
        eng = _engine(params, mesh=mesh)
        spec = eng.cache.pages_k.sharding.spec
        assert "model" in [ax for ax in spec if ax]  # heads axis sharded

    def test_component_mesh_axes(self, lm):
        _, params = lm
        comp = StreamingLM(max_new_tokens=4, page_size=8, max_slots=2,
                           mesh_axes={"model": 2}, **CFG)
        comp.load()
        out = comp.predict(np.array([[3, 1, 4]], np.int32), [])
        comp.shutdown()
        assert out.shape == (1, 4)


class TestStreamingComponent:
    def test_concurrent_predicts_share_the_engine(self, lm):
        module, params = lm
        comp = StreamingLM(max_new_tokens=5, max_slots=4, page_size=8,
                           steps_per_call=2, **CFG)
        comp.load()
        comp.engine = PagedEngine(  # swap in the test checkpoint
            params, dtype=jnp.float32, page_size=8, max_slots=4,
            steps_per_call=2, **CFG,
        )
        prompts = [np.array([[3, 1, 4]]), np.array([[1, 5, 9, 2]]), np.array([[6, 5]])]
        results = {}

        def call(i):
            results[i] = comp.predict(prompts[i], [])

        threads = [threading.Thread(target=call, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        comp.shutdown()
        for i, p in enumerate(prompts):
            want = _greedy_uncached(module, params, p.astype(np.int32), 5)
            assert results[i][0].tolist() == want

    def test_shutdown_unblocks_pending_waiters(self, lm):
        _, params = lm
        comp = StreamingLM(max_new_tokens=4, max_slots=2, page_size=8, **CFG)
        comp.load()
        comp.engine = PagedEngine(params, dtype=jnp.float32, page_size=8,
                                  max_slots=2, **CFG)
        # the invariant: a submitted stream NEVER leaves its waiter
        # hanging across shutdown — it either completed before the stop
        # or was errored out by the loop's exit cleanup
        stream = comp.engine.submit(np.array([1, 2, 3], np.int32), max_new_tokens=4)
        comp.shutdown()
        comp._loop_thread.join(timeout=30)
        assert not comp._loop_thread.is_alive()
        assert stream.event.wait(timeout=30)
        assert stream.result is not None or isinstance(stream.error, MicroserviceError)

    def test_predict_after_shutdown_errors_not_hangs(self, lm):
        _, params = lm
        comp = StreamingLM(max_new_tokens=3, max_slots=2, page_size=8, **CFG)
        comp.load()
        comp.engine = PagedEngine(params, dtype=jnp.float32, page_size=8,
                                  max_slots=2, **CFG)
        comp.shutdown()
        comp._loop_thread.join(timeout=30)
        with pytest.raises(MicroserviceError):
            comp.predict(np.array([[1, 2, 3]], np.int32), [])

    def test_tags_override_sampling(self, lm):
        _, params = lm
        comp = StreamingLM(max_new_tokens=3, max_slots=2, page_size=8, **CFG)
        comp.load()
        comp.engine = PagedEngine(params, dtype=jnp.float32, page_size=8,
                                  max_slots=2, **CFG)
        out = comp.predict(
            np.array([[3, 1, 4]], np.int32), [],
            meta={"tags": {"max_new_tokens": 7}},
        )
        comp.shutdown()
        assert out.shape == (1, 7)


class TestEngineStats:
    def test_counters_track_a_generation(self, lm):
        _, params = lm
        engine = _engine(params)
        engine.generate(np.array([5, 9, 13], np.int32), max_new_tokens=6)
        s = engine.engine_stats()
        assert s["prefills"] == 1
        assert s["completed"] == 1
        assert s["tokens"] == 6
        assert s["chunks"] >= 2  # 6 tokens at steps_per_call=4
        assert s["active_slots"] == 0 and s["queued_streams"] == 0
        assert s["pool_pages_used"] == 0  # everything freed on finish
        assert s["pool_pages_total"] == engine.num_pages - 1

    def test_evictions_and_stalls_counted_under_pressure(self, lm):
        _, params = lm
        # pool too small for two full-length streams -> stall + evict
        engine = _engine(params, num_pages=2 * (24 // 8) - 1, max_slots=2)
        s1 = engine.submit(np.arange(8, dtype=np.int32) % 60, max_new_tokens=12)
        s2 = engine.submit(np.arange(6, dtype=np.int32) % 60, max_new_tokens=12)
        engine.run()
        assert s1.result is not None and s2.result is not None
        s = engine.engine_stats()
        assert s["stalls"] + s["evictions"] > 0
        assert s["completed"] == 2

    def test_streaming_component_exports_gauges(self, lm):
        _, params = lm
        comp = StreamingLM(max_new_tokens=4, max_slots=2, page_size=8,
                           steps_per_call=2, **CFG)
        comp.load()
        comp.engine = PagedEngine(
            params, dtype=jnp.float32, page_size=8, max_slots=2,
            steps_per_call=2, **CFG,
        )
        comp.predict(np.array([[3, 1, 4]]), [])
        by_key = {m["key"]: m for m in comp.metrics()}
        comp.shutdown()
        assert by_key["paged_tokens_emitted"]["value"] == 4
        assert by_key["paged_streams_completed"]["value"] == 1
        assert 0.0 <= by_key["paged_pool_utilization"]["value"] <= 1.0
        # collected after every request -> cumulative values must be GAUGEs
        assert all(m["type"] == "GAUGE" for m in comp.metrics())


class TestStepsLadder:
    """max_steps_per_call: saturated decode grows chunks (x2 ladder) so
    one program call decodes more tokens; a waiting queue pins the short
    chunk so admission cadence stays the latency bound."""

    def test_ladder_reduces_chunks_and_stays_exact(self, lm):
        module, params = lm
        prompt = np.arange(9, dtype=np.int32) % CFG["vocab_size"]
        base = _engine(params)
        toks_base = base.generate(prompt, max_new_tokens=24)
        ladder = _engine(params, max_steps_per_call=16)
        toks_ladder = ladder.generate(prompt, max_new_tokens=24)
        np.testing.assert_array_equal(toks_base, toks_ladder)
        assert ladder.engine_stats()["chunks"] < base.engine_stats()["chunks"]

    def test_queue_pressure_pins_short_chunks(self, lm):
        module, params = lm
        # 5 streams into 4 slots: one always queued, so every chunk while
        # it waits must be the base size (admission cadence unharmed)
        eng = _engine(params, max_steps_per_call=16, num_pages=4 * 8 + 1)
        prompts = [
            (np.arange(5 + i, dtype=np.int32) % CFG["vocab_size"]) for i in range(5)
        ]
        streams = [eng.submit(p, max_new_tokens=12) for p in prompts]
        # first step: queue non-empty -> short chunk
        eng.step()
        assert eng.engine_stats()["chunks"] == 1
        eng.run()
        singles = [_engine(params).generate(p, max_new_tokens=12) for p in prompts]
        for s, want in zip(streams, singles):
            np.testing.assert_array_equal(s.result, want)


class TestBatchedPrefill:
    def test_same_bucket_joiners_prefill_in_one_call(self, lm):
        module, params = lm
        eng = _engine(params)
        prompts = [
            (np.arange(6 + i, dtype=np.int32) % CFG["vocab_size"]) for i in range(4)
        ]
        streams = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run()
        # all four prompts fit one bucket -> exactly one (bucket, k=4)
        # prefill program was built and stats count 4 prefilled streams
        assert eng.engine_stats()["prefills"] == 4
        assert len(eng._prefill_jit) == 1
        (bucket, k), = eng._prefill_jit.keys()
        assert k == 4
        for s, p in zip(streams, prompts):
            want = _greedy_uncached(module, params, p[None, :], 8)
            np.testing.assert_array_equal(s.result, np.asarray(want, np.int32))

    def test_mixed_buckets_split_calls_stay_exact(self, lm):
        module, params = lm
        eng = _engine(params, prompt_buckets=[8, 32])
        short = np.arange(5, dtype=np.int32) % CFG["vocab_size"]
        long = np.arange(20, dtype=np.int32) % CFG["vocab_size"]
        s1 = eng.submit(short, max_new_tokens=6)
        s2 = eng.submit(long, max_new_tokens=6)
        eng.run()
        for s, p in zip((s1, s2), (short, long)):
            want = _greedy_uncached(module, params, p[None, :], 6)
            np.testing.assert_array_equal(s.result, np.asarray(want, np.int32))
        assert {b for (b, _k) in eng._prefill_jit} == {8, 32}


class TestDraftModelLane:
    """draft='model': a small LM proposes tokens; verification keeps
    greedy output bit-exact whatever the draft proposes."""

    def _draft(self, seed=3):
        dc = dict(vocab_size=CFG["vocab_size"], d_model=16, num_layers=1,
                  num_heads=2, max_len=32)
        module = TransformerLM(dtype=jnp.float32, **dc)
        params = module.init(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32))["params"]
        return params, dc

    def test_greedy_bit_exact_with_random_draft(self, lm):
        module, params = lm
        dparams, dc = self._draft()
        prompts = [
            (np.arange(7 + 3 * i, dtype=np.int32) % CFG["vocab_size"]) for i in range(3)
        ]
        plain = _engine(params)
        spec = _engine(
            params,
            speculative={"draft": "model", "draft_k": 3, "draft_params": dparams,
                         "draft_config": dc, "draft_window": 16},
        )
        for p in prompts:
            want = plain.generate(p, max_new_tokens=10)
            got = spec.generate(p, max_new_tokens=10)
            np.testing.assert_array_equal(want, got)
        stats = spec.engine_stats()
        assert stats["spec_drafted"] > 0  # the model lane actually drafted

    def test_target_as_its_own_draft_accepts(self, lm):
        """Self-draft sanity: when the draft IS the target (same params,
        full-context window, window-relative == absolute positions for
        contexts shorter than the window), drafts are the target's own
        argmaxes and acceptance is high."""
        module, params = lm
        spec = _engine(
            params,
            speculative={"draft": "model", "draft_k": 3, "draft_params": params,
                         "draft_config": dict(CFG), "draft_window": CFG["max_len"]},
        )
        prompt = np.arange(6, dtype=np.int32) % CFG["vocab_size"]
        plain = _engine(params)
        np.testing.assert_array_equal(
            plain.generate(prompt, max_new_tokens=12),
            spec.generate(prompt, max_new_tokens=12),
        )
        s = spec.engine_stats()
        # left-padded zeros vs absolute positions differ slightly; the
        # bar is meaningful acceptance, not perfection
        assert s["spec_accepted"] / max(1, s["spec_drafted"]) > 0.5

    def test_model_draft_requires_params(self, lm):
        module, params = lm
        with pytest.raises(ValueError, match="draft_params"):
            _engine(params, speculative={"draft": "model"})

    def test_vocab_mismatch_rejected(self, lm):
        module, params = lm
        dparams, dc = self._draft()
        dc["vocab_size"] = CFG["vocab_size"] * 2
        with pytest.raises(ValueError, match="vocab"):
            _engine(
                params,
                speculative={"draft": "model", "draft_params": dparams,
                             "draft_config": dc},
            )


class TestLadderPoolPressure:
    def test_ladder_never_induces_eviction_churn(self, lm):
        """A shrunk pool: two streams each ultimately need 3 pages but
        only 5 are usable — incremental growth lets one finish and free
        pages for the other.  The ladder must not demand max-steps
        worth of pages upfront (that would mass-stall and evict,
        discarding decoded progress base-size chunks were making)."""
        module, params = lm
        eng = _engine(
            params, page_size=8, max_slots=2, steps_per_call=4,
            max_steps_per_call=32, num_pages=6,
        )
        prompts = [
            (np.arange(5, dtype=np.int32) % CFG["vocab_size"]),
            ((np.arange(5, dtype=np.int32) + 7) % CFG["vocab_size"]),
        ]
        streams = [eng.submit(p, max_new_tokens=19) for p in prompts]
        eng.run()
        stats = eng.engine_stats()
        assert stats["evictions"] == 0
        singles = [
            _engine(params, page_size=8).generate(p, max_new_tokens=19)
            for p in prompts
        ]
        for s, want in zip(streams, singles):
            np.testing.assert_array_equal(s.result, want)


class TestIdempotentLoad:
    def test_double_load_keeps_one_engine_and_one_stepper(self, lm):
        """The executor load()s on graph build while lazy predict may
        already have loaded: a second load must NOT replace the engine —
        the orphaned loop thread (which reads self.engine dynamically)
        would step the new engine concurrently with the new thread,
        racing the donated pool buffers ("Array has been deleted")."""
        comp = StreamingLM(max_new_tokens=6, page_size=8, max_slots=2,
                           steps_per_call=2, **CFG)
        try:
            prompt = np.array([5, 9, 13], np.int32)
            first = comp.predict(prompt[None], [], meta={"tags": {"seed": 0}})
            engine = comp.engine
            comp.load()  # what PredictorService graph build does
            assert comp.engine is engine
            # serving still healthy and deterministic after the re-load
            again = comp.predict(prompt[None], [], meta={"tags": {"seed": 0}})
            np.testing.assert_array_equal(first, again)
        finally:
            comp.shutdown()
