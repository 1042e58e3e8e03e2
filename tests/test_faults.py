"""Fault-injection harness + chaos tests (r10).

Each ``SELDON_TPU_FAULT`` point is driven under load with the allocator
audit enabled, asserting the graceful-degradation invariants the
runbook promises: no stuck streams (every waiter resolves), the
``SELDON_TPU_PAGED_DEBUG`` audit stays clean after every injected
failure, the queue drains, and ``fail_all`` is never needed (the engine
keeps serving afterwards).
"""

import asyncio
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.paged import PagedEngine
from seldon_core_tpu.models.transformer import TransformerLM
from seldon_core_tpu.runtime.component import MicroserviceError
from seldon_core_tpu.utils import faults


CFG = dict(vocab_size=64, d_model=32, num_layers=1, num_heads=2, max_len=64)


@pytest.fixture(scope="module")
def params():
    module = TransformerLM(dtype=jnp.float32, **CFG)
    return module.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture(autouse=True)
def _disarm():
    faults.clear()
    yield
    faults.clear()


def _engine(params, **kw):
    base = dict(dtype=jnp.float32, page_size=8, max_slots=2, steps_per_call=4)
    base.update(kw)
    return PagedEngine(params, **CFG, **base)


# ---------------------------------------------------------------------------
# registry / spec parsing
# ---------------------------------------------------------------------------


class TestSpec:
    def test_parse_single_point_defaults(self):
        faults.configure("paged.alloc")
        assert faults.enabled()
        assert faults.fire("paged.alloc")  # times=1 default
        assert not faults.fire("paged.alloc")  # disarmed after one firing

    def test_parse_params_and_multiple_points(self):
        faults.configure("paged.alloc:times=2;transport.delay:ms=25,times=1")
        assert faults.fire("paged.alloc")
        assert faults.fire("paged.alloc")
        assert not faults.fire("paged.alloc")
        assert faults.delay_s("transport.delay") == pytest.approx(0.025)
        assert faults.delay_s("transport.delay") == 0.0

    def test_unknown_point_or_param_rejected(self):
        with pytest.raises(ValueError):
            faults.configure("paged.everything")
        with pytest.raises(ValueError):
            faults.configure("paged.alloc:bogus=1")

    # ---- negative grammar (the tests PR 6 deferred): every
    # malformation must error LOUDLY naming the fragment — a chaos
    # harness that silently no-ops on a typo certifies resilience it
    # never exercised ------------------------------------------------------

    def test_malformed_kv_pair_rejected_loudly(self):
        # bare key, no '='
        with pytest.raises(ValueError, match=r"malformed fault parameter 'times'"):
            faults.configure("paged.alloc:times")
        # '=' with empty value
        with pytest.raises(ValueError, match=r"malformed fault parameter 'ms='"):
            faults.configure("transport.delay:ms=")
        assert not faults.enabled()  # nothing half-armed

    def test_bad_numeric_values_rejected_with_context(self):
        with pytest.raises(ValueError, match=r"bad value.*'times=lots'.*paged\.alloc"):
            faults.configure("paged.alloc:times=lots")
        with pytest.raises(ValueError, match=r"bad value.*'prob=maybe'"):
            faults.configure("paged.chunk:prob=maybe")
        with pytest.raises(ValueError, match=r"bad value.*'ms=fast'"):
            faults.configure("transport.delay:ms=fast")

    def test_out_of_range_values_rejected(self):
        with pytest.raises(ValueError, match="prob must be in"):
            faults.configure("paged.alloc:prob=1.5")
        with pytest.raises(ValueError, match="prob must be in"):
            faults.configure("paged.alloc:prob=-0.1")
        with pytest.raises(ValueError, match="times must be >= 0"):
            faults.configure("paged.alloc:times=-2")
        with pytest.raises(ValueError, match="ms must be >= 0"):
            faults.configure("transport.delay:ms=-50")

    def test_duplicate_point_rejected(self):
        with pytest.raises(ValueError, match="duplicate fault point"):
            faults.configure("paged.alloc:times=1;paged.alloc:times=2")

    def test_unknown_point_names_known_points(self):
        with pytest.raises(ValueError, match="transport.slow"):
            faults.configure("paged.everything")

    def test_inject_rejects_unknown_point(self):
        with pytest.raises(ValueError, match="unknown fault point"):
            faults.inject("paged.everything")

    def test_times_inf_still_parses(self):
        faults.configure("paged.alloc:times=inf,prob=1.0")
        for _ in range(5):
            assert faults.fire("paged.alloc")
        faults.clear()

    def test_failed_configure_leaves_registry_disarmed(self):
        faults.configure("paged.alloc:times=3")
        assert faults.enabled()
        with pytest.raises(ValueError):
            faults.configure("paged.alloc:times=3;bogus.point")
        # the bad spec cleared nothing mid-way: configure is atomic
        # (parse first, swap under the lock after)
        assert faults.enabled()
        assert faults.fire("paged.alloc")
        faults.clear()

    def test_env_configure_and_clear(self, monkeypatch):
        monkeypatch.setenv(faults.ENV_VAR, "paged.chunk:times=1")
        faults.configure()
        assert faults.enabled()
        with pytest.raises(faults.InjectedFault):
            faults.raise_if("paged.chunk")
        faults.clear()
        assert not faults.enabled()
        faults.raise_if("paged.chunk")  # disarmed: no-op

    def test_injected_fault_reads_as_grpc_unavailable(self):
        from seldon_core_tpu.engine.transport import (
            _grpc_retryable,
            _grpc_status_name,
        )

        e = faults.InjectedFault("transport.drop")
        assert _grpc_status_name(e) == "UNAVAILABLE"
        assert _grpc_retryable(e)
        assert isinstance(e, ConnectionError)

    def test_stats_count_firings(self):
        before = faults.stats().get("paged.alloc", 0)
        faults.inject("paged.alloc", times=3)
        for _ in range(5):
            faults.fire("paged.alloc")
        assert faults.stats()["paged.alloc"] == before + 3


# ---------------------------------------------------------------------------
# paged.alloc: allocator exhaustion under concurrent load, audit on
# ---------------------------------------------------------------------------


class TestAllocFaultChaos:
    def test_alloc_exhaustion_degrades_gracefully(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_PAGED_DEBUG", "1")
        eng = _engine(params, max_slots=2, num_pages=9)
        faults.inject("paged.alloc", times=4)
        streams = [
            eng.submit(np.arange(10) + i, max_new_tokens=12) for i in range(4)
        ]
        eng.run()  # audit runs at every chunk boundary
        assert faults.stats()["paged.alloc"] >= 1
        # invariant: no stuck streams — every waiter resolved, and only
        # with a result (injected exhaustion looks like pool pressure,
        # which the stall/evict path absorbs without failing anyone)
        for s in streams:
            assert s.event.is_set()
            assert s.result is not None or isinstance(s.error, MicroserviceError)
        assert not eng.has_work()  # queue drained
        with eng._lock:
            eng._check_invariants_locked()  # audit clean at rest
        # fail_all never needed: the engine keeps serving
        assert eng.generate(np.arange(6), max_new_tokens=4).shape == (4,)

    def test_alloc_fault_during_prefix_match_rolls_back(self, params, monkeypatch):
        """The admission-time alloc failure path must roll back matched
        prefix refcounts (the audit catches a missed rollback)."""
        monkeypatch.setenv("SELDON_TPU_PAGED_DEBUG", "1")
        eng = _engine(params, max_slots=2)
        shared = np.arange(16)  # two full pages -> registered prefixes
        first = eng.submit(shared, max_new_tokens=4)
        eng.run()
        assert first.result is not None
        faults.inject("paged.alloc", times=1)
        follower = eng.submit(
            np.concatenate([shared, np.arange(4)]), max_new_tokens=4
        )
        eng.run()
        assert follower.result is not None
        with eng._lock:
            eng._check_invariants_locked()


# ---------------------------------------------------------------------------
# paged.chunk: contained chunk failure — never fail_all
# ---------------------------------------------------------------------------


class TestChunkFaultChaos:
    def test_chunk_fault_fails_only_that_wave(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_PAGED_DEBUG", "1")
        eng = _engine(params, max_slots=2)
        faults.inject("paged.chunk", times=1)
        a = eng.submit(np.arange(10), max_new_tokens=8)
        b = eng.submit(np.arange(10) + 1, max_new_tokens=8)
        late = eng.submit(np.arange(10) + 2, max_new_tokens=8)
        eng.run()
        # the wave that hit the fault errored cleanly (503, named reason)
        faulted = [s for s in (a, b, late) if s.error is not None]
        assert faulted, "the injected chunk fault must surface somewhere"
        for s in faulted:
            assert s.error.status_code == 503
            assert s.error.reason == "ENGINE_CHUNK_FAULT"
            assert s.event.is_set()
        # streams outside the faulted wave completed normally
        survivors = [s for s in (a, b, late) if s.error is None]
        assert all(s.result is not None for s in survivors)
        assert eng.engine_stats()["chunk_faults"] == 1
        assert not eng.has_work()
        with eng._lock:
            eng._check_invariants_locked()

    def test_engine_serves_bit_exact_after_chunk_fault(self, params):
        eng = _engine(params)
        faults.inject("paged.chunk", times=1)
        doomed = eng.submit(np.arange(10), max_new_tokens=8)
        eng.run()
        assert doomed.error is not None
        faults.clear()
        got = eng.generate(np.arange(10), max_new_tokens=8)
        want = _engine(params).generate(np.arange(10), max_new_tokens=8)
        np.testing.assert_array_equal(got, want)

    def test_speculative_chunk_fault_contained_too(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_PAGED_DEBUG", "1")
        eng = _engine(
            params, speculative={"draft": "ngram", "draft_k": 2},
        )
        faults.inject("paged.chunk", times=1)
        s = eng.submit(np.array([3, 5, 3, 5, 3], np.int32), max_new_tokens=8)
        eng.run()
        assert s.event.is_set()
        assert s.result is not None or s.error.reason == "ENGINE_CHUNK_FAULT"
        assert not eng.has_work()
        with eng._lock:
            eng._check_invariants_locked()
        assert eng.engine_stats()["chunk_faults"] == 1


# ---------------------------------------------------------------------------
# transport delay / drop through the real node clients
# ---------------------------------------------------------------------------


def _run(coro):
    return asyncio.run(coro)


class TestTransportFaults:
    def test_rest_drop_recovers_via_retry(self):
        from aiohttp import web
        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.graph import Endpoint, UnitSpec
        from seldon_core_tpu.engine.transport import RestClient
        from seldon_core_tpu.runtime.message import InternalMessage

        calls = {"n": 0}

        async def ok(request):
            calls["n"] += 1
            return web.json_response({"data": {"ndarray": [[9.0]]}})

        async def scenario():
            app = web.Application()
            app.router.add_post("/predict", ok)
            server = TestServer(app)
            tc = TestClient(server)
            await tc.start_server()
            unit = UnitSpec(
                name="m", type="MODEL",
                endpoint=Endpoint(host=server.host, port=server.port,
                                  transport="REST"),
            )
            client = RestClient(unit, retries=3)
            faults.inject("transport.drop", times=1)
            msg = InternalMessage(payload=np.array([[1.0]]), kind="ndarray")
            out = await client.transform_input(msg)
            await client.close()
            await tc.close()
            return out

        out = _run(scenario())
        assert out.array().tolist() == [[9.0]]
        assert calls["n"] == 1  # first attempt dropped before the wire
        assert faults.stats()["transport.drop"] >= 1

    def test_rest_drop_exhaustion_carries_injected_attempts(self):
        from aiohttp import web
        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.graph import Endpoint, UnitSpec
        from seldon_core_tpu.engine.transport import RestClient
        from seldon_core_tpu.runtime.message import InternalMessage

        async def scenario():
            app = web.Application()
            server = TestServer(app)
            tc = TestClient(server)
            await tc.start_server()
            unit = UnitSpec(
                name="m", type="MODEL",
                endpoint=Endpoint(host=server.host, port=server.port,
                                  transport="REST"),
            )
            client = RestClient(unit, retries=2)
            faults.inject("transport.drop", times=5)
            msg = InternalMessage(payload=np.array([[1.0]]), kind="ndarray")
            try:
                await client.transform_input(msg)
            finally:
                await client.close()
                await tc.close()

        with pytest.raises(MicroserviceError) as ei:
            _run(scenario())
        assert len(ei.value.attempts) == 2
        assert all(a["status"] == "InjectedFault" for a in ei.value.attempts)

    def test_rest_delay_fires_and_call_still_succeeds(self):
        from aiohttp import web
        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.graph import Endpoint, UnitSpec
        from seldon_core_tpu.engine.transport import RestClient
        from seldon_core_tpu.runtime.message import InternalMessage

        async def ok(request):
            return web.json_response({"data": {"ndarray": [[9.0]]}})

        async def scenario():
            app = web.Application()
            app.router.add_post("/predict", ok)
            server = TestServer(app)
            tc = TestClient(server)
            await tc.start_server()
            unit = UnitSpec(
                name="m", type="MODEL",
                endpoint=Endpoint(host=server.host, port=server.port,
                                  transport="REST"),
            )
            client = RestClient(unit)
            faults.inject("transport.delay", times=1, delay_ms=50)
            msg = InternalMessage(payload=np.array([[1.0]]), kind="ndarray")
            t0 = time.perf_counter()
            out = await client.transform_input(msg)
            elapsed = time.perf_counter() - t0
            await client.close()
            await tc.close()
            return out, elapsed

        out, elapsed = _run(scenario())
        assert out.array().tolist() == [[9.0]]
        assert elapsed >= 0.05
        assert faults.stats()["transport.delay"] >= 1

    def test_transport_slow_is_latency_not_error_with_its_own_budget(self):
        """The straggler point (r12): `transport.slow` delays an
        attempt WITHOUT failing it, and its times/prob budget is
        independent of `transport.delay`/`transport.drop` — so a chaos
        scenario can arm stragglers and drops simultaneously and tell
        the effects apart."""
        from aiohttp import web
        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.graph import Endpoint, UnitSpec
        from seldon_core_tpu.engine.transport import RestClient
        from seldon_core_tpu.runtime.message import InternalMessage

        calls = {"n": 0}

        async def ok(request):
            calls["n"] += 1
            return web.json_response({"data": {"ndarray": [[9.0]]}})

        before = faults.stats()  # _fired_total is cumulative per process

        async def scenario():
            app = web.Application()
            app.router.add_post("/predict", ok)
            server = TestServer(app)
            tc = TestClient(server)
            await tc.start_server()
            unit = UnitSpec(
                name="m", type="MODEL",
                endpoint=Endpoint(host=server.host, port=server.port,
                                  transport="REST"),
            )
            client = RestClient(unit, retries=3)
            # both latency points armed with SEPARATE budgets, plus one
            # drop: every budget must fire independently
            faults.configure(
                "transport.slow:times=1,ms=80;"
                "transport.delay:times=1,ms=40;"
                "transport.drop:times=1"
            )
            msg = InternalMessage(payload=np.array([[1.0]]), kind="ndarray")
            t0 = time.perf_counter()
            out = await client.transform_input(msg)
            elapsed = time.perf_counter() - t0
            await client.close()
            await tc.close()
            return out, elapsed

        out, elapsed = _run(scenario())
        assert out.array().tolist() == [[9.0]]
        # slow fired (latency, no error): total covers both delays
        assert elapsed >= 0.08
        stats = faults.stats()
        assert stats["transport.slow"] - before.get("transport.slow", 0) == 1
        assert stats["transport.delay"] - before.get("transport.delay", 0) == 1
        # the drop still dropped — each budget independent of the others
        assert stats["transport.drop"] - before.get("transport.drop", 0) == 1
        assert calls["n"] == 1  # exactly one attempt reached the wire

    def test_grpc_drop_recovers_via_retry(self):
        async def scenario():
            import grpc

            from seldon_core_tpu.engine.graph import Endpoint, UnitSpec
            from seldon_core_tpu.engine.transport import GrpcClient
            from seldon_core_tpu.runtime import grpc_server
            from seldon_core_tpu.runtime.message import InternalMessage

            class Doubler:
                def predict(self, X, names, meta=None):
                    return np.asarray(X) * 2

            server = grpc_server.build_server(Doubler())
            port = server.add_insecure_port("127.0.0.1:0")
            await server.start()
            unit = UnitSpec(
                name="m", type="MODEL",
                endpoint=Endpoint(host="127.0.0.1", port=port,
                                  transport="GRPC"),
            )
            client = GrpcClient(unit, retries=3)
            faults.inject("transport.drop", times=1)
            msg = InternalMessage(payload=np.array([[2.0]]), kind="ndarray")
            out = await client.transform_input(msg)
            await client.close()
            await server.stop(None)
            return out

        out = _run(scenario())
        assert out.array().tolist() == [[4.0]]
        assert faults.stats()["transport.drop"] >= 1


# ---------------------------------------------------------------------------
# env-spec chaos: every point armed at once, concurrent load, audit on
# ---------------------------------------------------------------------------


class TestConcurrentChaos:
    def test_all_engine_points_under_concurrent_load(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_PAGED_DEBUG", "1")
        monkeypatch.setenv(
            faults.ENV_VAR, "paged.alloc:times=3;paged.chunk:times=2"
        )
        faults.configure()  # from the env, as a worker process would
        eng = _engine(params, max_slots=2, num_pages=9, max_queue=8)
        results = []
        lock = threading.Lock()

        def client(i):
            try:
                s = eng.submit(np.arange(10) + i, max_new_tokens=10)
                s.event.wait(timeout=60)
                with lock:
                    results.append((i, s.result is not None, s.error))
            except MicroserviceError as e:  # shed at submit is legal
                with lock:
                    results.append((i, False, e))

        threads = [
            threading.Thread(target=client, args=(i,)) for i in range(6)
        ]
        stepper = threading.Thread(target=eng.run)
        for t in threads:
            t.start()
        time.sleep(0.01)
        stepper.start()
        for t in threads:
            t.join(timeout=90)
            assert not t.is_alive(), "stuck client thread"
        # the engine may briefly idle between client submits: drain
        # whatever is left, then the queue must be empty
        for _ in range(50):
            if not eng.has_work():
                break
            eng.step()
        stepper.join(timeout=60)
        assert len(results) == 6
        for i, ok_, err in results:
            assert ok_ or isinstance(err, MicroserviceError), (i, err)
        assert not eng.has_work()
        with eng._lock:
            eng._check_invariants_locked()  # audit clean after the storm
        # fail_all never needed — the engine still serves, bit-exact
        faults.clear()
        got = eng.generate(np.arange(10), max_new_tokens=8)
        want = _engine(params).generate(np.arange(10), max_new_tokens=8)
        np.testing.assert_array_equal(got, want)
        fired = faults.stats()
        assert fired.get("paged.alloc", 0) >= 1
        assert fired.get("paged.chunk", 0) >= 1


# ---------------------------------------------------------------------------
# r17 fault points: paged.nan (poison-stream quarantine) and
# transport.corrupt (KV-container byte flips)
# ---------------------------------------------------------------------------


class TestR17Grammar:
    """Strict-grammar negative tests for the new points — same
    discipline as the PR 9 suite: every malformation errors LOUDLY."""

    def test_new_points_parse_with_defaults(self):
        faults.configure("paged.nan;transport.corrupt:k=3,times=2")
        assert faults.fire("paged.nan")
        assert not faults.fire("paged.nan")
        assert faults.fire_k("transport.corrupt") == 3
        assert faults.fire_k("transport.corrupt") == 3
        assert faults.fire_k("transport.corrupt") == 0  # budget spent

    def test_k_defaults_to_one(self):
        faults.configure("transport.corrupt")
        assert faults.fire_k("transport.corrupt") == 1

    def test_bad_k_value_rejected(self):
        with pytest.raises(ValueError, match=r"bad value.*'k=many'"):
            faults.configure("transport.corrupt:k=many")
        with pytest.raises(ValueError, match="k must be >= 1"):
            faults.configure("transport.corrupt:k=0")
        with pytest.raises(ValueError, match="k must be >= 1"):
            faults.configure("transport.corrupt:k=-4")
        assert not faults.enabled()  # nothing half-armed

    def test_unknown_param_on_new_points_rejected(self):
        with pytest.raises(ValueError, match="unknown fault parameter"):
            faults.configure("paged.nan:bytes=1")

    def test_new_points_listed_in_unknown_point_error(self):
        with pytest.raises(ValueError) as e:
            faults.configure("paged.everything")
        assert "paged.nan" in str(e.value)
        assert "transport.corrupt" in str(e.value)

    def test_corrupt_bytes_noop_when_disarmed(self):
        data = bytes(range(64))
        assert faults.corrupt_bytes("transport.corrupt", data) == data

    def test_corrupt_bytes_flips_when_armed(self):
        faults.inject("transport.corrupt", times=1, k=2)
        data = bytes(64)
        out = faults.corrupt_bytes("transport.corrupt", data)
        assert out != data and len(out) == len(data)
        # budget spent: second call passes through untouched
        assert faults.corrupt_bytes("transport.corrupt", data) == data

    @pytest.mark.parametrize("k", [1, 2, 64])
    def test_corrupt_bytes_flips_k_distinct_bytes(self, k):
        """Positions are drawn without replacement: a byte picked twice
        would flip back, and an armed point inject nothing."""
        faults.inject("transport.corrupt", times=1, k=k)
        data = bytes(range(64))
        out = faults.corrupt_bytes("transport.corrupt", data)
        assert sum(a != b for a, b in zip(out, data)) == k


class TestNanQuarantine:
    def test_injected_nan_quarantines_one_stream_wave_mates_bit_identical(
        self, params
    ):
        prompts = [np.arange(12) + i for i in range(3)]
        ref = _engine(params, max_slots=4)
        expect = [
            ref.generate(p, max_new_tokens=10, seed=i)
            for i, p in enumerate(prompts)
        ]
        eng = _engine(params, max_slots=4)
        streams = [
            eng.submit(p, max_new_tokens=10, seed=i)
            for i, p in enumerate(prompts)
        ]
        eng.step()  # prefill + first chunk, no fault
        fired_before = faults.stats().get("paged.nan", 0)
        faults.inject("paged.nan", times=1)
        eng.run()
        poisoned = [s for s in streams if s.error is not None]
        assert len(poisoned) == 1
        err = poisoned[0].error
        assert isinstance(err, MicroserviceError)
        assert err.status_code == 500
        assert err.reason == "NUMERIC_POISON"
        assert eng.engine_stats()["quarantined"] == 1
        assert faults.stats().get("paged.nan", 0) == fired_before + 1
        # the wave-mates' outputs are bit-identical to the no-fault run
        for s in streams:
            if s.error is None:
                i = streams.index(s)
                np.testing.assert_array_equal(s.result, expect[i])
        # the engine keeps serving bit-exact afterwards (never fail_all)
        got = eng.generate(np.arange(12), max_new_tokens=10, seed=0)
        np.testing.assert_array_equal(got, expect[0])

    def test_nan_guard_off_skips_screen(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_NAN_GUARD", "0")
        eng = _engine(params, max_slots=2)
        s = eng.submit(np.arange(12), max_new_tokens=8)
        faults.inject("paged.nan", times=1)
        eng.run()
        # guard off: the injected NaN lane is NOT retired — the stream
        # completes (with whatever the poisoned argmax produced); the
        # quarantine counter stays 0.  This is exactly the silent-
        # garbage failure mode the default-on guard exists to close.
        assert s.error is None
        assert eng.engine_stats()["quarantined"] == 0

    def test_quarantined_stream_drops_poisoned_chunk_tokens(self, params):
        eng = _engine(params, max_slots=2)
        s = eng.submit(np.arange(12), max_new_tokens=16, stream_tokens=True)
        eng.step()  # wave 1: prefill + chunk
        pushed_before = s.streamed
        faults.inject("paged.nan", times=1)
        eng.step()  # wave 2: poisoned chunk — tokens must NOT stream
        assert s.error is not None and s.error.reason == "NUMERIC_POISON"
        assert s.streamed == pushed_before
        # consumer unblocks via the end-of-stream sentinel
        items = []
        while s.token_queue.qsize():
            items.append(s.token_queue.get())
        assert items[-1] is None


class TestTransportCorrupt:
    def test_corrupt_handoff_rejects_with_named_error(self, params):
        from seldon_core_tpu.codec.bufview import (
            pack_kv_handoff,
            unpack_kv_handoff,
        )
        from seldon_core_tpu.codec.tensor import PayloadError

        eng = _engine(params)
        payload = eng.prefill_export(np.arange(20), seed=3)
        buf = pack_kv_handoff(payload)
        faults.inject("transport.corrupt", times=1, k=1)
        bad = faults.corrupt_bytes("transport.corrupt", buf)
        assert bad != buf
        with pytest.raises(PayloadError):
            unpack_kv_handoff(bad)
        # the pristine container still decodes
        out = unpack_kv_handoff(buf)
        np.testing.assert_array_equal(out["prompt"], payload["prompt"])
