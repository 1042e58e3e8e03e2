"""The wave loop's seam (PR 24): phase annotations on the profiler's
clock, work and waiting counted where they happen, programs named by
their static shape, and the profile window armed on a running engine.

Fast tier, CPU: tiny engines; one profiler session at a time (the
tests of this file run in one worker: ``--dist loadfile``).
"""

import glob
import os
import time

import numpy as np
import pytest

import jax.numpy as jnp

CFG = dict(vocab_size=64, d_model=32, num_layers=1, num_heads=2, max_len=128)
PHASES = ("admit", "launch", "wait", "harvest", "record")


def _tiny_engine(**kw):
    import jax

    from seldon_core_tpu.models.paged import PagedEngine
    from seldon_core_tpu.models.transformer import TransformerLM

    lm = TransformerLM(dtype=jnp.float32, **CFG)
    params = lm.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    base = dict(dtype=jnp.float32, page_size=8, max_slots=8, steps_per_call=4,
                prefix_cache=False)
    base.update(kw)
    return PagedEngine(params, **CFG, **base)


def _prompt(length, first):
    """Unique content per prompt: nothing shares a page-long prefix."""
    return ((np.arange(length, dtype=np.int32) * 7 + first) % 64).astype(np.int32)


def _run_chained(eng, between=None):
    """The halves as ``StreamingLM._loop`` composes them: wave N+1 is
    launched before wave N is harvested."""
    prev = None
    while eng.has_work():
        nxt = eng.launch()
        if between is not None:
            between(eng)
        eng.harvest(prev)
        prev = nxt


def _seam_events(trace_dir):
    """``(name, start_ns, end_ns, stats)`` of every ``seldon.wave*``
    event of a recorded trace, read as the benchmark reads one."""
    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    assert found, f"no xplane under {trace_dir}"
    out = []
    for plane in ProfileData.from_file(found[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("seldon.wave"):
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                {str(k): v for k, v in ev.stats}))
    return sorted(out, key=lambda e: e[1])


class TestPhasesOnTheProfilersClock:
    @pytest.mark.parametrize("loop", ["step_by_step", "chained"])
    def test_every_wave_is_a_step_tiled_by_its_phases(self, tmp_path, loop):
        """A step is admit, launch, then wait, harvest, record: of its own
        wave when ``step()`` runs the halves back to back, of the wave
        before when the serving loop chains them (the last wave of a
        burst is then read outside any step: nothing is left to launch)."""
        import jax

        run = _run_chained if loop == "chained" else (lambda e: e.run())
        eng = _tiny_engine()
        try:
            # warm every shape outside the trace, then the traced
            # sequence: one prompt alone, then 3 + 5 over two buckets
            for group in ([(5, 1)], [(6, 2), (7, 3), (9, 4)] + [(20 + i, 5 + i) for i in range(5)]):
                for n, f in group:
                    eng.submit(_prompt(n, f), max_new_tokens=6)
                eng.run()
            before = eng.engine_stats()
            wave0 = eng._seam.wave
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=options)
            try:
                eng.submit(_prompt(5, 11), max_new_tokens=6)
                run(eng)
                for n, f in [(6, 12), (7, 13), (9, 14)] + [(20 + i, 15 + i) for i in range(5)]:
                    eng.submit(_prompt(n, f), max_new_tokens=6)
                run(eng)
            finally:
                jax.profiler.stop_trace()
            after = eng.engine_stats()
        finally:
            eng.close()

        events = _seam_events(str(tmp_path))
        waves = [e for e in events if e[0] == "seldon.wave"]
        assert [w[3]["step_num"] for w in waves] == list(
            range(wave0 + 1, eng._seam.wave + 1))
        chunks = after["chunks"] - before["chunks"]
        assert len(waves) == chunks  # every wave of this sequence decodes

        def inside(wave, name):
            return [e for e in events if e[0] == f"seldon.wave.{name}"
                    and wave[1] <= e[1] and e[2] <= wave[2]]

        def of_wave(name, number):
            return [e for e in events if e[0] == f"seldon.wave.{name}"
                    and e[3].get("wave") == number]

        prefills, harvested, overlapped = [], [], []
        for i, wave in enumerate(waves):
            number = wave[3]["step_num"]
            got = {name: inside(wave, name) for name in ("admit", "launch")}
            assert {k: len(v) for k, v in got.items()} == {"admit": 1, "launch": 1}
            # its own wave's readback, each phase once, wherever it lies
            got.update({name: of_wave(name, number)
                        for name in ("wait", "harvest", "record")})
            assert {k: len(v) for k, v in got.items()} == dict.fromkeys(PHASES, 1)
            admit, launch, wait, harvest, record = (got[n][0] for n in PHASES)
            # the phases are in order, and tile the step they lie in
            assert admit[2] <= launch[1] and launch[2] <= wait[1]
            assert wait[2] <= harvest[1] and harvest[2] <= record[1]
            read = [e for n in ("wait", "harvest", "record") for e in inside(wave, n)]
            if loop == "step_by_step":
                assert read == [wait, harvest, record]
            else:
                # what this step reads is the wave before, under the chunk
                # it has just enqueued; its own is read by the next step,
                # or outside any once nothing is left to launch
                assert all(e[3]["wave"] == number - 1 and e[1] >= launch[2] for e in read)
                assert len(read) == 3 * launch[3]["overlapped"]
                if i + 1 < len(waves) and waves[i + 1][3]["step_num"] == number + 1 \
                        and inside(waves[i + 1], "launch")[0][3]["overlapped"]:
                    assert record[2] <= waves[i + 1][2] and wait[1] >= waves[i + 1][1]
                else:
                    assert wait[1] >= wave[2]
            assert set(admit[3]) >= {"admitted", "queue_depth"}
            assert set(launch[3]) >= {"steps", "lanes", "kv_tokens",
                                      "pages_live", "page_slots", "overlapped"}
            assert 0 < launch[3]["pages_live"] <= launch[3]["page_slots"]
            assert set(harvest[3]) >= {"tokens", "finished", "wave"}
            assert launch[3]["steps"] == 4 and launch[3]["lanes"] >= 1
            prefills += inside(wave, "prefill")
            harvested.append(harvest[3])
            overlapped.append(launch[3]["overlapped"])
        # a burst's first chunk finds the device empty, every later one
        # is enqueued behind an unread wave iff the loop chains
        assert sum(overlapped) == after["waves_overlapped"] - before["waves_overlapped"]
        assert sum(overlapped) == (len(waves) - 2 if loop == "chained" else 0)
        # one prefill annotation per prefill group, carrying its work
        assert len(prefills) == after["prefill_chunks"] - before["prefill_chunks"] == 3
        shapes = sorted((p[3]["bucket"], p[3]["k"], p[3]["rows"], p[3]["padded"],
                         p[3]["cached"]) for p in prefills)
        assert shapes == [(16, 1, 1, 16, 0), (16, 4, 3, 64, 0), (32, 8, 5, 256, 0)]
        assert sum(p[3]["tokens"] for p in prefills) == (
            after["prefill_tokens"] - before["prefill_tokens"])
        assert sum(p[3]["padded"] for p in prefills) == (
            after["prefill_padded_tokens"] - before["prefill_padded_tokens"])
        assert sum(h["tokens"] for h in harvested) == after["tokens"] - before["tokens"]
        assert sum(h["finished"] for h in harvested) == 9
        admitted = [inside(w, "admit")[0][3]["admitted"] for w in waves]
        assert sum(admitted) == 9

    def test_wave_number_rides_the_flight_recorder(self):
        eng = _tiny_engine()
        try:
            eng.submit(_prompt(5, 1), max_new_tokens=6)
            eng.run()
            records = eng.engine_stats(detail=True)["recorder"]
            assert [r["wave"] for r in records] == list(range(1, eng._seam.wave + 1))
        finally:
            eng.close()


class TestCountedWhereItHappens:
    def test_padding_decode_and_waits_against_hand_computed_values(self):
        eng = _tiny_engine()
        try:
            # groups of 1, 3 and 5 over two buckets (16 and 32)
            first = [(5, 1)]
            second = [(6, 2), (7, 3), (9, 4)] + [(20 + i, 5 + i) for i in range(5)]
            new = 6
            t_in = time.monotonic() - 1.5
            for n, f in first:
                s = eng.submit(_prompt(n, f), max_new_tokens=new, t_ingress=t_in)
                s.m_submit -= 2.0
            eng.run()
            for n, f in second:
                s = eng.submit(_prompt(n, f), max_new_tokens=new)
                s.m_submit -= 2.0
            eng.run()
            stats = eng.engine_stats(detail=True)
        finally:
            eng.close()
        lengths = [n for n, _f in first + second]
        assert stats["prefill_tokens"] == sum(lengths)
        # k * bucket per call: 1 x 16, 3 -> 4 x 16, 5 -> 8 x 32
        assert stats["prefill_padded_tokens"] == 16 + 4 * 16 + 8 * 32
        assert stats["prefill_chunks"] == 3
        # every stream runs `new` steps; step t attends length + t
        assert stats["decode_lane_steps"] == new * len(lengths)
        assert stats["decode_kv_tokens"] == sum(
            new * n + new * (new - 1) // 2 for n in lengths)
        assert stats["queue_waits"] == len(lengths)
        assert 2.0 * len(lengths) <= stats["queue_wait_s"] < 2.0 * len(lengths) + 1.0
        assert stats["ingress_waits"] == 1
        assert 1.5 <= stats["ingress_wait_s"] < 2.5
        # the device's idle time is kept by where the engine thread was,
        # and those are its whole (step by step, every readback leaves
        # the device empty: the harvest and the next launch are idle)
        assert stats["host_gap_s"] > 0.0
        assert "phase_s" not in stats
        assert stats["device_idle_s"] == pytest.approx(
            sum(stats["device_idle_by_s"].values()), abs=1e-9)
        assert stats["device_idle_by_s"]["harvest"] > 0.0
        assert stats["device_idle_by_s"]["launch.plan"] > 0.0
        assert set(stats["device_idle_by_s"]) == {
            "no_work", "between", "admit", "prefill.pack", "prefill.call",
            "prefill.tail", "launch.plan", "launch.call", "launch.post",
            "wait", "harvest", "record"}

    @pytest.mark.parametrize("lane,slots,live", [
        # 8 lanes in two buckets of 4; lengths 7 and 20, 4 steps, pages
        # of 8.  ring (the CPU default): tables cover the cache as the
        # chunk starts, 1 and 4 (3 rounded up) pages wide; its own
        # tokens stay in the ring, so each step reads 1 + 3 pool pages
        ("ring", 4 * (4 * 1 + 4 * 4), 4 * (1 + 3)),
        # pool (the kernel's lane): tables cover the chunk's growth too,
        # 2 and 4 pages; lane A crosses a page: 7, 8 | 9, 10 tokens
        ("kernel", 4 * (4 * 2 + 4 * 4), (1 + 1 + 2 + 2) + 4 * 3),
    ])
    def test_page_loop_slots_and_live_pages_of_a_two_lane_wave(
            self, monkeypatch, lane, slots, live):
        if lane == "kernel":
            monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "force")
        eng = _tiny_engine()
        try:
            assert eng._chunk_impl == ("pool" if lane == "kernel" else "ring")
            for n, f in [(7, 1), (20, 2)]:
                eng.submit(_prompt(n, f), max_new_tokens=4)
            eng.run()
            stats = eng.engine_stats()
        finally:
            eng.close()
        assert stats["chunks"] == 1 and stats["bucketed_chunks"] == 1
        assert stats["decode_lane_steps"] == 2 * 4
        assert stats["decode_page_slots"] == slots
        assert stats["decode_live_pages"] == live
        assert stats["decode_live_pages"] <= stats["decode_page_slots"]

    @pytest.mark.parametrize("name,part,whole", [
        # PR 27: live pages of the page loop's slots
        ("decode_live_page_pct", "decode_live_pages", "decode_page_slots"),
        # PR 29: chunks enqueued behind an unread wave, of all chunks
        ("wave_overlap_pct", "waves_overlapped", "chunks"),
    ])
    def test_share_readers_take_the_counters_deltas(self, name, part, whole):
        """``benchmarks/layer_metrics/<name>.py`` on a hand-made ``ctx``;
        an engine without the counters (the parent of the PR that brought
        them) gives it nothing, and it does not raise."""
        import importlib.util
        import sys

        bench = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks")
        sys.path.insert(0, bench)
        try:
            spec = importlib.util.spec_from_file_location(
                name, os.path.join(bench, "layer_metrics", f"{name}.py"))
            reader = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(reader)
        finally:
            sys.path.remove(bench)

        def ctx(before, after):
            return {"window": (100.0, 150.0), "engine": {"window": [before, after]},
                    "trace": None, "config": {}}

        before = {part: 100, whole: 400}
        after = {part: 1630, whole: 3400}
        assert reader.read(ctx(before, after)) == pytest.approx(51.0)
        older = {"tokens": 9, "decode_kv_tokens": 5}
        assert reader.read(ctx(older, dict(older, tokens=19))) is None
        assert reader.read(ctx(before, before)) is None
        assert reader.read(ctx(before, None)) is None
        assert reader.read({"window": (0.0, 1.0), "engine": {}, "trace": None}) is None

    def test_no_gap_is_counted_across_an_idle_engine(self):
        eng = _tiny_engine()
        try:
            eng.submit(_prompt(5, 1), max_new_tokens=4)
            eng.run()
            gap = eng.engine_stats()["host_gap_s"]
            time.sleep(0.3)  # idle: no work, so no gap
            eng.submit(_prompt(5, 2), max_new_tokens=4)
            eng.run()
            assert eng.engine_stats()["host_gap_s"] - gap < 0.25
        finally:
            eng.close()

    def test_no_gap_opens_while_a_wave_is_in_flight(self):
        """A saturated loop, chained: the readback of wave N returns with
        wave N+1 enqueued, so the device has its next program and the
        host's time is no gap, however long it takes."""
        eng = _tiny_engine()
        try:
            for i in range(8):
                eng.submit(_prompt(5 + i, i + 1), max_new_tokens=64)
            first = eng.launch()
            gap = eng.engine_stats()["host_gap_s"]  # up to the first dispatch

            def slow_host(_eng):
                time.sleep(0.02)

            prev = first
            eng.harvest(None)
            while eng.has_work():
                nxt = eng.launch()
                slow_host(eng)
                assert eng.engine_stats()["host_gap_s"] == gap
                eng.harvest(prev)
                prev = nxt
            stats = eng.engine_stats()
            # (the burst's last readback leaves the device empty: its
            # harvest and record are the engine's work, and counted)
            assert stats["host_gap_s"] - gap < 0.015
            gap = stats["host_gap_s"]
            assert stats["chunks"] == 16
            assert stats["waves_overlapped"] / stats["chunks"] > 0.9
            # the same waves one at a time: every readback leaves the
            # device empty, and the sleep is counted
            for i in range(8):
                eng.submit(_prompt(5 + i, i + 1), max_new_tokens=8)
            while eng.has_work():
                wave = eng.launch()
                eng.harvest(wave)
                slow_host(eng)
            after = eng.engine_stats()
            assert after["waves_overlapped"] == stats["waves_overlapped"]
            assert after["host_gap_s"] - gap >= 0.02
        finally:
            eng.close()

    def test_speculative_verify_counts_one_step_a_lane(self):
        eng = _tiny_engine(speculative={"draft": "ngram", "draft_k": 2})
        try:
            eng.submit(_prompt(9, 1), max_new_tokens=5)
            eng.run()
            stats = eng.engine_stats()
        finally:
            eng.close()
        assert stats["decode_lane_steps"] == stats["chunks"] >= 1
        assert stats["decode_kv_tokens"] >= 9 * stats["chunks"]
        # 9 tokens and up in pages of 8: 2 live pages a verify forward
        # or more, of the 8 lanes' tables
        assert 2 * stats["chunks"] <= stats["decode_live_pages"]
        assert stats["decode_live_pages"] <= stats["decode_page_slots"]
        assert stats["decode_page_slots"] % 8 == 0
        assert stats["prefill_padded_tokens"] == 16


class TestProgramsNamedByTheirShape:
    @staticmethod
    def _jitted(fn):
        return getattr(fn, "__wrapped__", fn)  # under the jit sentinel's wrapper

    def test_prefill_and_cached_prefill_module_names(self):
        eng = _tiny_engine()
        try:
            ps = eng.page_size
            bucket, k, rp = 16, 2, 2
            i32 = jnp.int32
            plain = self._jitted(eng._build_prefill(bucket, k)).lower(
                eng.params, *eng._kv_args(), jnp.zeros((k, bucket), i32),
                jnp.ones((k,), i32),
                jnp.zeros((k, eng._pages_pow2(-(-bucket // ps))), i32),
            ).as_text()
            assert f"module @jit_paged_prefill_b{bucket}_k{k} " in plain
            cached = self._jitted(eng._build_prefill_cached(bucket, k, rp)).lower(
                eng.params, *eng._kv_args(), jnp.zeros((k, bucket), i32),
                jnp.ones((k,), i32), jnp.zeros((k,), i32),
                jnp.zeros((k, rp), i32), jnp.zeros((k, -(-bucket // ps)), i32),
            ).as_text()
            assert f"module @jit_paged_prefill_cached_b{bucket}_k{k}_r{rp} " in cached
        finally:
            eng.close()

    @pytest.mark.parametrize("impl", ["ring", "pool"])
    def test_chunk_module_names_both_impls(self, impl, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", impl)
        eng = _tiny_engine()
        try:
            assert eng._chunk_impl == impl
            one = eng.lower_chunk(2, ((8, 4),)).as_text()
            assert "module @jit_paged_chunk_s2_8x4 " in one
            two = eng.lower_chunk(4, ((4, 2), (4, 8))).as_text()
            assert "module @jit_paged_chunk_s4_4x2_4x8 " in two
            assert "jit__unknown" not in one + two
        finally:
            eng.close()

    def test_import_and_spec_chunk_names(self):
        eng = _tiny_engine(speculative={"draft": "ngram", "draft_k": 2})
        try:
            assert self._jitted(eng._spec_chunk).__name__ == "paged_spec_chunk_w3_8"
            assert self._jitted(eng._build_import_kv(3)).__name__ == "paged_import_kv_p3"
        finally:
            eng.close()


def _streaming_lm():
    from seldon_core_tpu.models.paged import StreamingLM

    lm = StreamingLM(max_new_tokens=6, page_size=8, max_slots=2,
                     steps_per_call=4, **CFG)
    lm.load()
    return lm


def _gateway(lm):
    from seldon_core_tpu.engine import PredictorService, UnitSpec
    from seldon_core_tpu.engine.server import Gateway

    return Gateway([(PredictorService(
        UnitSpec(name="lm", type="MODEL", component=lm), name="main"), 1.0)])


class TestProfileWindow:
    def test_arm_trace_done_with_snapshots_at_the_edges(self, monkeypatch, tmp_path):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.server import build_gateway_app

        monkeypatch.setenv("SELDON_TPU_PROFILE_DIR", str(tmp_path))
        lm = _streaming_lm()
        body = {"data": {"ndarray": [[1, 2, 3, 4, 5]]}}

        async def scenario():
            client = TestClient(TestServer(build_gateway_app(_gateway(lm))))
            await client.start_server()
            try:
                idle = await (await client.get("/debug/profile")).json()
                # warm the shapes, so the window holds waves and no compile
                assert (await client.post("/api/v0.1/predictions", json=body)).status == 200
                bad = await client.post("/debug/profile", params={"seconds": "soon"})
                armed = await client.post("/debug/profile", params={"seconds": "0.2"})
                armed_doc = await armed.json()
                busy = await client.post("/debug/profile", params={"seconds": "0.2"})
                states = set()
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    assert (await client.post("/api/v0.1/predictions", json=body)).status == 200
                    doc = (await (await client.get("/debug/profile")).json())["main"]["lm"]
                    states.add(doc["state"])
                    if doc["state"] in ("done", "failed"):
                        break
                detail = await (await client.get(
                    "/debug/engine", params={"detail": "1"})).json()
                return idle, bad.status, armed.status, armed_doc, busy.status, states, doc, detail
            finally:
                await client.close()

        try:
            idle, bad, armed, armed_doc, busy, states, doc, detail = asyncio.run(scenario())
        finally:
            lm.shutdown()
        assert idle["main"]["lm"] == {"state": "idle"}
        assert bad == 400 and armed == 200 and busy == 409
        assert armed_doc["main"]["lm"]["state"] in ("armed", "tracing")
        assert "tracing" in states and doc["state"] == "done", doc
        assert doc["dir"] == str(tmp_path) and doc["t_stop"] - doc["t_start"] >= 0.2
        assert glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
        # the snapshots' deltas are exactly the waves run in between
        a, b = doc["stats_start"], doc["stats_stop"]
        ran = [r for r in detail["main"]["lm"]["recorder"]
               if doc["wave_start"] < r["wave"] <= doc["wave_stop"]]
        assert ran and doc["wave_stop"] - doc["wave_start"] == len(ran)
        assert b["chunks"] - a["chunks"] == sum(r["phase"] == "decode" for r in ran)
        assert b["tokens"] - a["tokens"] == sum(r["decode_tokens"] for r in ran)
        assert b["prefill_tokens"] - a["prefill_tokens"] == sum(
            r["prefill_tokens"] for r in ran)

    def test_route_answers_409_with_the_directory_unset(self, monkeypatch):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.server import build_gateway_app

        monkeypatch.delenv("SELDON_TPU_PROFILE_DIR", raising=False)
        lm = _streaming_lm()

        async def scenario():
            client = TestClient(TestServer(build_gateway_app(_gateway(lm))))
            await client.start_server()
            try:
                resp = await client.post("/debug/profile", params={"seconds": "1"})
                return resp.status, await resp.json(), await (
                    await client.get("/debug/profile")).json()
            finally:
                await client.close()

        try:
            status, doc, after = asyncio.run(scenario())
        finally:
            lm.shutdown()
        assert status == 409 and doc["status"]["reason"] == "PROFILE_DISABLED"
        assert after["main"]["lm"] == {"state": "idle"}

    def test_an_idle_engine_closes_its_window_at_a_boundary(self, monkeypatch, tmp_path):
        monkeypatch.setenv("SELDON_TPU_PROFILE_DIR", str(tmp_path))
        eng = _tiny_engine()
        try:
            eng.submit(_prompt(5, 1), max_new_tokens=4)
            eng.run()  # warm
            eng.arm_profile(0.05)
            eng.submit(_prompt(5, 2), max_new_tokens=4)
            eng.run()
            assert eng.profile_status()["state"] == "tracing"
            time.sleep(0.06)
            eng.wave_boundary()  # what StreamingLM's loop does while it idles
            doc = eng.profile_status()
            assert doc["state"] == "done"
            assert doc["stats_stop"]["chunks"] - doc["stats_start"]["chunks"] == 1
        finally:
            eng.close()


class TestIngressStamp:
    def test_sse_handler_stamps_and_the_engine_counts_it(self):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.server import build_gateway_app

        lm = _streaming_lm()

        async def scenario():
            client = TestClient(TestServer(build_gateway_app(_gateway(lm))))
            await client.start_server()
            try:
                resp = await client.post("/api/v0.1/generate/stream",
                                         json={"data": {"ndarray": [[1, 2, 3, 4, 5]]}})
                text = await resp.text()
                return resp.status, text
            finally:
                await client.close()

        try:
            status, text = asyncio.run(scenario())
            stats = lm.engine.engine_stats()
        finally:
            lm.shutdown()
        assert status == 200 and "event: end" in text
        assert stats["ingress_waits"] == 1 and 0.0 <= stats["ingress_wait_s"] < 30.0
        assert stats["queue_waits"] == 1


    @pytest.mark.parametrize("waiting", [3, 12])
    def test_streams_waiting_for_a_slot_do_not_silence_a_decoding_one(self, waiting):
        """ROADMAP S9: a reply's first pull (submit, the wait for a slot,
        the prefill) holds a thread of the dispatch pool, not one of the
        loop's default executor, which only ever waits a wave: with two
        default threads and more callers than that waiting for a slot, a
        stream that has one still gets its tokens."""
        import asyncio
        import threading
        from concurrent.futures import ThreadPoolExecutor

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.server import build_gateway_app
        from seldon_core_tpu.runtime.component import TPUComponent

        slot = threading.Event()

        class Stub(TPUComponent):
            def predict(self, X, names, meta=None):
                return X

            def predict_stream(self, X, names, meta=None):
                if int(X[0][0]) == 0:      # a caller with no slot yet
                    slot.wait(timeout=60)
                for i in range(3):
                    yield [i, i + 1]

        async def scenario():
            asyncio.get_running_loop().set_default_executor(ThreadPoolExecutor(2))
            client = TestClient(TestServer(build_gateway_app(_gateway(Stub()))))
            await client.start_server()
            try:
                async def ask(first):
                    resp = await client.post("/api/v0.1/generate/stream",
                                             json={"data": {"ndarray": [[first, 2, 3]]}})
                    return await resp.text()

                queued = [asyncio.ensure_future(ask(0)) for _ in range(waiting)]
                await asyncio.sleep(0.3)   # they are in their first pull
                decoding = await asyncio.wait_for(ask(7), timeout=20)
                assert not any(q.done() for q in queued)
                slot.set()
                return decoding, await asyncio.gather(*queued)
            finally:
                slot.set()
                await client.close()

        decoding, queued = asyncio.run(scenario())
        assert decoding.count("data:") == 4 and "event: end" in decoding
        assert all(text.count("data:") == 4 for text in queued)


def test_device_report_gives_the_peak_beside_bytes_in_use(monkeypatch):
    import jax

    from seldon_core_tpu.parallel import mesh

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"bytes_in_use": 10, "peak_bytes_in_use": 30}

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Dev(), Dev()])
    report = mesh.device_report()
    assert report["bytes_in_use"] == [10, 10]
    assert report["peak_bytes_in_use"] == [30, 30]


def test_wave_gaps_tool_splits_a_gap_by_the_innermost_annotation():
    from tools.profile_wave_gaps import gaps_of, split

    ms = 1_000_000
    ops = [(0, 10 * ms), (30 * ms, 40 * ms), (41 * ms, 50 * ms)]
    assert gaps_of(ops) == [(10 * ms, 30 * ms), (40 * ms, 41 * ms)]
    marks = [
        (5 * ms, 26 * ms, "seldon.wave"),
        (5 * ms, 12 * ms, "seldon.wave.wait"),
        (12 * ms, 16 * ms, "seldon.wave.harvest"),
        (17 * ms, 26 * ms, "seldon.wave.admit"),
        (20 * ms, 24 * ms, "seldon.wave.prefill"),
    ]
    parts = split((10 * ms, 30 * ms), marks)
    want = {"wait": 0.002, "harvest": 0.004, "uncovered": 0.001, "admit": 0.005,
            "prefill": 0.004, "between": 0.004}
    assert parts == pytest.approx(want)


def test_wave_gaps_tool_splits_a_gap_under_an_overlapped_step():
    """Chained, wave N's wait, harvest and record lie inside wave N+1's
    step, after its launch: the innermost annotation still wins, and the
    burst's last readback, outside any step, keeps its phases' names."""
    from tools.profile_wave_gaps import gaps_of, split

    ms = 1_000_000
    ops = [(0, 10 * ms), (22 * ms, 60 * ms), (75 * ms, 80 * ms)]
    assert gaps_of(ops) == [(10 * ms, 22 * ms), (60 * ms, 75 * ms)]
    marks = [
        (8 * ms, 40 * ms, "seldon.wave"),           # step N+1
        (8 * ms, 9 * ms, "seldon.wave.admit"),
        (9 * ms, 24 * ms, "seldon.wave.launch"),     # enqueues late: a gap
        (14 * ms, 18 * ms, "seldon.wave.prefill"),
        (24 * ms, 30 * ms, "seldon.wave.wait"),      # wave N's, under N+1's chunk
        (30 * ms, 33 * ms, "seldon.wave.harvest"),
        (33 * ms, 40 * ms, "seldon.wave.record"),
        (41 * ms, 62 * ms, "seldon.wave.wait"),      # wave N+1's, no step left
        (62 * ms, 66 * ms, "seldon.wave.harvest"),
        (66 * ms, 70 * ms, "seldon.wave.record"),
    ]
    assert split((10 * ms, 22 * ms), marks) == pytest.approx(
        {"launch": 0.008, "prefill": 0.004})
    assert split((60 * ms, 75 * ms), marks) == pytest.approx(
        {"wait": 0.002, "harvest": 0.004, "record": 0.004, "between": 0.005})


def _throttled(fn, rounds=40):
    """``fn`` with ``rounds`` matrix products folded into its first
    output: the dispatch returns as soon as ever (a host callback would
    hold it on the CPU backend), the outputs are late."""
    import jax

    def slow(*args):
        out = fn(*args)
        x = jnp.full((512, 512), 1e-3, jnp.float32)
        burnt = jax.lax.fori_loop(0, rounds, lambda _i, a: jnp.tanh(a @ x), x)
        return (out[0] + (0 * burnt[0, 0]).astype(out[0].dtype),) + tuple(out[1:])

    return jax.jit(slow)


class TestTheEngineThreadsTimeByPhase:
    """PR 35: every phase's wall time is booked where it ends, gap or
    no gap, on the clock ``engine_stats()`` carries."""

    @pytest.mark.parametrize("loop", ["step_by_step", "chained"])
    def test_walls_sum_to_the_clock_and_match_the_annotations(self, tmp_path, loop):
        import jax

        run = _run_chained if loop == "chained" else (lambda e: e.run())
        eng = _tiny_engine()
        try:
            groups = ([(5, 1)], [(6, 2), (7, 3), (9, 4)])
            for group in groups:  # warm the shapes
                for n, f in group:
                    eng.submit(_prompt(n, f), max_new_tokens=10)
                eng.run()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=options)
            try:
                before = eng.engine_stats(detail=True)
                for group in groups:
                    for n, f in group:
                        eng.submit(_prompt(n, f + 10), max_new_tokens=10)
                    run(eng)
                    time.sleep(0.02)  # between: waiting, not work
                after = eng.engine_stats(detail=True)
            finally:
                jax.profiler.stop_trace()
        finally:
            eng.close()
        walls = {k: after["phase_wall_s"][k] - before["phase_wall_s"][k]
                 for k in after["phase_wall_s"]}
        assert set(walls) == {"admit", "prefill", "launch", "wait", "harvest",
                              "record", "between"}
        elapsed = after["clock_s"] - before["clock_s"]
        # the phases are the engine thread's whole time: they sum to the
        # elapsed clock, and without ``between`` to work + wait
        assert sum(walls.values()) == pytest.approx(elapsed, abs=2e-3)
        assert walls["between"] >= 2 * 0.02
        work = after["host_work_s"] - before["host_work_s"]
        wait = after["host_wait_s"] - before["host_wait_s"]
        assert work + wait == pytest.approx(elapsed - walls["between"], abs=2e-3)
        assert wait == pytest.approx(walls["wait"]) and work > 0.0 and wait > 0.0
        # and each is its annotations' time in the recorded trace (a
        # prefill group nests in the phase that runs it, which lends it
        # that much)
        events = [e for e in _seam_events(str(tmp_path)) if e[0] != "seldon.wave"]
        # (the parts of a tiled phase lie inside it: prefill.pack/call/tail,
        # launch.plan/call/post — and tile it)
        parts = [e for e in events if e[0].count(".") == 3]
        events = [e for e in events if e[0].count(".") == 2]
        for phase, names in (("prefill", ("pack", "call", "tail")),
                             ("launch", ("plan", "call", "post"))):
            for _name, start, end, _stats in [e for e in events
                                              if e[0] == f"seldon.wave.{phase}"]:
                inner = [p for p in parts if start <= p[1] and p[2] <= end
                         and p[0].startswith(f"seldon.wave.{phase}.")]
                assert [p[0].rsplit(".", 1)[1] for p in inner] == list(names)
                assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
        traced = dict.fromkeys(walls, 0.0)
        for name, start, end, _stats in events:
            traced[name.rsplit(".", 1)[1]] += (end - start) / 1e9
        nested = [e for e in events if e[0] == "seldon.wave.prefill"]
        for name, start, end, _stats in events:
            if name != "seldon.wave.prefill":
                traced[name.rsplit(".", 1)[1]] -= sum(
                    (e[2] - e[1]) / 1e9 for e in nested
                    if start <= e[1] and e[2] <= end)
        assert len(events) >= 5 * 6
        for phase in ("admit", "prefill", "launch", "wait", "harvest", "record"):
            # (an annotation opens after the clock is read and closes
            # before it: tens of microseconds an event)
            assert traced[phase] == pytest.approx(
                walls[phase], abs=1e-4 * len(events) + 0.1 * walls[phase]), phase

    def test_a_throttled_chunk_is_waited_for_not_worked_on(self):
        eng = _tiny_engine()
        try:
            eng.submit(_prompt(5, 1), max_new_tokens=4)
            eng.run()
            (key, fn), = eng._chunk_jit.items()
            eng._chunk_jit[key] = _throttled(fn)
            eng.submit(_prompt(5, 2), max_new_tokens=4)
            eng.run()  # the throttled program is compiled
            before = eng.engine_stats()
            eng.submit(_prompt(5, 3), max_new_tokens=4)
            t0 = time.monotonic()
            wave = eng.launch()
            t1 = time.monotonic()
            assert eng.engine_stats()["host_wait_s"] == before["host_wait_s"]
            eng.harvest(wave)
            t2 = time.monotonic()
            after = eng.engine_stats()
        finally:
            eng.close()
        assert t2 - t1 > t1 - t0  # the chunk ran under the harvest, not the launch
        wait = after["host_wait_s"] - before["host_wait_s"]
        work = after["host_work_s"] - before["host_work_s"]
        assert wait >= 0.9 * (t2 - t1) - 2e-3
        assert work <= (t1 - t0) + 0.1 * (t2 - t1) + 2e-3


class TestARequestsWayOnTheEnginesClock:
    def test_first_token_and_decode_sums_against_hand_computed_stamps(self):
        """Groups of 1 and 3, two waves each (6 tokens in chunks of 4):
        the sums lie between what the test's own stamps around
        ``launch`` and ``harvest`` allow."""
        eng = _tiny_engine()
        try:
            eng.submit(_prompt(5, 1), max_new_tokens=6)
            for n, f in [(6, 2), (7, 3), (9, 4)]:
                eng.submit(_prompt(n, f), max_new_tokens=6)
            eng.run()  # warm
            lo = dict.fromkeys(("ttft_s", "first_token_s", "decode_stream_s"), 0.0)
            hi = dict(lo)
            base = eng.engine_stats()
            for group in ([(5, 11)], [(6, 12), (7, 13), (9, 14)]):
                k = len(group)
                t_in = time.monotonic() - 1.5
                for i, (n, f) in enumerate(group):
                    # the first of a group carries a handler's stamp
                    eng.submit(_prompt(n, f), max_new_tokens=6,
                               t_ingress=t_in if i == 0 else None)
                s0 = time.monotonic()
                time.sleep(0.03)               # in the queue
                a0 = time.monotonic()
                first = eng.launch()           # admitted in here
                a1 = time.monotonic()
                time.sleep(0.05)
                h0 = time.monotonic()
                eng.harvest(first)             # first tokens in here
                h1 = time.monotonic()
                time.sleep(0.04)
                second = eng.launch()
                f0 = time.monotonic()
                eng.harvest(second)            # finished in here
                f1 = time.monotonic()
                assert not eng.has_work()
                lo["first_token_s"] += k * (h0 - a1)
                hi["first_token_s"] += k * (h1 - a0)
                lo["ttft_s"] += (h0 - t_in) + (k - 1) * (h0 - s0)
                hi["ttft_s"] += (h1 - t_in) + (k - 1) * (h1 - (s0 - 0.02))
                lo["decode_stream_s"] += k * (f0 - h1)
                hi["decode_stream_s"] += k * (f1 - h0)
            stats = eng.engine_stats()
        finally:
            eng.close()
        got = {key: stats[key] - base[key] for key in stats
               if isinstance(stats[key], (int, float))}
        assert got["ttfts"] == got["first_tokens"] == 4
        assert got["decode_stream_tokens"] == 4 * (6 - 1)
        for key in lo:
            assert lo[key] <= got[key] <= hi[key], (key, lo[key], got[key], hi[key])
        # a request's way to its first token is its waits and its wave
        assert got["ttft_s"] == pytest.approx(
            got["ingress_wait_s"] + got["queue_wait_s"] + got["first_token_s"])

    @pytest.mark.parametrize("speculative", [None, {"draft": "ngram", "draft_k": 2}])
    def test_gen_prefill_ends_where_a_readback_proves_it_and_prices_a_prompt(
            self, speculative):
        """D10: a throttled prefill program's seconds are in
        ``gen.prefill`` and in ``predict_cost_s``'s prefill term — both
        end at the first-token harvest (the speculative engine's group
        reads its pending token back itself) and neither says ``timed``."""
        from seldon_core_tpu.utils import tracing

        tracer = tracing.setup_tracing("wave-seam-test")
        eng = _tiny_engine(speculative=speculative)
        try:
            eng.submit(_prompt(5, 1), max_new_tokens=4)
            eng.run()
            (key, fn), = eng._prefill_jit.items()
            eng._prefill_jit[key] = _throttled(fn)
            eng.submit(_prompt(5, 2), max_new_tokens=4)
            eng.run()  # the throttled program is compiled
            before = eng.engine_stats()
            eng.submit(_prompt(5, 3), max_new_tokens=4, trace_id="puid-35")
            t0 = time.monotonic()
            wave = eng.launch()
            t1 = time.monotonic()
            eng.harvest(wave)
            t2 = time.monotonic()
            eng.run()
            after = eng.engine_stats()
            spans = {s.name: s for s in tracer.find("puid-35")}
            cost = eng.predict_cost_s(5, 0)
        finally:
            eng.close()
            tracing._tracer = None
        prefill = spans["gen.prefill"]
        assert "timed" not in prefill.tags and prefill.tags["prompt_len"] == 5
        if speculative is None:
            assert t2 - t1 > t1 - t0  # enqueued at once, run under the harvest
            ran = t2 - t1
        else:
            ran = 0.5 * (t2 - t0)  # the group waited for its pending token
        assert prefill.duration_s >= 0.9 * ran
        first = after["first_token_s"] - before["first_token_s"]
        assert after["first_tokens"] - before["first_tokens"] == 1 and first >= 0.9 * ran
        # three prompts of 5 tokens so far; two ran the throttled program
        assert after["prefill_tokens"] == 15
        assert cost == pytest.approx(5 * after["first_token_s"] / 15)
        assert cost >= 0.9 * ran / 3
        # the decode span begins where the prefill's ended
        decode = spans["gen.decode"]
        assert decode.start_s >= prefill.start_s
        assert prefill.duration_s + decode.duration_s <= (t2 - t0) + (
            after["clock_s"] - before["clock_s"])


class TestATokensWayOut:
    def test_a_consumer_a_wave_behind_is_behind_and_one_that_keeps_up_is_not(self):
        eng = _tiny_engine()
        try:
            eng.submit(_prompt(5, 9), max_new_tokens=12)
            eng.run()  # warm
            # 12 tokens in chunks of 4: three events a stream
            keeps_up = eng.submit(_prompt(5, 1), max_new_tokens=12, stream_tokens=True)
            events = eng.stream_events(keeps_up)
            got = []
            while eng.has_work():
                eng.step()
                got.append(next(events))      # one a wave, as it lands
            assert next(events, None) is None
            stats = eng.engine_stats()
            assert [len(g) for g in got] == [4, 4, 4]
            assert (stats["deliveries"], stats["deliveries_behind"]) == (3, 0)
            # (unstamped, an event's way out ends at the next pull)
            assert 0.0 <= stats["deliver_lag_s"] < 5.0

            sleeper = eng.submit(_prompt(5, 2), max_new_tokens=12, stream_tokens=True)
            eng.run()                          # it sleeps through every wave
            pushed = list(sleeper.push_stamps)
            assert len(pushed) == 3 and pushed == sorted(pushed)
            events = eng.stream_events(sleeper)
            late = [next(events)]
            # the transport's stamp rides the next pull: 0.25 s, 0.5 s
            # and 1 s after each push
            late.append(events.send(pushed[0] + 0.25))
            late.append(events.send(pushed[1] + 0.5))
            with pytest.raises(StopIteration):
                events.send(pushed[2] + 1.0)
            after = eng.engine_stats()
        finally:
            eng.close()
        assert np.concatenate(late).tolist() == sleeper.result.tolist()
        assert after["deliveries"] - stats["deliveries"] == 3
        # the first two found their successor queued, the last had none
        assert after["deliveries_behind"] - stats["deliveries_behind"] == 2
        assert after["deliver_lag_s"] - stats["deliver_lag_s"] == pytest.approx(1.75)

    @pytest.mark.parametrize("lane", ["sse", "grpc"])
    def test_the_handler_stamps_the_write_and_sends_it_back(self, lane):
        """Both streaming handlers pull the next event with the
        ``time.monotonic()`` at which their transport took the one
        before: the SSE lane after ``await resp.write``, the gRPC lane
        after its ``yield`` is taken."""
        import asyncio

        from seldon_core_tpu.engine.server import (add_seldon_service,
                                                   build_gateway_app)
        from seldon_core_tpu.runtime.component import TPUComponent

        sent, yielded = [], []

        class Stub(TPUComponent):
            def predict(self, X, names, meta=None):
                return X

            def predict_stream(self, X, names, meta=None):
                for i in range(3):
                    yielded.append(time.monotonic())
                    sent.append((yield np.asarray([i, i + 1], np.int32)))

        async def sse():
            from aiohttp.test_utils import TestClient, TestServer

            client = TestClient(TestServer(build_gateway_app(_gateway(Stub()))))
            await client.start_server()
            try:
                resp = await client.post("/api/v0.1/generate/stream",
                                         json={"data": {"ndarray": [[1, 2, 3]]}})
                return (await resp.text()).count("data: {\"tokens\"")
            finally:
                await client.close()

        async def grpc_lane():
            import grpc

            from seldon_core_tpu.proto import services
            from seldon_core_tpu.runtime.message import InternalMessage

            server = grpc.aio.server()
            add_seldon_service(server, _gateway(Stub()))
            port = server.add_insecure_port("127.0.0.1:0")
            await server.start()
            channel = grpc.aio.insecure_channel(f"127.0.0.1:{port}")
            try:
                call = services.unary_stream_callable(channel, "Seldon", "GenerateStream")
                req = InternalMessage(payload=np.array([[1, 2, 3]], "int32"),
                                      kind="ndarray").to_proto()
                return len([msg async for msg in call(req)])
            finally:
                await channel.close()
                await server.stop(grace=None)

        t0 = time.monotonic()
        assert asyncio.run(sse() if lane == "sse" else grpc_lane()) == 3
        t1 = time.monotonic()
        assert len(sent) == 3 and all(isinstance(w, float) for w in sent)
        # each stamp lies after the event's yield and before the next
        assert all(y <= w for y, w in zip(yielded, sent))
        assert all(w <= y for w, y in zip(sent, yielded[1:] + [t1]))
        assert t0 <= sent[0]

    def test_the_served_path_counts_its_deliveries(self):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.server import build_gateway_app

        lm = _streaming_lm()

        async def scenario():
            client = TestClient(TestServer(build_gateway_app(_gateway(lm))))
            await client.start_server()
            try:
                resp = await client.post("/api/v0.1/generate/stream",
                                         json={"data": {"ndarray": [[1, 2, 3, 4, 5]]}})
                return await resp.text()
            finally:
                await client.close()

        try:
            text = asyncio.run(scenario())
            stats = lm.engine.engine_stats()
        finally:
            lm.shutdown()
        # 6 tokens in chunks of 4: two events, written and counted
        assert text.count("data: {\"tokens\"") == 2 and "event: end" in text
        assert stats["deliveries"] == 2 and stats["deliveries_behind"] <= 1
        assert 0.0 < stats["deliver_lag_s"] < 30.0
        assert stats["ttfts"] == stats["first_tokens"] == 1
        assert stats["decode_stream_tokens"] == 5
        assert stats["ttft_s"] >= stats["first_token_s"] > 0.0


# ---------------------------------------------------------------------------
# PR 50: the device's idle time on the program's own clock
# ---------------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _FakeArray:
    """Ready at ``done_at`` on the fake clock: waiting for it moves the
    clock there (never back)."""

    def __init__(self, clock, done_at, deleted=False):
        self.clock, self.done_at, self.deleted = clock, done_at, deleted

    def block_until_ready(self):
        if self.deleted:
            raise RuntimeError("Array has been deleted.")
        self.clock.t = max(self.clock.t, self.done_at)
        return self


def _watched(programs):
    """Run the watcher's loop, on this thread, over ``programs``:
    ``(enq, done | None | "deleted", transitions)`` each."""
    from seldon_core_tpu.models.paged import _DeviceClock

    clock = _FakeClock()
    device = _DeviceClock(clock)
    for enq, done, transitions in programs:
        out = None if done is None else _FakeArray(
            clock, 0.0 if done == "deleted" else done, deleted=done == "deleted")
        device._queue.put((enq, out, transitions))
    device._queue.put(None)
    device._run()
    return device


class TestTheDeviceClocksArithmetic:
    def test_busy_and_idle_close_from_first_enqueue_to_last_completion(self):
        rng = np.random.default_rng(50)
        programs, t, phases = [], 1.0, ("admit", "prefill.pack", "launch.call", "harvest")
        for i in range(200):
            trans = []
            for _ in range(int(rng.integers(0, 4))):
                t += float(rng.uniform(0.0, 0.004))
                trans.append((phases[int(rng.integers(0, 4))], t))
            t += float(rng.uniform(0.0, 0.004))
            enq = t
            # late or early against the enqueue that follows it
            programs.append((enq, None if i % 17 == 5 else enq + float(rng.uniform(0.0, 0.02)),
                             trans))
        # completions in order: a program ends no earlier than the one before
        reach, ordered = 0.0, []
        for enq, done, trans in programs:
            if done is not None:
                done = reach = max(reach, done, enq)
            ordered.append((enq, done, trans))
        last_done = max(d for _e, d, _t in ordered if d is not None)
        tail = [i for i, p in enumerate(ordered) if p[1] is not None][-1]
        device = _watched(ordered[:tail + 1])
        busy, idle, programs_n, by = device.totals
        assert busy + idle == pytest.approx(last_done - ordered[0][0], abs=1e-9)
        assert sum(by.values()) == pytest.approx(idle, abs=1e-9)
        assert programs_n == tail + 1 and idle > 0.0 and busy > 0.0
        assert set(by) == set(device.WHERE)

    def test_an_idle_interval_over_three_transitions_is_split_at_them(self):
        device = _watched([
            (1.0, 2.0, []),
            # done at 2.0; the thread: wait until 2.5, harvest until 2.75,
            # record until 3.5, then launch.plan to the enqueue at 4.0
            (4.0, 5.0, [("wait", 1.5), ("harvest", 2.5), ("record", 2.75),
                        ("launch.plan", 3.5)]),
        ])
        busy, idle, programs, by = device.totals
        assert (busy, idle, programs) == (2.0, 2.0, 2)
        assert {k: v for k, v in by.items() if v} == pytest.approx(
            {"wait": 0.5, "harvest": 0.25, "record": 0.75, "launch.plan": 0.5})

    def test_a_program_enqueued_before_the_one_before_finished_books_no_idle(self):
        device = _watched([
            (1.0, 3.0, [("launch.call", 0.5)]),
            (2.0, 4.5, [("launch.post", 1.5), ("wait", 1.75)]),  # queued behind it
            (4.0, 6.0, [("harvest", 3.5)]),                      # and again
        ])
        busy, idle, programs, by = device.totals
        assert (busy, idle, programs) == (5.0, 0.0, 3)
        assert not any(by.values())

    @pytest.mark.parametrize("missing", [None, "deleted"])
    def test_a_program_without_a_stamp_takes_the_next_one_as_its_bound(self, missing):
        device = _watched([
            (1.0, 2.0, []),
            (3.0, missing, [("admit", 2.5)]),      # idle 2.0 -> 3.0, then no stamp
            (3.5, 6.0, [("launch.call", 3.25)]),   # bounds both: busy 3.0 -> 6.0
            (7.0, 7.5, [("harvest", 6.5)]),
        ])
        busy, idle, programs, by = device.totals
        assert (busy, idle, programs) == (1.0 + 3.0 + 0.5, 1.0 + 1.0, 4)
        assert {k: v for k, v in by.items() if v} == pytest.approx(
            # the first interval began under the phase before any
            # transition (an idle engine: no_work)
            {"no_work": 0.5, "admit": 0.5, "launch.call": 0.5, "harvest": 0.5})
        # the last program of all without a stamp stays open, uncounted
        device = _watched([(1.0, 2.0, []), (3.0, missing, [])])
        assert device.totals[:3] == (1.0, 1.0, 1)

    def test_no_work_runs_from_an_emptied_engine_to_the_next_wave(self):
        """``end_wave(False)`` to the next ``begin_wave``: the callers'
        turn-around; ``end_wave(True)`` leaves ``between``."""
        import jax

        from seldon_core_tpu.models.paged import _WaveSeam

        class Engine:
            _jax = jax

        clock = _FakeClock()
        seam = _WaveSeam(Engine(), None)
        seam._clock = clock
        handed = []
        seam.device.watch = lambda enq, out, trans: handed.append((enq, trans))

        def wave(more, admit_s):
            seam.begin_wave()
            seam.enter("admit")
            clock.t += admit_s
            seam.enter("launch")
            seam.sub("call")
            seam.dispatched()
            seam.sub("post")
            seam.enter("wait")
            clock.t += 1.0       # the chunk: done as the wait returns
            done = clock.t
            seam.enter("harvest")
            clock.t += 0.25
            seam.end_wave(more)
            return done

        from seldon_core_tpu.models.paged import _DeviceClock

        device = _DeviceClock(clock)
        clock.t = 10.0
        seam._walls = (dict.fromkeys(seam.PHASES, 0.0), "between", clock.t)
        done = wave(more=True, admit_s=0.5)
        device.settle(handed[0][0], done, handed[0][1])
        clock.t += 2.0           # between two steps of a loop with work
        done = wave(more=False, admit_s=0.5)
        device.settle(handed[1][0], done, handed[1][1])
        clock.t += 4.0           # nobody asks
        done = wave(more=False, admit_s=0.5)
        device.settle(handed[2][0], done, handed[2][1])
        _busy, idle, _n, by = device.totals
        assert {k: v for k, v in by.items() if v} == pytest.approx(
            {"harvest": 0.5, "between": 2.0, "no_work": 4.0, "admit": 1.0})
        assert idle == pytest.approx(7.5)
        # (the engine thread's own time keeps one name for both)
        assert seam.phase_walls()["between"] == pytest.approx(6.0)


class TestTheDevicesIdleTimeServed:
    def test_a_pause_between_two_bursts_is_no_work_and_the_sum_closes(self):
        lm = _streaming_lm()
        try:
            def burst(first):
                streams = [lm.engine.submit(_prompt(5 + i, first + i), max_new_tokens=6)
                           for i in range(3)]
                lm._wake.set()
                for s in streams:
                    assert s.event.wait(120)

            burst(1)     # compiles
            burst(11)
            emptied = time.monotonic()  # its last harvest left no stream
            deadline = time.monotonic() + 10
            while lm.engine.engine_stats()["device_programs"] < lm.engine._seam.seq:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            before = lm.engine.engine_stats()
            time.sleep(1.0)
            asked = time.monotonic()
            burst(21)
            while lm.engine.engine_stats()["device_programs"] < lm.engine._seam.seq:
                assert time.monotonic() < deadline + 10
                time.sleep(0.01)
            after = lm.engine.engine_stats()
            seq = lm.engine._seam.seq
        finally:
            lm.shutdown()
        pause = after["device_idle_by_s"]["no_work"] - before["device_idle_by_s"]["no_work"]
        assert asked - emptied >= 1.0
        assert pause == pytest.approx(asked - emptied, rel=0.1)
        assert after["device_programs"] == seq > before["device_programs"]
        assert sum(after["device_idle_by_s"].values()) == pytest.approx(
            after["device_idle_s"], abs=1e-9)
        # busy + idle is the clock from the first enqueue to the last
        # completion: all of the pause, and no more than the snapshots'
        # own clock but for where each burst's last completion fell
        # before its snapshot
        grown = (after["device_busy_s"] + after["device_idle_s"]
                 - before["device_busy_s"] - before["device_idle_s"])
        assert 1.0 <= grown <= after["clock_s"] - before["clock_s"] + 0.25

    def test_shutdown_ends_the_watcher_and_one_listener_serves_every_engine(self):
        import threading

        from jax._src import monitoring

        from seldon_core_tpu.utils import jitwatch

        def listeners():
            return [cb for cb in monitoring.get_event_duration_listeners()
                    if cb is jitwatch._on_duration]

        # (its OWN engine's watcher, by identity: the process's other
        # ``seldon-device-clock`` threads — an earlier test's engine whose
        # watcher ends while this one counts — are not this test's)
        lm = _streaming_lm()
        try:
            s = lm.engine.submit(_prompt(5, 1), max_new_tokens=4)
            lm._wake.set()
            assert s.event.wait(120)
            watcher = lm.engine._seam.device._thread
            assert watcher in threading.enumerate() and watcher.name == "seldon-device-clock"
            assert len(listeners()) == 1
            thread = lm._loop_thread
        finally:
            lm.shutdown()
        thread.join(30)
        watcher.join(30)
        assert watcher not in threading.enumerate()
        eng = _tiny_engine()   # a second engine of the process
        try:
            assert len(listeners()) == 1
            # an engine that never dispatched has no thread, and closing
            # one twice is nothing
            assert eng._seam.device._thread is None
        finally:
            eng.close()
            eng.close()

    def test_an_engine_dropped_without_close_ends_its_watcher(self):
        import gc
        import threading

        eng = _tiny_engine()
        eng.submit(_prompt(5, 1), max_new_tokens=4)
        eng.run()
        thread = eng._seam.device._thread
        assert thread.is_alive()
        del eng
        gc.collect()
        thread.join(10)
        assert not thread.is_alive()


class TestEveryCompileWhereItHappens:
    def test_an_eager_scatter_of_a_new_index_shape_is_counted_once(self):
        from prometheus_client import REGISTRY

        from seldon_core_tpu.utils import jitwatch

        def metric():
            return sum(
                s.value for m in REGISTRY.collect() if m.name == "seldon_tpu_jit_compiles"
                for s in m.samples if s.name.endswith("_total")
                and not s.labels["program"].startswith("paged_"))

        eng = _tiny_engine()
        try:
            eng.submit(_prompt(5, 1), max_new_tokens=4)
            eng.run()
            seam = eng._seam
            seam.begin_wave()
            seam.enter("harvest")
            before, counted = eng.engine_stats(), metric()
            table = jnp.zeros((37, 3), jnp.float32)
            at = jnp.asarray(np.arange(11, dtype=np.int32))
            table.at[at].set(1.0).block_until_ready()   # (37, 3) by 11: new
            first, counted_first = eng.engine_stats(detail=True), metric()
            table.at[at].set(2.0).block_until_ready()   # the same shapes
            second, counted_second = eng.engine_stats(), metric()
            seam.end_wave(False)
        finally:
            eng.close()
        assert first["xla_compiles"] - before["xla_compiles"] >= 1
        assert first["xla_compile_s"] > before["xla_compile_s"]
        assert second["xla_compiles"] == first["xla_compiles"]
        assert counted_first - counted >= 1 and counted_second == counted_first
        newest = first["xla_compile_ring"][-1]
        assert newest["where"] == "harvest" and newest["wave"] == seam.wave
        assert newest["fun_name"] and newest["seconds"] > 0.0
        assert len(first["xla_compile_ring"]) <= jitwatch.COMPILE_RING
        assert "xla_compile_ring" not in second

    def test_an_entry_points_compile_is_the_sentinels_alone(self):
        """A prefill program's first call: the sentinel counts its
        signature, the listener the backend compile, and the metric
        moves once, under the sentinel's name."""
        from prometheus_client import REGISTRY

        def by_program():
            return {s.labels["program"]: s.value
                    for m in REGISTRY.collect() if m.name == "seldon_tpu_jit_compiles"
                    for s in m.samples if s.name.endswith("_total")}

        eng = _tiny_engine()
        try:
            before, programs = eng.engine_stats(), by_program()
            eng.submit(_prompt(5, 1), max_new_tokens=4)
            eng.run()
            after, programs_after = eng.engine_stats(detail=True), by_program()
        finally:
            eng.close()
        assert after["xla_compiles"] - before["xla_compiles"] >= 2  # prefill, chunk
        grown = {k: v - programs.get(k, 0.0) for k, v in programs_after.items()
                 if v != programs.get(k, 0.0)}
        assert grown.get("paged_prefill") == 1 and grown.get("paged_chunk") == 1
        assert not any("paged_prefill_b" in k or "paged_chunk_s" in k for k in grown)
        named = [e for e in after["xla_compile_ring"] if "paged_prefill_b16_k1" in e["fun_name"]]
        assert named and named[-1]["where"] == "prefill.call"


class TestSharedStateUnderManyThreads:
    """More threads than cores, a shortened switch interval: what a
    lost update or a torn read would break."""

    def test_every_read_of_the_device_clock_is_a_consistent_four(self):
        import sys
        import threading

        from seldon_core_tpu.models.paged import _DeviceClock

        class Ready:
            def block_until_ready(self):
                return self

        device = _DeviceClock(time.perf_counter)
        torn, stop = [], threading.Event()

        def reader():
            last = 0.0
            while not stop.is_set():
                busy, idle, programs, by = device.totals
                if abs(sum(by.values()) - idle) > 1e-9 or busy + idle < last - 1e-9:
                    torn.append((busy, idle, programs, dict(by)))
                last = busy + idle

        readers = [threading.Thread(target=reader) for _ in range(12)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in readers:
                t.start()
            deadline = time.monotonic() + 20
            for seq in range(1, 4001):
                now = time.perf_counter()
                device.watch(now, Ready() if seq % 7 else None,
                             [("harvest", now - 2e-6), ("launch.call", now - 1e-6)])
                assert time.monotonic() < deadline
            device.stop(timeout=20)
        finally:
            stop.set()
            sys.setswitchinterval(interval)
            for t in readers:
                t.join(20)
        assert not any(t.is_alive() for t in readers)
        assert not device._thread.is_alive()
        assert not torn, torn[:2]
        busy, idle, programs, by = device.totals
        assert programs >= 4000 - 4000 // 7 and busy + idle > 0.0
        assert sum(by.values()) == pytest.approx(idle, abs=1e-9)

    def test_no_compile_is_lost_when_many_threads_compile_at_once(self):
        import sys
        import threading

        from seldon_core_tpu.utils import jitwatch

        def compile_many():
            # as under a sentinel-wrapped call: the metric is not this test's
            jitwatch._inside.depth = 1
            for _ in range(500):
                jitwatch._on_duration(jitwatch.BACKEND_COMPILE_EVENT, 0.001,
                                      fun_name="paged_stress")

        before = jitwatch.compile_totals()
        threads = [threading.Thread(target=compile_many) for _ in range(16)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        count, seconds = jitwatch.compile_totals()
        assert count - before[0] == 16 * 500
        assert seconds - before[1] == pytest.approx(16 * 500 * 0.001)
        ring = jitwatch.compile_ring()
        assert len(ring) == jitwatch.COMPILE_RING
        assert all(e["fun_name"] == "paged_stress" and e["where"] == "" for e in ring)
