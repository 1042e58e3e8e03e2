"""The wave loop one wave deep (PR 29): ``PagedEngine.launch`` and
``PagedEngine.harvest`` chained as ``StreamingLM._loop`` chains them —
wave N+1 launched from the state wave N will leave, before wave N's
tokens are read — against the same halves run back to back (``step()``).

Per stream, tokens, result and error must be the same either way: what
a lane's end state cannot be predicted from (eos, a cancel, numeric
poison) is discarded at the harvest, never guessed, and what needs
harvested state (an eviction's victim, a speculative round, an armed
fault, the allocator audit) harvests first.

Fast tier, CPU, toy engines.
"""

import numpy as np
import pytest

import jax.numpy as jnp

CFG = dict(vocab_size=64, d_model=32, num_layers=1, num_heads=2, max_len=128)


@pytest.fixture(scope="module")
def params():
    import jax

    from seldon_core_tpu.models.transformer import TransformerLM

    lm = TransformerLM(dtype=jnp.float32, **CFG)
    return lm.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]


def _engine(params, **kw):
    from seldon_core_tpu.models.paged import PagedEngine

    base = dict(dtype=jnp.float32, page_size=8, max_slots=4, steps_per_call=4,
                prefix_cache=False)
    base.update(kw)
    return PagedEngine(params, **CFG, **base)


def _prompt(length, first):
    return ((np.arange(length, dtype=np.int32) * 7 + first) % 64).astype(np.int32)


def run_serial(eng, hook=None):
    """``step()`` by ``step()``: each wave harvested before the next is
    launched.  ``hook(eng)`` lands while the wave is in flight."""
    while eng.has_work():
        wave = eng.launch()
        if hook is not None:
            hook(eng)
        eng.harvest(wave)


def run_chained(eng, hook=None):
    """As ``StreamingLM._loop`` composes the halves."""
    prev = None
    while eng.has_work():
        nxt = eng.launch()
        if hook is not None:
            hook(eng)
        eng.harvest(prev)
        prev = nxt
    assert prev is None or prev.done


# 4 slots, 7 streams of mixed lengths and budgets: joiners take the slots
# of streams whose last wave is still in flight
MIX = [(5, 1, 6), (9, 2, 12), (20, 3, 4), (7, 4, 9), (12, 5, 16), (6, 6, 5), (30, 7, 8)]


# 2 slots over 4 usable pages: both lanes need a third page for the same
# chunk and none is free
TIGHT = [(7, 1, 20), (7, 2, 20), (6, 3, 9)]


def _submit_mix(eng, mix=None, **kw):
    return [eng.submit(_prompt(n, f), max_new_tokens=new, seed=f, **kw)
            for n, f, new in mix or MIX]


def _outcome(stream):
    err = stream.error
    return (list(stream.tokens),
            None if stream.result is None else stream.result.tolist(),
            None if err is None else getattr(err, "reason", type(err).__name__))


def _drain_queue(stream):
    """What a consumer of the stream's token queue saw, and how often
    the queue was closed."""
    seen, closed = [], 0
    while not stream.token_queue.empty():
        item = stream.token_queue.get_nowait()
        if item is None:
            closed += 1
        else:
            seen += item
    return seen, closed


def _eos_scenario(params):
    """An eos id that one stream of MIX meets in the middle of its second
    chunk: found from a run without eos."""
    eng = _engine(params)
    try:
        streams = _submit_mix(eng)
        eng.run()
        toks = [s.result.tolist() for s in streams]
    finally:
        eng.close()
    for i, row in enumerate(toks):
        for pos in (5, 6):
            if len(row) > pos + 2 and row[pos] not in row[:pos]:
                return i, pos, row[pos]
    raise AssertionError(f"no stream of MIX has a fresh token mid-chunk: {toks}")


class _Poison:
    """Turn one stream's lane non-finite right behind the chunk that
    follows its first: the screen enqueued there must judge it."""

    def __init__(self, eng, victim):
        self.eng, self.victim, self.fired = eng, victim, False
        self.inner = eng._screen_logits
        eng._screen_logits = self

    def __call__(self, runnable):
        if not self.fired and self.victim in runnable and self.victim.planned == 4:
            self.fired = True
            eng = self.eng
            eng._logits = eng._logits.at[self.victim.slot].set(jnp.nan)
        return self.inner(runnable)


def _scenario(name, params, run, monkeypatch):
    """One engine, one named scenario, driven by ``run``; returns
    ``(outcomes per stream, engine stats, extras)``."""
    kw, sub, extras = {}, {}, {}
    if name == "sampled":
        sub = dict(temperature=0.8, top_k=8)
    elif name == "chunk_budget":
        kw = dict(chunk_token_budget=24)
    elif name == "int8_kv":
        monkeypatch.setenv("SELDON_TPU_KV_DTYPE", "int8")
    elif name == "pool_pressure":
        kw, sub = dict(num_pages=5, max_slots=2), dict(mix=TIGHT)
    elif name == "speculative":
        kw = dict(speculative={"draft": "ngram", "draft_k": 2})
    elif name == "eos":
        _i, _pos, eos = _eos_scenario(params)
        sub = dict(eos_id=eos, stream_tokens=True)
    elif name in ("cancel", "poison"):
        sub = dict(stream_tokens=True)
    eng = _engine(params, **kw)
    try:
        streams = _submit_mix(eng, **sub)
        hook = None
        if name == "cancel":
            target = streams[4]  # 16 tokens to make: four chunks

            def hook(e):
                # lands with a wave in flight, once the first chunk's
                # tokens are in hand
                if len(target.tokens) >= 4 and not target.cancelled:
                    e.cancel(target)
        elif name == "poison":
            extras["poison"] = _Poison(eng, streams[4])
        run(eng, hook)
        stats = eng.engine_stats()
        if sub.get("stream_tokens"):
            extras["queues"] = [_drain_queue(s) for s in streams]
        return [_outcome(s) for s in streams], stats, extras
    finally:
        eng.close()


SCENARIOS = ["mixed", "sampled", "chunk_budget", "int8_kv", "eos", "cancel",
             "poison", "pool_pressure", "speculative"]


@pytest.mark.parametrize("name", SCENARIOS)
def test_chained_loop_delivers_what_step_by_step_delivers(name, params, monkeypatch):
    serial, s_stats, s_extra = _scenario(name, params, run_serial, monkeypatch)
    chained, c_stats, c_extra = _scenario(name, params, run_chained, monkeypatch)
    assert chained == serial
    for key in ("tokens", "completed", "prefill_tokens", "evictions", "quarantined"):
        assert c_stats[key] == s_stats[key], key
    assert s_stats["waves_overlapped"] == 0
    if name == "speculative":
        # the host drafts from accepted tokens: a round is one piece
        assert c_stats["waves_overlapped"] == 0
    else:
        assert c_stats["waves_overlapped"] > 0
        assert c_stats["chunks"] >= s_stats["chunks"]
    if name == "pool_pressure":
        # every decoder stalled: the eviction waited for the harvest, and
        # chose the victim the serial engine chooses
        assert s_stats["evictions"] > 0
        assert c_stats["waves_overlapped"] < c_stats["chunks"]
    if name in ("eos", "cancel", "poison"):
        # what was streamed is what the stream holds, cut at eos, and
        # every queue was closed exactly once
        assert c_extra["queues"] == s_extra["queues"]
        for (tokens, result, _err), (seen, closed) in zip(chained, c_extra["queues"]):
            assert closed == 1
            assert seen == tokens[:len(seen)]
            if result is not None:
                assert seen == result[:len(seen)]
    if name == "eos":
        i, pos, eos = _eos_scenario(params)
        tokens, result, err = chained[i]
        assert err is None and tokens[pos] == eos and eos not in tokens[:pos]
        seen, _closed = c_extra["queues"][i]
        assert seen == tokens[:pos + 1]  # nothing after eos is streamed
        assert result[pos:] == [eos] * (len(result) - pos)
    if name == "cancel":
        tokens, result, err = chained[4]
        assert err is None and len(tokens) == 8  # the wave in flight, no more
    if name == "poison":
        assert c_extra["poison"].fired and s_extra["poison"].fired
        tokens, result, err = chained[4]
        # none of the poisoned chunk's tokens: the first chunk's four only
        assert err == "NUMERIC_POISON" and result is None and len(tokens) == 4
        assert c_stats["quarantined"] == 1
        assert all(e is None for _t, _r, e in chained[:4] + chained[5:])


def test_joiners_take_the_slots_of_predicted_finishers(params):
    """The second half of ``mixed``: while wave N is in flight its
    predicted finishers hold no slot, so the launch of N+1 admits into
    them; their pages wait for the harvest."""
    eng = _engine(params, max_slots=2)
    try:
        a = eng.submit(_prompt(5, 1), max_new_tokens=4, seed=1)
        b = eng.submit(_prompt(6, 2), max_new_tokens=12, seed=2)
        c = eng.submit(_prompt(7, 3), max_new_tokens=8, seed=3)
        w1 = eng.launch()
        assert [s for s, _slot, _n in w1.lanes] == [a, b]
        # a will finish in w1: its slot is free, its pages are not
        assert eng._slots[a.slot] is None and a.pages and a.inflight == 4
        assert not a.tokens and a.result is None
        held = eng.engine_stats()["pool_pages_used"]
        w2 = eng.launch()
        assert w2.overlapped and not w1.overlapped
        assert {s for s, _slot, _n in w2.lanes} == {b, c}
        assert c.slot == a.slot and eng._slots[c.slot] is c
        assert eng.engine_stats()["pool_pages_used"] > held
        eng.harvest(w1)
        assert a.result is not None and len(a.tokens) == 4 and not a.pages
        assert eng._slots[c.slot] is c and int(eng._lengths[c.slot]) == 7 + 4
        assert len(b.tokens) == 4 and b.inflight == 4
        eng.harvest(w2)
        assert len(c.tokens) == 4 and b.inflight == 0 == c.inflight
        eng.run()
        ref = _engine(params, max_slots=2)
        try:
            want = [ref.generate(_prompt(n, f), max_new_tokens=new, seed=f)
                    for n, f, new in [(5, 1, 4), (6, 2, 12), (7, 3, 8)]]
        finally:
            ref.close()
        for stream, row in zip((a, b, c), want):
            assert stream.result.tolist() == row.tolist()
    finally:
        eng.close()


def test_step_is_one_whole_harvested_wave(params):
    eng = _engine(params)
    try:
        s = eng.submit(_prompt(5, 1), max_new_tokens=6, seed=1)
        assert eng.step() is True
        assert len(s.tokens) == 4 and s.inflight == 0 and not eng._inflight
        assert eng.step() is False
        assert s.result is not None and not eng.has_work()
        stats = eng.engine_stats()
        assert stats["chunks"] == 2 and stats["waves_overlapped"] == 0
    finally:
        eng.close()


def test_fail_all_with_a_wave_in_flight_fails_its_streams_once(params):
    eng = _engine(params, max_slots=2)
    try:
        streams = [eng.submit(_prompt(5 + i, i + 1), max_new_tokens=new, seed=i,
                              stream_tokens=True)
                   for i, new in enumerate([4, 12, 6])]
        first = eng.launch()  # streams[0] a predicted finisher: no slot
        second = eng.launch()  # streams[2] joins into its slot
        assert second.overlapped and streams[0].slot == streams[2].slot
        boom = RuntimeError("device lost")
        eng.fail_all(boom)
        assert not eng._inflight and first.done and second.done
        for s in streams:
            assert s.error is boom and s.result is None and s.event.is_set()
            assert s.inflight == 0 and not s.pages
            _seen, closed = _drain_queue(s)
            assert closed == 1
        assert not eng.has_work()
        assert eng.harvest(first) is False  # nothing left to read
        stats = eng.engine_stats()
        assert stats["pool_pages_used"] == 0 and stats["chunks"] == 0
        # the engine stays usable, and serves what a fresh one serves
        again = eng.generate(_prompt(9, 5), max_new_tokens=6, seed=3)
        ref = _engine(params, max_slots=2)
        try:
            want = ref.generate(_prompt(9, 5), max_new_tokens=6, seed=3)
        finally:
            ref.close()
        assert again.tolist() == want.tolist()
    finally:
        eng.close()


@pytest.mark.parametrize("why", ["debug_invariants", "armed_fault", "preemption"])
def test_launch_harvests_first_where_it_must_know(why, params, monkeypatch):
    """The allocator audit and an armed fault point look at one wave at
    a time, and a preemption evicts by real progress: the chained loop
    then runs one wave deep in name only."""
    from seldon_core_tpu.utils import faults

    kw = {}
    if why == "debug_invariants":
        monkeypatch.setenv("SELDON_TPU_PAGED_DEBUG", "1")
    if why == "preemption":
        kw = dict(max_slots=2)
    eng = _engine(params, **kw)
    try:
        if why == "armed_fault":
            faults.inject("transport.delay", times=1, delay_ms=1)  # any armed point
        streams = [eng.submit(_prompt(5 + i, i + 1), max_new_tokens=12, seed=i)
                   for i in range(2)]
        first = eng.launch()
        if why == "preemption":
            # both slots hold priority 0: the arrival may evict one, and
            # picks it by the tokens it has really made
            streams.append(eng.submit(_prompt(9, 7), max_new_tokens=4, seed=7,
                                      priority=5))
        second = eng.launch()
        assert first.done, "the launch read the wave in flight before it planned"
        assert second is not None and not second.overlapped
        eng.harvest(first)
        eng.harvest(second)
        run_chained(eng)
        stats = eng.engine_stats()
        if why == "preemption":
            assert stats["preempted"] == 1 and stats["restored"] == 1
        else:
            assert stats["waves_overlapped"] == 0
        ref = _engine(params, **kw)
        try:
            for s in streams:
                want = ref.generate(s.prompt, max_new_tokens=s.max_new, seed=s.seed)
                assert s.error is None and s.result.tolist() == want.tolist()
        finally:
            ref.close()
    finally:
        faults.clear()
        eng.close()


def test_streaming_lm_loop_overlaps_and_drains_on_shutdown(params):
    """The serving loop itself: saturated, nearly every chunk is enqueued
    behind an unread one, answers are the serial engine's, and a stop
    reads the last wave before anyone looks at stream state."""
    import threading

    from seldon_core_tpu.models.paged import StreamingLM

    lm = StreamingLM(max_new_tokens=24, page_size=8, max_slots=2, steps_per_call=4,
                     **CFG)
    lm.load()
    try:
        prompts = [_prompt(5 + i, i + 1) for i in range(6)]
        rows = [None] * len(prompts)

        def call(i):
            rows[i] = np.asarray(lm.predict(prompts[i][None, :], None))[0]

        threads = [threading.Thread(target=call, args=(i,)) for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        stats = lm.engine.engine_stats()
        assert stats["waves_overlapped"] > 0.8 * stats["chunks"] > 0
        lm.drain(journal_path="")
        assert not lm.engine._inflight
    finally:
        lm.shutdown()
    from seldon_core_tpu.models.paged import PagedEngine

    ref = PagedEngine(lm.engine.params, **CFG, dtype=lm.engine._dtype, page_size=8,
                      max_slots=2, steps_per_call=4)
    try:
        for prompt, row in zip(prompts, rows):
            want = ref.generate(prompt, max_new_tokens=24, seed=0)
            assert row is not None and row.tolist() == want.tolist()
    finally:
        ref.close()


def test_loop_under_concurrent_callers_and_cancels_leaves_nothing_behind():
    """More callers than cores against the chained loop, a third of them
    walking away after their first tokens (a cancel from another thread,
    with a wave in flight): every full answer is the serial engine's, and
    no slot, page or wave is left behind."""
    import sys
    import threading

    from seldon_core_tpu.models.paged import PagedEngine, StreamingLM

    lm = StreamingLM(max_new_tokens=16, page_size=8, max_slots=4, steps_per_call=4,
                     **CFG)
    lm.load()
    prompts = [_prompt(5 + i % 9, i + 1) for i in range(24)]
    rows = [None] * len(prompts)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        def call(i):
            got = []
            for chunk in lm.predict_stream(prompts[i][None, :], None, {}):
                got.extend(int(t) for t in chunk)
                if i % 3 == 0:
                    break  # the generator's close cancels the stream
            rows[i] = got

        threads = [threading.Thread(target=call, args=(i,)) for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        deadline = 200
        while lm.engine.has_work() and deadline:
            threading.Event().wait(0.05)
            deadline -= 1
        stats = lm.engine.engine_stats()
        assert not lm.engine.has_work() and not lm.engine._inflight
        assert stats["active_slots"] == 0 and stats["pool_pages_used"] == 0
        assert stats["waves_overlapped"] > 0
        with lm.engine._lock:
            lm.engine._check_invariants_locked()
    finally:
        sys.setswitchinterval(switch)
        lm.shutdown()
    ref = PagedEngine(lm.engine.params, **CFG, dtype=lm.engine._dtype, page_size=8,
                      max_slots=4, steps_per_call=4)
    try:
        for i, (prompt, row) in enumerate(zip(prompts, rows)):
            want = ref.generate(prompt, max_new_tokens=16, seed=0).tolist()
            assert row is not None
            if i % 3:
                assert row == want
            else:  # what it saw before it left is a prefix of the answer
                assert row and row == want[:len(row)]
    finally:
        ref.close()
