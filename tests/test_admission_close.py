"""A wave's admission closes where its chunk is planned (PR 37, second
session): a request that is queued while the host packs and dispatches a
prefill joins THAT wave's chunk, so callers who are answered by one
harvest and ask again a moment later keep sharing a wave, and which
callers share one is not whatever their arrival order once was.  And a
prefill call is not padded with whole empty rows once a row alone fills
the MXU (``prefill_group_cuts``).

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


def _arrive_under_prefill(eng, arrivals):
    """``arrivals[i]`` are submitted while the wave's i-th prefill pass is
    being dispatched (a caller's round trip is shorter than that)."""
    inner = eng._prefill_streams
    calls, late = [], []

    def prefill(streams):
        out = inner(streams)
        for n, f, new in arrivals.get(len(calls), ()):
            late.append(eng.submit(_prompt(n, f), max_new_tokens=new, seed=f))
        calls.append(len(streams))
        return out

    eng._prefill_streams = prefill
    return calls, late


@pytest.mark.parametrize("arrivals, passes", [
    ({0: [(9, 2, 4)]}, [1, 1]),
    ({0: [(9, 2, 4), (7, 3, 4)]}, [1, 2]),
    ({0: [(9, 2, 4)], 1: [(7, 3, 4)]}, [1, 1, 1]),
    ({}, [1]),
])
def test_a_request_queued_under_a_prefill_joins_that_waves_chunk(params, arrivals, passes):
    eng = _engine(params)
    first = eng.submit(_prompt(6, 1), max_new_tokens=4, seed=1)
    calls, late = _arrive_under_prefill(eng, arrivals)
    before = eng.engine_stats()
    wave = eng.launch()
    assert calls == passes
    assert len(wave.lanes) == sum(passes) and wave.admitted_n == sum(passes)
    eng.harvest(wave)
    after = eng.engine_stats()
    assert after["chunks"] - before["chunks"] == 1
    assert after["prefills"] - before["prefills"] == sum(passes)
    # one chunk of four steps: every stream of the wave ends in it, together
    assert all(s.result is not None for s in [first, *late])
    assert not eng.has_work()


def test_the_tokens_are_those_of_streams_admitted_a_wave_apart(params):
    want = {}
    for joined in (True, False):
        eng = _engine(params)
        first = eng.submit(_prompt(6, 1), max_new_tokens=9, seed=1)
        if joined:
            _calls, late = _arrive_under_prefill(eng, {0: [(9, 2, 7)]})
            eng.run()
        else:
            eng.harvest(eng.launch())
            late = [eng.submit(_prompt(9, 2), max_new_tokens=7, seed=2)]
            eng.run()
        want[joined] = [s.result.tolist() for s in [first, *late]]
    assert want[True] == want[False]


def test_a_wave_takes_no_more_joiners_than_the_engine_has_slots(params):
    """A worker whose streams end at prefill frees their slots at once:
    arrivals that never stop must not keep one launch from returning."""
    eng = _engine(params, max_slots=2)
    eng.submit(_prompt(6, 1), max_new_tokens=1, seed=1)
    inner = eng._prefill_streams
    sent = []

    def prefill(streams):
        out = inner(streams)
        for s in streams:  # ends at its prefill: one token asked for
            eng.cancel(s)
        sent.append(eng.submit(_prompt(6, len(sent) + 2), max_new_tokens=1,
                               seed=len(sent) + 2))
        return out

    eng._prefill_streams = prefill
    eng.launch()
    assert 1 <= len(sent) <= eng.max_slots


def test_a_full_engine_admits_nothing_on_its_second_pass(params):
    eng = _engine(params, max_slots=2)
    streams = [eng.submit(_prompt(6 + i, i + 1), max_new_tokens=12, seed=i + 1)
               for i in range(5)]
    calls, _late = _arrive_under_prefill(eng, {})
    wave = eng.launch()
    assert calls == [2] and len(wave.lanes) == 2
    eng.harvest(wave)
    eng.run()
    assert all(s.result is not None and len(s.result) == 12 for s in streams)


@pytest.mark.parametrize("rows, bucket, most, cuts", [
    (1, 1024, 16, [1]),
    (2, 1024, 16, [2]),
    (3, 1024, 16, [2, 1]),       # the doc cell's three: not k4
    (4, 1024, 16, [4]),
    (7, 1024, 8, [4, 2, 1]),
    (9, 1024, 4, [4, 4, 1]),     # the positions cap cuts first
    (3, 2048, 2, [2, 1]),
    (3, 512, 16, [3]),           # one empty row of 512: padded, as before
    (5, 512, 16, [4, 1]),
    (6, 512, 16, [4, 2]),
    (7, 512, 16, [7]),
    (5, 256, 16, [5]),
    (3, 256, 16, [3]),
    (0, 1024, 4, []),
])
def test_a_prefill_call_is_not_padded_with_a_row_the_mxu_would_fill(rows, bucket, most, cuts):
    from seldon_core_tpu.models.paged import PREFILL_PAD_POSITIONS, prefill_group_cuts

    got = prefill_group_cuts(rows, bucket, most)
    assert got == cuts and sum(got) == rows
    for n in got:
        k = 1 << (n - 1).bit_length()
        assert n <= max(most, 1) and (k - n) * bucket < PREFILL_PAD_POSITIONS


def test_three_long_prompts_prefill_as_two_calls_and_pay_no_empty_row(params):
    from seldon_core_tpu.models.paged import capacity  # (where the cuts read it)

    eng = _engine(params, max_slots=4)
    bucket = next(b for b in eng.prompt_buckets if b >= 40)
    streams = [eng.submit(_prompt(40, i + 1), max_new_tokens=3, seed=i + 1) for i in range(3)]
    before = eng.engine_stats()
    old = capacity.PREFILL_PAD_POSITIONS
    capacity.PREFILL_PAD_POSITIONS = bucket  # a toy row stands for a row of 1,024
    try:
        eng.run()
    finally:
        capacity.PREFILL_PAD_POSITIONS = old
    after = eng.engine_stats()
    assert after["prefill_padded_tokens"] - before["prefill_padded_tokens"] == 3 * bucket
    assert after["prefill_head_rows"] - before["prefill_head_rows"] == 2 + 1
    assert set(eng._prefill_jit) == {(bucket, 2), (bucket, 1)}
    assert all(s.result is not None and len(s.result) == 3 for s in streams)


@pytest.mark.parametrize("rows, k", [(1, 1), (2, 2), (3, 4)])
def test_a_prefill_call_counts_the_rows_it_unembeds(params, rows, k):
    """``prefill_head_rows`` rises by ``k`` a call — a row a prompt of
    the padded group (PR 49) — beside ``k * bucket`` padded positions."""
    eng = _engine(params, max_slots=4)
    bucket = next(b for b in eng.prompt_buckets if b >= 9)
    for i in range(rows):
        eng.submit(_prompt(9, i + 1), max_new_tokens=2, seed=i + 1)
    before = eng.engine_stats()
    eng.run()
    after = eng.engine_stats()
    assert set(eng._prefill_jit) == {(bucket, k)}
    assert after["prefill_head_rows"] - before["prefill_head_rows"] == k
    assert after["prefill_padded_tokens"] - before["prefill_padded_tokens"] == k * bucket
