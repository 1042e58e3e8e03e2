"""SmallThinker's engines beside the float32 pair of
``test_smallthinker_paged.py`` (PR 41; a part of that file until PR 44
split it, whose sizes, prompts and tolerances these cases take): the
stated precision, and four steps a call.  Each engine serves one case:
built in it, closed after it."""

from functools import partial

import numpy as np
import pytest

import jax.numpy as jnp

import paged_harness as harness
from test_smallthinker_paged import (
    BF16_ATOL, BF16_SEED, NEW, PROMPTS, _build, _held_nothing, _reference, _serve)


@pytest.fixture
def kernel_engine(monkeypatch):
    yield from harness.own(monkeypatch, partial(_build, "kernel"))


class TestLogits:
    def test_bfloat16_prefill_and_decode(self, kernel_engine):
        eng, params = kernel_engine(jnp.bfloat16, seed=BF16_SEED)
        for prompt, (tokens, rows) in zip(PROMPTS, _serve(eng, PROMPTS)):
            want = _reference(params, prompt, tokens)
            np.testing.assert_allclose(rows, want, atol=BF16_ATOL, rtol=0)
        assert _held_nothing(eng)

    def test_a_lane_passes_the_window_inside_a_chunk(self, kernel_engine):
        """Four steps a call from a prompt of 3 and one of 6: both reach
        the window's 8 positions between two host visits, so the window
        table's base moves only after the chunk that slid past it; the
        tokens are the one-step engine's, which the logits tests hold."""
        eng, params = kernel_engine(jnp.float32, steps_per_call=4)
        prompts = [PROMPTS[1], PROMPTS[0][:6]]
        streams = [eng.submit(np.asarray(p, np.int32), max_new_tokens=NEW)
                   for p in prompts]
        while not all(s.event.is_set() for s in streams):
            eng.step()
        for prompt, stream in zip(prompts, streams):
            tokens = stream.result.tolist()
            want = _reference(params, prompt, tokens)
            # greedy: each served token is the reference's top-1 given
            # the same prefix
            assert tokens == want.argmax(-1).tolist()
        assert _held_nothing(eng)
        assert eng.engine_stats()["window_pages_released"] > 0
