"""A prefill unembeds the one row a prompt it returns (PR 49): the final
norm and the head run on ``(k, 1, d)``, chosen by what the call passes
(``PagedTransformerLM.__call__(last=)``), in every prefill program of
every spec.

Small sizes, CPU, float32, the gather lane.  Each spec's engine serves
three prompts of one bucket together — one short of the bucket, one that
fills it and a shorter one, so the group of four holds a pad row
(``true_lens`` 1) — and every prefill program the engine builds is run
beside the same program with the LM unembedding every position and the
row gathered after, which is what every prefill did before this PR.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paged_harness as harness

BUCKET = 16
ENGINE = dict(max_len=64, page_size=4, max_slots=4, prompt_buckets=[BUCKET, 32])
RNG = np.random.default_rng(49)
# one short of the bucket | fills it | shorter: a group of 4 with a pad row
PROMPTS = [RNG.integers(0, 64, size=n).tolist() for n in (BUCKET - 1, BUCKET, 9)]
NEW = 6
# spec -> paged_harness's tiny model of it
ARCHS = {"gpt2": "gpt2", "olmoe": "olmoe", "deepseek_v3": "gigachat",
         "longcat_flash": "longcat", "dots3_note": "dots3",
         "smallthinker": "smallthinker", "xing4": "xing4",
         "olmo_hybrid": "olmo_hybrid"}
# the specs that serve a cached prefix (a cache of kinds and a state a
# lane refuse one by name)
CACHED = ["gpt2", "olmoe", "deepseek_v3", "longcat_flash", "xing4"]
# float32 on the CPU: the same row through the same norm and matmul, at
# another shape; logits of unit spread differ in the order of a dot's
# sums at most
ATOL = 2e-5

# the tokens each spec's engine served for PROMPTS on the parent commit
# (005d049: greedy, weight seed 3, this file's engine and ``serve``)
PARENT_TOKENS = {
    "gpt2": [[5, 93, 85, 75, 5, 37], [83, 76, 75, 78, 11, 16], [76, 44, 75, 22, 78, 11]],
    "olmoe": [[5, 93, 62, 7, 12, 43], [5, 41, 32, 20, 57, 37], [56, 82, 70, 42, 31, 57]],
    "deepseek_v3": [[70, 42, 31, 47, 82, 18], [5, 65, 83, 41, 43, 49], [56, 38, 33, 84, 51, 49]],
    "longcat_flash": [[5, 26, 24, 33, 8, 7], [5, 41, 32, 8, 34, 30], [39, 41, 88, 95, 70, 38]],
    "dots3_note": [[2, 1, 20, 36, 47, 39], [21, 29, 10, 1, 20, 36], [34, 43, 2, 1, 39, 45]],
    "smallthinker": [[38, 45, 55, 19, 25, 19], [24, 24, 24, 32, 39, 32], [41, 55, 43, 19, 55, 43]],
    "xing4": [[84, 18, 91, 3, 49, 48], [89, 77, 22, 22, 33, 33], [56, 38, 7, 12, 7, 8]],
    "olmo_hybrid": [[77, 41, 48, 33, 76, 64], [18, 77, 70, 19, 34, 57], [89, 35, 71, 20, 13, 17]],
}


def both_ways(eng, name, seen):
    """Wrap the program builder ``eng.<name>``: every call of a program
    it builds also runs that program traced with the LM called without
    ``last`` — all positions unembedded, the row gathered after — on
    copies of the pools, and ``seen`` gets ``(true_lens, the program's
    last, that row)``."""
    build, lm = getattr(eng, name), eng._lm

    def every_position(module, params, *args, last, **kw):
        logits, *rest = lm(module, params, *args, **kw)
        assert logits.shape[:2] == args[0].shape  # (k, bucket, vocab)
        return (jnp.take_along_axis(logits, last[:, None, None], axis=1), *rest)

    def builder(*shape):
        new, old = build(*shape), build(*shape)

        def call(params, pk, pv, *args, **kw):
            eng._lm = every_position  # read when ``old`` traces
            try:
                want = old(params, *jax.tree_util.tree_map(jnp.copy, (pk, pv)),
                           *args, **kw)[0]
            finally:
                del eng._lm
            out = new(params, pk, pv, *args, **kw)
            seen.append((np.asarray(args[1]), np.asarray(out[0]), np.asarray(want)))
            return out
        return call

    setattr(eng, name, builder)


def serve(eng, prompts, new=NEW):
    streams = [eng.submit(np.asarray(p, np.int32), max_new_tokens=new)
               for p in prompts]
    with harness.tracing(eng):
        while not all(s.event.is_set() for s in streams):
            eng.step()
    return [s.result.tolist() for s in streams]


@pytest.fixture
def served():
    """``get(arch, **PagedEngine's) -> (engine, tokens a prompt, what
    both_ways saw)``: ``arch``'s engine after it served PROMPTS, closed
    at the case's end."""
    made = []

    def get(arch, **kw):
        spec, sizes = harness.spec_and_sizes(ARCHS[arch])
        eng, _params = harness.build(spec, sizes, "gather", jnp.float32,
                                     **ENGINE, **kw)
        made.append(eng)
        seen = []
        both_ways(eng, "_build_prefill", seen)
        both_ways(eng, "_build_prefill_cached", seen)
        return eng, serve(eng, PROMPTS), seen

    yield get
    for eng in made:
        eng.close()


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_a_prefill_returns_the_row_all_positions_gave(served, arch):
    eng, tokens, seen = served(arch)
    (lens, last, want), = seen  # one call: the three and a pad row
    assert sorted(lens.tolist()) == [1, 9, BUCKET - 1, BUCKET]
    assert last.shape == want.shape == (4, eng.vocab_size)
    assert last.dtype == np.float32 and want.std() > 0.1
    np.testing.assert_allclose(last, want, atol=ATOL, rtol=0)
    stats = eng.engine_stats()
    assert stats["prefill_head_rows"] == 4
    assert stats["prefill_padded_tokens"] == 4 * BUCKET
    assert tokens == PARENT_TOKENS[arch]


@pytest.mark.parametrize("arch", CACHED)
def test_a_cached_suffix_prefill_returns_it_too(served, arch):
    """The first three publish their pages; the same prompts with new
    tails then prefill their suffixes over the cached pages."""
    eng, _tokens, seen = served(arch, prefix_cache=True)
    del seen[:]
    rng = np.random.default_rng(50)
    tails = [p[:8] + rng.integers(0, 64, size=n).tolist()
             for p, n in zip(PROMPTS, (7, 8, 1))]
    serve(eng, tails)
    assert eng.engine_stats()["prefix_hits"] >= 3
    assert seen and all(len(lens) > 1 for lens, _last, _want in seen)
    for lens, last, want in seen:
        assert last.shape == want.shape == (len(lens), eng.vocab_size)
        np.testing.assert_allclose(last, want, atol=ATOL, rtol=0)
    assert {1, 7, 8} <= {n for lens, _l, _w in seen for n in lens.tolist()}


VOCAB = 101  # no other width of any tiny model


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_the_lowered_prefill_holds_no_all_position_logits(arch):
    """``_build_prefill(16, 2)`` as lowered: no array of ``(2, 16,
    vocab)`` in any type, and the one matmul into the vocabulary has 2
    rows and not ``bucket x k``, flattened or not."""
    spec, sizes = harness.spec_and_sizes(ARCHS[arch])
    eng, _params = harness.build(spec, dict(sizes, vocab_size=VOCAB), "gather",
                                 jnp.bfloat16, **ENGINE)
    try:
        i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
        more = {}
        if spec.kinds:
            more["window"] = (i32(2, eng.cache.window_pages), i32(2))
        if spec.linear:
            more["slots"] = i32(2)
        with harness.tracing(eng):
            text = eng._build_prefill(BUCKET, 2).__wrapped__.lower(
                eng.params, *eng._kv_args(), i32(2, BUCKET), i32(2), i32(2, 4),
                **more).as_text()
    finally:
        eng.close()
    assert not re.search(rf"tensor<2x{BUCKET}x{VOCAB}x", text)
    heads = re.findall(rf"stablehlo\.dot_general.*-> tensor<([0-9x]*)x{VOCAB}x", text)
    assert heads == ["2x1"]
