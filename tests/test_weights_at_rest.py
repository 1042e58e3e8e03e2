"""The engine's tree rests in the type its programs multiply in (PR 37).

A float32 GPT-2 tree under a bfloat16 engine is cast once, as the engine
takes it (``models/spec.py rest_tree`` by ``PagedEngine.resting_tree``):
matrices, added biases, embeddings and the head to bf16, the norms
float32.  The programs then multiply the values flax's ``promote_dtype``
made of the float32 tree in every call on the parent — the same logits,
bit for bit — and no program converts a matrix again.  What is done is a
function of each leaf's type beside the compute type: a tree already so,
a float32 engine and a quantised tree pass by identity.

Small size, CPU.  The engine's own compiled programs are driven through
the seams its other tests use.
"""

import gc
import re
import weakref
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models import generate
from seldon_core_tpu.models.disagg import DisaggregatedLM
from seldon_core_tpu.models.generate import load_lm_params
from seldon_core_tpu.models.paged import PagedEngine, StreamingLM
from seldon_core_tpu.models.spec import GPT2, OLMOE, rest_tree
from seldon_core_tpu.ops.surgery import QuantizedKernel, quantize_params

CFG = dict(vocab_size=64, d_model=32, num_layers=2, num_heads=2, max_len=64)
PAGE, SLOTS = 8, 4
PROMPT = np.random.default_rng(37).integers(0, 64, size=21).tolist()
LANES = {
    "gather": {"SELDON_TPU_PAGED_KERNEL": "0", "SELDON_TPU_CHUNK_IMPL": "pool"},
    "ring": {"SELDON_TPU_PAGED_KERNEL": "0", "SELDON_TPU_CHUNK_IMPL": "ring"},
}
# XLA may elide an f32 -> bf16 -> f32 round trip (``xla_allow_excess_
# precision``, on by default), which would let the parent's CPU programs
# multiply the unrounded float32 weights.  It does not here: the bitwise
# cases below pass with the flag on (as the engine compiles, and as they
# run) and off (tried with ``compile(compiler_options=...)``, PR 37).


def _leaves(tree):
    return {jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _is_norm(path):
    return "Norm" in path or "norm" in path


def _engine(monkeypatch, lane, params, dtype=jnp.bfloat16, **kw):
    for k, v in LANES[lane].items():
        monkeypatch.setenv(k, v)
    return PagedEngine(params, **{**CFG, **kw.pop("sizes", {})}, page_size=PAGE,
                       max_slots=SLOTS, steps_per_call=1, dtype=dtype, **kw)


def _table(pages):
    row = np.zeros((CFG["max_len"] // PAGE,), np.int32)
    row[:pages] = np.arange(1, 1 + pages)
    return row


def _serve(eng, tree, steps):
    """Prefill ``PROMPT`` and decode ``steps`` greedy tokens through
    ``eng``'s programs handed ``tree``: the prefill's last-position
    logits, the tokens, the logits after each step."""
    bucket = next(b for b in eng.prompt_buckets if b >= len(PROMPT))
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :len(PROMPT)] = PROMPT
    pages_h = eng._pages_pow2(-(-bucket // PAGE))
    last, pk, pv = eng._build_prefill(bucket, 1)(
        tree, *eng._kv_args(), jnp.asarray(tokens),
        jnp.asarray([len(PROMPT)], jnp.int32), jnp.asarray(_table(pages_h)[None, :pages_h]))
    eng.cache.store(pk, pv)
    first = np.asarray(last[0])
    logits = jnp.zeros((SLOTS, CFG["vocab_size"]), jnp.float32).at[0].set(last[0])
    lengths = np.zeros((SLOTS,), np.int32)
    lengths[0] = len(PROMPT)
    tables = np.zeros((SLOTS, CFG["max_len"] // PAGE), np.int32)
    tables[0] = _table(CFG["max_len"] // PAGE)
    done = np.ones((SLOTS,), bool)
    done[0] = False
    keys, toks, rows = eng._keys, [], []
    for _ in range(steps):
        horizon = eng._pages_pow2(-(-(int(lengths[0]) + 1) // PAGE))
        tok, pk, pv, logits, lengths_out, keys, *_ = eng._get_chunk(
            1, ((SLOTS, horizon),))(
            tree, *eng._kv_args(), logits, jnp.asarray(lengths),
            jnp.asarray(tables[:, :horizon]), keys, jnp.asarray(done),
            jnp.zeros((SLOTS,), jnp.int32), jnp.full((SLOTS,), 99, jnp.int32),
            jnp.zeros((SLOTS,), jnp.float32), jnp.zeros((SLOTS,), jnp.int32),
            jnp.full((SLOTS,), -1, jnp.int32), jnp.arange(SLOTS, dtype=jnp.int32))
        eng.cache.store(pk, pv)
        lengths = np.array(lengths_out)
        toks.append(int(tok[0, 0]))
        rows.append(np.asarray(logits[0]))
    return first, toks, np.stack(rows)


@pytest.mark.parametrize("lane", sorted(LANES))
def test_a_cast_tree_serves_the_parent_s_logits_bit_for_bit(monkeypatch, lane):
    """The parent handed its programs the float32 tree and flax cast
    each matrix where it was used; the same programs handed the float32
    tree are the parent's.  Prefill and 16 decode steps: equal bits."""
    wide = load_lm_params("", CFG, 37)
    eng, parent = _engine(monkeypatch, lane, wide), _engine(monkeypatch, lane, wide)
    try:
        assert eng.params["head"]["kernel"].dtype == jnp.bfloat16
        got = _serve(eng, eng.params, 16)
        want = _serve(parent, jax.device_put(wide), 16)
        assert got[1] == want[1]
        assert got[0].tobytes() == want[0].tobytes()
        assert got[2].tobytes() == want[2].tobytes()
        assert got[2].std() > 0.1 and len(set(got[1])) > 1
    finally:
        eng.close()
        parent.close()


def test_matrices_rest_in_the_compute_type_and_norms_in_float32(monkeypatch):
    wide = load_lm_params("", CFG, 37)
    before = {k: np.array(v) for k, v in _leaves(wide).items()}
    eng = _engine(monkeypatch, "gather", wide)
    try:
        held = _leaves(eng.params)
        assert set(held) == set(before)
        for path, leaf in held.items():
            if _is_norm(path):
                assert leaf.dtype == jnp.float32, path
                assert leaf is _leaves(wide)[path]
            else:  # kernels, added biases, embeddings, the head
                assert leaf.dtype == jnp.bfloat16, path
                # the rounding promote_dtype applied in every call
                assert np.array_equal(
                    np.asarray(leaf), before[path].astype(jnp.bfloat16)), path
        report = eng.lane_report()
        assert report["weight_bytes"] == sum(leaf.nbytes for leaf in held.values())
        assert report["weight_bytes"] < sum(v.nbytes for v in before.values()) * 0.6
        assert report["weights"] == "bfloat16"
        # the caller's tree is alive and what it was
        for path, leaf in _leaves(wide).items():
            assert not leaf.is_deleted() and leaf.dtype == jnp.float32
            assert np.array_equal(np.asarray(leaf), before[path]), path
    finally:
        eng.close()


THIRD_SPECS = {
    "olmoe": (replace(OLMOE, num_experts=8, experts_per_tok=2, expert_width=32),
              dict(CFG, vocab_size=97, d_model=64, num_heads=4)),
    "rope_dense": (replace(OLMOE, name="rope-dense", ffn="gelu", num_experts=0,
                           experts_per_tok=0, expert_width=0), CFG),
    "layernorm_experts": (replace(GPT2, name="ln-moe", ffn="moe", num_experts=8,
                                  experts_per_tok=2, expert_width=32,
                                  weights_f32=False), CFG),
}


@pytest.mark.parametrize("case", ["float32_engine", "int8", "w8a8", *sorted(THIRD_SPECS)])
def test_a_tree_already_as_it_rests_is_taken_by_identity(monkeypatch, case):
    """Nothing to cast: a float32 engine, a tree made in the compute
    type (norms and a router float32 on purpose), a tree the engine
    quantises (the surgery rounds the float32 values; what it leaves
    float32 is dequantised beside the int8 at program entry)."""
    kw, spec, sizes = {}, GPT2, CFG
    if case in THIRD_SPECS:
        spec, sizes = THIRD_SPECS[case]
        kw = dict(spec=spec, sizes=sizes)
    elif case == "float32_engine":
        kw = dict(dtype=jnp.float32)
    else:  # d 64: most of the tree's bytes are kernels the surgery takes
        sizes = dict(CFG, d_model=64)
        kw = dict(sizes=sizes, **(
            {"quantize": "int8"} if case == "int8" else {"precision": "w8a8"}))
    given = load_lm_params("", sizes, 5, spec=spec)
    eng = _engine(monkeypatch, "gather", given, **kw)
    try:
        assert rest_tree(given, spec, sizes, kw.get("dtype", jnp.bfloat16)) is (
            given) or case in ("int8", "w8a8")
        is_q = lambda x: isinstance(x, QuantizedKernel)  # noqa: E731
        want, manifest = (quantize_params(given) if case in ("int8", "w8a8")
                          else (given, []))
        assert eng.quantize_manifest == manifest
        assert bool(manifest) == (case in ("int8", "w8a8"))
        held = jax.tree_util.tree_leaves(eng.params, is_leaf=is_q)
        for got, leaf in zip(held, jax.tree_util.tree_leaves(want, is_leaf=is_q),
                             strict=True):
            if is_q(leaf):  # the integers of the float32 values
                assert np.array_equal(np.asarray(got.q), np.asarray(leaf.q))
                assert np.array_equal(np.asarray(got.scale), np.asarray(leaf.scale))
            else:
                assert got is leaf
        assert eng.lane_report()["weights"] == {
            "float32_engine": "float32", "int8": "int8", "w8a8": "int8"}.get(
                case, "bfloat16")
    finally:
        eng.close()


def test_no_chunk_program_is_handed_a_float32_matrix(monkeypatch):
    """The CPU's stand-in for the chip's ``convert_bf16_*`` rows: the
    lowered chunk of a bf16 GPT-2 engine has no float32 parameter of
    rank 2 or more among its weights (the first argument's leaves), so
    there is no matrix for a program to convert."""
    eng = _engine(monkeypatch, "gather", load_lm_params("", CFG, 37))
    try:
        text = eng.lower_chunk(1, ((SLOTS, 2),)).as_text()
        main = re.search(r"func\.func public @main\((.*?)\) ->", text, re.S).group(1)
        args = re.findall(r"%arg\d+: tensor<([^>]*)>", main)
        weights = args[:len(jax.tree_util.tree_leaves(eng.params))]
        assert len(weights) == 30
        wide = [a for a in weights if a.endswith("xf32") and a.count("x") >= 2]
        assert not wide, wide
        assert sum(a.endswith("xbf16") for a in weights) == 20
        # and the five norms' scales and biases are handed over in float32
        assert sum(a.endswith("xf32") for a in weights) == 10
    finally:
        eng.close()


def _watch_the_loader(monkeypatch):
    """Weak references to every leaf ``load_lm_params`` returns."""
    seen = []

    def loader(*args, **kw):
        tree = load_lm_params(*args, **kw)
        seen.append({path: weakref.ref(leaf) for path, leaf in _leaves(tree).items()})
        return tree

    monkeypatch.setattr(generate, "load_lm_params", loader)
    return seen


def test_load_lets_the_float32_tree_go_before_it_builds_the_engine(monkeypatch):
    """``hbm_peak_gib`` is a lifetime peak: the float32 tree, its cast
    and the pool at once would be the process's.  Once ``load()`` is
    back nothing holds a float32 matrix the loader made."""
    seen = _watch_the_loader(monkeypatch)
    lm = StreamingLM(page_size=PAGE, max_slots=2, steps_per_call=2,
                     max_new_tokens=4, **CFG)
    try:
        lm.load()
        gc.collect()
        (made,) = seen
        for path, ref in made.items():
            leaf = ref()
            if _is_norm(path):  # float32 as they rest: the engine's, by identity
                assert leaf is _leaves(lm.engine.params)[path]
            else:
                assert leaf is None or leaf.is_deleted(), path
        assert lm.engine.lane_report()["weights"] == "bfloat16"
        out = lm.predict(np.asarray([PROMPT], np.int32), [], {})
        assert np.asarray(out).shape == (1, 4)
    finally:
        lm.shutdown()


def test_a_disaggregated_deployment_s_prefill_engines_share_one_cast_tree(monkeypatch):
    seen = _watch_the_loader(monkeypatch)
    dis = DisaggregatedLM(prefill_workers=2, page_size=PAGE, max_slots=2,
                          steps_per_call=2, max_new_tokens=4, **CFG)
    try:
        dis.load()
        gc.collect()
        first, second = (_leaves(e.params) for e in dis._prefill_engines)
        assert first["['head']['kernel']"].dtype == jnp.bfloat16
        for path in first:
            assert first[path] is second[path], path
        # the decode engine's tree and the workers' were loaded apart
        assert len(seen) == 2
        for made in seen:
            assert all(ref() is None or ref().is_deleted()
                       for path, ref in made.items() if not _is_norm(path))
    finally:
        dis.shutdown()
