"""What the model files share (PR 44): the decode lanes and the
environment each is built and traced under, the tiny models by
architecture, and the code that drives an engine's own compiled
programs through the seams the engine calls them by.  A plain module.

The helpers are stateless over a reused engine: they write the slot's
pages from page 1 on, read no further than the lengths they pass, and
keep the programs they run in the engine's own caches, so a second case
on an engine compiles nothing again.  A new configuration's test file
takes its engines from here, once a module (ROADMAP D15).
"""

import contextlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.paged import PagedEngine
from seldon_core_tpu.models.spec import GPT2, init_params
from seldon_core_tpu.ops import kernels

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))
from reference import (  # noqa: E402
    deepseek_v3, dots3_note, jamba, ling3_flash, longcat_flash, olmo_hybrid,
    olmoe, smallthinker, xing4)

PAGE, MAX_LEN, SLOTS = 8, 64, 4
PROMPT = np.random.default_rng(5).integers(0, 97, size=29).tolist()

# the decode lanes: the Pallas kernel (interpreted off a TPU), the XLA
# gather in the pool chunk, and the ring chunk (multi-head pools only)
LANES = {
    "kernel": {"SELDON_TPU_PAGED_KERNEL": "force", "SELDON_TPU_CHUNK_IMPL": "pool"},
    "gather": {"SELDON_TPU_PAGED_KERNEL": "0", "SELDON_TPU_CHUNK_IMPL": "pool"},
    "ring": {"SELDON_TPU_PAGED_KERNEL": "0", "SELDON_TPU_CHUNK_IMPL": "ring"},
}

# the tiny models, as their sources' configuration files name the sizes
MODELS = {
    # d 64, 4 heads of 16, 8 experts top-2 of width 32, 2 layers
    "olmoe": (olmoe, dict(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        num_experts=8, num_experts_per_tok=2, intermediate_size=32,
        rms_norm_eps=1e-5, rope_theta=10000, vocab_size=97)),
    # DeepSeek-V3 as GigaChat3.1 configures it: ranks 24 / 16, heads of 8
    # nope + 4 rope against values of 12, 8 experts top-2 in 4 groups of
    # which 2 are kept, 1 dense + 2 expert layers; the replica holds 4
    "gigachat": (deepseek_v3, dict(
        hidden_size=64, num_hidden_layers=3, num_attention_heads=4, vocab_size=97,
        n_routed_experts=4, n_routed_experts_published=8, expert_offset=2,
        num_experts_per_tok=2, moe_intermediate_size=32,
        first_k_dense_replace=1, intermediate_size=96, n_shared_experts=1,
        n_group=4, topk_group=2, routed_scaling_factor=2.5, norm_topk_prob=True,
        q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=12, rope_theta=100000,
        rope_scaling=dict(factor=64, original_max_position_embeddings=16,
                          beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1),
        rms_norm_eps=1e-6)),
    # LongCat-Flash: 8 real experts of 32 and 4 identity experts, top-4
    # times 6, dense FFNs of 96, 2 double layers; the replica holds 4
    "longcat": (longcat_flash, dict(
        hidden_size=64, num_layers=2, num_attention_heads=4, vocab_size=97,
        n_routed_experts=4, n_routed_experts_published=8, expert_offset=2,
        zero_expert_num=4, moe_topk=4, expert_ffn_hidden_size=32, ffn_hidden_size=96,
        routed_scaling_factor=6, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, rope_theta=10000000,
        mla_scale_q_lora=True, mla_scale_kv_lora=True, rms_norm_eps=1e-5)),
    # Xing4.0: the "gigachat" entry's ranks and heads under a residual of
    # 4 rows (20 Sinkhorn iterations, clamp +-30), 8 experts top-2 in one
    # group, every one held, 1 dense + 2 expert layers
    "xing4": (xing4, dict(
        hidden_size=64, num_hidden_layers=3, num_attention_heads=4, vocab_size=97,
        n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        first_k_dense_replace=1, intermediate_size=96, n_shared_experts=1,
        n_group=1, topk_group=1, routed_scaling_factor=2.0, norm_topk_prob=True,
        q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=12, rope_theta=10000,
        rope_scaling=dict(factor=64, original_max_position_embeddings=16,
                          beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1),
        rms_norm_eps=1e-6, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)),
    # dots3-note: d 32, full | window | window | full; the full layers 4
    # heads under an indexer of 4 heads keeping 16 keys, the window
    # layers 2 heads over 9 positions; 1 dense + 3 expert layers of 8
    # experts top-2, the replica holds 2
    "dots3": (dots3_note, dict(
        hidden_size=32, num_hidden_layers=4, num_attention_heads=4, vocab_size=64,
        layer_types=["full_attention", "sliding_attention", "sliding_attention",
                     "full_attention"],
        first_k_dense_replace=1, intermediate_size=48, moe_intermediate_size=16,
        n_routed_experts=2, n_routed_experts_published=8, expert_offset=2,
        n_shared_experts=1, num_experts_per_tok=2, routed_scaling_factor=1,
        norm_topk_prob=True, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, rope_theta=80000000,
        swa_num_attention_heads=2, swa_q_lora_rank=24, swa_kv_lora_rank=32,
        swa_qk_nope_head_dim=16, swa_qk_rope_head_dim=8, swa_v_head_dim=8,
        swa_rope_theta=50000, sliding_window_size=9, index_n_heads=4,
        index_head_dim=16, index_topk=16, apply_mla_qkv_lora_rescale=True,
        rms_norm_eps=1e-5)),
    # SmallThinker: 4 heads of 16 over 2 K/V heads, full | window x 3
    # twice (the window layers rotate, 8 positions), 8 experts top-2 of
    # width 32, the replica holds 4
    "smallthinker": (smallthinker, dict(
        hidden_size=64, num_hidden_layers=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, vocab_size=64,
        sliding_window_layout=[0, 1, 1, 1, 0, 1, 1, 1],
        rope_layout=[0, 1, 1, 1, 0, 1, 1, 1], sliding_window_size=8,
        rope_theta=1500000, rms_norm_eps=1e-6, moe_ffn_hidden_size=32,
        moe_num_primary_experts=4, moe_num_primary_experts_published=8,
        expert_offset=2, moe_num_active_primary_experts=2, norm_topk_prob=True)),
    # Olmo-Hybrid: d 64, 4 heads of 16 in the full layers; linear layers
    # of 4 heads, 8 (q, k) against 64 (v): two heads rest side by side
    # in 128 lanes; linear x 3, full, twice
    "olmo_hybrid": (olmo_hybrid, dict(
        model_type="olmo_hybrid", vocab_size=97, hidden_size=64,
        intermediate_size=96, num_hidden_layers=8, num_attention_heads=4,
        num_key_value_heads=4, rms_norm_eps=1e-6, max_position_embeddings=128,
        layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
        linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=64, linear_conv_kernel_dim=4,
        linear_allow_neg_eigval=True)),
    # Ling-3.0-flash: d 64, 4 heads of 16 (KDA: 16 x 16 a head, one decay a
    # key channel; MLA: latent 16 + 4 rope, heads of 8 + 4 against 8, no q
    # bottleneck); KDA x 2, MLA, twice; a dense first layer of 96, then 32
    # sigmoid-routed experts of 32 in 4 groups (2 kept), top-4, of which
    # the replica holds 8
    "ling3": (ling3_flash, dict(
        model_type="bailing_hybrid", vocab_size=97, hidden_size=64,
        intermediate_size=96, num_hidden_layers=6, num_attention_heads=4,
        head_dim=16, layer_group_size=3, rms_norm_eps=1e-6,
        kv_lora_rank=16, q_lora_rank=None, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, rope_theta=6000000,
        short_conv_kernel_size=4, kda_lower_bound=-5, kda_safe_gate=True,
        num_kv_heads_for_linear_attn=0, first_k_dense_replace=1,
        num_experts=8, num_experts_published=32, expert_offset=8,
        num_experts_per_tok=4, moe_intermediate_size=32, num_shared_experts=1,
        n_group=4, topk_group=2, routed_scaling_factor=2.5, norm_topk_prob=True,
        expert_swiglu_limit_list=[0] * 6, share_expert_swiglu_limit_list=[0] * 6)),
    # Jamba: d 64, 4 query heads of 16 over ONE K/V head in the attention
    # layers (1 and 5 of 8: period 4, offset 1); Mamba layers of 128
    # channels (expand 2) over 16 state columns — one whole (16, 128) tile
    # a lane —, a dt rank of 8, a convolution of 4 taps with its bias; a
    # dense SwiGLU of 96 in every layer, the head tied
    "jamba": (jamba, dict(
        model_type="jamba", vocab_size=97, hidden_size=64,
        intermediate_size=96, num_hidden_layers=8, num_attention_heads=4,
        num_key_value_heads=1, attn_layer_period=4, attn_layer_offset=1,
        mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8,
        mamba_conv_bias=True, mamba_proj_bias=False, num_experts=1,
        num_experts_per_tok=1, rms_norm_eps=1e-6, tie_word_embeddings=True,
        max_position_embeddings=128)),
}


def spec_and_sizes(arch):
    """``(ModelSpec, the engine's sizes)`` of the tiny ``arch``."""
    if arch == "gpt2":
        return GPT2, dict(vocab_size=97, d_model=64, num_layers=2, num_heads=4)
    ref, model = MODELS[arch]
    return ref.spec_and_config(model)


@contextlib.contextmanager
def environment(**env):
    """``os.environ`` with ``env`` set, put back after."""
    with pytest.MonkeyPatch.context() as patch:
        for k, v in env.items():
            patch.setenv(k, v)
        yield


def tracing(eng):
    """The environment of ``eng``'s lane.  The lane's knob is read when
    an engine is built **and** whenever a program of it is traced (the
    LM hands its blocks the whole pool or a layer's slice by
    ``paged_kernel_mode()``), and a module's engine traces long after
    ``build`` returned: whoever makes it trace holds this around the
    call, or a kernel engine would trace the gather."""
    return environment(**LANES[eng.lane])


def build(spec, sizes, lane, dtype, *, params=None, seed=3, **kw):
    """``(engine, params)``: a ``PagedEngine`` of ``spec`` on ``lane``
    over ``params`` (drawn from ``seed`` where not given), the lane's
    environment set around construction and put back."""
    kw = {"max_len": MAX_LEN, "page_size": PAGE, "max_slots": SLOTS,
          "steps_per_call": 1, **kw}
    if params is None:
        params = init_params(spec, dict(sizes, max_len=kw["max_len"]), seed,
                             dtype=dtype)
    with environment(**LANES[lane]):
        eng = PagedEngine(params, **sizes, dtype=dtype, spec=spec, **kw)
    eng.lane = lane
    return eng, params


def fixtures(spec, sizes):
    """``engines, own_engine``: a model file's two fixtures.

    ``engines(lane, dtype=float32, seed=3) -> (engine, params)``, one
    engine a key for the whole module, closed at its end: for every case
    that traces the programs as they stand.

    ``own_engine(lane, dtype=float32, spec=spec, params=None, seed=3,
    **PagedEngine's) -> (engine, params)`` for a case that patches what
    a trace reads, serves another spec or tree, changes the engine or
    counts from zero: the tree is ``spec``'s whatever spec is served
    (drawn again by the programs the first draw compiled), the lane's
    environment is held to the case's end, the engine closed after it."""
    @pytest.fixture(scope="module")
    def engines():
        made = {}

        def get(lane, dtype=jnp.float32, seed=3):
            key = (lane, jnp.dtype(dtype).name, seed)
            if key not in made:
                made[key] = build(spec, sizes, lane, dtype, seed=seed)
            return made[key]

        yield get
        for eng, _params in made.values():
            eng.close()

    @pytest.fixture
    def own_engine(monkeypatch):
        def make(lane, dtype=jnp.float32, params=None, seed=3, **kw):
            if params is None:
                params = init_params(spec, sizes, seed, dtype=dtype)
            return build(kw.pop("spec", spec), sizes, lane, dtype,
                         params=params, **kw)

        yield from own(monkeypatch, make)

    return engines, own_engine


def own(monkeypatch, make):
    """The body of a case's fixture: ``get(...) -> make(...)``'s
    ``(engine, params)``, the engine's lane held to the case's end
    (``hold``) and the engine closed after it."""
    made = []

    def get(*args, **kw):
        made.append(make(*args, **kw))
        hold(monkeypatch, made[-1][0])
        return made[-1]

    yield get
    for eng, _params in made:
        eng.close()


# ---- an engine's own programs, driven in its first slot rows ----

def tables(eng, k):
    """Slot rows for ``k`` prompts: row 0 from page 1, row 1 from page
    9 (at 8 pages a slot), and so on."""
    width = eng.max_len // eng.page_size
    return 1 + np.arange(k * width, dtype=np.int32).reshape(k, width)


def _padded(eng, prompts):
    bucket = next(b for b in eng.prompt_buckets if b >= max(map(len, prompts)))
    tokens = np.zeros((len(prompts), bucket), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    return bucket, jnp.asarray(tokens), jnp.asarray([len(p) for p in prompts], jnp.int32)


def prefill_group(eng, prompts):
    """The whole-prefill program on ``prompts`` together: last-position
    logits ``(k, vocab)`` and a routed spec's histogram (else ``None``);
    the engine's pools now hold their rows."""
    k = len(prompts)
    bucket, tokens, lens = _padded(eng, prompts)
    pages_h = eng._pages_pow2(-(-bucket // eng.page_size))
    with tracing(eng):
        if (bucket, k) not in eng._prefill_jit:
            eng._prefill_jit[bucket, k] = eng._build_prefill(bucket, k)
        last, pk, pv, *hist = eng._prefill_jit[bucket, k](
            eng.params, *eng._kv_args(), tokens, lens,
            jnp.asarray(tables(eng, k)[:, :pages_h]))
    assert (pv is None) == (eng.cache.pages_v is None)  # one pool or two
    eng.cache.store(pk, pv)
    return np.asarray(last), (np.asarray(hist[0]) if hist else None)


def prefill(eng, prompt):
    last, hist = prefill_group(eng, [prompt])
    return last[0], hist


def resume_group(eng, prompts, cached):
    """The cached-suffix program on each ``prompt[cached:]`` over its
    slot row, whose first ``cached`` tokens (a page boundary) are
    written already: last-position logits ``(k, vocab)``.  At ``cached``
    0 it gathers the whole table and masks it out: what every from-zero
    prefill traced before PR 33."""
    k, ps = len(prompts), eng.page_size
    bucket, tokens, lens = _padded(eng, [p[cached:] for p in prompts])
    wp, first = -(-bucket // ps), cached // ps
    rp = eng._pages_pow2(first or wp)
    full = tables(eng, k)
    with tracing(eng):
        if (bucket, k, rp) not in eng._prefill_cached_jit:
            eng._prefill_cached_jit[bucket, k, rp] = eng._build_prefill_cached(
                bucket, k, rp)
        last, pk, pv, *_hist = eng._prefill_cached_jit[bucket, k, rp](
            eng.params, *eng._kv_args(), tokens, lens,
            jnp.full((k,), cached, jnp.int32), jnp.asarray(full[:, :rp]),
            jnp.asarray(full[:, first:first + wp]))
    eng.cache.store(pk, pv)
    return np.asarray(last)


def cached_suffix(eng, prompt, cached):
    """Prefill ``prompt[:cached]`` whole, then ``prompt[cached:]`` with
    the cached-suffix program over those pages: last-position logits."""
    prefill(eng, prompt[:cached])
    return resume_group(eng, [prompt], cached)[0]


def decode(eng, last, length, steps):
    """``steps`` greedy decode steps of lane 0 through the one-step
    chunk program: the tokens it chose, the logits after each, and the
    last step's routing account."""
    slots, width = eng.max_slots, eng.max_len // eng.page_size
    logits = jnp.zeros((slots, eng.vocab_size), jnp.float32).at[0].set(last)
    lengths = np.zeros((slots,), np.int32)
    lengths[0] = length
    slot_rows = np.zeros((slots, width), np.int32)
    slot_rows[0] = tables(eng, 1)[0]
    done = np.ones((slots,), bool)
    done[0] = False
    keys = eng._keys
    toks, rows, moe_acc = [], [], None
    for _ in range(steps):
        horizon = eng._pages_pow2(-(-(int(lengths[0]) + 1) // eng.page_size))
        with tracing(eng):
            out = eng._get_chunk(1, ((slots, horizon),))(
                eng.params, *eng._kv_args(), logits, jnp.asarray(lengths),
                jnp.asarray(slot_rows[:, :horizon]), keys, jnp.asarray(done),
                jnp.zeros((slots,), jnp.int32), jnp.full((slots,), 99, jnp.int32),
                jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.int32),
                jnp.full((slots,), -1, jnp.int32), jnp.arange(slots, dtype=jnp.int32))
        tok, pk, pv, logits, lengths_out, keys, _done, _emitted, moe_acc = out
        eng.cache.store(pk, pv)
        lengths = np.array(lengths_out)
        toks.append(int(tok[0, 0]))
        rows.append(np.asarray(logits[0]))
    return toks, np.stack(rows), np.asarray(moe_acc)


def run_program(eng, program, prompt=PROMPT):
    """``(served logits rows, the token sequence they are rows of, first
    row's position)`` for ``"prefill"``, ``"cached"`` (the suffix past
    two pages) or ``"decode"`` (six steps behind a prefill)."""
    n = len(prompt)
    if program == "prefill":
        last, _hist = prefill(eng, prompt)
        return last[None], prompt, n - 1
    if program == "cached":
        return cached_suffix(eng, prompt, 2 * eng.page_size)[None], prompt, n - 1
    last, _hist = prefill(eng, prompt)
    toks, rows, _acc = decode(eng, last, n, steps=6)
    return rows, prompt + toks, n


# ---- an engine's front door (submit / step): its allocator, its tables ----

def hold(monkeypatch, eng):
    """``eng``'s lane environment until the case ends: for a case that
    drives a module's engine through ``submit`` / ``step`` itself."""
    for k, v in LANES[eng.lane].items():
        monkeypatch.setenv(k, v)


def serve(eng, prompts, new):
    """Serve ``prompts`` together, a token a step: per prompt ``(tokens,
    rows)`` with ``rows[i]`` the engine's logits after ``i`` tokens
    (``rows[0]``: the prefill program's, read where the engine calls
    it; the programs built for that go back into the engine's own cache,
    so neither a second ``serve`` nor a case that submits for itself
    compiles them again)."""
    first = {}
    build, built = eng._build_prefill, dict(eng._prefill_jit)

    def spy(bucket, k):
        if (bucket, k) not in built:
            built[bucket, k] = build(bucket, k)

        def call(*args, **kw):
            out = built[bucket, k](*args, **kw)
            lens = np.asarray(args[4])
            for row, n in zip(np.asarray(out[0]), lens):
                first[int(n)] = row
            return out
        return call

    eng._build_prefill = spy
    eng._prefill_jit.clear()
    try:
        streams = [eng.submit(np.asarray(p, np.int32), max_new_tokens=new)
                   for p in prompts]
        slots, rows = {}, [[] for _ in prompts]
        for _step in range(new):
            with tracing(eng):
                eng.step()
            for i, s in enumerate(streams):
                if s.slot is not None:
                    slots[i] = s.slot
                rows[i].append(np.asarray(eng._logits[slots[i]]))
        assert all(s.event.is_set() for s in streams)
    finally:
        eng._build_prefill = build
        eng._prefill_jit.clear()
        eng._prefill_jit.update(built)
    return [(s.result.tolist(), np.stack([first[len(p)]] + r[:-1]))
            for s, p, r in zip(streams, prompts, rows)]


def served_one(eng, prompt, new):
    """``serve(eng, [prompt], new)[0]``, once an engine: the
    wrong-program cases all read the same served rows."""
    kept = eng.__dict__.setdefault("harness_served", {})
    key = (tuple(prompt), new)
    if key not in kept:
        kept[key] = serve(eng, [prompt], new)[0]
    return kept[key]


def held_nothing(eng):
    """An idle engine of row kinds holds no page of either kind, and no
    slot keeps a table or a base behind (an idle lane under a stale base
    had a negative length in its window's terms, which hung the chip's
    kernel — PERF.md section 6, PR 38)."""
    stats = eng.engine_stats()
    with eng._lock:
        eng._check_invariants_locked()
    return (stats["full_pages_held"], stats["window_pages_held"],
            stats["pool_pages_used"]) == (0, 0, 0) and not (
                eng.cache.wtables.any() or eng.cache.wbase.any())


# ---- what a program traced ----

def pallas_calls(fn, *args):
    """``(name, output shapes)`` of every ``pallas_call`` ``fn`` traces."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"] if "name" in eqn.params
                              else eqn.params["name_and_src_info"].name,
                              [tuple(o.aval.shape) for o in eqn.outvars]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def fused_here(monkeypatch, block=16):
    """Toy sizes: on the kernel lane (the Pallas interpreter) the rule
    answers ``"fused"`` for a latent engine's bf16 from-zero prefill of
    any bucket of at least ``block`` positions.  The blocks are read
    when an engine is built and when a program is traced: a case that
    changes them builds its engines after, inside itself."""
    monkeypatch.setattr(kernels, "CAUSAL_BLOCK_Q", block)
    monkeypatch.setattr(kernels, "CAUSAL_BLOCK_K", block)
