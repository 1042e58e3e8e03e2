"""The stream decode kernel, compiled by Mosaic for a described v5e at
the served widths (GPT-2-large's 20 x 64 over 36 layers, OLMoE's
16 x 128 over 8) — no chip attached, nothing runs.

Interpret mode (every other kernel test) checks arithmetic and nothing
about lowering: what Mosaic refuses (an unaligned DMA slice, a scalar-
prefetch table too large for SMEM, a (layer, page) index it cannot
lower, a stacked bf16 operand or a dynamic loop bound it will not take)
shows only here or on the chip.  Since PR 25 the kernel takes the
WHOLE ``(36, 513, 64, 1280)`` pools in ``pl.ANY``, the layer as a
scalar-prefetch operand, and under int8-KV the whole ``(36, 513)`` scale
tables in SMEM: those are the shapes compiled.

One file, one module fixture: only the worker that is given this file
loads the TPU compiler, and only after a test of it has started.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from seldon_core_tpu.ops import kernels

L, NUM_PAGES, PS, H, HD = 36, 513, 64, 20, 64
D = H * HD
RANK, SLOTS = 8, 8


@pytest.fixture(scope="module", autouse=True)
def the_compiler_as_it_serves():
    """``tests/conftest.py`` turns most of XLA's optimisations off to
    save the suite's compile time; here the compiler's verdict at the
    cells' shapes is the point, so this file compiles as a server does."""
    before = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", before)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Compile, do not interpret, although the backend is the CPU; and
    keep the persistent compile cache out of it (an entry compiled for
    a described chip cannot be read back without one)."""
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


# (layers, heads, head_dim): GPT-2-large, and OLMoE as the benchmark
# serves it (PR 26: 8 of its 16 layers)
GEOMETRIES = {"gpt2-large": (L, H, HD), "olmoe": (8, 16, 128)}


@pytest.mark.parametrize("lanes,pages,variant", [
    # the chat chunk's two buckets and the doc chunk's one, each with
    # the int8 pool and the in-kernel LoRA fold
    (16, 8, "bf16"), (16, 16, "bf16"), (32, 16, "bf16"),
    (16, 8, "int8"), (32, 16, "int8"), (16, 8, "lora"), (32, 16, "lora"),
])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_stream_kernel_compiles_on_the_whole_pool(one_chip, mosaic, geometry,
                                                  lanes, pages, variant):
    layers, heads, head_dim = GEOMETRIES[geometry]
    d = heads * head_dim

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool_dtype = jnp.int8 if variant == "int8" else jnp.bfloat16

    def fn(q, pk, pv, tables, lengths, layer, sk, sv, x, a_t, b, idx):
        return kernels.paged_attention_decode(
            q, pk, pv, tables, lengths, layer=layer, page_size=PS,
            kv_scales=(sk, sv) if variant == "int8" else None,
            lora=(x, a_t, b, idx, head_dim ** -0.5) if variant == "lora" else None)

    compiled = jax.jit(fn).lower(
        spec((lanes, heads, head_dim), jnp.bfloat16),
        spec((layers, NUM_PAGES, PS, d), pool_dtype),
        spec((layers, NUM_PAGES, PS, d), pool_dtype),
        spec((lanes, pages), jnp.int32), spec((lanes,), jnp.int32), spec((), jnp.int32),
        spec((layers, NUM_PAGES), jnp.float32), spec((layers, NUM_PAGES), jnp.float32),
        spec((lanes, d), jnp.bfloat16), spec((layers, SLOTS, RANK, d), jnp.bfloat16),
        spec((layers, SLOTS, RANK, 3 * d), jnp.bfloat16), spec((lanes,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and " = " in ln]
    assert len(calls) == 1, calls
    # what the benchmark's readers find the kernel by: its FIRST output
    # is (lanes, 1, heads * head_dim) f32 — three dims
    assert calls[0].partition(" = ")[2].lstrip("(").startswith(
        f"f32[{lanes},1,{d}]"), calls[0][:200]
    # the pool goes to the kernel as it rests: nothing shaped like a
    # layer of it exists, and the only pool-shaped values are parameters
    whole, layer = f"[{layers},{NUM_PAGES},{PS},{d}]", f"[{NUM_PAGES},{PS},{d}]"
    assert layer not in text.replace(whole, "")
    produced = [ln for ln in text.splitlines()
                if whole in ln.partition(" = ")[2].partition("(")[0]
                and " parameter(" not in ln]
    assert not produced, produced[:3]


# ---------------------------------------------------------------------------
# OLMoE (PR 26): the routed expert layer at its published widths
# ---------------------------------------------------------------------------

@pytest.fixture(params=["cpu", "tpu"])
def backend(request, monkeypatch):
    """What ``ops/moe.py`` is told its grouped matmuls run on: the
    process's own backend (the CPU: ``ragged_dot`` at every shape), or
    the TPU the programs here are compiled for (the rule's choice)."""
    from seldon_core_tpu.ops import moe

    if request.param == "tpu":
        monkeypatch.setattr(moe, "matmul_backend", lambda: "tpu")
    return request.param


def assert_expert_kernels(text, impl, rows, d, f):
    """The three ``ragged_dot``s as the TPU compiler's own Mosaic
    grouped-matmul kernels, or the two streaming kernels (``impl``
    "stream": a segment's ``rows``) or the two tiled ones ("tiled": all
    the ``rows`` of the call) by name; every way custom calls whose
    first output is 2-D ``(rows, width)``: what the benchmark's readers
    know an expert layer's kernels by."""
    assert "tpu_custom_call" in text
    if impl == "stream":
        assert "ragged-dot" not in text and "moe_tiled" not in text
        assert "moe_stream_gate_up" in text and "moe_stream_down" in text
        assert f"f32[{rows},{f}]" in text and f"f32[{rows},{d}]" in text
    elif impl == "tiled":
        assert "ragged-dot" not in text and "moe_stream" not in text
        assert "moe_tiled_gate_up" in text and "moe_tiled_down" in text
        assert f"bf16[{rows},{f}]" in text and f"f32[{rows},{d}]" in text
    else:
        assert text.count("ragged-dot") >= 3
        assert "moe_stream" not in text and "moe_tiled" not in text


@pytest.mark.parametrize("rows", [32, 512, 4096])
def test_expert_layer_compiles_as_grouped_matmul_kernels(one_chip, mosaic, backend, rows):
    """A decode step's 32 rows and prefill groups of 512 and 4,096
    through ``ops/moe.py`` at 64 experts of 2048 x 1024, top-8, the
    decode step inside a ``scan`` as the chunk runs it: on a TPU the
    first streams its experts through the Pallas kernel, the second (64
    rows an expert) too, in two segments of 2,048 rows, and the third
    (512 rows an expert: over the ridge) runs the tiled kernel over all
    its 32,768 rows in one call a matmul."""
    from seldon_core_tpu.ops import moe

    d, f, e, k = 2048, 1024, 64, 8

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(h, w_router, w_gate, w_up, w_down):
        def step(h, _):
            gates, experts = moe.route(h, w_router, k)
            out = moe.expert_ffn(h.astype(jnp.bfloat16), w_gate, w_up, w_down, gates, experts)
            return h + out, moe.expert_histogram(experts, e)

        if rows > 32:
            return step(h, None)
        out, hist = jax.lax.scan(step, h, None, length=2)
        return out, hist.sum(axis=0)

    compiled = jax.jit(layer).lower(
        spec((rows, d), jnp.float32), spec((d, e), jnp.float32),
        spec((e, d, f), jnp.bfloat16), spec((e, d, f), jnp.bfloat16),
        spec((e, f, d), jnp.bfloat16)).compile()
    text = compiled.as_text()
    impl = "ragged_dot" if backend != "tpu" else "stream" if rows < 4096 else "tiled"
    assert_expert_kernels(
        text, impl,
        rows * k if impl == "tiled" else min(rows * k, moe.stream_segment_rows(d)), d, f)
    # only routed rows are materialised: the temporaries are a few
    # copies of the rows x top-k assignments (the down projection leaves
    # in f32 and is re-ordered once), a quarter of what a dense
    # all-experts einsum's rows x 64 x (1024 + 1024 + 2048) would hold
    routed_f32 = rows * k * d * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * routed_f32
    assert 3 * routed_f32 < rows * e * (2 * f + d) * 2


# ---------------------------------------------------------------------------
# GigaChat3.1 / DeepSeek-V3 (PR 30): the latent kernel and the held experts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lanes,pages", [(64, 16), (64, 32), (64, 64), (128, 64)])
def test_latent_kernel_compiles_on_the_whole_pool(one_chip, mosaic, lanes, pages):
    """The long-answer cell's chunk buckets: 64 or 128 lanes of 64 heads
    over tables of 16-64 pages, on the whole ``(6, 8193, 64, 640)`` pool
    (a 576-value row in 640 lanes: Mosaic cuts HBM in whole 128-lane
    tiles, which is why the pool is shaped so)."""
    layers, num_pages, heads, lanes_w, rank = 6, 8193, 64, 640, 512

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, pool, tables, lengths, layer):
        return kernels.latent_attention_decode(
            q, pool, tables, lengths, layer=layer, page_size=PS, rank=rank)

    compiled = jax.jit(fn).lower(
        spec((lanes, heads, lanes_w), jnp.bfloat16),
        spec((layers, num_pages, PS, lanes_w), jnp.bfloat16),
        spec((lanes, pages), jnp.int32), spec((lanes,), jnp.int32),
        spec((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and " = " in ln]
    assert len(calls) == 1, calls
    # what the benchmark's readers find the kernel by: its first output
    # is (lanes, heads, rank) f32
    assert calls[0].partition(" = ")[2].lstrip("(").startswith(
        f"f32[{lanes},{heads},{rank}]"), calls[0][:200]
    whole = f"[{layers},{num_pages},{PS},{lanes_w}]"
    produced = [ln for ln in text.splitlines()
                if whole in ln.partition(" = ")[2].partition("(")[0]
                and " parameter(" not in ln]
    assert not produced, produced[:3]


def test_a_576_wide_pool_cannot_be_cut_by_the_kernel(one_chip, mosaic):
    """Why the row rests in 640 lanes and not 576."""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, pool, tables, lengths, layer):
        return kernels.latent_attention_decode(
            q, pool, tables, lengths, layer=layer, page_size=PS, rank=512)

    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(fn).lower(
            spec((64, 64, 576), jnp.bfloat16), spec((6, 513, PS, 576), jnp.bfloat16),
            spec((64, 16), jnp.int32), spec((64,), jnp.int32),
            spec((), jnp.int32)).compile()


@pytest.mark.parametrize("rows", [128, 1024, 8192])
def test_held_expert_layer_compiles_with_rows_of_a_pass(one_chip, mosaic, backend, rows):
    """A decode step's 128 rows and prefill groups of 1,024 and 8,192
    through ``ops/moe.py`` at GigaChat3.1's widths, 8 of 256 experts
    held: the grouped matmuls take a pass's rows (``held_rows_cap``),
    not the ``rows x 8`` assignments, so the temporaries stay a few
    hundred MB; on a TPU the decode pass streams its experts through
    the Pallas kernel inside the pass loop, the 1,024-row pass too (in
    two segments of 512), and the largest — over the ridge: one and a
    half even shares, 384 rows an expert — runs the tiled kernel over
    the pass's 3,072 rows."""
    from seldon_core_tpu.ops import moe

    d, f, e, held, k = 7168, 2048, 256, 8, 8

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(h, w_router, bias, w_gate, w_up, w_down):
        gates, experts = moe.route_grouped(h, w_router, bias, k, 8, 4, True, 2.5)
        out = moe.expert_ffn_held(
            h.astype(jnp.bfloat16), w_gate, w_up, w_down, gates, experts, 0, e)
        return out, moe.expert_histogram(experts, e)

    compiled = jax.jit(layer).lower(
        spec((rows, d), jnp.float32), spec((d, e), jnp.float32),
        spec((e,), jnp.float32), spec((held, d, f), jnp.bfloat16),
        spec((held, d, f), jnp.bfloat16), spec((held, f, d), jnp.bfloat16)).compile()
    text = compiled.as_text()
    # four times an even 1/32 share under the ridge, 1.5 times over it
    cap = moe.held_rows_cap(rows, k, held, e)
    impl = "ragged_dot" if backend != "tpu" else "stream" if rows < 8192 else "tiled"
    assert_expert_kernels(
        text, impl, cap if impl == "tiled" else min(cap, moe.stream_segment_rows(d)), d, f)
    # a pass's rows at an expert's width (cut into segments, at d_model)
    assert cap == {128: 128, 1024: 1024, 8192: 3072}[rows]
    assert f"[{cap},{f}]" in text or f"[{cap},{d}]" in text
    assert f"[{rows * k},{d}]" not in text  # never all the assignments' rows
    assert f"[{cap + 1},{d}]" not in text   # nor a zero row under a pass's result
    assert compiled.memory_analysis().temp_size_in_bytes < 12 * rows * d * 4


# (cell, rows of a call, d_model, experts, n_group, topk_group, k): a
# decode step's and a prefill group's rows through each cell's router
ROUTER_SHAPES = [
    ("ling", 128, 4096, 512, 8, 4, 8), ("ling", 2048, 4096, 512, 8, 4, 8),
    ("gigachat", 128, 7168, 256, 8, 4, 8), ("gigachat", 2048, 7168, 256, 8, 4, 8),
    ("dots3", 128, 5120, 256, 1, 1, 8), ("xing4", 4096, 3584, 64, 1, 1, 4),
]


@pytest.mark.parametrize("cell,rows,d,e,n_group,topk_group,k", ROUTER_SHAPES, ids=[
    f"{c}-{r}" for c, r, *_ in ROUTER_SHAPES])
def test_the_grouped_router_sorts_once_at_the_cells_shapes(
        one_chip, mosaic, cell, rows, d, e, n_group, topk_group, k):
    """``route_grouped`` traces one ``top_k`` — the last selection — and no
    ``sort``, and the chip's compiler makes one sort of it, over ``(rows,
    experts)``: nothing sorts a group (PR 57; ``sort_f32_128_8_64_`` was
    a twentieth of the Ling cell's busy time).  Where every group is kept
    the group step is not traced: the program is the plain sigmoid
    router's, operation for operation."""
    from seldon_core_tpu.ops import moe

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def grouped(h, w, bias):
        return moe.route_grouped(h, w, bias, k, n_group, topk_group, True, 2.5)

    def plain(h, w, bias):
        with jax.named_scope(moe.ROUTER_SCOPE):
            scores = jax.nn.sigmoid(jnp.dot(
                h, w, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32))
            _, experts = jax.lax.top_k(scores + bias, k)
            gates = jnp.take_along_axis(scores, experts, axis=-1)
            gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20) * 2.5
        return gates, experts.astype(jnp.int32)

    shapes = (spec((rows, d)), spec((d, e)), spec((e,)))
    jaxpr = str(jax.make_jaxpr(grouped)(*shapes))
    assert jaxpr.count(" top_k[") == 1 and " sort[" not in jaxpr, jaxpr
    if topk_group >= n_group:
        assert jaxpr == str(jax.make_jaxpr(plain)(*shapes))
    else:
        assert "argmax" in jaxpr and jaxpr.count("reduce_max") == 2
    text = jax.jit(grouped).lower(*shapes).compile().as_text()
    sorts = [ln for ln in text.splitlines() if " sort(" in ln and " = " in ln]
    assert len(sorts) == 1 and f"f32[{rows},{e}]" in sorts[0], sorts


# ---------------------------------------------------------------------------
# LongCat-Flash (PR 32): the same two kernels at another pool and other widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lanes,pages", [(64, 8), (64, 16), (64, 32), (64, 48), (128, 48)])
def test_latent_kernel_compiles_on_the_double_layer_s_pool(one_chip, mosaic, lanes, pages):
    """The think cell's chunk buckets on the whole ``(8, 6145, 64, 640)``
    pool — 4 layers x 2 attentions — with a traced attention index (the
    block passes ``2 * layer + i``), at tables of 8 to 48 pages: 48, the
    per-stream table at 3,072 tokens, is no power of two."""
    attentions, num_pages, heads, lanes_w, rank = 8, 6145, 64, 640, 512

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, pool, tables, lengths, layer):
        return kernels.latent_attention_decode(
            q, pool, tables, lengths, layer=2 * layer + 1, page_size=PS, rank=rank)

    compiled = jax.jit(fn).lower(
        spec((lanes, heads, lanes_w), jnp.bfloat16),
        spec((attentions, num_pages, PS, lanes_w), jnp.bfloat16),
        spec((lanes, pages), jnp.int32), spec((lanes,), jnp.int32),
        spec((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and " = " in ln]
    assert len(calls) == 1, calls
    assert calls[0].partition(" = ")[2].lstrip("(").startswith(
        f"f32[{lanes},{heads},{rank}]"), calls[0][:200]
    whole = f"[{attentions},{num_pages},{PS},{lanes_w}]"
    produced = [ln for ln in text.splitlines()
                if whole in ln.partition(" = ")[2].partition("(")[0]
                and " parameter(" not in ln]
    assert not produced, produced[:3]


@pytest.mark.parametrize("rows", [128, 512, 2048, 4096])
def test_shortcut_experts_compile_with_rows_of_a_pass(one_chip, mosaic, backend, rows):
    """A decode step's 128 tokens and prefill groups of 512 to 4,096
    through LongCat-Flash's router and its held experts at the published
    widths, 16 of 512 real experts held beside 256 identity experts: a
    pass holds four times an even share of the ``rows x 12`` picks over
    all 768 outputs, which is ``rows`` itself (at 4,096 the ridge's own
    rows, where the rule's two halves meet); on a TPU every pass under
    256 rows an expert streams through the Pallas kernel (2,048 rows in
    segments of 640), and 4,096 rows over 16 experts run the tiled
    kernel."""
    from seldon_core_tpu.ops import moe

    d, f, real, zero, held, k = 6144, 2048, 512, 256, 16, 12

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(h, w_router, bias, w_gate, w_up, w_down):
        gates, experts = moe.route_zero(h, w_router, bias, k, 6.0)
        out = moe.expert_ffn_held(
            h.astype(jnp.bfloat16), w_gate, w_up, w_down, gates, experts, 0, real + zero)
        out = out + moe.identity_experts(h, gates, experts, real)
        return out, moe.expert_histogram(experts, real + zero), \
            moe.real_pick_histogram(experts, real)

    compiled = jax.jit(layer).lower(
        spec((rows, d), jnp.float32), spec((d, real + zero), jnp.float32),
        spec((real + zero,), jnp.float32), spec((held, d, f), jnp.bfloat16),
        spec((held, d, f), jnp.bfloat16), spec((held, f, d), jnp.bfloat16)).compile()
    text = compiled.as_text()
    cap = moe.held_rows_cap(rows, k, held, real + zero)
    assert cap == rows
    assert moe.layer_expert_matmul(rows, k, held, real + zero, d, f, jnp.bfloat16,
                                   held_pass=True, backend="tpu") == (
        "stream" if rows < 4096 else "tiled")
    impl = "ragged_dot" if backend != "tpu" else "stream" if rows < 4096 else "tiled"
    assert_expert_kernels(
        text, impl, cap if impl == "tiled" else min(cap, moe.stream_segment_rows(d)), d, f)
    assert f"[{rows * k},{d}]" not in text  # never all the picks' rows
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * rows * d * 4


# (cell, k, bucket, heads, d_qk, d_v): the five cells' largest from-zero
# prefill groups.  The latent cells' run the fused causal kernel; the
# multi-head cells' stay on XLA, which ties with it there on the chip
# (PERF.md §5) — Mosaic takes them all the same
PREFILL_SHAPES = [
    ("gpt2-doc", 4, 1024, 20, 64, 64), ("gpt2-chat", 4, 512, 20, 64, 64),
    ("olmoe", 4, 512, 16, 128, 128),
    ("gigachat", 1, 2048, 64, 192, 192), ("gigachat", 2, 1024, 64, 192, 192),
    ("longcat", 2, 1024, 64, 192, 128), ("longcat", 4, 512, 64, 192, 128),
]


@pytest.mark.parametrize("cell,k,bucket,heads,d_qk,d_v", PREFILL_SHAPES, ids=[
    f"{c}_b{b}_k{k}" for c, k, b, _h, _q, _v in PREFILL_SHAPES])
def test_prefill_causal_kernel_compiles_at_the_cells_shapes(
        one_chip, mosaic, cell, k, bucket, heads, d_qk, d_v):
    """PR 33: a head's whole K and V resident in VMEM, a dynamic loop
    over the key blocks under the diagonal, 64- and 192-wide heads (half
    a lane tile; one and a half), d_qk != d_v."""
    def spec(d):
        return jax.ShapeDtypeStruct((k, bucket, heads, d), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(
        lambda q, key, v: kernels.causal_attention(q, key, v, d_qk ** -0.5)
    ).lower(spec(d_qk), spec(d_qk), spec(d_v)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "prefill_causal_attention" in text
    assert kernels.prefill_attention_impl(
        bucket, d_qk, d_v, jnp.bfloat16, 0, True) == "fused"


@pytest.mark.parametrize("k,bucket", [(1, 4096), (2, 3072), (1, 7168)],
                         ids=["b4096_k1", "b3072_k2", "b7168_k1"])
def test_prefill_kernel_under_a_chosen_set_compiles_at_dots3s_shapes(
        one_chip, mosaic, k, bucket):
    """PR 43: an indexed layer's prefill (128 heads of 192 / 128) under
    the selection's ``(k, L, L)`` mask — packed 32 keys a word, a
    ``(512, 128)`` int32 tile a query block and 4,096 keys, a dynamic
    bit a key block, four columns of 128 lanes joined a score block."""
    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, key, v, m: kernels.causal_attention(q, key, v, 192 ** -0.5, chosen=m)
    ).lower(spec(k, bucket, 128, 192), spec(k, bucket, 128, 192),
            spec(k, bucket, 128, 128), spec(k, bucket, bucket, dtype=jnp.bool_)
            ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "prefill_chosen_attention" in text
    assert kernels.prefill_attention_impl(
        bucket, 192, 128, jnp.bfloat16, 0, True) == "fused"


def test_a_stack_of_layers_is_compiled_once_and_called(one_chip, mosaic):
    """PR 37: the engine compiles its programs on a TPU with
    ``paged.TPU_COMPILER_OPTIONS``.  The chip's compiler knows the
    option by that name, and a stack of identical GPT-2-large MLPs on
    bf16 weights carries no more program text with it than without
    (the whole prefill: 91.5 MB without, 7.8 MB with; PERF.md §6)."""
    from seldon_core_tpu.models import paged

    layers = 12

    def stack(x, w_in, w_out):
        for i in range(layers):
            x = x + jax.nn.gelu(x @ w_in[i]) @ w_out[i]
        return x

    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    lowered = jax.jit(stack).lower(
        bf16(1024, D), [bf16(D, 4 * D)] * layers, [bf16(4 * D, D)] * layers)
    plain = lowered.compile().memory_analysis().generated_code_size_in_bytes
    shared = lowered.compile(compiler_options=paged.TPU_COMPILER_OPTIONS)
    assert shared.memory_analysis().generated_code_size_in_bytes <= plain


# ---------------------------------------------------------------------------
# dots3-note (PR 38): the latent kernel at two shapes with a start offset,
# the windowed causal kernel, and the XLA programs of the selection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,heads,lanes_w,rank,num_pages,pages", [
    ("full", 128, 640, 512, 14337, 64), ("full", 128, 640, 512, 14337, 112),
    ("window", 64, 1152, 1024, 1281, 10),
    ("masked", 128, 640, 512, 14337, 64), ("masked", 128, 640, 512, 14337, 112)])
def test_latent_kernel_compiles_at_both_of_dots3_s_shapes(
        one_chip, mosaic, kind, heads, lanes_w, rank, num_pages, pages):
    """The long-doc cell's chunk: 128 lanes of 128 heads on the full
    layers' ``(3, 14337, 64, 640)`` pool (tables of 64 and 112 pages), and
    of 64 heads on the window layers' ``(3, 1281, 64, 1152)`` pool through
    a table of 10 pages with each lane's first live position; ``masked``
    (PR 40): the full layers' call under a selection's mask, a lane's
    ``(steps, 1024)`` int32 rows of it blocked into VMEM."""
    lanes, offset, masked = 128, kind == "window", kind == "masked"

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, pool, tables, lengths, layer, starts, chosen):
        return kernels.latent_attention_decode(
            q, pool, tables, lengths, layer=layer, page_size=PS, rank=rank,
            **({"starts": starts} if offset else {}),
            **({"chosen": chosen} if masked else {}))

    compiled = jax.jit(fn).lower(
        spec((lanes, heads, lanes_w), jnp.bfloat16),
        spec((3, num_pages, PS, lanes_w), jnp.bfloat16),
        spec((lanes, pages), jnp.int32), spec((lanes,), jnp.int32),
        spec((), jnp.int32), spec((lanes,), jnp.int32),
        spec((lanes, pages * PS), jnp.bool_)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and " = " in ln]
    assert len(calls) == 1, calls
    # what the benchmark's readers tell the two kernels apart by
    assert calls[0].partition(" = ")[2].lstrip("(").startswith(
        f"f32[{lanes},{heads},{rank}]"), calls[0][:200]


@pytest.mark.parametrize("lanes,pages", [(64, 64), (64, 112), (128, 112)])
def test_index_kernel_compiles_at_the_cells_shapes(one_chip, mosaic, lanes, pages):
    """PR 51: a decode step's indexer scores over the ``(3, 14337, 64,
    128)`` key pool, a bucket of the long-doc cell's chunk each; the first
    output three dims, ``(lanes, pages, page_size)`` — what the
    benchmark's readers tell it from a grouped matmul by."""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, w, pool, tables, lengths, layer: kernels.index_scores_decode(
            q, w, pool, tables, lengths, layer=layer, page_size=PS, scale=1 / 90.5)
    ).lower(
        spec((lanes, 64, 128), jnp.bfloat16), spec((lanes, 64), jnp.float32),
        spec((3, 14337, PS, 128), jnp.bfloat16), spec((lanes, pages), jnp.int32),
        spec((lanes,), jnp.int32), spec((), jnp.int32)).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln and " = " in ln]
    assert len(calls) == 1, calls
    assert calls[0].partition(" = ")[2].startswith(f"f32[{lanes},{pages},{PS}]"), calls[0][:200]


@pytest.mark.parametrize("k,bucket", [(1, 4096), (1, 3072), (2, 3072)])
def test_windowed_causal_kernel_compiles_at_the_cells_shapes(
        one_chip, mosaic, k, bucket):
    """A window layer's prefill: 64 heads of 256 against values of 128,
    513 positions, a head's K and V of 4,096 positions resident."""
    heads, d_qk, d_v = 64, 256, 128

    def spec(d):
        return jax.ShapeDtypeStruct((k, bucket, heads, d), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(
        lambda q, key, v: kernels.causal_attention(
            q, key, v, d_qk ** -0.5, window=513)
    ).lower(spec(d_qk), spec(d_qk), spec(d_v)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "prefill_window_attention" in text
    assert kernels.prefill_attention_impl(
        bucket, d_qk, d_v, jnp.bfloat16, 0, True) == "fused"


def test_the_selection_s_programs_compile_at_the_cells_shapes(one_chip, mosaic):
    """What a full layer adds: a decode step's scores of 7,168 cached
    indexer keys a lane (PR 51: a page loop over the key pool, no key
    gathered), the best 2,048 as a mask (PR 55: by counting, no sort in
    either program) and the page loop
    under it (PR 40: no row is gathered, and the step's temporaries are
    the scores' alone); a prefill's indexed attention over 4,096
    positions, a block of queries at a time.  Neither may need more than
    a few hundred MB beside its operands."""
    from seldon_core_tpu.ops import mla

    lanes, pages, topk, rank = 128, 112, 2048, 512

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q_full, q_i, w_i, key_row, own, pool, idx_pool, table, lengths):
        cached = kernels.index_scores_decode(
            q_i[:, 0], w_i[:, 0], idx_pool, table, lengths, layer=1,
            page_size=PS, scale=1 / 90.5).reshape(lanes, pages * PS)
        own_sc = mla.index_scores(q_i, w_i, key_row, 1 / 90.5)[:, 0, 0]
        is_cached, own_in = mla.step_mask(cached, own_sc, lengths, topk)
        return mla.merge(
            kernels.latent_attention_decode(
                q_full, pool, table, lengths, layer=1, page_size=PS, rank=rank,
                chosen=is_cached),
            mla.ctx_state(q_full, own, own_in[:, None], rank))

    compiled = jax.jit(step).lower(
        spec((lanes, 128, 640)), spec((lanes, 1, 64, 128)),
        spec((lanes, 1, 64), jnp.float32), spec((lanes, 1, 128)),
        spec((lanes, 1, 640)), spec((3, 14337, PS, 640)),
        spec((3, 14337, PS, 128)), spec((lanes, pages), jnp.int32),
        spec((lanes,), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # no operation makes the (lanes x topk, 640) array of gathered rows
    assert f"bf16[{lanes * topk},640]" not in text
    assert f"bf16[{lanes},{topk},640]" not in text
    # ... nor the (lanes x pages, 64, 128) array of gathered keys
    assert f"bf16[{lanes * pages},{PS},128]" not in text
    assert f"bf16[{lanes},{pages},{PS},128]" not in text
    # ... and no row is sorted to learn its 2,048th score (PR 55)
    assert "sort(" not in text

    seg = 4096

    def prefill(q_nope, q_rope, rows, w_uk, w_uv, q_i, w_i, k_i):
        return mla.indexed_attention(
            q_nope, q_rope, rows, w_uk, w_uv, 192 ** -0.5, jnp.bfloat16,
            q_i, w_i, k_i, 1 / 90.5, topk)

    compiled = jax.jit(prefill).lower(
        spec((1, seg, 128, 128)), spec((1, seg, 128, 64)), spec((1, seg, 640)),
        spec((128, 512, 128)), spec((128, 512, 128)), spec((1, seg, 64, 128)),
        spec((1, seg, 64), jnp.float32), spec((1, seg, 128))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.0e9
    assert "sort(" not in compiled.as_text()


# ---------------------------------------------------------------------------
# grouped-query heads (PR 41): SmallThinker's 28 query heads of 128 on 4
# K/V heads, pools of 512 lanes — the full layers' (3, 10241, 64, 512)
# under the block table, the window layers' (9, 4225, 64, 512) under the
# 66-column window table with a first live position a lane
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,layers,num_pages,pages,lanes", [
    ("full", 3, 10241, 160, 64), ("full", 3, 10241, 32, 32),
    ("window", 9, 4225, 66, 64), ("window", 9, 4225, 66, 32)])
def test_grouped_page_loop_compiles_at_smallthinker_s_shapes(
        one_chip, mosaic, kind, layers, num_pages, pages, lanes):
    heads, kv_heads, hd = 28, 4, 128

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(q, pk, pv, tables, lengths, starts):
        return kernels.paged_attention_decode(
            q, pk, pv, tables, lengths, layer=1, page_size=PS,
            **({"starts": starts} if kind == "window" else {}))

    pool = spec((layers, num_pages, PS, kv_heads * hd), jnp.bfloat16)
    compiled = jax.jit(call).lower(
        spec((lanes, heads, hd), jnp.bfloat16), pool, pool,
        spec((lanes, pages), jnp.int32), spec((lanes,), jnp.int32),
        spec((lanes,), jnp.int32)).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln and " = " in ln]
    assert len(calls) == 1, calls
    # what the benchmark's reader knows the page loop by: the attended
    # values of every query head, the heads in whole sublane tiles
    assert calls[0].partition(" = ")[2].lstrip("(").startswith(
        f"f32[{lanes},32,{hd}]"), calls[0][:200]


@pytest.mark.parametrize("k,bucket,window", [
    (1, 8192, 0), (1, 8192, 4096), (1, 10240, 4096), (2, 2048, 0), (1, 3072, 4096)])
def test_grouped_causal_kernel_compiles_at_smallthinker_s_shapes(
        one_chip, mosaic, k, bucket, window):
    """A prefill from zero: 28 query heads on 4 K/V heads by the block
    index, a K/V head's 8,192 (10,240) positions resident, a window
    layer's blocks behind 4,096 skipped."""
    def spec(heads):
        return jax.ShapeDtypeStruct((k, bucket, heads, 128), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(
        lambda q, key, v: kernels.causal_attention(
            q, key, v, 128 ** -0.5, window=window)
    ).lower(spec(28), spec(4), spec(4)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and (
        "prefill_window_attention" if window else "prefill_causal_attention") in text
    assert f"bf16[{k * 28},{bucket},128]" in text
    assert kernels.prefill_attention_impl(
        bucket, 128, 128, jnp.bfloat16, 0, True) == "fused"


# ---------------------------------------------------------------------------
# Xing4.0 (PR 45): the mixing's two kernels, and the whole LM's programs
# at the configuration's widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("positions", [4096, 128])
def test_the_mixing_s_kernels_compile_at_xing4_s_widths(monkeypatch, one_chip, mosaic,
                                                        positions):
    """Mosaic takes ``hyper_pre_mix`` and ``hyper_post_mix`` at 4 rows of
    3,584: a 4,096-position prefill call and a 128-lane decode step."""
    from seldon_core_tpu.ops import hyper

    monkeypatch.setattr(hyper, "backend", lambda: "tpu")
    n, c = 4, 3584
    kw = dict(iters=20, eps=1e-6, lo=-30.0, hi=30.0)

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = {"phi": shape((24, n * c)), "bias": shape((24,)), "scale": shape((3,))}
    pre = jax.jit(lambda x, p: hyper.hyper_pre(x, p, **kw)).lower(
        shape((n, positions, c)), params).compile()
    post = jax.jit(hyper.hyper_post, donate_argnums=0).lower(
        shape((n, positions, c)), shape((positions, c), jnp.bfloat16),
        shape((positions, n)), shape((positions, n, n))).compile()
    assert "tpu_custom_call" in pre.as_text() and "tpu_custom_call" in post.as_text()
    # the rows are rewritten where they rest: no second copy of them
    assert post.memory_analysis().temp_size_in_bytes < n * positions * c * 4 // 2


def test_xing4_s_programs_compile_and_the_prefill_cap_s_count_holds(
        monkeypatch, one_chip, mosaic):
    """The whole LM at the configuration's sizes (abstract weights: 7.95
    GB as they rest), a 4,096-position prefill from zero and a 128-lane
    decode step over the (6, 9217, 64, 640) pool: both compile, every
    layer runs its latent / prefill kernel, its grouped matmuls and the
    mixing's pair, and the prefill's temporaries and outputs are within a
    third of ``prefill_position_bytes``'s count (339 KB a position; 1.39
    GB against the compiler's 1.17 with one row unembedded, PR 49)."""
    import json

    from seldon_core_tpu.models import paged
    from seldon_core_tpu.models.spec import declared_tree, model_spec
    from seldon_core_tpu.ops import hyper, moe

    monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "force")
    monkeypatch.setattr(hyper, "backend", lambda: "tpu")
    monkeypatch.setattr(moe, "matmul_backend", lambda: "tpu")
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        cfg = json.load(f)
    served = {p["name"]: p["value"]
              for p in cfg["deployment"]["predictors"][0]["graph"]["parameters"]}
    spec = model_spec(served["arch"], **json.loads(served["arch_sizes"]))
    sizes = dict(vocab_size=int(served["vocab_size"]), d_model=int(served["d_model"]),
                 num_layers=int(served["num_layers"]), num_heads=int(served["num_heads"]))

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    tree = declared_tree(spec, dict(sizes, max_len=int(served["max_len"])), jnp.bfloat16)
    resting = sum(leaf.size * leaf.dtype.itemsize
                  for leaf in jax.tree_util.tree_leaves(tree))
    assert abs(resting - 7.95e9) < 0.02e9
    params = jax.tree_util.tree_map(lambda leaf: shape(leaf.shape, leaf.dtype), tree)
    lm = paged.get_paged_lm_class()(dtype=jnp.bfloat16, spec=spec, decode_kernel=True,
                                    max_len=int(served["max_len"]), **sizes)
    pool = shape((6, int(served["num_pages"]), 64, 640), jnp.bfloat16)

    def run(params, tokens, positions, pool, tables, lengths, last):
        return lm.apply({"params": params}, tokens, positions, pool, None, tables,
                        lengths, token_mask=jnp.ones(tokens.shape, bool), last=last)

    def compiled(batch, seg, width, last=None):
        i32 = jnp.int32
        return jax.jit(run).lower(
            params, shape((batch, seg), i32), shape((batch, seg), i32), pool,
            shape((batch, width), i32), shape((batch,), i32), last).compile(
                compiler_options=paged.TPU_COMPILER_OPTIONS)

    # as the engine's prefill calls it: one row a prompt unembedded (PR 49)
    prefill = compiled(1, 4096, 0, shape((1,), jnp.int32))
    memory = prefill.memory_analysis()
    counted = 4096 * paged.prefill_position_bytes(spec, 3584, 16384, 32)
    by_compiler = memory.temp_size_in_bytes + memory.output_size_in_bytes
    assert 2 / 3 < counted / by_compiler < 3 / 2, (counted, by_compiler)
    step = compiled(128, 1, 72)
    assert step.memory_analysis().temp_size_in_bytes < 1 << 29
    for program in (prefill, step):
        # a layer: the attention's kernel, the experts' or none (the dense
        # layer), and four of the mixing's
        assert program.as_text().count("tpu_custom_call") >= 6 * 5
    # a prefill's held pass (16,384 rows over 64 whole experts: the
    # ridge's own line) runs the tiled kernel, a decode step's the
    # streaming one; neither leaves a ragged_dot
    assert_expert_kernels(prefill.as_text(), "tiled", 16384, 3584, 1024)
    assert_expert_kernels(step.as_text(), "stream", moe.stream_segment_rows(3584),
                          3584, 1024)


# (cell, experts held, d_model, expert width, rows): the passes over the
# ridge that the cells form (PERF.md section 5, PR 46's table)
TILED_SHAPES = [
    ("xing4", 64, 3584, 1024, 16384), ("olmoe", 64, 2048, 1024, 16384),
    ("smallthinker", 16, 2560, 768, 7168), ("smallthinker", 16, 2560, 768, 18432),
    ("gigachat", 8, 7168, 2048, 2048), ("gigachat", 8, 7168, 2048, 3072),
    ("dots3", 8, 5120, 1536, 2560), ("longcat", 16, 6144, 2048, 4096),
]


@pytest.mark.parametrize("cell,held,d,f,rows", TILED_SHAPES)
def test_the_tiled_kernels_compile_at_the_cells_shapes(one_chip, cell, held, d, f, rows):
    """The tiled grouped SwiGLU alone at each width family's rows over
    the ridge: two Mosaic kernels, gate and up leaving ``(rows, f)`` in
    bfloat16 and the down projection ``(rows, d)`` in float32, and no
    copy of the rows beside them (the streaming kernel keeps them whole
    in float32: a call's temporaries here are the first kernel's
    output)."""
    from seldon_core_tpu.ops import moe

    assert moe.expert_matmul_impl(rows, held, d, f, jnp.bfloat16, "tpu") == "tiled"

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(
            lambda *a: moe._tiled_swiglu(*a, interpret=False)).lower(
                spec((rows, d), jnp.bfloat16), spec((held, d, f), jnp.bfloat16),
                spec((held, d, f), jnp.bfloat16), spec((held, f, d), jnp.bfloat16),
                spec((held,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    assert_expert_kernels(compiled.as_text(), "tiled", rows, d, f)
    assert compiled.memory_analysis().temp_size_in_bytes < rows * f * 2 + (1 << 20)


def test_the_state_step_kernel_compiles_at_olmo_hybrid_s_shape(monkeypatch, one_chip, mosaic):
    """``ops/delta.py delta_state_step`` on every slot's state of one
    linear layer, (128, 15, 96, 384) float32: one Mosaic call, the state
    rewritten where it rests (aliased: no second 283 MB)."""
    from seldon_core_tpu.ops import delta

    monkeypatch.setattr(delta, "backend", lambda: "tpu")

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    slots, heads, dk, dv = 128, 30, 96, 192
    state = shape(delta.state_shape(slots, heads, dk, dv))
    assert state.shape == (128, 15, 96, 384)
    step = jax.jit(
        lambda s, q, k, v, la, b, on: delta.step(s, q, k, v, la, b, pack=2, active=on),
        donate_argnums=0,
    ).lower(state, shape((slots, heads, dk)), shape((slots, heads, dk)),
            shape((slots, heads, dv)), shape((slots, heads)), shape((slots, heads)),
            shape((slots,), jnp.bool_)).compile()
    assert step.as_text().count("tpu_custom_call") == 1
    memory = step.memory_analysis()
    assert memory.alias_size_in_bytes >= 128 * 15 * 96 * 384 * 4
    assert memory.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("prompts,length,heads,dk,dv,channel", [
    (4, 512, 30, 96, 192, False),     # Olmo-Hybrid's b512_k4: one decay a head
    (1, 2048, 32, 128, 128, True),    # Ling-3.0-flash's b2048_k1: a decay a key channel
])
def test_the_scan_kernel_compiles_at_the_cells_widths(
        monkeypatch, one_chip, mosaic, prompts, length, heads, dk, dv, channel):
    """``ops/delta.py delta_chunk_scan`` at a prefill call's shapes: one
    Mosaic call (two heads' ``(64, 64)`` matrices side by side: a lane
    concatenation and a lane slice at 64 that interpret mode never
    lowers), the output ``(prompts, H, L, d_v)`` and the final state its
    results, nothing of a chunk's matrices in HBM."""
    from seldon_core_tpu.ops import delta

    monkeypatch.setattr(delta, "backend", lambda: "tpu")
    assert delta.scan_impl(dk) == "pallas"

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    rows = (prompts, length, heads)
    scan = jax.jit(lambda *a: delta.chunked_scan(*a)).lower(
        shape(*rows, dk), shape(*rows, dk), shape(*rows, dv),
        shape(*rows, dk) if channel else shape(*rows), shape(*rows)).compile()
    assert scan.as_text().count("tpu_custom_call") == 1
    laid = 4 * prompts * length * heads * (2 * dk + 2 * dv + (dk if channel else 0))
    assert scan.memory_analysis().temp_size_in_bytes < 2 * laid


def test_olmo_hybrid_s_programs_compile_and_the_prefill_cap_s_count_holds(
        monkeypatch, one_chip, mosaic):
    """The whole LM at the configuration's sizes (abstract weights: 4.87 GB
    as they rest): a ``b512_k4`` prefill from zero (the chunked scan in
    six layers, the fused causal kernel in two) and a 128-lane decode step
    over the state a lane and the (2, 3073, 64, 3840) pools.  Both
    compile; the decode step runs the state kernel in every linear layer
    and the page loop in every full one; the prefill's temporaries and
    outputs stay under ``prefill_position_bytes``'s count, which no
    longer lies within a factor of two of them (1.25 GB against the
    compiler's 0.39 since PR 53, 0.78 before it: the scan's kernel keeps a
    chunk's matrices and solved rows in VMEM, where the count still
    prices XLA's form of them and the ``4 * vocab_size`` a position no
    program has held since PR 49 — the cap and the groups it forms are
    left as they were, ROADMAP S3 c')."""
    import json

    from seldon_core_tpu.models import paged
    from seldon_core_tpu.models.spec import declared_tree, model_spec
    from seldon_core_tpu.ops import delta

    monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "force")
    monkeypatch.setattr(delta, "backend", lambda: "tpu")
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs",
                           "olmo-hybrid-7b.json")) as f:
        cfg = json.load(f)
    served = {p["name"]: p["value"]
              for p in cfg["deployment"]["predictors"][0]["graph"]["parameters"]}
    spec = model_spec(served["arch"])
    sizes = dict(vocab_size=int(served["vocab_size"]), d_model=int(served["d_model"]),
                 num_layers=int(served["num_layers"]), num_heads=int(served["num_heads"]))

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    tree = declared_tree(spec, dict(sizes, max_len=int(served["max_len"])), jnp.bfloat16)
    resting = sum(leaf.size * leaf.dtype.itemsize
                  for leaf in jax.tree_util.tree_leaves(tree))
    assert abs(resting - 4.87e9) < 0.01e9
    params = jax.tree_util.tree_map(lambda leaf: shape(leaf.shape, leaf.dtype), tree)
    lm = paged.get_paged_lm_class()(dtype=jnp.bfloat16, spec=spec, decode_kernel=True,
                                    max_len=int(served["max_len"]), **sizes)
    pool = shape((2, int(served["num_pages"]), 64, 3840), jnp.bfloat16)
    i32, slots = jnp.int32, int(served["max_slots"])

    def prefill(params, tokens, positions, pk, pv, tables, lengths, true_lens):
        # as the engine's prefill calls it: one row a prompt unembedded (PR 49)
        return lm.apply({"params": params}, tokens, positions, pk, pv, tables, lengths,
                        delta={"true_lens": true_lens}, last=true_lens - 1)

    def step(params, tokens, positions, pk, pv, tables, lengths, state, conv, active):
        return lm.apply({"params": params}, tokens, positions, pk, pv, tables, lengths,
                        delta={"state": state, "conv": conv, "active": active})

    def common(batch, seg, width):
        return (params, shape((batch, seg), i32), shape((batch, seg), i32), pool, pool,
                shape((batch, width), i32), shape((batch,), i32))

    opts = dict(compiler_options=paged.TPU_COMPILER_OPTIONS)
    first = jax.jit(prefill).lower(*common(4, 512, 0), shape((4,), i32)).compile(**opts)
    memory = first.memory_analysis()
    counted = 2048 * paged.prefill_position_bytes(spec, 3840, 100_352, 30)
    by_compiler = memory.temp_size_in_bytes + memory.output_size_in_bytes
    assert 1 < counted / by_compiler < 4, (counted, by_compiler)
    state = tuple(shape(delta.state_shape(slots, 30, 96, 192), jnp.float32)
                  for _ in range(6))
    conv = tuple(shape((slots, 3, 11_520), jnp.bfloat16) for _ in range(6))
    decode = jax.jit(step, donate_argnums=(7, 8)).lower(
        *common(slots, 1, 16), state, conv, shape((slots,), jnp.bool_)).compile(**opts)
    # six state kernels and two page loops a step; no copy of a state
    assert decode.as_text().count("tpu_custom_call") >= 8
    assert decode.memory_analysis().temp_size_in_bytes < 1 << 30
    assert first.as_text().count("tpu_custom_call") >= 2  # the fused causal kernel


def test_the_state_step_kernel_compiles_under_a_channel_decay(monkeypatch, one_chip, mosaic):
    """``ops/delta.py delta_state_step`` at Ling-3.0-flash's shape — every
    slot's state of one KDA layer, (128, 32, 128, 128) float32, the decay a
    key channel riding with the rows ``(128, 32, 3, 128)``: one Mosaic call,
    the state rewritten where it rests (aliased: no second 268 MB)."""
    from seldon_core_tpu.ops import delta

    monkeypatch.setattr(delta, "backend", lambda: "tpu")

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    slots, heads, dk, dv = 128, 32, 128, 128
    state = shape(delta.state_shape(slots, heads, dk, dv))
    assert state.shape == (128, 32, 128, 128) and delta.pack_of(heads, dv) == 1
    step = jax.jit(
        lambda s, q, k, v, la, b, on: delta.step(s, q, k, v, la, b, active=on),
        donate_argnums=0,
    ).lower(state, shape((slots, heads, dk)), shape((slots, heads, dk)),
            shape((slots, heads, dv)), shape((slots, heads, dk)), shape((slots, heads)),
            shape((slots,), jnp.bool_)).compile()
    assert step.as_text().count("tpu_custom_call") == 1
    memory = step.memory_analysis()
    assert memory.alias_size_in_bytes >= 128 * 32 * 128 * 128 * 4
    assert memory.temp_size_in_bytes < 64 << 20


def test_ling3_s_programs_compile_and_the_prefill_cap_s_count_holds(
        monkeypatch, one_chip, mosaic):
    """The whole LM at the configuration's sizes (abstract weights: 3.74 GB
    as they rest): a ``b2048_k1`` prefill from zero (the block-wise chunked
    scan in ten layers, the fused causal kernel in two, a held pass in
    eleven) and a 128-lane decode step over the state a lane and the ONE
    latent pool (2, 12289, 64, 640).  Both compile; the decode step runs
    the state kernel in every KDA layer, the latent page loop in every MLA
    layer and two grouped matmuls a routed layer (34 Mosaic calls: 10 + 2 +
    22) in under 0.1 GiB of temporaries; the prefill's temporaries are
    within a factor of two of ``prefill_position_bytes``'s count (0.85 GiB
    against the compiler's 0.86: the decay a channel is priced)."""
    import json

    from seldon_core_tpu.models import paged
    from seldon_core_tpu.models.spec import declared_tree, model_spec
    from seldon_core_tpu.ops import delta, moe

    monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "force")
    monkeypatch.setattr(delta, "backend", lambda: "tpu")
    monkeypatch.setattr(moe, "matmul_backend", lambda: "tpu")
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs",
                           "ling-3.0-flash.json")) as f:
        cfg = json.load(f)
    served = {p["name"]: p["value"]
              for p in cfg["deployment"]["predictors"][0]["graph"]["parameters"]}
    spec = model_spec(served["arch"], **json.loads(served["arch_sizes"]))
    sizes = dict(vocab_size=int(served["vocab_size"]), d_model=int(served["d_model"]),
                 num_layers=int(served["num_layers"]), num_heads=int(served["num_heads"]))

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    tree = declared_tree(spec, dict(sizes, max_len=int(served["max_len"])), jnp.bfloat16)
    resting = sum(leaf.size * leaf.dtype.itemsize
                  for leaf in jax.tree_util.tree_leaves(tree))
    assert abs(resting - 3.74e9) < 0.01e9
    params = jax.tree_util.tree_map(lambda leaf: shape(leaf.shape, leaf.dtype), tree)
    lm = paged.get_paged_lm_class()(dtype=jnp.bfloat16, spec=spec, decode_kernel=True,
                                    max_len=int(served["max_len"]), **sizes)
    pool = shape((2, int(served["num_pages"]), 64, 640), jnp.bfloat16)
    i32, slots = jnp.int32, int(served["max_slots"])

    def prefill(params, tokens, positions, pk, tables, lengths, true_lens):
        real = jnp.arange(tokens.shape[1])[None, :] < true_lens[:, None]
        return lm.apply({"params": params}, tokens, positions, pk, None, tables, lengths,
                        delta={"true_lens": true_lens}, last=true_lens - 1,
                        token_mask=real)

    def step(params, tokens, positions, pk, tables, lengths, state, conv, active):
        return lm.apply({"params": params}, tokens, positions, pk, None, tables, lengths,
                        delta={"state": state, "conv": conv, "active": active},
                        token_mask=active[:, None])

    def common(batch, seg, width):
        return (params, shape((batch, seg), i32), shape((batch, seg), i32), pool,
                shape((batch, width), i32), shape((batch,), i32))

    opts = dict(compiler_options=paged.TPU_COMPILER_OPTIONS)
    state = tuple(shape(delta.state_shape(slots, 32, 128, 128), jnp.float32)
                  for _ in range(10))
    conv = tuple(shape((slots, 3, 12_288), jnp.bfloat16) for _ in range(10))
    decode = jax.jit(step, donate_argnums=(6, 7)).lower(
        *common(slots, 1, 32), state, conv, shape((slots,), jnp.bool_)).compile(**opts)
    assert decode.as_text().count("tpu_custom_call") == 10 + 2 + 2 * 11
    assert decode.memory_analysis().temp_size_in_bytes < 1 << 28
    first = jax.jit(prefill).lower(*common(1, 2048, 0), shape((1,), i32)).compile(**opts)
    memory = first.memory_analysis()
    counted = 2048 * paged.prefill_position_bytes(spec, 2560, 19_648, 32)
    by_compiler = memory.temp_size_in_bytes + memory.output_size_in_bytes
    assert 1 / 2 < counted / by_compiler < 2, (counted, by_compiler)
    assert first.as_text().count("tpu_custom_call") >= 2 + 2 * 11


def test_the_ssm_kernels_compile_at_jamba_s_shapes(monkeypatch, one_chip, mosaic):
    """``ops/ssm.py ssm_state_step`` on every slot's state of one Mamba
    layer, (256, 16, 5120) float32 — one Mosaic call, the state rewritten
    where it rests (aliased: no second 84 MB) — and ``ssm_scan`` at the
    cell's widest prefill call (8 prompts of 512): one Mosaic call whose
    state never leaves VMEM (no ``(positions, N, E)`` array anywhere)."""
    from seldon_core_tpu.ops import ssm

    monkeypatch.setattr(ssm, "backend", lambda: "tpu")
    slots, n, e = 256, 16, 5120
    assert ssm.step_impl(n, e) == ssm.scan_impl(n, e) == "pallas"

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    step = jax.jit(
        lambda s, x, dt, b, c, a, d, on: ssm.step(s, x, dt, b, c, a, d, active=on),
        donate_argnums=0,
    ).lower(shape((slots, n, e)), shape((slots, e)), shape((slots, e)),
            shape((slots, n)), shape((slots, n)), shape((n, e)), shape((e,)),
            shape((slots,), jnp.bool_)).compile()
    assert step.as_text().count("tpu_custom_call") == 1
    memory = step.memory_analysis()
    assert memory.alias_size_in_bytes >= slots * n * e * 4
    assert memory.temp_size_in_bytes < 64 << 20
    k, length = 8, 512
    scan = jax.jit(lambda *a: ssm.scan(*a[:-1], true_lens=a[-1])).lower(
        shape((k, length, e)), shape((k, length, e)), shape((k, length, n)),
        shape((k, length, n)), shape((n, e)), shape((e,)),
        shape((k,), jnp.int32)).compile()
    assert scan.as_text().count("tpu_custom_call") == 1
    assert f"{length},{n},{e}" not in scan.as_text()  # no state a position
    assert scan.memory_analysis().temp_size_in_bytes < 4 * 4 * k * length * e


def test_jamba_s_programs_compile_and_the_prefill_cap_s_count_holds(
        monkeypatch, one_chip, mosaic):
    """The whole LM at the configuration's sizes (abstract weights: 6.06 GB
    as they rest, the tied matrix once): a ``b512_k8`` prefill from zero
    (the scan's kernel in 26 layers, the fused causal kernel at 20 query
    heads over 1 K/V head in two) and a 256-lane decode step over the
    state a lane and the (2, 6145, 64, 128) pools.  Both compile; the
    decode step runs the state kernel in every Mamba layer and the grouped
    page loop in both attention layers; the prefill's temporaries stay
    under ``prefill_position_bytes``'s count."""
    import json

    from seldon_core_tpu.models import paged
    from seldon_core_tpu.models.spec import declared_tree, model_spec
    from seldon_core_tpu.ops import ssm

    monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "force")
    monkeypatch.setattr(ssm, "backend", lambda: "tpu")
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs",
                           "jamba2-3b.json")) as f:
        cfg = json.load(f)
    served = {p["name"]: p["value"]
              for p in cfg["deployment"]["predictors"][0]["graph"]["parameters"]}
    spec = model_spec(served["arch"])
    sizes = dict(vocab_size=int(served["vocab_size"]), d_model=int(served["d_model"]),
                 num_layers=int(served["num_layers"]), num_heads=int(served["num_heads"]))

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    tree = declared_tree(spec, dict(sizes, max_len=int(served["max_len"])), jnp.bfloat16)
    assert "head" not in tree  # tied: the embedding is the one matrix
    resting = sum(leaf.size * leaf.dtype.itemsize
                  for leaf in jax.tree_util.tree_leaves(tree))
    assert abs(resting - 6.06e9) < 0.01e9, resting
    params = jax.tree_util.tree_map(lambda leaf: shape(leaf.shape, leaf.dtype), tree)
    lm = paged.get_paged_lm_class()(dtype=jnp.bfloat16, spec=spec, decode_kernel=True,
                                    max_len=int(served["max_len"]), **sizes)
    pool = shape((2, int(served["num_pages"]), 64, 128), jnp.bfloat16)
    i32, slots = jnp.int32, int(served["max_slots"])

    def prefill(params, tokens, positions, pk, pv, tables, lengths, true_lens):
        return lm.apply({"params": params}, tokens, positions, pk, pv, tables, lengths,
                        delta={"true_lens": true_lens}, last=true_lens - 1)

    def step(params, tokens, positions, pk, pv, tables, lengths, state, conv, active):
        return lm.apply({"params": params}, tokens, positions, pk, pv, tables, lengths,
                        delta={"state": state, "conv": conv, "active": active})

    def common(batch, seg, width):
        return (params, shape((batch, seg), i32), shape((batch, seg), i32), pool, pool,
                shape((batch, width), i32), shape((batch,), i32))

    opts = dict(compiler_options=paged.TPU_COMPILER_OPTIONS)
    state = tuple(shape(spec.state_shape(slots), jnp.float32) for _ in range(26))
    conv = tuple(shape((slots, 3, 5120), jnp.bfloat16) for _ in range(26))
    decode = jax.jit(step, donate_argnums=(7, 8)).lower(
        *common(slots, 1, 32), state, conv, shape((slots,), jnp.bool_)).compile(**opts)
    assert decode.as_text().count("tpu_custom_call") >= 26 + 2
    assert decode.memory_analysis().temp_size_in_bytes < 1 << 30
    first = jax.jit(prefill).lower(*common(8, 512, 0), shape((8,), i32)).compile(**opts)
    memory = first.memory_analysis()
    counted = 4096 * paged.prefill_position_bytes(spec, 2560, 65_536, 20)
    by_compiler = memory.temp_size_in_bytes + memory.output_size_in_bytes
    assert 1 < counted / by_compiler < 4, (counted, by_compiler)
    assert first.as_text().count("tpu_custom_call") >= 26 + 2
