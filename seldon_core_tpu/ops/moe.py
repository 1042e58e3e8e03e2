"""Routed experts: a router and a grouped expert matmul over the routed
rows only.

``route``: logits, softmax and the chosen gates in float32 (on a TPU an
f32 matmul runs in bf16 passes unless told otherwise, so the router's
is ``HIGHEST``); top-k of the probabilities, gates **not** renormalised
(OLMoE's ``norm_topk_prob: false``) unless ``norm`` says so
(SmallThinker's ``true``); the logits may be handed over
(``router_logits``: a router that reads the attention's input computes
them before the attention, in the same program).  The experts' gate
activation is a static argument of the routed entries below (``act``:
"silu" | "relu", SwiGLU | ReGLU); "silu" traces what it always did.

``expert_ffn``: the ``T x k`` (token, expert) assignments are sorted by
expert and the three SwiGLU matmuls run as one grouped SwiGLU over the
sorted rows with the experts' group sizes: every routed pair is
computed, none is dropped (no capacity factor), and an expert nobody
chose costs nothing but its place in the group table.  Matmuls take
the activations' type (bf16 when serving) and accumulate in f32.

``grouped_swiglu`` is that one entry, for ``expert_ffn`` and
``expert_ffn_held`` alike, and its implementation is a function of the
call's static shape, operand type and backend
(``expert_matmul_impl``).  In bf16 on a TPU, under the chip's ridge in
mean rows a group: a Pallas kernel written for a call bound by
streaming the hit experts' matrices (``stream_matmul``: sorted rows
resident in VMEM, each hit expert's matrices streamed once in blocks
of whole K, gate and up in one call with ``silu(gate) * up`` applied
in VMEM).  At and over the ridge: a Pallas kernel written for a call
bound by its rows' FLOPs (``tiled_matmul``: one call a matmul at any
row count, rows and results in bf16 blocks through the pipeline, row
tiles that start at each group's own first row so a group wastes under
a tile, the next group's matrices fetched behind the compute, nothing
visited past the last group).  Everywhere else ``jax.lax.ragged_dot``,
which XLA's TPU backend lowers to a Mosaic grouped-matmul kernel of
its own (``ragged-dot-*`` custom calls).  Every way the device trace
shows Mosaic kernels with a 2-D ``(rows, width)`` output, and one
entry serves a 256-row decode step inside a scan and a 16,384-row
prefill group.

``route_grouped`` is the DeepSeek-V3 router: sigmoid scores in float32,
a correction bias that takes part in the *selection* alone, groups of
experts scored by their two best of which the best few are kept, top-k
among those, and the chosen experts' own sigmoid scores divided by
their sum and scaled.  The group step sorts nothing (``kept_groups``):
a group's best is a maximum, its second the maximum with the first's
one position taken out, and a group is kept when fewer than
``topk_group`` groups beat it (a larger score, or an equal one at a
lower index — ``lax.top_k``'s rule, so the same mask); where every
group is kept (``n_group == topk_group``, dots3's and Xing4's one
group) no group step is traced at all.  The one ``top_k`` left is the
last selection.

``route_zero`` is the LongCat-Flash router: softmax in float32 over the
real experts and, after them, the identity ("zero-computation")
experts; a correction bias that takes part in the *selection* alone;
top-k of the whole width; the chosen probabilities times a constant,
not renormalised.  A pick past the real experts is an identity expert:
``identity_experts`` adds ``gate * h`` for it, with no matrices and no
row in any grouped matmul.

``expert_ffn_held`` is one replica's share of an expert-parallel layer:
the router scores every expert, this replica holds ``w_gate.shape[0]``
of them from ``offset`` on, and the result is the part those experts
give.  An assignment to an absent expert costs no expert FLOPs and no
weight bytes, and what it would have added is left out; nothing stands
in for the absent replicas or for their exchange.

``EXPERTS_SCOPE`` names the expert layer's operations in the HLO
metadata (``jax.named_scope``), whatever the compiler calls them.
"""

from __future__ import annotations

import functools
import math

EXPERTS_SCOPE = "moe_experts"
ROUTER_SCOPE = "moe_router"


def router_logits(h, w_router):
    """``h`` ``(T, d)`` x ``w_router`` ``(d, E)`` in float32 at
    ``HIGHEST``: the routing logits ``(T, E)``."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope(ROUTER_SCOPE):
        return jnp.dot(
            h.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )


def route(h, w_router, top_k: int, *, logits=None, norm: bool = False):
    """``h`` ``(T, d)``, ``w_router`` ``(d, E)`` -> ``(gates (T, k) f32,
    experts (T, k) int32)``: the top-k softmax probabilities as they
    are, and whose they are.  ``logits`` ``(T, E)``: the router's
    outputs where they were computed already (:func:`router_logits` of
    other rows than the experts act on; ``h`` and ``w_router`` are then
    not read).  ``norm``: the chosen probabilities divided by their sum
    — the same numbers as a softmax over the chosen logits."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope(ROUTER_SCOPE):
        if logits is None:
            logits = jnp.dot(
                h.astype(jnp.float32), w_router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, top_k)
        if norm:
            gates = gates / gates.sum(axis=-1, keepdims=True)
    return gates, experts.astype(jnp.int32)


def activation(act: str):
    """The experts' gate activation by name."""
    import jax

    try:
        return {"silu": jax.nn.silu, "relu": jax.nn.relu}[act]
    except KeyError:
        raise ValueError(f"expert activation {act!r}: 'silu' or 'relu'") from None


# ---------------------------------------------------------------------------
# the grouped SwiGLU: one entry, the implementation chosen by shape
# ---------------------------------------------------------------------------

# Mean rows a group (rows / groups) under which a call takes the
# streaming kernel below; at and over it the tiled one (and
# ``ragged_dot`` wherever neither kernel is taken).  Under the
# chip's ridge (~240 FLOP/B: 240 rows an expert) a call is bound by
# streaming the hit experts' matrices, and there XLA's ``ragged_dot``
# reads 23-38 % of that stream at 8 and more rows a group (59-70 % at a
# decode step's 4).  A layer's three matmuls on the v5e, ``ragged_dot``
# / this kernel in ms, at OLMoE's 64 x (2048, 1024) by rows: 256 1.66 /
# 1.13 (59 -> 87 % of the stream), 2,048 2.72 / 1.17, 4,096 2.89 / 1.34,
# 8,192 3.25 / 1.87, 16,384 4.33 / 2.88; at GigaChat's 8 held x (7168,
# 2048) by a pass's rows (a quarter of them real): 128 0.94 / 0.79 (69
# -> 82 %), 512 2.00 / 0.96, 1,024 2.00 / 1.14, 2,048 2.32 / 1.30,
# 4,096 3.24 / 2.41, 8,192 3.88 / 3.53 (my chip runs, PR 31,
# tools/probe_moe.py).  The line is the ridge, not the last row count
# the kernel won at: past it the call is a compute-bound matmul, which
# this kernel's resident float32 rows and segments were not built for
# and the tiled kernel is (PR 46: at Xing4's 64 x (3584, 1024), 16,384
# rows, ``ragged_dot`` 7.70 ms / this kernel 4.75 / the tiled one 3.85).
STREAM_MAX_MEAN_ROWS = 256
# Bytes of one streamed weight block (whole K, as wide an N as fits):
# two matrices (gate and up), double-buffered, are four of these in
# VMEM.  A decode step's share of the stream by block bytes (same runs):
# OLMoE 1 MiB (2048 x 256) 82 %, 2 MiB 87, 4 MiB (whole N) 88; GigaChat
# (7168 x 128) 79, (x 256) 75, (x 512, 7 MiB) 82, (x 1024) 81.
STREAM_BLOCK_BYTES = 8 << 20
# Bytes of sorted rows one kernel call keeps resident (in float32, and
# the pipeline allots them twice): a call of more rows is cut into
# segments of :func:`stream_segment_rows`, each its own pair of kernel
# calls over the groups its rows belong to — the rows are sorted by
# group, so only a group that straddles a cut is streamed twice.
STREAM_SEGMENT_BYTES = 16 << 20
# Rows one matmul of the tiled kernel takes (the lane at and over the
# ridge, :func:`tiled_matmul`): a group's rows are computed this many at
# a time from the 16-row tile its first row lies in, so a group wastes
# under a tile.  A layer's three matmuls on the v5e by this tile, 128 /
# 256 / 512 rows, in ms (my chip runs, PR 46, tools/probe_moe.py; the
# probe's own pass over the rows, ~0.5 ms at the first shape, is in
# every number): Xing4's 64 x (3584, 1024) at 16,384 rows 3.85 / 4.21 /
# 5.74 (``ragged_dot`` 7.70) and with 12,288 of them real 3.46 / 3.60
# (7.05); OLMoE's 64 x (2048, 1024) at 16,384 rows 2.08 / 2.37 (4.33),
# at 32,768 3.87 / 4.17 (6.78); SmallThinker's 16 x (2560, 768) at
# 13,824 rows 1.07 / 1.14 / 1.33 (1.81); GigaChat's 8 x (7168, 2048) at
# 2,048 rows, a quarter real, 1.11 / 1.23 (2.32).  64 rows read 128's
# 3.84 at the first shape: with the compute taken out the two calls
# still take 3.15 ms (they move 1.83 GB of weights, rows and results),
# with the weights' stream taken out 3.53 — the lane sits within a
# fifth of either, and what a smaller tile saves in rows the stream
# does not give back.
TILED_ROW_TILE = 128
# Bytes of one block of sorted rows in, or of their results out, in the
# tiled kernel's pipeline (each buffered twice, beside four streamed
# weight blocks of :data:`STREAM_BLOCK_BYTES`): 1,024 rows at every
# width the cells have.  A group that straddles a block's edge costs a
# tile more: Xing4's 16,384 rows in blocks of 512 / 1,024 / 2,048 rows
# 4.02 / 3.85 / 3.84 ms, OLMoE's 2.21 / 2.08 (same runs).
TILED_ROWS_BYTES = 16 << 20


def matmul_backend() -> str:
    """Where a grouped matmul traced now will run: ``"tpu"``, or the
    process's default backend.  The tests of the streaming kernel answer
    ``"interpret"`` here (the Pallas interpreter)."""
    import jax

    return jax.default_backend()


def stream_block(k: int, n: int, itemsize: int = 2,
                 block_bytes: int = STREAM_BLOCK_BYTES) -> int:
    """The width of one streamed ``(k, width)`` weight block: the widest
    multiple of 128 that divides ``n`` within ``block_bytes`` (at least
    128; ``n`` itself where it is no multiple of 128)."""
    if n % 128:
        return n
    fits = [w for w in range(128, n + 1, 128)
            if n % w == 0 and k * w * itemsize <= block_bytes]
    return max(fits, default=128)


def stream_row_tile(rows: int, groups: int) -> int:
    """Rows one matmul of the streaming kernel takes (a group's rows are
    computed this many at a time from the 8-row tile its first row lies
    in): the power of two from the mean rows a group, within 16 and
    128.  It hardly matters under the ridge (a decode step at 8 to 128:
    OLMoE 1.12-1.14 ms, GigaChat 0.79-0.83; 2,048 rows at 16 to 256:
    1.16-1.22 ms): a matmul of few rows costs what loading its weights
    into the MXU costs."""
    tile = 16
    while tile < 128 and tile * groups < rows:
        tile *= 2
    return tile


def stream_segment_rows(width: int) -> int:
    """Rows of one kernel call at rows ``width`` wide: what
    :data:`STREAM_SEGMENT_BYTES` holds of them in float32, in whole
    hundreds and twenty-eights."""
    return STREAM_SEGMENT_BYTES // (4 * width) // 128 * 128


def tiled_row_block(rows: int, d: int, f: int) -> int:
    """Sorted rows the tiled SwiGLU's pipeline holds at a time, one
    count for both kernels (they share the visits): blocks of ``(rows,
    d)`` operands in and ``(rows, width)`` bfloat16 results out for gate
    and up, ``(rows, f)`` in and ``(rows, width)`` float32 out for the
    down projection, each buffered twice.  The most of 1,024, 512 and
    256 that the call has and :data:`TILED_ROWS_BYTES` hold of each; 0
    where not even 256 fit."""
    blocks = ((d, 2), (stream_block(d, f), 2), (f, 2), (stream_block(f, d), 4))
    fits = [b for b in (1024, 512, 256) if b <= max(rows, 256)
            and all(b * width * size <= TILED_ROWS_BYTES for width, size in blocks)]
    return max(fits, default=0)


def expert_matmul_impl(rows: int, groups: int, k: int, n: int, dtype,
                       backend: str) -> str:
    """``"stream"``, ``"tiled"`` or ``"ragged_dot"`` for a grouped
    SwiGLU of ``rows`` sorted rows over ``groups`` experts of ``(k, n)``
    gate and up and ``(n, k)`` down matrices: a pure function of what a
    trace can see.  Where the operands are bfloat16 and the backend is a
    TPU (or the Pallas interpreter): under :data:`STREAM_MAX_MEAN_ROWS`
    mean rows a group the streaming kernel, at and over it the tiled
    one, each where a block of whole K and its rows fit the kernel's
    VMEM at these widths; ``ragged_dot`` everywhere else."""
    import jax.numpy as jnp

    if backend not in ("tpu", "interpret") or jnp.dtype(dtype) != jnp.bfloat16:
        return "ragged_dot"
    block_fits = max(k, n) * 128 * 2 <= STREAM_BLOCK_BYTES
    if rows >= STREAM_MAX_MEAN_ROWS * groups:
        fits = block_fits and tiled_row_block(rows, k, n) > 0
        return "tiled" if fits else "ragged_dot"
    fits = block_fits and stream_segment_rows(max(k, n)) >= 256
    return "stream" if fits else "ragged_dot"


def _stream_kernel(ids_ref, starts_ref, sizes_ref, hit_ref, x_ref, *refs,
                   rows, row_tile, gated, act="silu"):
    """One grid step ``(j, v)``: the ``v``-th *hit* group's rows through
    the ``j``-th ``(K, width)`` block of its matrix (or of its gate and
    up matrices, with ``act(gate) * up`` applied here).

    The sorted rows rest whole in VMEM in float32 (a dynamic row slice
    must start on a tile of 8, which bfloat16's packed 16 would coarsen)
    and are cast to the matrices' type a tile at a time.  A group's rows
    are computed ``row_tile`` at a time from the 8-row tile its first
    row lies in; what a tile holds of *earlier* groups is kept, what it
    holds of *later* ones is overwritten when their turn comes (groups
    are visited in ascending order), so rows past the last group are
    left with whatever was computed there.  ``ids`` lists the hit groups
    first and then repeats the last: the pipeline fetches a block only
    when its index changes, so a group with no rows fetches nothing."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    n_w = 2 if gated else 1
    w_refs, out_ref, acc_ref = refs[:n_w], refs[n_w], refs[n_w + 1]
    v = pl.program_id(1)
    group = ids_ref[v]
    start = starts_ref[group]
    end = start + sizes_ref[group]

    @pl.when(v == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(v < hit_ref[0])
    def _():
        base = (start // 8) * 8

        def tile(c, carry):
            r0 = pl.multiple_of(base + c * row_tile, 8)
            x = x_ref[pl.ds(r0, row_tile), :].astype(w_refs[0].dtype)
            y = jnp.dot(x, w_refs[0][0], preferred_element_type=jnp.float32)
            if gated:
                up = jnp.dot(x, w_refs[1][0], preferred_element_type=jnp.float32)
                y = activation(act)(y) * up
            row = r0 + jax.lax.broadcasted_iota(jnp.int32, (row_tile, 1), 0)
            acc_ref[pl.ds(r0, row_tile), :] = jnp.where(
                row >= start, y, acc_ref[pl.ds(r0, row_tile), :])
            return carry

        jax.lax.fori_loop(0, pl.cdiv(end - base, row_tile), tile, 0)

    @pl.when(v == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc_ref[pl.ds(0, rows), :].astype(out_ref.dtype)


def stream_matmul(x, matrices, sizes, *, interpret: bool, row_tile: int = 16,
                  block_bytes: int = STREAM_BLOCK_BYTES, act: str = "silu"):
    """``x`` ``(R, K)`` sorted by group, ``sizes`` ``(G,)`` -> ``(R, N)``
    float32: ``x[g's rows] @ W[g]`` for one ``(G, K, N)`` matrix, or
    ``act(x @ W_gate[g]) * (x @ W_up[g])`` for two, as ONE Pallas call
    (``moe_stream_down`` / ``moe_stream_gate_up``) that streams each hit
    group's matrices once in ``(K, width)`` blocks
    (:func:`stream_block`), double-buffered by the pipeline, with the
    rows and the accumulator resident.  Operands in the matrices' type,
    float32 accumulation.  The output is 2-D ``(R, N)``: what the
    benchmark's readers know a grouped matmul by."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, k = x.shape
    groups, _, n = matrices[0].shape
    gated = len(matrices) == 2
    width = stream_block(k, n, jnp.dtype(matrices[0].dtype).itemsize, block_bytes)
    sizes = sizes.astype(jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    seen = jnp.cumsum((sizes > 0).astype(jnp.int32))    # hit groups up to g
    hit = seen[-1]
    # the v-th hit group is the first whose count passes v; past the
    # last hit group the list repeats it
    v = jnp.minimum(jnp.arange(groups), hit - 1)
    ids = jnp.minimum((seen[None, :] <= v[:, None]).sum(axis=-1),
                      groups - 1).astype(jnp.int32)
    padded = -(-rows // 8) * 8 + row_tile
    x = jnp.concatenate(
        [x.astype(jnp.float32), jnp.zeros((padded - rows, k), jnp.float32)])
    w_spec = pl.BlockSpec((1, k, width), lambda j, v, ids, *_: (ids[v], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // width, groups),
        in_specs=[pl.BlockSpec((padded, k), lambda j, v, *_: (0, 0))]
        + [w_spec] * len(matrices),
        out_specs=pl.BlockSpec((rows, width), lambda j, v, *_: (0, j)),
        scratch_shapes=[pltpu.VMEM((padded, width), jnp.float32)],
    )
    # resident rows and the streamed blocks twice (the pipeline's two
    # buffers), the output block twice, the accumulator
    vmem = (2 * padded * k * 4 + 2 * len(matrices) * k * width * 2
            + 2 * rows * width * 4 + padded * width * 4)
    return pl.pallas_call(
        functools.partial(_stream_kernel, rows=rows, row_tile=row_tile,
                          gated=gated, **({} if act == "silu" else {"act": act})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(vmem + (16 << 20), 120 << 20)),
        interpret=interpret,
        name="moe_stream_gate_up" if gated else "moe_stream_down",
    )(ids, starts, sizes, hit.reshape(1), x, *matrices)


def stream_swiglu(rows, w_gate, w_up, w_down, sizes, *, interpret: bool,
                  act: str = "silu"):
    """The grouped SwiGLU as two streaming kernels a segment of
    :func:`stream_segment_rows` rows: ``act(gate) * up`` in the first,
    the down projection of what it leaves in the second.  A segment is
    handed the part of each group's rows that lies in it; one with no
    row of any group (a held pass's rows past its groups) runs nothing
    and comes back as zeros.  Jitted: a program traces and lowers it
    once for all its layers."""
    return _swiglu_jit(_stream_swiglu)(rows, w_gate, w_up, w_down, sizes,
                                       interpret=interpret, act=act)


@functools.lru_cache(maxsize=None)
def _swiglu_jit(swiglu):
    """A kernel lane's SwiGLU, jitted once a process."""
    import jax

    return jax.jit(swiglu, static_argnames=("interpret", "act"))


def _stream_swiglu(rows, w_gate, w_up, w_down, sizes, *, interpret: bool,
                   act: str = "silu"):
    import jax
    import jax.numpy as jnp

    total, d = rows.shape
    groups, _, f = w_gate.shape
    kw = dict(interpret=interpret, row_tile=stream_row_tile(total, groups))

    def run(x, part):
        hidden = stream_matmul(x, (w_gate, w_up), part, act=act, **kw)
        return stream_matmul(hidden, (w_down,), part, **kw)

    seg = stream_segment_rows(max(d, f))
    if total <= seg:
        return run(rows, sizes)
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    starts = ends - sizes
    outs = []
    for lo in range(0, total, seg):
        hi = min(lo + seg, total)
        part = jnp.clip(ends, lo, hi) - jnp.clip(starts, lo, hi)
        outs.append(jax.lax.cond(
            part.sum() > 0, run,
            lambda x, _part: jnp.zeros((x.shape[0], d), jnp.float32),
            rows[lo:hi], part))
    return jnp.concatenate(outs)


def tiled_visits(sizes, rows: int, row_block: int):
    """The tiled kernel's grid, from the groups' sizes: a *visit* is a
    (group, row block) pair that share rows, in ascending order of both
    — a group that lies in one block of ``row_block`` sorted rows is one
    visit, one that straddles a block's edge one a block.  ``(groups
    (V,), blocks (V,), starts (G,), ends (G,), visits (1,))`` int32 with
    ``V = blocks + G - 1``, the most there can be; past the last real
    visit the lists repeat it, so the pipeline fetches nothing more."""
    import jax.numpy as jnp

    sizes = sizes.astype(jnp.int32)
    groups = sizes.shape[0]
    blocks = -(-rows // row_block)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // row_block
    count = jnp.where(sizes > 0, (ends - 1) // row_block - first + 1, 0)
    upto = jnp.cumsum(count)                      # visits up to and with g
    v = jnp.minimum(jnp.arange(blocks + groups - 1), jnp.maximum(upto[-1] - 1, 0))
    group = jnp.minimum((upto[None, :] <= v[:, None]).sum(axis=-1), groups - 1)
    block = first[group] + v - (upto[group] - count[group])
    return (group.astype(jnp.int32),
            jnp.clip(block, 0, blocks - 1).astype(jnp.int32),
            starts, ends, upto[-1:])


def _tiled_kernel(groups_ref, blocks_ref, starts_ref, ends_ref, visits_ref,
                  x_ref, *refs, row_block, row_tile, gated, act="silu"):
    """One grid step ``(j, v)``: the rows that the ``v``-th visit's
    group has in its row block through the ``j``-th ``(K, width)`` block
    of the group's matrix (or of its gate and up matrices, with
    ``act(gate) * up`` applied here, in float32).

    The block's rows arrive in the matrices' type through the pipeline
    and its results leave the same way; consecutive visits to one block
    find both where they were, and consecutive visits of one group find
    its matrices.  The group's rows are computed ``row_tile`` at a time
    from the 16-row tile its first row in the block lies in (a packed
    bfloat16 tile), the last tile pulled back inside the block; what a
    tile holds of *earlier* groups is kept, what it holds of *later*
    ones is overwritten when their turn comes, so rows past the last
    group are left with whatever was computed there, and a block no
    group reaches is never written."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    n_w = 2 if gated else 1
    w_refs, out_ref = refs[:n_w], refs[n_w]
    v = pl.program_id(1)

    @pl.when(v < visits_ref[0])
    def _():
        group = groups_ref[v]
        first = blocks_ref[v] * row_block
        lo = jnp.maximum(starts_ref[group] - first, 0)
        hi = jnp.minimum(ends_ref[group] - first, row_block)
        base = (lo // 16) * 16

        def tile(c, carry):
            r0 = pl.multiple_of(
                jnp.minimum(base + c * row_tile, row_block - row_tile), 16)
            x = x_ref[pl.ds(r0, row_tile), :]
            y = jnp.dot(x, w_refs[0][0], preferred_element_type=jnp.float32)
            if gated:
                up = jnp.dot(x, w_refs[1][0], preferred_element_type=jnp.float32)
                y = activation(act)(y) * up
            row = r0 + jax.lax.broadcasted_iota(jnp.int32, (row_tile, 1), 0)
            out_ref[pl.ds(r0, row_tile), :] = jnp.where(
                row >= lo, y.astype(out_ref.dtype), out_ref[pl.ds(r0, row_tile), :])
            return carry

        jax.lax.fori_loop(0, pl.cdiv(hi - base, row_tile), tile, 0)


def tiled_matmul(x, matrices, visits, *, interpret: bool, row_block: int,
                 row_tile: int, out_dtype=None,
                 block_bytes: int = STREAM_BLOCK_BYTES, act: str = "silu"):
    """``x`` ``(R, K)`` sorted by group, ``visits`` = :func:`tiled_visits`
    of the groups' sizes at ``row_block`` -> ``(R, N)`` in ``out_dtype``
    (float32 by default): ``x[g's rows] @ W[g]`` for one ``(G, K, N)``
    matrix, or ``act(x @ W_gate[g]) * (x @ W_up[g])`` for two, as ONE
    Pallas call at any row count (``moe_tiled_down`` /
    ``moe_tiled_gate_up``): a grid over the visits, rows and results in
    blocks of ``row_block`` through the pipeline, each hit group's
    matrices streamed once in ``(K, width)`` blocks
    (:func:`stream_block`) behind the group before it.  Operands in the
    matrices' type, float32 accumulation; rows past the last group are
    undefined.  The output is 2-D ``(R, N)``: what the benchmark's
    readers know a grouped matmul by."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, k = x.shape
    n = matrices[0].shape[-1]
    gated = len(matrices) == 2
    out_dtype = jnp.dtype(jnp.float32 if out_dtype is None else out_dtype)
    width = stream_block(k, n, jnp.dtype(matrices[0].dtype).itemsize, block_bytes)
    row_tile = min(row_tile, row_block)
    w_spec = pl.BlockSpec((1, k, width), lambda j, v, g, *_: (g[v], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n // width, visits[0].shape[0]),
        in_specs=[pl.BlockSpec((row_block, k), lambda j, v, g, b, *_: (b[v], 0))]
        + [w_spec] * len(matrices),
        out_specs=pl.BlockSpec((row_block, width), lambda j, v, g, b, *_: (b[v], j)),
    )
    # the rows', the matrices' and the results' blocks twice (the
    # pipeline's two buffers) and a tile's float32 products
    vmem = (2 * row_block * k * 2 + 2 * len(matrices) * k * width * 2
            + 2 * row_block * width * out_dtype.itemsize
            + (len(matrices) + 1) * row_tile * width * 4)
    return pl.pallas_call(
        functools.partial(_tiled_kernel, row_block=row_block, row_tile=row_tile,
                          gated=gated, **({} if act == "silu" else {"act": act})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(vmem + (16 << 20), 120 << 20)),
        interpret=interpret,
        name="moe_tiled_gate_up" if gated else "moe_tiled_down",
    )(*visits, x.astype(matrices[0].dtype), *matrices)


def tiled_swiglu(rows, w_gate, w_up, w_down, sizes, *, interpret: bool,
                 act: str = "silu"):
    """The grouped SwiGLU as two tiled kernels over all the rows:
    ``act(gate) * up`` in the first, which leaves it in the matrices'
    type (what the down projection's operand is rounded to on every
    lane), the down projection in the second.  Jitted: a program traces
    and lowers it once for all its layers."""
    return _swiglu_jit(_tiled_swiglu)(rows, w_gate, w_up, w_down, sizes,
                                      interpret=interpret, act=act)


def _tiled_swiglu(rows, w_gate, w_up, w_down, sizes, *, interpret: bool,
                  act: str = "silu"):
    total, d = rows.shape
    row_block = tiled_row_block(total, d, w_gate.shape[-1])
    kw = dict(interpret=interpret, row_block=row_block, row_tile=TILED_ROW_TILE)
    visits = tiled_visits(sizes, total, row_block)
    hidden = tiled_matmul(rows, (w_gate, w_up), visits, act=act,
                          out_dtype=w_down.dtype, **kw)
    return tiled_matmul(hidden, (w_down,), visits, **kw)


def ragged_swiglu(rows, w_gate, w_up, w_down, sizes, inner, act: str = "silu"):
    """The grouped SwiGLU as three ``jax.lax.ragged_dot``s; gate and up
    leave theirs in ``inner``."""
    import jax
    import jax.numpy as jnp

    gate = jax.lax.ragged_dot(rows, w_gate, sizes, preferred_element_type=inner)
    up = jax.lax.ragged_dot(rows, w_up, sizes, preferred_element_type=inner)
    hidden = (activation(act)(gate.astype(jnp.float32))
              * up.astype(jnp.float32)).astype(w_down.dtype)
    # what leaves the layer stays float32 up to the residual add
    return jax.lax.ragged_dot(hidden, w_down, sizes,
                              preferred_element_type=jnp.float32)


def grouped_swiglu(rows, w_gate, w_up, w_down, sizes, *, inner=None,
                   act: str = "silu"):
    """``W_down[g] (act(r W_gate[g]) * (r W_up[g]))`` for every row
    ``r`` of ``rows`` ``(R, d)``, sorted by group with ``sizes`` ``(G,)``
    rows each (rows past the groups come back undefined): ``(R, d)``
    float32.  ``w_gate`` / ``w_up`` ``(G, d, f)``, ``w_down`` ``(G, f,
    d)``.  The implementation is :func:`expert_matmul_impl`'s answer for
    this call's static shape, type and backend; ``inner`` is the type
    gate and up leave ``ragged_dot`` in (the rows' type by default; both
    kernels keep them in float32 until ``act(gate) * up`` is taken)."""
    import jax

    d, f = w_gate.shape[1:]
    backend = matmul_backend()
    impl = expert_matmul_impl(rows.shape[0], w_gate.shape[0], d, f,
                              rows.dtype, backend)
    with jax.named_scope(EXPERTS_SCOPE):
        if impl == "stream":
            return stream_swiglu(rows, w_gate, w_up, w_down, sizes,
                                  interpret=backend != "tpu", act=act)
        if impl == "tiled":
            return tiled_swiglu(rows, w_gate, w_up, w_down, sizes,
                                 interpret=backend != "tpu", act=act)
        return ragged_swiglu(rows, w_gate, w_up, w_down, sizes,
                             rows.dtype if inner is None else inner, act)


def expert_ffn(h, w_gate, w_up, w_down, gates, experts, *, act: str = "silu"):
    """``sum_k gates[t, k] * W_down[e] (act(h W_gate[e]) * (h W_up[e]))``
    with ``e = experts[t, k]``: ``h`` ``(T, d)``, ``w_gate``/``w_up``
    ``(E, d, f)``, ``w_down`` ``(E, f, d)`` -> ``(T, d)`` float32."""
    import jax.numpy as jnp

    tokens, top_k = experts.shape
    num_experts = w_gate.shape[0]
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)       # assignments, by expert
    sizes = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1)
    rows = h.astype(w_gate.dtype)[order // top_k]           # (T*k, d)
    inner = jnp.float32 if h.dtype == jnp.float32 else w_gate.dtype
    out = grouped_swiglu(rows, w_gate, w_up, w_down, sizes, inner=inner,
                         act=act)
    # back to (token, k) order, then the gated sum over a token's k
    out = out[jnp.argsort(order)].reshape(tokens, top_k, -1)
    return jnp.einsum("tkd,tk->td", out, gates)


def route_grouped(h, w_router, bias, top_k: int, n_group: int,
                  topk_group: int, norm: bool, scale: float):
    """``h`` ``(T, d)``, ``w_router`` ``(d, E)``, ``bias`` ``(E,)`` ->
    ``(gates (T, k) f32, experts (T, k) int32)``, as HF
    ``modeling_deepseek_v3.py`` routes: ``s = sigmoid(h W)``; selection
    scores ``s + bias``; a group's score is the sum of its two largest;
    the experts of all but the ``topk_group`` best groups are set to 0
    (not -inf) and the ``top_k`` largest chosen; the gates are ``s``
    (never ``s + bias``) of the chosen, over their sum (+ 1e-20) when
    ``norm``, times ``scale``.  Ties go to the lower index.

    The group step sorts nothing (:func:`kept_groups`), and where every
    group is kept (``topk_group >= n_group``, a fact of the spec) it is
    not traced at all: no group's experts would be set to 0."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope(ROUTER_SCOPE):
        logits = jnp.dot(
            h.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        scores = jax.nn.sigmoid(logits)                       # (T, E)
        choice = scores + bias.astype(jnp.float32)
        if topk_group < n_group:
            tokens, num_experts = choice.shape
            grouped = choice.reshape(tokens, n_group, num_experts // n_group)
            keep = kept_groups(grouped, topk_group)
            choice = jnp.where(keep[:, :, None], grouped, 0.0).reshape(
                tokens, num_experts)
        _, experts = jax.lax.top_k(choice, top_k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
        if norm:
            gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
        gates = gates * scale
    return gates, experts.astype(jnp.int32)


def kept_groups(grouped, topk_group: int):
    """``grouped`` ``(T, G, S)`` float32 -> ``bool (T, G)``: the
    ``topk_group`` groups whose two largest add up to most, ties to the
    lower index — the mask ``lax.top_k`` of ``lax.top_k(grouped, 2)``'s
    sums gives, by maxima and a count.  A group's second largest is the
    maximum with the one position ``argmax`` names taken out (a group
    whose two largest are equal scores twice the first); a group is kept
    when fewer than ``topk_group`` groups beat it, and one beats another
    with a larger score, or an equal one at a lower index."""
    import jax.numpy as jnp

    n_group, size = grouped.shape[1:]
    first = grouped.max(axis=-1)                              # (T, G)
    at = jnp.argmax(grouped, axis=-1)
    rest = jnp.where(jnp.arange(size) == at[..., None], -jnp.inf, grouped)
    score = first + rest.max(axis=-1)
    other, own = score[:, None, :], score[:, :, None]         # g' last, g before
    index = jnp.arange(n_group)
    beats = (other > own) | ((other == own) & (index[None, :] < index[:, None]))
    return beats.sum(axis=-1) < topk_group


def route_zero(h, w_router, bias, top_k: int, scale: float):
    """``h`` ``(T, d)``, ``w_router`` ``(d, E + Z)``, ``bias`` ``(E +
    Z,)`` -> ``(gates (T, k) f32, experts (T, k) int32)``, as HF
    ``modeling_longcat_flash.py`` routes: ``p = softmax(h W)`` over the
    real and the identity experts together; the ``top_k`` largest of ``p
    + bias`` chosen; the gates are ``p`` (never ``p + bias``) of the
    chosen times ``scale``, not renormalised.  Ties go to the lower
    index."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope(ROUTER_SCOPE):
        logits = jnp.dot(
            h.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        probs = jax.nn.softmax(logits, axis=-1)
        _, experts = jax.lax.top_k(probs + bias.astype(jnp.float32), top_k)
        gates = jnp.take_along_axis(probs, experts, axis=-1) * scale
    return gates, experts.astype(jnp.int32)


def identity_experts(h, gates, experts, num_experts: int):
    """The identity experts' part of a token's sum: ``(sum of the gates
    of its picks >= num_experts) * h`` — ``h`` ``(T, d)`` float32 -> ``(T,
    d)`` float32.  No matrices: a token's own chip computes it."""
    import jax.numpy as jnp

    gate = jnp.where(experts >= num_experts, gates, 0.0).sum(axis=-1)
    return gate[:, None] * h.astype(jnp.float32)


def real_pick_histogram(experts, num_experts: int, mask=None):
    """Tokens by how many of their picks are real experts (``<
    num_experts``): ``int32[k + 1]`` over the rows ``mask`` keeps."""
    import jax
    import jax.numpy as jnp

    real = (experts < num_experts).sum(axis=-1)                    # (T,)
    hit = jax.nn.one_hot(real, experts.shape[-1] + 1, dtype=jnp.int32)
    if mask is not None:
        hit = hit * mask.astype(jnp.int32)[:, None]
    return hit.sum(axis=0)


# A pass of ``expert_ffn_held`` under the ridge holds this many times the
# rows an even router would send here: routing is not even (PERF.md
# section 6, PR 30: a share's load swings 2.5 to 3.4 % about 3.125), and
# a second pass streams the held experts' matrices again, where empty
# rows cost only their MXU time.
HELD_ROWS_HEADROOM = 4
# ... and never fewer than this: a pass's sort slice, gathers and three
# kernel launches are not worth a smaller one.
HELD_ROWS_MIN = 64
# Over the ridge (a pass of :data:`STREAM_MAX_MEAN_ROWS` rows an expert
# and more) it is the other way round: a row, real or empty, costs the
# gather that brings it in, and a real one its FLOPs and the float32
# ``d_model`` the down projection writes once (the readings below are
# older: then ``ragged_dot`` spent FLOPs on empty rows too, and a mask
# and a zero row copied ``(rows, d_model)`` in float32 twice more), and
# a second pass costs only its rows.  So a pass holds this many times
# the even share, in whole 512s.  The whole held pass on the v5e, ms a
# layer, sized at that power of two / 2 x / 1.5 x / 1.25 x the even share
# / two passes of 0.75 x (my chip runs, PR 42, tools/probe_moe.py
# --held; near-even seeded routing): SmallThinker's 16 x (2560, 768) at
# 2,048 tokens 3.05 / 1.89 / 1.63 / 1.62 / 1.44, 4,096 5.86 / 4.32 / 2.64
# / 2.59 / 3.55, 8,192 11.25 / 8.15 / 7.70 / 7.46 / 6.49; GigaChat's 8 x
# (7168, 2048) at 6,144 14.75 / 9.03 / 9.15 / 8.88 / 12.44, 8,192 17.95 /
# 16.92 / 11.21 / 11.17 / 16.15.  1.25 buys under 3 % over 1.5 and leaves
# a share that runs a fifth over even (a cell's busiest expert is 1.3-1.4
# times its mean) a second pass; two passes cost from a sixth less to a
# third more than one of twice the rows, never a pass's weights again.
HELD_ROWS_RIDGE_HEADROOM = 1.5


def held_rows_cap(tokens: int, top_k: int, held: int, num_experts: int) -> int:
    """Rows one pass of :func:`expert_ffn_held` computes, from the share
    of the ``tokens x top_k`` assignments that ``held`` of
    ``num_experts`` experts get when routing is even.  More local
    assignments than a pass holds are computed in further passes, none
    dropped.

    Under the ridge — where :data:`HELD_ROWS_HEADROOM` times that
    share, rounded up to a power of two, still leaves an expert fewer
    than :data:`STREAM_MAX_MEAN_ROWS` rows (a decode chunk, a small
    prefill) — that power of two.  At and over it,
    :data:`HELD_ROWS_RIDGE_HEADROOM` times the share in whole 512s,
    never over every assignment and never under the ridge's own
    ``STREAM_MAX_MEAN_ROWS x held``: what the power of two is at the
    line, so the two halves meet there."""
    even = tokens * top_k * held / num_experts
    cap = HELD_ROWS_MIN
    while cap < HELD_ROWS_HEADROOM * even:
        cap *= 2
    ridge = STREAM_MAX_MEAN_ROWS * held
    if cap < ridge:
        return cap

    def tiles(rows):
        return math.ceil(rows / 512) * 512

    return max(ridge, min(tiles(HELD_ROWS_RIDGE_HEADROOM * even),
                          tiles(tokens * top_k)))


def held_pass_account(local, cap: int):
    """What a program's held passes did, from its routing histogram:
    ``local`` holds a routed layer's local assignments each (any
    integers a layer), ``cap`` the program's :func:`held_rows_cap`.
    ``(rows computed, local assignments, passes beyond a layer's
    first)``: a layer runs ``ceil(local / cap)`` passes of ``cap`` rows
    (:func:`expert_ffn_held`'s loop), none where nothing is local."""
    local = [int(n) for n in local]
    passes = [-(-n // cap) for n in local]
    return (cap * sum(passes), sum(local),
            sum(max(0, p - 1) for p in passes))


def layer_expert_matmul(tokens: int, top_k: int, held: int, num_experts: int,
                        d_model: int, width: int, dtype, *, held_pass: bool,
                        backend=None) -> str:
    """What :func:`grouped_swiglu` runs in an expert layer over
    ``tokens`` tokens: :func:`expert_matmul_impl` at the rows the layer
    hands it — every assignment (:func:`expert_ffn`), or one pass's
    :func:`held_rows_cap` (:func:`expert_ffn_held`, ``held_pass``).  An
    engine's ``lane_report()`` says it of each of its programs."""
    rows = (held_rows_cap(tokens, top_k, held, num_experts) if held_pass
            else tokens * top_k)
    return expert_matmul_impl(
        rows, held, d_model, width, dtype,
        matmul_backend() if backend is None else backend)


def expert_ffn_held(h, w_gate, w_up, w_down, gates, experts, offset: int,
                    num_experts: int, *, act: str = "silu"):
    """The held experts' part of :func:`expert_ffn`'s sum: ``w_gate`` /
    ``w_up`` ``(H, d, f)`` and ``w_down`` ``(H, f, d)`` are experts
    ``offset .. offset + H`` of the ``num_experts`` that ``experts``
    ``(T, k)`` names (every output the router scores, identity experts
    included: they are what an even share is a share of).

    The assignments are sorted local-first by expert and computed
    :func:`held_rows_cap` rows a pass (:func:`grouped_swiglu` over the
    rows' group sizes, as ``expert_ffn``), in a ``while_loop`` that runs as
    many passes as the local assignments need: one, unless routing
    piles onto this share.  A pass's rows go back to their tokens by
    ``top_k`` gathers of ``(T, d)`` (a token's j-th assignment reads its
    row; where it is absent or another pass's, the gathered row is
    selected to zero before the gate multiplies it) — no scatter, which
    serialises on a TPU, and no copy of the pass's ``(rows, d)`` result
    between the kernel and the gathers."""
    import jax
    import jax.numpy as jnp

    tokens, top_k = experts.shape
    held = w_gate.shape[0]
    d_model = h.shape[-1]
    cap = held_rows_cap(tokens, top_k, held, num_experts)
    local = experts - offset
    is_local = (local >= 0) & (local < held)
    key = jnp.where(is_local, local, held).reshape(-1)      # absent sort last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rank = jnp.argsort(order).astype(jnp.int32).reshape(tokens, top_k)
    n_local = is_local.sum().astype(jnp.int32)
    pad = jnp.full((cap,), tokens * top_k, jnp.int32)
    order = jnp.concatenate([order, pad])
    key_sorted = jnp.concatenate(
        [key, jnp.full((1,), held, key.dtype)])[order]
    gates = jnp.where(is_local, gates, 0.0)
    rows_in = h.astype(w_gate.dtype)
    inner = jnp.float32 if h.dtype == jnp.float32 else w_gate.dtype

    def one_pass(state):
        base, y = state
        idx = jax.lax.dynamic_slice(order, (base,), (cap,))
        which = jax.lax.dynamic_slice(key_sorted, (base,), (cap,))
        sizes = (which[:, None] == jnp.arange(held)[None, :]).sum(
            axis=0).astype(jnp.int32)
        rows = rows_in[jnp.minimum(idx // top_k, tokens - 1)]       # (cap, d)
        out = grouped_swiglu(rows, w_gate, w_up, w_down, sizes, inner=inner,
                             act=act)
        # rows past the groups hold whatever the kernel left there (NaN
        # for all the pass knows): no valid index points at one, and an
        # absent assignment reads row 0 (real in any pass that runs) and
        # is selected to zero — never multiplied to it
        at = rank - base
        valid = is_local & (at >= 0) & (at < cap)
        at = jnp.where(valid, at, 0)
        for j in range(top_k):
            y = y + jnp.where(valid[:, j, None], out[at[:, j]],
                              0.0) * gates[:, j, None]
        return base + cap, y

    _, y = jax.lax.while_loop(
        lambda state: state[0] < n_local, one_pass,
        (jnp.int32(0), jnp.zeros((tokens, d_model), jnp.float32)))
    return y


def swiglu(h, w_gate, w_up, w_down):
    """``W_down (silu(h W_gate) * (h W_up))``: a dense SwiGLU FFN or a
    shared expert; ``h`` ``(T, d)`` in the matmuls' type, f32 out."""
    import jax
    import jax.numpy as jnp

    inner = jnp.float32 if h.dtype == jnp.float32 else w_gate.dtype
    gate = jnp.dot(h, w_gate, preferred_element_type=inner)
    up = jnp.dot(h, w_up, preferred_element_type=inner)
    act = (jax.nn.silu(gate.astype(jnp.float32))
           * up.astype(jnp.float32)).astype(w_down.dtype)
    return jnp.dot(act, w_down, preferred_element_type=jnp.float32)


def expert_histogram(experts, num_experts: int, mask=None):
    """Assignments per expert, ``int32[E]``, over the rows ``mask``
    (``(T,)`` bool) keeps: what the engine's routing counters add up."""
    import jax
    import jax.numpy as jnp

    hit = jax.nn.one_hot(experts, num_experts, dtype=jnp.int32)   # (T, k, E)
    if mask is not None:
        hit = hit * mask.astype(jnp.int32)[:, None, None]
    return hit.sum(axis=(0, 1))
