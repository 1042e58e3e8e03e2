"""Pallas TPU kernels for serving hot ops.

Two ops dominate image/tabular serving outside the model matmuls:

* ``fused_normalize`` — uint8 NHWC batch -> normalised activation dtype
  in one VMEM pass (cast + per-channel affine fused; otherwise XLA
  runs a convert + broadcast-multiply + add chain over HBM before the
  first conv).
* ``int8_matmul`` — weight-quantised dense layer: int8 weights dequant
  *inside* the matmul tile (per-output-channel scales), halving weight
  HBM footprint and bandwidth.  ``Int8Dense`` wraps it as a flax module
  and ``quantize_weights`` converts trained f32/bf16 kernels.

On a TPU the kernels compile to Mosaic; on any other backend they run in
the Pallas interpreter (:func:`interpret_mode`), which checks the
arithmetic on the virtual CPU mesh and says nothing about lowering or
speed.  Serving components report the mode in ``/health/status`` and
``tools/probe_kernels.py`` is the on-chip compile check.
(reference has no counterpart — its data plane never touches the
accelerator; this is part of the TPU-first redesign.)
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

# Mosaic's default scoped-VMEM limit on a v5e core; blocks are
# double-buffered, so one grid step may hold half of it
_VMEM_LIMIT_BYTES = 16 * 1024 * 1024


def interpret_mode() -> bool:
    """Whether ``pallas_call`` runs INTERPRETED in this process — true on
    every backend but a TPU.  The one place the decision lives: every
    kernel below passes it, and ``health_status`` of the serving
    components reports it, so a client can tell a Mosaic-compiled lane
    from the interpreter."""
    import jax

    return jax.default_backend() != "tpu"


def _padded_block_bytes(shape, dtype) -> int:
    """VMEM bytes of one block under the TPU tiling: the minor dim pads
    to 128 lanes, the second-minor to the dtype's sublane count."""
    itemsize = np.dtype(dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    dims = list(shape)
    dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) > 1:
        dims[-2] = -(-dims[-2] // sublanes) * sublanes
    return int(np.prod(dims)) * itemsize


# ---------------------------------------------------------------------------
# fused uint8 -> normalised float
# ---------------------------------------------------------------------------

def _normalize_kernel(x_ref, scale_ref, shift_ref, o_ref):
    import jax.numpy as jnp

    # Mosaic has no direct uint8->float cast; hop through int32
    x = x_ref[...].astype(jnp.int32).astype(jnp.float32)
    o_ref[...] = (x * scale_ref[...] + shift_ref[...]).astype(o_ref.dtype)


def fused_normalize(x, scale, shift, out_dtype=None):
    """(batch, H, W, C) uint8 -> out_dtype, y = x * scale + shift per channel.

    scale/shift: (C,) arrays; e.g. imagenet normalisation folded into
    a = 1/(255*std), b = -mean/std.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    out_dtype = out_dtype or jnp.bfloat16
    batch = x.shape[0]
    img_shape = x.shape[1:]
    c = img_shape[-1]
    scale = jnp.asarray(scale, jnp.float32).reshape((1,) * (len(img_shape) - 1) + (c,))
    shift = jnp.asarray(shift, jnp.float32).reshape((1,) * (len(img_shape) - 1) + (c,))
    # one image per grid step, in + out blocks double-buffered.  The
    # channel dim is the minor one and pads 3 -> 128 lanes, so a
    # 224x224x3 image needs ~64 MiB: Mosaic refuses it on the v5e
    # (RESOURCE_EXHAUSTED in vmem, tools/probe_kernels.py).  Refused
    # here on every backend, so the interpreter cannot pass a shape the
    # chip will not take.
    need = 2 * (
        _padded_block_bytes(img_shape, x.dtype)
        + _padded_block_bytes(img_shape, out_dtype)
    )
    if need > _VMEM_LIMIT_BYTES:
        raise ValueError(
            f"fused_normalize: one {tuple(img_shape)} image block needs "
            f"{need / 2**20:.0f} MiB of VMEM (its {c}-wide minor dim pads to "
            f"128 lanes), over the {_VMEM_LIMIT_BYTES / 2**20:.0f} MiB a TPU "
            "core allows — serve this shape with normalize=False (XLA fuses "
            "the cast and affine into the first conv's input)"
        )

    return pl.pallas_call(
        _normalize_kernel,
        grid=(batch,),
        in_specs=[
            pl.BlockSpec((1, *img_shape), lambda i: (i, *([0] * len(img_shape)))),
            pl.BlockSpec(scale.shape, lambda i: (0,) * scale.ndim),
            pl.BlockSpec(shift.shape, lambda i: (0,) * shift.ndim),
        ],
        out_specs=pl.BlockSpec((1, *img_shape), lambda i: (i, *([0] * len(img_shape)))),
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype),
        interpret=interpret_mode(),
    )(x, scale, shift)


def imagenet_affine(mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)) -> Tuple[np.ndarray, np.ndarray]:
    """Fold 'x/255 then standardise' into one per-channel affine."""
    mean = np.asarray(mean, np.float32)
    std = np.asarray(std, np.float32)
    return 1.0 / (255.0 * std), -mean / std


# ---------------------------------------------------------------------------
# int8-weight matmul (dequant fused into the tile)
# ---------------------------------------------------------------------------

def _int8_matmul_kernel(x_ref, w_ref, scale_ref, o_ref):
    import jax.numpy as jnp

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)  # dequant happens in-register
    acc = jnp.dot(x, w, preferred_element_type=jnp.float32)
    o_ref[...] = (acc * scale_ref[...]).astype(o_ref.dtype)


def int8_matmul(x, w_int8, scale, block_m: int = 128, block_n: int = 128, out_dtype=None):
    """y = (x @ dequant(w)) with w stored int8, per-column scales.

    x: (M, K) float; w_int8: (K, N) int8; scale: (N,) f32.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    out_dtype = out_dtype or x.dtype
    if x.ndim != 2 or w_int8.ndim != 2:
        raise ValueError(
            f"int8_matmul wants 2-D operands, got x{tuple(x.shape)} @ "
            f"w_int8{tuple(w_int8.shape)}"
        )
    m, k = x.shape
    k2, n = w_int8.shape
    if k != k2:
        raise ValueError(
            f"int8_matmul contraction mismatch: x is (M={m}, K={k}) but "
            f"w_int8 is (K={k2}, N={n}) — the inner (K) dims must agree"
        )
    scale = jnp.asarray(scale, jnp.float32)
    if tuple(scale.shape) != (n,):
        raise ValueError(
            f"int8_matmul scale must be one f32 per output column: want "
            f"shape ({n},) to match w_int8's N={n}, got {tuple(scale.shape)}"
        )
    # Ragged shapes pad up to the Mosaic register tile rather than
    # surfacing the raw Mosaic/XLA "not divisible" error: blocks are
    # rounded to the f32 (8, 128) tile (a 100-row M becomes a 104-row
    # block, a 70-col N a 128-col block), inputs zero-pad to the block
    # grid, and the pad region is sliced off the output.  K pads to the
    # 128-lane tile on every backend (zero K columns contribute exactly
    # 0.0 to the contraction), so the interpreter runs the chip's path.
    bm = min(block_m, -(-m // 8) * 8)
    bn = min(block_n, -(-n // 128) * 128)
    m_pad = (-m) % bm
    n_pad = (-n) % bn
    k_pad = (-k) % 128
    if m_pad or k_pad:
        x = jnp.pad(x, ((0, m_pad), (0, k_pad)))
    if n_pad or k_pad:
        w_int8 = jnp.pad(w_int8, ((0, k_pad), (0, n_pad)))
    if n_pad:
        scale = jnp.pad(scale, (0, n_pad))
    mp, np_ = x.shape[0], w_int8.shape[1]
    k = x.shape[1]
    scale2d = jnp.asarray(scale, jnp.float32)[None, :]

    out = pl.pallas_call(
        _int8_matmul_kernel,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        interpret=interpret_mode(),
    )(x, w_int8, scale2d)
    return out[:m, :n]


def quantize_weights(w) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-output-channel int8 quantisation of a (K, N) kernel."""
    w = np.asarray(w, np.float32)
    max_abs = np.abs(w).max(axis=0)
    scale = np.where(max_abs > 0, max_abs / 127.0, 1.0).astype(np.float32)
    w_q = np.clip(np.round(w / scale[None, :]), -127, 127).astype(np.int8)
    return w_q, scale


class Int8Dense:
    """A serving-time dense layer with int8 weights.

    Built from a trained kernel/bias; callable on device arrays.  Used
    to swap heavy projection layers of a served model for the
    quantised kernel (half the HBM, same API).
    """

    def __init__(self, kernel, bias=None):
        self.w_q, self.scale = quantize_weights(kernel)
        self.bias = None if bias is None else np.asarray(bias, np.float32)

    def __call__(self, x):
        import jax.numpy as jnp

        y = int8_matmul(x, jnp.asarray(self.w_q), jnp.asarray(self.scale))
        if self.bias is not None:
            y = y + jnp.asarray(self.bias, y.dtype)
        return y


# ---------------------------------------------------------------------------
# flash attention (single-chip blockwise online softmax)
# ---------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_k: int, n_kv: int, scale: float, valid_k: int):
    """Grid cell (batch*head, q-block, kv-block) of FULL (not causal)
    attention: the kv axis is the innermost grid dimension, so the
    online-softmax carry lives in VMEM scratch across kv steps — KV
    streams block-by-block from HBM and VMEM holds O(block_q * d +
    block_k * d), independent of sequence length (the standard TPU
    flash-attention shape).  The causal form is :func:`_causal_kernel`."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (block_q, d)
    k = k_ref[0].astype(jnp.float32)  # (block_k, d)
    v = v_ref[0].astype(jnp.float32)
    s = q @ k.T
    if valid_k % block_k:  # tail block carries sequence padding
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos >= valid_k, -jnp.inf, s)
    m = m_ref[...]
    blk_max = jnp.max(s, axis=-1)
    new_m = jnp.maximum(m, blk_max)
    safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
    p = jnp.exp(s - safe_m[:, None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    correction = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    m_ref[...] = new_m
    l_ref[...] = l_ref[...] * correction + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * correction[:, None] + p @ v

    @pl.when(j == n_kv - 1)
    def _emit():
        l = l_ref[...]
        l = jnp.where(l > 0, l, 1.0)  # fully-masked rows output zeros
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, causal: bool = False, block_q: int = 128, block_k: int = 128):
    """Blockwise attention, numerically identical to plain softmax
    attention but O(L) memory: the (L, L) score matrix never exists and
    VMEM holds only the current q/kv blocks + the carry.

    Shapes follow plain_attention: (batch, seq, heads, head_dim).  The
    per-chip counterpart of ring attention (which shards ACROSS chips;
    this streams WITHIN one chip's sequence shard).  Non-tiling lengths
    are block-padded (padded keys masked in-kernel).  Causal attention
    of a sequence over itself is :func:`causal_attention` (the kernel a
    latent block's prefill from position zero runs); cross-length
    causal, and a causal sequence whose K and V outgrow that kernel's
    share of VMEM, fall back to the einsum path.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from seldon_core_tpu.parallel.ring_attention import plain_attention

    b, sq, h, d = q.shape
    sk = k.shape[1]
    if causal:
        fits = _causal_kv_bytes(sq, d, d, q.dtype) <= _CAUSAL_KV_VMEM_BYTES
        if sq != sk or not fits:
            # cross-length causal has no absolute-position convention
            # here; the causal kernel keeps a head's K and V whole in
            # VMEM, and a sequence too long for that has the einsum form
            return plain_attention(q, k, v, causal=causal)
        return causal_attention(
            q, k, v, 1.0 / float(np.sqrt(d)), block_q=block_q,
            # (its query block holds whole key blocks)
            block_k=block_k if block_q % block_k == 0 else block_q)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    # non-tiling lengths (e.g. ViT's 197 tokens) pad up to the block
    # grid; padded keys are masked inside the kernel, padded query rows
    # are sliced off the output
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    valid_k = sk
    if pad_q or pad_k:
        cfg = [(0, 0), (0, 0), (0, 0), (0, 0)]
        if pad_q:
            qcfg = list(cfg)
            qcfg[1] = (0, pad_q)
            q = jnp.pad(q, qcfg)
        if pad_k:
            kcfg = list(cfg)
            kcfg[1] = (0, pad_k)
            k = jnp.pad(k, kcfg)
            v = jnp.pad(v, kcfg)
    sq_p, sk_p = sq + pad_q, sk + pad_k
    n_kv = sk_p // block_k

    # (B, L, H, D) -> (B*H, L, D): one grid row per (batch, head)
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    qf, kf, vf = fold(q), fold(k), fold(v)
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, n_kv=n_kv,
        scale=1.0 / float(np.sqrt(d)), valid_k=valid_k,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, sq_p // block_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, j: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, j: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(qf, kf, vf)
    out = out.reshape(b, h, sq_p, d).transpose(0, 2, 1, 3)
    return out[:, :sq] if pad_q else out


# ---------------------------------------------------------------------------
# causal attention of a segment over itself (a prefill from position 0)
# ---------------------------------------------------------------------------

# Queries a grid step holds and keys one step of its loop scores: the
# v5e sweep's best or within its noise at every cell's shape (tools/
# profile_prefill_attention.py, PERF.md §5: a GPT-2 b1024_k4 layer 1.20
# ms at 128 x 128, 0.52 at 256 x 256, 0.36 at 512 x 512)
CAUSAL_BLOCK_Q = 512
CAUSAL_BLOCK_K = 512
# the most VMEM a head's resident K and V (double-buffered) may take of
# a core's 128 MiB
_CAUSAL_KV_VMEM_BYTES = 48 * 1024 * 1024


def _causal_kv_bytes(seg_len: int, d_qk: int, d_v: int, dtype) -> int:
    """VMEM a head's whole K and V take, twice (the pipeline fetches the
    next head's while this one's are read)."""
    return 2 * (_padded_block_bytes((seg_len, d_qk), dtype)
                + _padded_block_bytes((seg_len, d_v), dtype))


def prefill_attention_impl(seg_len: int, d_qk: int, d_v: int, dtype,
                           table_width: int, kernel_lane: bool) -> str:
    """``"fused"`` or ``"xla"`` for the latent block's attention of a
    prefill segment of ``seg_len`` positions whose read table is
    ``table_width`` pages wide: a pure function of what a trace can see.
    The fused kernel (:func:`causal_attention`) where the segment starts
    at position zero (a table of no width: there is no cache to read),
    the engine's kernel lane serves (``kernel_lane``: the LM handed the
    block the whole pool, ``models/paged/lanes.py
    paged_kernel_static_eligible`` — a TPU or the forced interpreter, no
    mesh, which GSPMD cannot partition the custom call over, and
    ``SELDON_TPU_PAGED_KERNEL`` not "0"), the operands are bfloat16, the
    segment is at least one query block long and a head's K and V fit
    the kernel's VMEM; XLA's ``naive_attention`` everywhere else.  A
    layer with an indexer asks at its own widths and takes the answer
    for the attention under its selection (``ops/mla.py
    indexed_attention``: the kernel under the chosen set's mask, or XLA
    a block of queries at a time).  The multi-head block does not ask:
    at its cells' shapes the v5e sweep reads XLA over the segment alone
    as fast (``models/paged/blocks.py _segment_attention`` has the numbers)."""
    import jax.numpy as jnp

    if table_width or not kernel_lane or jnp.dtype(dtype) != jnp.bfloat16:
        return "xla"
    fits = _causal_kv_bytes(seg_len, d_qk, d_v, dtype) <= _CAUSAL_KV_VMEM_BYTES
    return "fused" if seg_len >= CAUSAL_BLOCK_Q and fits else "xla"


def _lanes(x, width: int):
    """A lane-replicated ``(rows, 128)`` statistic at ``width`` lanes."""
    import jax.numpy as jnp

    if width <= 128:
        return x[:, :width]
    return jnp.tile(x, (1, -(-width // 128)))[:, :width]


def _causal_kernel(q_ref, k_ref, v_ref, *refs, block_q: int, block_k: int,
                   scale: float, window: int = 0, chosen_lanes: int = 0):
    """Grid cell (batch*head, q-block).  The head's K and V rest WHOLE
    in VMEM: their block index is the head alone, so the pipeline
    fetches them once a head — each key from HBM exactly once, none
    again for a later query block — and the loop below walks the key
    blocks a query block can see: ``qi * block_q / block_k`` of them
    wholly under the diagonal (no mask is built for those) and the
    ``block_q / block_k`` the diagonal crosses.  Blocks above it are
    neither fetched again nor computed.

    The operands go into the MXU in their own type (bfloat16 in a
    prefill) with float32 accumulation; scores, running max, sum and
    ``exp`` are float32; the weights are cast to V's type for ``p @ v``
    (as the XLA form casts its softmax).  The running max and sum are
    kept 128 lanes wide, every lane the row's value: a ``(rows, 1)``
    statistic costs a relayout at every use (0.60 against 0.36 ms a
    GPT-2 ``b1024_k4`` layer, my chip run, PR 33).  Every row sees key 0
    in the first block the loop takes, so no running max is -inf after
    it and no ``exp(-inf - -inf)`` arises; rows past a prompt's true
    length (a bucket's padding) are computed like any other and read by
    nobody.

    ``window`` (0: none): row ``i`` attends keys ``i - window + 1 .. i``.
    The loop then starts at the first key block any of the query block's
    rows can see — blocks wholly behind the window are neither read nor
    scored — and every block it takes is masked on both edges (there are
    ``(block_q + window) / block_k`` of them, not a prompt's worth).  A
    row may see nothing in a block before its own, so the running max can
    be -inf there and the rescale guards it.

    ``chosen_lanes`` (0: no mask; else a fourth operand stands before the
    output, :func:`_pack_chosen`'s words): row ``i`` attends the keys its
    bits name — a subset of ``0..i``, so it is the only mask such a call
    builds, the diagonal's blocks included.  The loop keeps its bound:
    every key block up to the diagonal is weighed under the words' bits,
    ``block_k / chosen_lanes`` columns of 128 lanes at a time (one ``and``
    and one compare a score), none above it is touched.  A row's chosen
    set need not hold a key of the first blocks, so the rescale is
    guarded as a window's; a row of the entry's padding has none at all
    and divides by 1."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if chosen_lanes:
        chosen_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    qi = pl.program_id(1)
    q = q_ref[0]                                   # (block_q, d_qk)
    sub = block_q // block_k
    d_v = acc_ref.shape[-1]
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(j, masked: bool):
        start = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, pl.ds(start, block_k), :]     # (block_k, d_qk)
        v = v_ref[0, pl.ds(start, block_k), :]     # (block_k, d_v)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if chosen_lanes:
            # key s: bit (s % (32 * lanes)) // lanes of word s % lanes in
            # tile s // (32 * lanes) — a block's keys are whole columns
            # of one tile's lanes, each under one bit
            at = start % (32 * chosen_lanes)
            words = chosen_ref[0, start // (32 * chosen_lanes)]
            cols = [
                jnp.where(
                    (words & jnp.left_shift(1, at // chosen_lanes + c)) != 0,
                    s[:, c * chosen_lanes:(c + 1) * chosen_lanes], -jnp.inf)
                for c in range(block_k // chosen_lanes)]
            s = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=-1)
        elif masked:
            q_at = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_at = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen = k_at <= q_at
            if window:
                seen &= k_at > q_at - window
            s = jnp.where(seen, s, -jnp.inf)
        m = m_ref[...]                             # (block_q, 128)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1)[:, None])
        if window or chosen_lanes:
            # a row that has seen no key yet: exp(-inf - -inf)
            m_at = jnp.where(m_new == -jnp.inf, 0.0, m_new)
            alpha = jnp.exp(m - m_at)
            p = jnp.exp(s - _lanes(m_at, block_k))
        else:
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - _lanes(m_new, block_k))
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1)[:, None]
        acc_ref[...] = _lanes(alpha, d_v) * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    first = qi * sub

    def under(j, carry):
        step(j, masked=bool(window))
        return carry

    lo = (jnp.maximum(qi * block_q - (window - 1), 0) // block_k
          if window else 0)
    if chosen_lanes:
        jax.lax.fori_loop(0, first + sub, under, 0)
        total = l_ref[...]
        o_ref[0] = (acc_ref[...] / _lanes(
            jnp.where(total == 0.0, 1.0, total), d_v)).astype(o_ref.dtype)
        return
    jax.lax.fori_loop(lo, first, under, 0)
    for i in range(sub):
        step(first + i, masked=True)
    o_ref[0] = (acc_ref[...] / _lanes(l_ref[...], d_v)).astype(o_ref.dtype)


def _pack_chosen(chosen, lanes: int):
    """A ``(B, L, L)`` mask (row ``t``, key ``s``) as the words the
    masked causal kernel reads: ``(B, tiles, L, lanes)`` int32, key
    ``s`` bit ``(s % (32 * lanes)) // lanes`` of word ``s % lanes`` in
    tile ``s // (32 * lanes)`` — a key block of ``lanes`` keys is one
    bit of a tile's every lane, so the kernel unpacks with an ``and``
    and no relayout, and a row's 4,096 keys are 512 bytes (one fetch a
    query block: an eighth of the mask as int8)."""
    import jax
    import jax.numpy as jnp

    b, rows, keys = chosen.shape
    tiles = -(-keys // (32 * lanes))
    bits = jnp.pad(chosen != 0, [(0, 0), (0, 0), (0, tiles * 32 * lanes - keys)])
    bits = bits.reshape(b, rows, tiles, 32, lanes).astype(jnp.uint32)
    words = (bits << jnp.arange(32, dtype=jnp.uint32)[:, None]).sum(
        axis=3, dtype=jnp.uint32)
    return jnp.swapaxes(jax.lax.bitcast_convert_type(words, jnp.int32), 1, 2)


def causal_attention(q, k, v, scale: float, *, block_q: int = None,
                     block_k: int = None, window: int = 0, chosen=None):
    """Causal softmax attention of a segment over itself in ONE kernel:
    ``q`` ``(B, L, h, d_qk)``, ``k`` ``(B, L, h, d_qk)``, ``v`` ``(B, L,
    h, d_v)`` -> ``(B, L, h, d_v)`` in q's type.  Row ``i`` attends keys
    ``0..i`` with weights ``softmax(scale * q_i . k_j)``; no score
    matrix reaches HBM.  ``d_qk`` and ``d_v`` may differ (latent
    attention's 192 against 128).  A length that is no multiple of the
    query block is padded to one (pad keys lie after every real row, so
    causality hides them) and the pad rows cut off.  ``window`` (0:
    none): row ``i`` attends keys ``i - window + 1 .. i`` only, and key
    blocks wholly behind a query block's window are skipped.  ``k`` and
    ``v`` may hold fewer heads than ``q`` (grouped-query attention: ``h``
    a multiple of theirs): query head ``c`` reads K/V head ``c // (h /
    kv_heads)`` through the block index — nothing is repeated to ``h``
    heads, and the pipeline fetches a K/V head once for the query heads
    that follow one another on it.

    ``chosen`` (None: every key up to the row's own): a ``(B, L, L)``
    mask, bool or any integer, one for all heads of a batch row — row
    ``t`` attends the keys ``s`` where ``chosen[b, t, s]``, which the
    caller keeps within ``s <= t`` (a learned selection's chosen set:
    ``ops/mla.py indexed_attention``).  The loop's bound is the causal
    one — key blocks above a query block's diagonal are neither fetched
    nor scored — and every block it visits is weighed under the mask,
    which reaches the kernel packed 32 keys a word
    (:func:`_pack_chosen`).  It is a fact of the call's structure:
    without it the traced call is the one every other caller traces,
    with it the call is ``prefill_chosen_attention`` with a fourth
    operand.  A key block of more than 128 keys must hold whole columns
    of 128 and divide 4,096.

    The kernel's call is a ``pallas_call`` whose output is ``(B * h, L,
    d_v)``: three dims, which is how the benchmark's readers tell it
    from the grouped expert matmuls (two) — see
    ``benchmarks/layer_metrics/moe_work.py``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, seg, h, d_qk = q.shape
    d_v = v.shape[-1]
    kv_heads = k.shape[2]
    if h % kv_heads:
        raise ValueError(
            f"causal_attention: {h} query heads over {kv_heads} K/V heads")
    share = h // kv_heads
    block_q = block_q or CAUSAL_BLOCK_Q
    block_k = min(block_k or CAUSAL_BLOCK_K, block_q)
    if block_q % block_k:
        raise ValueError(
            f"causal_attention: block_q {block_q} must be a multiple of "
            f"block_k {block_k}")
    if _causal_kv_bytes(seg, d_qk, d_v, q.dtype) > _CAUSAL_KV_VMEM_BYTES:
        raise ValueError(
            f"causal_attention: a head's K and V of {seg} positions do not "
            f"fit the {_CAUSAL_KV_VMEM_BYTES >> 20} MiB they may take of VMEM")
    if block_q > seg:
        # one query block, cut to the segment; its key blocks stay
        # where they still divide it
        block_q = -(-seg // 8) * 8
        block_k = block_k if block_q % block_k == 0 else block_q
    pad = (-seg) % block_q
    if pad:
        q, k, v = (jnp.pad(x, [(0, 0), (0, pad), (0, 0), (0, 0)])
                   for x in (q, k, v))
    seg_p = seg + pad
    masked = {}
    if chosen is not None:
        lanes = min(block_k, 128)
        if window or chosen.shape != (b, seg, seg) or (32 * lanes) % block_k:
            raise ValueError(
                f"causal_attention: a chosen set {chosen.shape} under window "
                f"{window} and key blocks of {block_k} (it stands alone, is "
                f"{(b, seg, seg)}, and its key blocks divide {32 * lanes})")
        # (pad rows choose nothing and no row chooses a pad key)
        words = _pack_chosen(
            jnp.pad(chosen, [(0, 0), (0, pad), (0, pad)]), lanes)
        masked = {"chosen_lanes": lanes}

    # (B, L, h, d) -> (B*h, L, d): one head's rows contiguous
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(-1, seg_p, x.shape[-1])

    def kv_at(i, qi):
        # grid cell i = batch * h + query head: its K/V head's rows
        return ((i // h) * kv_heads + (i % h) // share, 0, 0)

    kv_index = kv_at if share > 1 else (lambda i, qi: (i, 0, 0))

    need = (_causal_kv_bytes(seg_p, d_qk, d_v, q.dtype)
            + 8 * block_q * max(block_k, 128) * 4
            + 4 * _padded_block_bytes((block_q, max(d_qk, d_v)), np.float32))
    if masked:
        need += 2 * _padded_block_bytes(
            (words.shape[1], block_q, lanes), np.int32)
    out = pl.pallas_call(
        functools.partial(_causal_kernel, block_q=block_q, block_k=block_k,
                          scale=float(scale), **(
                              {"window": int(window)} if window else {}),
                          **masked),
        grid=(b * h, seg_p // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d_qk), lambda i, qi: (i, qi, 0)),
            pl.BlockSpec((1, seg_p, d_qk), kv_index),
            pl.BlockSpec((1, seg_p, d_v), kv_index),
        ] + ([
            # a batch row's words for the query block, every tile: the
            # same for all of its heads
            pl.BlockSpec((1, words.shape[1], block_q, lanes),
                         lambda i, qi: (i // h, 0, qi, 0)),
        ] if masked else []),
        out_specs=pl.BlockSpec((1, block_q, d_v), lambda i, qi: (i, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, seg_p, d_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(max(need, _VMEM_LIMIT_BYTES)),
        ),
        name=("prefill_window_attention" if window
              else "prefill_chosen_attention" if masked
              else "prefill_causal_attention"),
        interpret=interpret_mode(),
    )(fold(q), fold(k), fold(v), *([words] if masked else []))
    out = out.reshape(b, h, seg_p, d_v).transpose(0, 2, 1, 3)
    return out[:, :seg] if pad else out


def flash_attn_fn(block_q: int = 128, block_k: int = 128):
    """Drop-in ``attn_fn`` for the transformer family."""

    def fn(q, k, v, causal: bool = False):
        return flash_attention(q, k, v, causal=causal, block_q=block_q, block_k=block_k)

    return fn


# ---------------------------------------------------------------------------
# paged attention decode (flash-decoding over a paged K/V pool)
# ---------------------------------------------------------------------------

def _split3(x):
    """An f32 array as three f32 terms that sum to it EXACTLY and are
    each exactly representable in bf16 (8 significant bits a term, by
    masking the low half of the word: a truncation no compiler pass
    can fold away, where an f32 -> bf16 -> f32 round trip may be)."""
    import jax
    import jax.numpy as jnp

    def top(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.int32)
        return jax.lax.bitcast_convert_type(
            bits & jnp.int32(-65536), jnp.float32)

    hi = top(x)
    rest = x - hi
    mid = top(rest)
    return hi, mid, rest - mid


def _paged_attention_kernel(tables_ref, lens_ref, layer_ref, *refs,
                                page_size, heads, head_dim, quantized=False,
                                fold_lora=False, q_scale=1.0, kv_heads=None,
                                offset=False):
    """One slot of streaming flash-decoding: grid=(B,), the WHOLE
    ``(L, pages, ps, h*hd)`` K/V pools stay in HBM and each slot's live
    pages arrive via double-buffered manual DMA of
    ``pool.at[layer, page]`` — the layer is a scalar-prefetch operand,
    so every layer of a model runs the same compiled kernel and no
    layer of the pool is ever sliced out for it.

    **A lane pays for its live pages only** (PR 27).  A lane of length
    0 writes the neutral flash state (``acc`` 0, ``m`` -inf, ``l`` 0)
    and leaves: no projector is built, no page fetched.  A live lane
    runs a ``fori_loop`` over its own ``ceil(length / page_size)``
    pages (capped at the table's width), :func:`_pages_per_step` of
    them a step, and no more.  Before PR 27 the loop ran the table's
    full width for every lane and threw the dead iterations' arithmetic
    away (``pl.when`` guarded the DMAs alone), so a call's time
    followed lanes x table width whatever the cache held: 1.7 us a slot
    at 20 x 64 and 2.6 at 16 x 128 on the v5e, the lanes at the chat
    cell's lengths or all full (PERF.md §6, PR 27).

    **A lane does not start on a cold DMA.**  The page buffers, their
    semaphores and one SMEM word are scratch of the whole call, not of
    a grid step: a lane's last loop step starts the NEXT live lane's
    first copies into the buffer it is not reading (an empty lane
    passes the baton on), and leaves in ``turn_ref`` which buffer that
    was.  The grid runs in order on one core
    (``dimension_semantics=("arbitrary",)``).  Worth 18-23 % of a call
    at the chat cell's lengths.

    Everything stays in the pool's flattened (ps, h*hd) layout — Mosaic
    supports neither value shape-casts nor batched dots, so the
    per-head contractions are block-diagonal MXU matmuls with the heads
    on the SUBLANES: a ``(hp, D)`` projector (row c = head c's slice of
    q, zero elsewhere; ``hp`` = heads rounded up to 8) gives the scores
    ``projector @ k^T`` as ``(hp, tokens)``, the softmax state is
    ``(hp, 1)`` columns, and ``w @ v`` gives ``(hp, D)``, of which row
    c's head-c block is the answer: it accumulates whole across the
    steps and the block diagonal is cut out once, when the lane ends.

    **A step's matmuls are one bf16 MXU pass each, at f32 precision.**
    K and V are bf16 at rest (int8 under ``quantized``: both exact in
    bf16), so only the SMALL operand carries 24 bits: it is split into
    three bf16 terms (:func:`_split3`), stacked on the rows — ``(3*hp,
    D)`` for q, ``(3*hp, tokens)`` for the softmax weights — and the
    three row blocks of the f32 result are added.  Every product is
    exact and every sum is f32: what ``Precision.HIGHEST`` (six passes,
    four of them over the 16 zero mantissa bits of a bf16 page cast to
    f32) gave before; against a float64 host oracle on the chip the max
    error is 0.8e-6 at 20 x 64 and 1.0e-6 at 16 x 128, the six-pass
    kernel's 1.3e-6 and 1.1e-6 (tools/probe_kernels.py).  An f32 pool
    (CPU exactness lanes) keeps ``HIGHEST`` on f32 operands, in the
    same layout.  With both, a live page costs 0.47-0.65 us at 20 x 64
    (its DMA: 0.40) and 0.75-0.91 at 16 x 128 (0.64): 61-86 % of the
    DMA roofline, from 12 %.

    Both trace-time static flags leave the base program alone:

    * ``quantized`` — int8 pages with one f32 scale per page per k/v in
      scalar-prefetched tables; the scale multiplies the page's SCORES
      and its softmax WEIGHTS (a ``(1, tokens)`` row), never the page.
    * ``kv_heads`` (grouped-query heads; None: as many as ``heads``) —
      the pool's row is ``kv_heads * head_dim`` wide and query head
      ``c`` reads K/V head ``c // (heads / kv_heads)``: the projector's
      row ``c`` holds head ``c``'s slice of q on ITS K/V HEAD's columns,
      so a page's K and V slices are streamed once and one matmul scores
      them against every query head of each group; ``w @ v`` gives
      ``(hp, kv_heads * head_dim)``, of which row ``c`` keeps its K/V
      head's block.  The rows of a group share columns, so the answer is
      cut out as ``(hp, head_dim)`` rows (one static lane slice a K/V
      head) where one K/V head a query head sums the masked rows into
      one flat row: q and the output then ride ``(heads, head_dim)``.
    * ``offset`` — a fourth scalar-prefetch operand ``starts`` ``(B,)``
      gives each lane's first live position (a window's trailing edge),
      as :func:`_latent_attention_kernel` has it: the page loop starts
      at the step that holds it and positions before it are masked like
      those past the length.
    * ``fold_lora`` — the per-lane qkv LoRA BGMV delta computes INSIDE
      this launch: the lane's adapter slot id (scalar prefetch) indexes
      the factor pools in HBM, one DMA brings the lane's (r, D)/(r, 3D)
      factors into VMEM, two VPU reductions produce the (3D,) delta,
      the q third folds into the scores in-register (``q_scale`` is the
      1/sqrt(hd) the caller already applied to q), and the RAW delta
      emits as a fourth output for the caller's self-term and pool
      write.  Slot 0 holds zero factors: a lane of length 0 on slot 0
      writes an exact 0.0 delta without fetching them.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pos = 0
    if offset:
        starts_ref = refs[0]
        pos = 1
    if quantized:
        sk_ref, sv_ref = refs[pos], refs[pos + 1]
        pos += 2
    if fold_lora:
        adapter_ref = refs[pos]
        pos += 1
    q_ref = refs[pos]
    pos += 1
    if fold_lora:
        x_ref, a_hbm, b_hbm = refs[pos], refs[pos + 1], refs[pos + 2]
        pos += 3
    pk_hbm, pv_hbm = refs[pos], refs[pos + 1]
    acc_ref, m_ref, l_ref = refs[pos + 2], refs[pos + 3], refs[pos + 4]
    pos += 5
    delta_ref = None
    if fold_lora:
        delta_ref = refs[pos]
        pos += 1
    # scratch that outlives a grid step: the two page buffers, their
    # DMA semaphores and the buffer the next lane starts in
    k_buf, v_buf, sems, turn_ref = refs[pos:pos + 4]
    if fold_lora:
        a_scr, b_scr, lsems = refs[pos + 4:pos + 7]

    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    layer = layer_ref[0]
    h, hd = heads, head_dim
    grouped = kv_heads is not None and kv_heads != heads
    share = h // kv_heads if grouped else 1    # query heads a K/V head
    D = (kv_heads if grouped else h) * hd      # the pool's row
    hp = -(-h // 8) * 8
    width = tables_ref.shape[1]
    span = k_buf.shape[1]
    group = span // page_size
    length = lens_ref[b]
    # bf16 and int8 pages are exact in bf16: the one-pass lane.  An f32
    # pool needs all of its bits: HIGHEST on f32 operands
    one_pass = pk_hbm.dtype != jnp.float32
    nt_dims = (((1,), (1,)), ((), ()))
    highest = jax.lax.Precision.HIGHEST

    def pages_of(lane):
        # a lane masked done may hold more context than the table slice
        # it was given (models/paged/engine.py _pages_horizon): never read
        # past it
        pages = jnp.minimum(
            jax.lax.div(lens_ref[lane] + page_size - 1, page_size), width)
        # (a window's lengths are counted from its table's first column
        # and may fall below zero on an idle lane: an empty lane too)
        return jnp.maximum(pages, 0) if offset else pages

    def first_of(lane):
        """The first step of a lane's page loop."""
        if not offset:
            return 0
        # (never past the lane's last step: the step a predecessor
        # started for it is always waited for)
        last = jnp.maximum(
            jax.lax.div(pages_of(lane) + group - 1, group) - 1, 0)
        return jnp.minimum(
            jax.lax.div(jnp.maximum(starts_ref[lane], 0), span), last)

    def copies(lane, j, slot, which):
        """``(held, copy)`` of loop step ``j`` of ``lane`` into buffer
        ``slot``: K (``which`` 0) or V (1), up to ``group`` pages.  A
        page the lane does not hold (``held`` false) is neither started
        nor waited for."""
        pool, buf = ((pk_hbm, k_buf), (pv_hbm, v_buf))[which]
        n = pages_of(lane)
        for g in range(group):
            idx = j * group + g
            page = tables_ref[lane, jnp.clip(idx, 0, n - 1)]
            yield idx < n, pltpu.make_async_copy(
                pool.at[layer, page],
                buf.at[slot, pl.ds(g * page_size, page_size)],
                sems.at[slot, which, g])

    def start(lane, j, slot):
        for which in (0, 1):
            for held, copy in copies(lane, j, slot, which):
                pl.when(held)(copy.start)

    def wait(j, slot, which):
        for held, copy in copies(b, j, slot, which):
            pl.when(held)(copy.wait)

    n_pages = pages_of(b)
    n_steps = jax.lax.div(n_pages + group - 1, group)
    j_first = first_of(b)
    # the next lane's first pages are fetched while this lane's last
    # are reduced, so a lane does not start on a cold DMA: whoever runs
    # before a live lane (live or not) starts its first step's copies
    # and leaves the buffer they went to in ``turn_ref``
    after = jnp.minimum(b + 1, lanes - 1)
    hand_on = (b + 1 < lanes) & (pages_of(after) > 0)

    @pl.when(b == 0)
    def _first():
        turn_ref[0] = 0
        start(0, first_of(0), 0)

    base = turn_ref[0]

    def stack(x):
        """The small operand of a page's matmul: its three bf16 terms
        on the rows (one pass), or itself (f32 pool)."""
        if not one_pass:
            return x
        return jnp.concatenate(_split3(x), axis=0).astype(jnp.bfloat16)

    def unstack(y):
        """Rows (3*hp, n) of a one-pass result -> (hp, n): the small
        terms first, so the sum rounds once at the large one."""
        if not one_pass:
            return y
        return (y[2 * hp:] + y[hp:2 * hp]) + y[:hp]

    def pages(ref):
        x = ref[...]
        if not one_pass:
            return x
        if x.dtype != jnp.bfloat16:
            x = x.astype(jnp.float32)  # int8 has no direct bf16 cast
        return x.astype(jnp.bfloat16)

    def page_scales(ref, j):
        """(1, span): the scale of each column's page (int8 pool)."""
        col_page = jax.lax.broadcasted_iota(
            jnp.int32, (1, span), 1) // page_size
        out = jnp.zeros((1, span), jnp.float32)
        for g in range(group):
            held = jnp.minimum(j * group + g, n_pages - 1)
            out = jnp.where(
                col_page == g, ref[layer, tables_ref[b, held]], out)
        return out

    def lora_delta():
        """The lane's raw (1, 3D) qkv delta, written to its output."""
        lane = adapter_ref[b]
        cp_a = pltpu.make_async_copy(a_hbm.at[layer, lane], a_scr, lsems.at[0])
        cp_b = pltpu.make_async_copy(b_hbm.at[layer, lane], b_scr, lsems.at[1])
        cp_a.start()
        cp_b.start()
        cp_a.wait()
        cp_b.wait()
        xflat = x_ref[0].astype(jnp.float32)           # (1, D) block input
        # BGMV on the VPU: t = A[lane]^T x (rank,), delta = t B[lane]
        t = (a_scr[...].astype(jnp.float32) * xflat).sum(axis=1, keepdims=True)
        delta = (t * b_scr[...].astype(jnp.float32)).sum(axis=0, keepdims=True)
        delta_ref[0] = delta                           # (1, 3D) raw, unscaled
        return delta

    @pl.when(n_pages == 0)
    def _dead():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        if fold_lora:
            # a lane with no cache still owes its delta (the caller's
            # self term and pool write read it) unless it sits on slot
            # 0, whose factors are zero by contract
            @pl.when(adapter_ref[b] != 0)
            def _delta():
                lora_delta()

            @pl.when(adapter_ref[b] == 0)
            def _no_delta():
                delta_ref[...] = jnp.zeros_like(delta_ref)

        @pl.when(hand_on)
        def _hand_on():
            start(after, first_of(after), base)

    @pl.when(n_pages > 0)
    def _live():
        if grouped:
            # q arrives (hp, hd), pre-scaled, zero past the heads: row
            # c's slice is laid on the columns of its K/V head, c // share
            qflat = jnp.concatenate(
                [q_ref[0].astype(jnp.float32)] * kv_heads, axis=1)  # (hp, D)
        else:
            qflat = q_ref[0].astype(jnp.float32)       # (1, D), pre-scaled
            if fold_lora:
                qflat = qflat + q_scale * lora_delta()[:, :D]
        # row c of the projector holds head c's slice of q on its own
        # columns (its K/V head's, where heads are grouped); rows past
        # the heads are zero
        row = jax.lax.broadcasted_iota(jnp.int32, (hp, D), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (hp, D), 1)
        if grouped:
            own = (row < 0)
            for g in range(kv_heads):
                own |= ((row >= g * share) & (row < (g + 1) * share)
                        & (col >= g * hd) & (col < (g + 1) * hd))
        else:
            own = (col >= row * hd) & (col < (row + 1) * hd)
        terms = _split3(qflat) if one_pass else (qflat,)
        qb = jnp.concatenate(
            [jnp.where(own, t, 0.0) for t in terms], axis=0,
        ).astype(jnp.bfloat16 if one_pass else jnp.float32)  # (3*hp | hp, D)

        def step(j, carry):
            m_prev, l_prev, acc = carry            # (hp, 1), (hp, 1), (hp, D)
            slot = jax.lax.rem(base + j - j_first if offset else base + j, 2)

            @pl.when(j + 1 < n_steps)
            def _prefetch():
                start(b, j + 1, 1 - slot)

            @pl.when((j + 1 == n_steps) & hand_on)
            def _hand_on():
                start(after, first_of(after), 1 - slot)

            wait(j, slot, 0)
            s = unstack(jax.lax.dot_general(
                qb, pages(k_buf.at[slot]), nt_dims,
                preferred_element_type=jnp.float32,
                precision=None if one_pass else highest))     # (hp, span)
            at = j * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
            if quantized:
                s = s * page_scales(sk_ref, j)
            if offset:
                # (a step may reach past the table where its pages do not
                # divide the table's width)
                live = ((at < jnp.minimum(length, width * page_size))
                        & (at >= starts_ref[b]))
            else:
                live = at < length
            s = jnp.where(live, s, -jnp.inf)
            # every step the loop reaches holds a live token, so m_new
            # is finite and the first step's alpha is exp(-inf) = 0
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            if offset:  # a step may lie wholly before the window's edge
                m_at = jnp.where(m_new == -jnp.inf, 0.0, m_new)
                alpha = jnp.exp(m_prev - m_at)
                w = jnp.exp(s - m_at)
            else:
                alpha = jnp.exp(m_prev - m_new)
                w = jnp.exp(s - m_new)             # (hp, span); dead columns 0
            l_new = l_prev * alpha + w.sum(axis=1, keepdims=True)
            if quantized:
                w = w * page_scales(sv_ref, j)
            wait(j, slot, 1)
            for g in range(1, group):
                # a page the lane does not hold was not fetched: its
                # weights are 0, and 0 x whatever the buffer held must
                # be 0
                @pl.when(j * group + g >= n_pages)
                def _blank(g=g):
                    v_buf[slot, pl.ds(g * page_size, page_size)] = jnp.zeros(
                        (page_size, D), v_buf.dtype)
            pv = unstack(jnp.dot(
                stack(w), pages(v_buf.at[slot]),
                preferred_element_type=jnp.float32,
                precision=None if one_pass else highest))     # (hp, D)
            return m_new, l_new, acc * alpha + pv

        init = (
            jnp.full((hp, 1), -jnp.inf, jnp.float32),
            jnp.zeros((hp, 1), jnp.float32),
            jnp.zeros((hp, D), jnp.float32),
        )
        m_fin, l_fin, acc_fin = jax.lax.fori_loop(j_first, n_steps, step, init)
        turn_ref[0] = jax.lax.rem(
            base + n_steps - j_first if offset else base + n_steps, 2)
        if grouped:
            # row c keeps its K/V head's block: one lane slice a K/V
            # head, the rows of the other groups zero
            kept = jnp.where(own, acc_fin, 0.0)
            out = kept[:, :hd]
            for g in range(1, kv_heads):
                out = out + kept[:, g * hd:(g + 1) * hd]
            acc_ref[0] = out
        else:
            # the block diagonal, cut out once: row c keeps head c's
            # columns
            acc_ref[0] = jnp.where(own, acc_fin, 0.0).sum(
                axis=0, keepdims=True)
        # m/l lane-padded to (h, 128): Mosaic wants 128-divisible last
        # block dims; every lane carries the same value (grouped heads
        # leave in whole sublane tiles, hp of them)
        rows = m_ref.shape[1]
        m_ref[0] = jnp.broadcast_to(m_fin[:rows], m_ref.shape[1:])
        l_ref[0] = jnp.broadcast_to(l_fin[:rows], l_ref.shape[1:])


def _pages_per_step(page_size: int, table_width: int) -> int:
    """Pages one step of the stream kernel's loop reduces: enough tokens
    that the MXU's 128 columns (the scores) and 128 contraction rows
    (``w @ v``) are full, and no more than a table holds.  On the v5e
    two 64-token pages a step took a live page from 0.80 to 0.55 us at
    20 x 64 (lanes full) and four gave nothing more; at 16 x 128, where
    a page's DMA is 0.64 us, two changed little (PERF.md §6, PR 27)."""
    return max(1, min(128 // page_size, table_width))


# Tokens one step of the page loop reduces where query heads share K/V
# heads (the ungrouped loop's is 128: ``_pages_per_step``).  A grouped
# row is narrow (4 x 128 values against GPT-2-large's 1,280): a 64-token
# page is 0.16 us of DMA for K and V together, so a two-page step is
# mostly the loop's own fixed cost, as the latent kernel found
# (:data:`LATENT_STEP_TOKENS`).  At 512 tokens the four page buffers are
# 2 MB of VMEM.
GROUPED_STEP_TOKENS = 512


def _stream_decode(q, pk, pv, block_tables, lengths, layer, kv_scales, lora,
                   starts=None, *, quantized, fold, q_scale, interpret):
    """The stream kernel's ``pallas_call`` on the whole flat pool (see
    :func:`paged_attention_decode`, which calls it jitted; ``quantized``
    and ``fold`` say whether ``kv_scales`` and ``lora`` are there, and
    ``starts`` whether the lanes have a first live position)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, h, hd = q.shape
    P = block_tables.shape[1]
    ps = pk.shape[2]
    if quantized:
        sk, sv = kv_scales
    D = h * hd
    if pk.shape[3] != D:
        # fewer K/V heads than query heads: the kernel of the pool's own
        # row, q and the answer riding (heads, head_dim)
        return _grouped_stream_decode(
            q, pk, pv, block_tables, lengths, layer, starts, interpret=interpret)
    scalar_args = [block_tables, lengths, layer.reshape(1), *_given(starts)]
    n_prefetch = len(scalar_args)
    if quantized:
        scalar_args += [sk, sv]
        n_prefetch += 2
    if fold:
        x, a_T, b_f, adapter_idx = lora
        scalar_args.append(jnp.asarray(adapter_idx, jnp.int32))
        n_prefetch += 1
    # the kernel works in the pool's flat (ps, h*hd) layout: HBM
    # page slices need a 128-aligned minor dim and Mosaic has no
    # value shape-casts.  The pool arrives in it; only q (KBs) is
    # re-laid here
    q = q.reshape(B, 1, D)
    # q/acc ride as (B, 1, D) with (1, 1, D) blocks: the (8, 128)
    # divisibility rule applies to the LAST TWO dims, and the
    # singleton middle dim satisfies it.  Index lambdas take the
    # grid ids then every scalar-prefetch operand, so *prefetch
    # absorbs the variable tail.
    lane_spec = pl.BlockSpec((1, 1, D), lambda b, *prefetch: (b, 0, 0))
    in_specs = [lane_spec]
    tensor_args = [q]
    if fold:
        in_specs += [
            lane_spec,                          # x — block inputs
            pl.BlockSpec(memory_space=pl.ANY),  # A^T factor pool
            pl.BlockSpec(memory_space=pl.ANY),  # B factor pool
        ]
        tensor_args += [x.reshape(B, 1, D), a_T, b_f]
    in_specs += [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    tensor_args += [pk, pv]
    pad_spec = pl.BlockSpec((1, h, 128), lambda b, *prefetch: (b, 0, 0))
    out_specs = [lane_spec, pad_spec, pad_spec]
    out_shape = [
        jax.ShapeDtypeStruct((B, 1, D), jnp.float32),
        jax.ShapeDtypeStruct((B, h, 128), jnp.float32),
        jax.ShapeDtypeStruct((B, h, 128), jnp.float32),
    ]
    if fold:
        out_specs.append(
            pl.BlockSpec((1, 1, 3 * D), lambda b, *prefetch: (b, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, 1, 3 * D), jnp.float32))
    # scratch that persists over the grid: a lane's last step starts
    # the next lane's first copies into the other page buffer
    span = _pages_per_step(ps, P) * ps
    scratch_shapes = [
        pltpu.VMEM((2, span, D), pk.dtype),
        pltpu.VMEM((2, span, D), pv.dtype),
        pltpu.SemaphoreType.DMA((2, 2, span // ps)),
        pltpu.SMEM((1,), jnp.int32),
    ]
    if fold:
        rank = a_T.shape[2]
        scratch_shapes += [
            pltpu.VMEM((rank, D), a_T.dtype),
            pltpu.VMEM((rank, 3 * D), b_f.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(B,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    kernel = functools.partial(
        _paged_attention_kernel, page_size=ps, heads=h, head_dim=hd,
        quantized=quantized, fold_lora=fold,
        q_scale=q_scale, offset=len(_given(starts)) == 1)
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # lanes in order, on one core: each hands the next its
        # first pages
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*scalar_args, *tensor_args)
    acc, m, l = outs[0], outs[1], outs[2]
    res = (acc.reshape(B, h, hd), m[:, :, 0], l[:, :, 0])
    if fold:
        res = res + (outs[3].reshape(B, 3 * D),)
    return res


def _grouped_stream_decode(q, pk, pv, block_tables, lengths, layer, starts, *,
                           interpret):
    """:func:`_stream_decode` for a pool whose row holds fewer K/V heads
    than ``q`` has query heads: the same kernel body with ``kv_heads``
    set, :data:`GROUPED_STEP_TOKENS` a step, q and the answer as
    ``(1, heads, head_dim)`` blocks (the rows of a group share the row's
    columns, so nothing sums them into one flat row).  The native pool
    type only: no int8 scales, no LoRA fold."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, h, hd = q.shape
    ps, D = pk.shape[2], pk.shape[3]
    kv_heads = D // hd
    hp = -(-h // 8) * 8    # the heads in whole sublane tiles, in and out
    q = jnp.pad(q, ((0, 0), (0, hp - h), (0, 0)))
    span = max(1, min(GROUPED_STEP_TOKENS // ps, block_tables.shape[1])) * ps
    scalar_args = [block_tables, lengths, layer.reshape(1), *_given(starts)]
    head_spec = pl.BlockSpec((1, hp, hd), lambda b, *prefetch: (b, 0, 0))
    pad_spec = pl.BlockSpec((1, hp, 128), lambda b, *prefetch: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalar_args),
        grid=(B,),
        in_specs=[head_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[head_spec, pad_spec, pad_spec],
        scratch_shapes=[
            pltpu.VMEM((2, span, D), pk.dtype),
            pltpu.VMEM((2, span, D), pv.dtype),
            pltpu.SemaphoreType.DMA((2, 2, span // ps)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(
            _paged_attention_kernel, page_size=ps, heads=h, head_dim=hd,
            kv_heads=kv_heads, offset=starts is not None),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, hp, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, hp, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, hp, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*scalar_args, q, pk, pv)
    return acc[:, :h], m[:, :h, 0], l[:, :h, 0]



@functools.lru_cache(maxsize=None)
def _stream_decode_jit():
    """:func:`_stream_decode` under ``jax.jit``, made on first use
    (nothing imports jax with this module)."""
    import jax

    return jax.jit(_stream_decode, static_argnames=(
        "quantized", "fold", "q_scale", "interpret"))


def paged_attention_decode(q, pk, pv, block_tables, lengths, *, layer,
                           page_size, kv_scales=None, lora=None, starts=None):
    """Unnormalised flash state of decode attention over one layer of a
    paged pool, addressed IN the whole pool.

    ``q`` (B, h, hd) — current-step queries, already scaled;
    ``pk``/``pv`` — the WHOLE pools, ``(L, num_pages, ps, h*hd)``;
    ``layer`` — which layer of them to attend over, a python int or a
    traced int32 scalar; ``block_tables`` (B, P); ``lengths`` (B,)
    cached token counts.  Returns ``(acc, m, l)`` f32 — merge with the
    in-segment term via the usual flash rule.

    Why the whole pool: on the v5e a ``pool[layer]`` slice is an 84 MB
    copy at GPT-2-large size, and re-laying a ``(ps, h, hd)`` page to
    the flat ``(ps, h*hd)`` form the kernel DMAs is another (a
    minor-dims reshape is NOT free under the (8, 128) tiling: it was
    ``copy_bf16_513_64_1280_``, 12 % of device time; PERF.md §6, PR 25).
    The kernel therefore takes the pool in ``pl.ANY`` and the layer as a
    scalar-prefetch operand — one compiled kernel for every layer — and
    DMAs ``pool.at[layer, page]``.

    ``kv_scales`` (r18): ``(sk, sv)`` per-page f32 scale tables
    ``(L, num_pages)`` for an int8 pool, indexed ``[layer, page]`` like
    the pool — pages dequantise in-register inside the online-softmax
    loop (no dequantised copy of the cache ever exists in HBM).

    ``lora`` (r18): ``(x, a_T, b, adapter_idx, q_scale)`` folds the
    per-lane qkv BGMV delta into the same launch — ``x`` (B, d) block
    inputs, ``a_T`` (L, slots, r, d) TRANSPOSED first factors (the DMA
    wants the 128-aligned d minor), ``b`` (L, slots, r, 3d), both
    indexed ``[layer, slot]``, ``adapter_idx`` (B,) int32 slot ids,
    ``q_scale`` the static 1/sqrt(hd) already applied to q.  The return
    grows a fourth element: the raw (B, 3d) f32 delta for the caller's
    self-term and pool write.

    **Grouped-query heads** are read off the operands: a pool whose row
    is narrower than ``h * hd`` holds ``row / hd`` K/V heads, and query
    head ``c`` attends K/V head ``c // (h / kv_heads)`` — each page's K
    and V slices are streamed once and scored against every query head
    of their group (never repeated to ``h`` heads in HBM or VMEM).
    ``starts`` ``(B,)`` int32 (None: 0): a lane attends positions
    ``starts .. lengths - 1`` of its table's span only — a window's live
    rows — and pages wholly before ``starts`` are not read
    (:func:`latent_attention_decode` has the same operand).  Neither
    takes ``kv_scales`` or ``lora`` yet; without either the kernel
    traced is the one a caller that never heard of them traces.

    TPU-first replacement for the ``pk[layer, block_tables]`` gather in
    ``PagedTransformerBlock`` (models/paged/blocks.py): the gather copies the
    whole live cache through HBM per layer per step; here pages stream
    HBM->VMEM, indexed by the scalar-prefetched block table
    (the vLLM paged-attention idea recast in pallas; reference has no
    counterpart — it is pre-LLM).  grid=(B,), double-buffered manual
    DMA; a lane's loop runs its own ``ceil(length / page_size)`` pages
    and an empty lane none, so a call's time follows the live pages
    (0.5-0.9 us each on the v5e), not the table's width
    (:func:`_paged_attention_kernel`).  Mosaic needs a 128-aligned
    ``h*hd`` for the page DMA: ``models/paged.paged_kernel_static_eligible``
    keeps other geometries off this lane on hardware.
    """
    import jax.numpy as jnp

    if pk.ndim != 4 or pv.ndim != 4:
        raise ValueError(
            "paged_attention_decode reads the whole pool as a 4-d "
            f"(layers, pages, page_size, heads * head_dim) array, got {pk.shape}"
        )
    if page_size != pk.shape[2]:
        raise ValueError(
            f"page_size={page_size} does not match the pool's page dim "
            f"{pk.shape[2]}"
        )

    grouped = pk.shape[3] != q.shape[1] * q.shape[2]
    if grouped and (pk.shape[3] % q.shape[2]
                    or q.shape[1] % (pk.shape[3] // q.shape[2])):
        raise ValueError(
            f"paged_attention_decode: a pool row of {pk.shape[3]} does not "
            f"hold K/V heads of {q.shape[2]} that {q.shape[1]} query heads "
            "divide over")
    if (grouped or starts is not None) and (
            kv_scales is not None or lora is not None):
        raise ValueError(
            "paged_attention_decode: grouped-query heads and a window's "
            "starts take the native pool only (no kv_scales, no lora fold)")
    if kv_scales is not None:
        kv_scales = tuple(jnp.asarray(s, jnp.float32) for s in kv_scales)
    q_scale = 1.0
    if lora is not None:
        *lora, q_scale = lora
        lora = tuple(lora)
    # one jitted function for every layer's call: the chunk programs
    # unroll the layers, and a program that holds the kernel 36 (or
    # 72) times over traces and lowers it ONCE — the layer is a
    # traced scalar, so the calls share one jaxpr.  Traced anew per
    # call the kernel cost ~1.1 s a layer before any compile-cache
    # lookup: 40 s of set-up a chunk shape at 36 layers, in every
    # run (PERF.md §6, PR 27)
    return _stream_decode_jit()(
        q, pk, pv, block_tables, lengths, jnp.asarray(layer, jnp.int32),
        kv_scales, lora,
        **({} if starts is None else {"starts": starts.astype(jnp.int32)}),
        quantized=kv_scales is not None, fold=lora is not None,
        q_scale=float(q_scale), interpret=interpret_mode())


# ---------------------------------------------------------------------------
# latent attention decode (absorbed MLA over a paged pool of latent rows)
# ---------------------------------------------------------------------------

# Tokens one step of the latent kernel's page loop reduces (the stream
# kernel's is 128: ``_pages_per_step``).  A latent row is read once for
# all heads, so a 64-token page is 0.10 us of DMA and 0.05 us of MXU on
# the v5e and a two-page step is mostly the loop's own fixed cost (its
# DMA waits, the flash rescale of a (heads, rank) accumulator).  64
# lanes of 600-2,000 tokens on the v5e, us a live page by step size: 64
# tokens 0.51, 128 0.31, 256 0.23, 512 0.18, 1,024 0.16 (17 -> 56 % of
# the rows' DMA roofline; lanes of 2,000-4,000: 30 -> 67 %; my chip
# runs, PR 30, tools/profile_latent_kernel.py).  A step's last pages
# may be blanks (a lane's length is no multiple of the step): under one
# step's work a lane.  At 1,024 tokens the two page buffers are 2.6 MB
# of VMEM.
LATENT_STEP_TOKENS = 1024


def _latent_pages_per_step(page_size: int, table_width: int,
                           step_tokens: int) -> int:
    return max(1, min(step_tokens // page_size, table_width))


def _latent_attention_kernel(tables_ref, lens_ref, layer_ref, *refs,
                             page_size, rank, group, offset=False,
                             masked=False):
    """One lane of absorbed latent attention: the stream kernel's page
    loop (:func:`_paged_attention_kernel`) over ONE pool whose row is
    ``[c_kv ; k_r]``, read once for all heads.

    Shared with the stream kernel, in the same words: ``grid=(B,)`` in
    order on one core, the whole ``(L, pages, ps, W)`` pool in HBM, the
    layer a scalar-prefetch operand, double-buffered manual DMA of
    ``pool.at[layer, page]``, several pages a step
    (:data:`LATENT_STEP_TOKENS`), a
    ``fori_loop`` over the lane's own ``ceil(length / page_size)`` pages
    (an empty lane writes the neutral state and leaves), and the hand-on
    of the next live lane's first pages through ``turn_ref``.  Not
    shared: there is no block diagonal to build or cut — ``q`` arrives
    ``(heads, W)`` with ``W_uk`` folded in, the heads on the sublanes,
    and a page's scores are one ``(heads, W) x (W, tokens)`` matmul, its
    values one ``(heads, tokens) x (tokens, rank)`` matmul on the row's
    first ``rank`` lanes (the rotary tail is key only); there is one
    pool, so one buffer and one semaphore a page; and **no
    :func:`_split3`**: both small operands ride in the pool's type, one
    bf16 MXU pass each (121 FLOP a byte against the v5e's ridge of 240:
    three passes would make the kernel compute-bound), as the XLA lane
    rounds them.  An f32 pool (the CPU exactness lane) multiplies at
    ``HIGHEST``.  ``W`` is the pool's lane-aligned row (576 values in
    640 lanes, the tail zero in q and in the pool alike): Mosaic slices
    HBM in whole 128-lane tiles, and under the (8, 128) tiling a
    576-wide minor dim occupies 640 lanes of HBM whatever it is
    called.  Heads, row width and rank are the operands': 64 heads on a
    640-lane row of rank 512 and 64 on a 1,152-lane row of rank 1,024
    are one kernel.

    ``offset``: a fourth scalar-prefetch operand ``starts`` ``(B,)``
    gives each lane's first live position (a window's trailing edge):
    the page loop starts at the step that holds it and positions before
    it are masked like those past the length.

    ``masked``: a second blocked operand ``chosen`` ``(1, steps, span)``
    int32, the lane's row of a mask over its table's span cut by the
    loop's steps: a position whose entry is 0 gets weight 0 — a
    selection's rows alone are attended.  The lane's pages are fetched
    by its length all the same; the mask only cuts weights."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if offset:
        starts_ref, *refs = refs
    q_ref, *refs = refs
    if masked:
        chosen_ref, *refs = refs
    pool_hbm, acc_ref, m_ref, l_ref, buf, sems, turn_ref = refs
    # either may leave a whole step without a live position, and either
    # caller's lengths may fall below zero (an idle lane)
    sparse = offset or masked
    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    layer = layer_ref[0]
    width = tables_ref.shape[1]
    heads, row_w = q_ref.shape[1], q_ref.shape[2]
    span = buf.shape[1]  # ``group`` pages of ``page_size`` tokens
    length = lens_ref[b]

    def first_of(lane):
        """The first step of a lane's page loop."""
        if not offset:
            return 0
        # (never past the lane's last step: the step a predecessor
        # started for it is always waited for)
        last = jnp.maximum(
            jax.lax.div(pages_of(lane) + group - 1, group) - 1, 0)
        return jnp.minimum(
            jax.lax.div(jnp.maximum(starts_ref[lane], 0), span), last)
    precision = (jax.lax.Precision.HIGHEST
                 if pool_hbm.dtype == jnp.float32 else None)
    nt_dims = (((1,), (1,)), ((), ()))

    def pages_of(lane):
        pages = jnp.minimum(
            jax.lax.div(lens_ref[lane] + page_size - 1, page_size), width)
        # (a length below zero is an empty lane too: neither branch
        # below would take it, and the hand-on chain would break there)
        return jnp.maximum(pages, 0) if sparse else pages

    def copies(lane, j, slot):
        n = pages_of(lane)
        for g in range(group):
            idx = j * group + g
            page = tables_ref[lane, jnp.clip(idx, 0, n - 1)]
            yield idx < n, pltpu.make_async_copy(
                pool_hbm.at[layer, page],
                buf.at[slot, pl.ds(g * page_size, page_size)],
                sems.at[slot, g])

    def start(lane, j, slot):
        for held, copy in copies(lane, j, slot):
            pl.when(held)(copy.start)

    def wait(j, slot):
        for held, copy in copies(b, j, slot):
            pl.when(held)(copy.wait)

    n_pages = pages_of(b)
    n_steps = jax.lax.div(n_pages + group - 1, group)
    j_first = first_of(b)
    after = jnp.minimum(b + 1, lanes - 1)
    hand_on = (b + 1 < lanes) & (pages_of(after) > 0)

    @pl.when(b == 0)
    def _first():
        turn_ref[0] = 0
        start(0, first_of(0), 0)

    base = turn_ref[0]

    @pl.when(n_pages == 0)
    def _dead():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(hand_on)
        def _hand_on():
            start(after, first_of(after), base)

    @pl.when(n_pages > 0)
    def _live():
        q = q_ref[0]                                   # (heads, W), scaled

        def step(j, carry):
            m_prev, l_prev, acc = carry      # (heads, 1) x 2, (heads, rank)
            slot = jax.lax.rem(base + j - j_first, 2)

            @pl.when(j + 1 < n_steps)
            def _prefetch():
                start(b, j + 1, 1 - slot)

            @pl.when((j + 1 == n_steps) & hand_on)
            def _hand_on():
                start(after, first_of(after), 1 - slot)

            wait(j, slot)
            for g in range(1, group):
                # a page the lane does not hold was not fetched: its
                # weights are 0, and 0 x whatever the buffer held must
                # be 0
                @pl.when(j * group + g >= n_pages)
                def _blank(g=g):
                    buf[slot, pl.ds(g * page_size, page_size)] = jnp.zeros(
                        (page_size, row_w), buf.dtype)
            rows = buf[slot]                           # (span, W)
            latent = rows[:, :rank]
            s = jax.lax.dot_general(
                q, rows, nt_dims, precision=precision,
                preferred_element_type=jnp.float32)           # (heads, span)
            at = j * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
            # (a step may reach past the table where its pages do not
            # divide the table's width, and a lane masked done may hold
            # more than the table it was handed)
            live = at < jnp.minimum(length, width * page_size)
            if offset:
                live &= at >= starts_ref[b]
            if masked:
                live &= chosen_ref[0, pl.ds(j, 1), :] != 0
            s = jnp.where(live, s, -jnp.inf)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            if sparse:  # a step wholly before the window's edge, or
                # one that holds no chosen row
                m_at = jnp.where(m_new == -jnp.inf, 0.0, m_new)
                alpha = jnp.exp(m_prev - m_at)
                w = jnp.exp(s - m_at)
            else:
                alpha = jnp.exp(m_prev - m_new)
                w = jnp.exp(s - m_new)
            l_new = l_prev * alpha + w.sum(axis=1, keepdims=True)
            pv = jnp.dot(w.astype(buf.dtype), latent, precision=precision,
                         preferred_element_type=jnp.float32)  # (heads, rank)
            return m_new, l_new, acc * alpha + pv

        init = (
            jnp.full((heads, 1), -jnp.inf, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, rank), jnp.float32),
        )
        m_fin, l_fin, acc_fin = jax.lax.fori_loop(
            j_first, n_steps, step, init)
        turn_ref[0] = jax.lax.rem(base + n_steps - j_first, 2)
        acc_ref[0] = acc_fin
        m_ref[0] = jnp.broadcast_to(m_fin, m_ref.shape[1:])
        l_ref[0] = jnp.broadcast_to(l_fin, l_ref.shape[1:])


def _given(value) -> tuple:
    """``(value,)``, or ``()`` for None: whether an optional operand was
    handed over is a fact of the call's structure, fixed at trace time,
    not a traced value — a helper, so that a jitted caller spells no
    ternary the jit-purity linter cannot tell from tracer control flow."""
    if value is None:
        return ()
    return (value,)


def _latent_decode(q, pool, block_tables, lengths, layer, starts=None,
                   chosen=None, *, rank, step_tokens, interpret):
    """The latent kernel's ``pallas_call`` on the whole pool (see
    :func:`latent_attention_decode`, which calls it jitted)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, h, W = q.shape
    ps = pool.shape[2]
    group = _latent_pages_per_step(ps, block_tables.shape[1], step_tokens)
    span = group * ps
    q_spec = pl.BlockSpec((1, h, W), lambda b, *prefetch: (b, 0, 0))
    acc_spec = pl.BlockSpec((1, h, rank), lambda b, *prefetch: (b, 0, 0))
    pad_spec = pl.BlockSpec((1, h, 128), lambda b, *prefetch: (b, 0, 0))
    starts = _given(starts)  # a fourth scalar-prefetch operand, or none
    # a second blocked operand, or none: a lane's row of the mask, a row
    # of ``span`` positions a step of its page loop (a dynamic index on
    # the sublanes, where Mosaic takes one); a last step that reaches
    # past the table's span holds 0 there
    steps = -(-block_tables.shape[1] // group)
    chosen = tuple(
        jnp.pad(mask, ((0, 0), (0, steps * span - mask.shape[1]))
                ).reshape(B, steps, span)
        for mask in _given(chosen))
    mask_specs = [pl.BlockSpec((1, steps, span),
                               lambda b, *prefetch: (b, 0, 0))] * len(chosen)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + len(starts),
        grid=(B,),
        in_specs=[q_spec, *mask_specs, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[acc_spec, pad_spec, pad_spec],
        scratch_shapes=[
            pltpu.VMEM((2, span, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2, span // ps)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_latent_attention_kernel, page_size=ps, rank=rank,
                          group=group, offset=len(starts) == 1,
                          masked=len(chosen) == 1),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, h, rank), jnp.float32),
            jax.ShapeDtypeStruct((B, h, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, h, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables, lengths, layer.reshape(1), *starts,
      q.astype(pool.dtype), *chosen, pool)
    return acc, m[:, :, 0], l[:, :, 0]


@functools.lru_cache(maxsize=None)
def _latent_decode_jit():
    import jax

    return jax.jit(_latent_decode, static_argnames=(
        "rank", "step_tokens", "interpret"))


def latent_attention_decode(q, pool, block_tables, lengths, *, layer,
                            page_size, rank, starts=None, chosen=None):
    """Unnormalised flash state of absorbed latent attention (MLA) over
    one layer of a paged pool of latent rows, addressed IN the whole
    pool: :func:`paged_attention_decode`'s twin for a cache whose row is
    ``[c_kv ; k_r]`` and has no V.

    ``q`` ``(B, h, W)`` — the step's queries with ``W_uk`` folded in and
    the rotary part appended, already scaled, zero in the lanes that
    pad the row to ``W``; ``pool`` — the WHOLE ``(L, num_pages, ps, W)``
    pool; ``layer`` a python int or traced
    int32 scalar; ``block_tables`` ``(B, P)``; ``lengths`` ``(B,)``;
    ``rank`` — the row's leading values that are also the value read.
    Returns ``(acc (B, h, rank), m (B, h), l (B, h))`` float32 — what
    ``ops/mla.py ctx_state`` returns for the same rows under the same
    mask; join the step's own row with ``ops/mla.py merge``.  ``starts`` ``(B,)``
    int32 (None: 0): a lane attends positions ``starts .. lengths - 1``
    of its table's span only — a window's live rows — and pages wholly
    before ``starts`` are not read.  ``chosen`` ``(B, P * page_size)``
    bool (None: every position): of those positions a lane attends the
    ones its row of the mask keeps — a selection's rows, each at weight
    exactly 0 or as the flash rule has it; the kernel streams the lane's
    pages by its length whatever the mask says.  Either is a fact of
    the call's structure: without it the kernel traced is the one a
    caller that never heard of it traces.

    A row is read once for all heads: at 64 heads and 512 + 64 values
    it needs 1,152 B and 139,264 FLOP; the DMA moves its 640 lanes,
    1,280 B."""
    import jax.numpy as jnp

    if pool.ndim != 4 or q.shape[-1] != pool.shape[-1]:
        raise ValueError(
            "latent_attention_decode reads the whole pool as a 4-d (layers, "
            f"pages, page_size, row) array of q's row width, got {pool.shape} "
            f"for q {q.shape}")
    if page_size != pool.shape[2]:
        raise ValueError(
            f"page_size={page_size} does not match the pool's page dim "
            f"{pool.shape[2]}")
    span = (q.shape[0], block_tables.shape[1] * page_size)
    if chosen is not None and chosen.shape != span:
        raise ValueError(
            f"chosen masks the table's span {span}, got {chosen.shape}")
    return _latent_decode_jit()(
        q, pool, block_tables, lengths, jnp.asarray(layer, jnp.int32),
        **{name: value.astype(jnp.int32)
           for name, value in (("starts", starts), ("chosen", chosen))
           if value is not None},
        rank=int(rank), step_tokens=LATENT_STEP_TOKENS,
        interpret=interpret_mode())


# ---------------------------------------------------------------------------
# learned sparse attention's indexer, a decode step: the cached keys
# scored where they rest
# ---------------------------------------------------------------------------

# Tokens one step of the indexer's page loop scores, at most (the latent
# kernel's is LATENT_STEP_TOKENS): the pages of a step divide the table's
# width, 32 of a 64-page table and 28 of a 112-page one.  A key page is
# 16 KB, 0.02 us of DMA; a step costs ~0.3 us and ~22 ns a page on the
# v5e, so a longer step spreads more — and fetches more blanks in a lane's
# last.  64 lanes, us a call by step (the share of keys x 256 B over 819
# GB/s): 64 pages at 3,800 keys a lane 512 tokens 240 (32 %), 1,024 176
# (43), 2,048 142 (54), 4,096 127 (60); 112 pages at 3,800 245, 181, 176
# (44), 197; at 7,100 413, 290, 235 (59), 206; at 2,100 152, 126, 136,
# 113 — XLA's gather and product 272 and 640 whatever the lanes hold (my
# chip runs, PR 51, tools/profile_latent_kernel.py --index).  At 2,048
# tokens the two key buffers are 1 MB of VMEM.
INDEX_STEP_TOKENS = 2048


def _index_scores_kernel(tables_ref, lens_ref, layer_ref, q_ref, w_ref,
                         pool_hbm, out_ref, buf, sems, turn_ref, *,
                         page_size, group, scale):
    """One lane of the indexer's scores over its cached keys (``ops/mla.py
    index_scores`` of one query): the latent kernel's page loop
    (:func:`_latent_attention_kernel`: ``grid=(B,)`` in order on one
    core, the whole ``(L, pages, ps, d)`` key pool in HBM, the layer a
    scalar-prefetch operand, double-buffered manual DMA of
    ``pool.at[layer, page]``, ``group`` pages a step, a ``fori_loop``
    over the lane's own ``ceil(length / page_size)`` pages, the hand-on
    of the next live lane's first pages through ``turn_ref``) with the
    scores in place of the flash state: ``(heads, d) x (d, tokens)`` in
    one MXU pass of the pool's type with float32 sums, ReLU, the heads'
    float32 weights ``(heads, 1)``, the sum over the heads and ``x
    scale``, written as the pages' rows of the lane's ``(pages,
    page_size)`` float32 scores, 0.0 at and past the length.

    Not shared with it: a key page is 16 KB, 0.02 us of DMA, and what a
    page costs is its descriptor and its wait on the scalar core.  So a
    step's copies signal ONE semaphore a buffer and the step waits once,
    for the buffer's bytes (a DMA semaphore counts bytes): every step
    fetches ``group`` pages, a last step's blanks as copies of the
    lane's last page, whose scores are not selected.  A lane of length 0
    fetches nothing."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    layer = layer_ref[0]
    width = tables_ref.shape[1]
    span = buf.shape[1]  # ``group`` pages of ``page_size`` tokens
    length = jnp.minimum(lens_ref[b], width * page_size)
    precision = (jax.lax.Precision.HIGHEST
                 if pool_hbm.dtype == jnp.float32 else None)
    nt_dims = (((1,), (1,)), ((), ()))

    def pages_of(lane):
        return jnp.clip(
            jax.lax.div(lens_ref[lane] + page_size - 1, page_size), 0, width)

    def start(lane, j, slot):
        """Step ``j`` of a LIVE lane into buffer ``slot``."""
        last = pages_of(lane) - 1
        for g in range(group):
            page = tables_ref[lane, jnp.minimum(j * group + g, last)]
            pltpu.make_async_copy(
                pool_hbm.at[layer, page],
                buf.at[slot, pl.ds(g * page_size, page_size)],
                sems.at[slot]).start()

    n_pages = pages_of(b)
    n_steps = jax.lax.div(n_pages + group - 1, group)
    after = jnp.minimum(b + 1, lanes - 1)
    hand_on = (b + 1 < lanes) & (pages_of(after) > 0)

    @pl.when(b == 0)
    def _first():
        turn_ref[0] = 0

        @pl.when(n_pages > 0)
        def _own():
            start(0, 0, 0)

    base = turn_ref[0]
    out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((n_pages == 0) & hand_on)
    def _dead():
        start(after, 0, base)

    @pl.when(n_pages > 0)
    def _live():
        q = q_ref[0]                                   # (heads, d)
        w = w_ref[0]                                   # (heads, 1) float32

        def step(j, carry):
            slot = jax.lax.rem(base + j, 2)

            @pl.when(j + 1 < n_steps)
            def _prefetch():
                start(b, j + 1, 1 - slot)

            @pl.when((j + 1 == n_steps) & hand_on)
            def _hand_on():
                start(after, 0, 1 - slot)

            # (the buffer's bytes: the step's copies together)
            pltpu.make_async_copy(
                buf.at[slot], buf.at[slot], sems.at[slot]).wait()
            s = jax.lax.dot_general(
                q, buf[slot], nt_dims, precision=precision,
                preferred_element_type=jnp.float32)           # (heads, span)
            s = (jnp.maximum(s, 0.0) * w).sum(axis=0, keepdims=True) * scale
            at = j * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
            s = jnp.where(at < length, s, 0.0)                # (1, span)
            for g in range(group):
                out_ref[0, pl.ds(j * group + g, 1), :] = (
                    s[:, g * page_size:(g + 1) * page_size])
            return carry

        jax.lax.fori_loop(0, n_steps, step, 0)
        turn_ref[0] = jax.lax.rem(base + n_steps, 2)


def _index_decode(q_idx, w_idx, pool, block_tables, lengths, layer, *,
                  scale, step_tokens, interpret):
    """The indexer kernel's ``pallas_call`` on the whole key pool (see
    :func:`index_scores_decode`, which calls it jitted)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, h, d = q_idx.shape
    ps, P = pool.shape[2], block_tables.shape[1]
    # the most pages a step that divide the table's width: no step
    # reaches past the lane's block of the output
    group = max(g for g in range(1, max(1, step_tokens // ps) + 1) if P % g == 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, h, d), lambda b, *prefetch: (b, 0, 0)),
                  pl.BlockSpec((1, h, 1), lambda b, *prefetch: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, P, ps), lambda b, *prefetch: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, group * ps, d), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_index_scores_kernel, page_size=ps, group=group,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, P, ps), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="index_scores_decode",
    )(block_tables, lengths, layer.reshape(1), q_idx.astype(pool.dtype),
      w_idx.astype(jnp.float32)[..., None], pool)


@functools.lru_cache(maxsize=None)
def _index_decode_jit():
    import jax

    return jax.jit(_index_decode, static_argnames=(
        "scale", "step_tokens", "interpret"))


def index_scores_decode(q_idx, w_idx, idx_pool, block_tables, lengths, *,
                        layer, page_size, scale):
    """A decode step's indexer scores of every cached key of its lane
    (``ops/mla.py index_scores`` of one query a lane over the keys its
    block table names), the keys read where they rest: the WHOLE
    ``(L, num_pages, ps, d)`` key pool stays in HBM and a lane's page
    loop fetches its own ``ceil(length / ps)`` pages
    (:func:`latent_attention_decode`'s twin for the indexer's pool).

    ``q_idx`` ``(B, heads, d)`` in the pool's type, ``w_idx`` ``(B,
    heads)`` float32, ``block_tables`` ``(B, P)``, ``lengths`` ``(B,)``,
    ``layer`` a python int or traced int32 scalar.  Returns ``(B, P,
    ps)`` float32 — three dims, the table's span a page a row; reshape
    to ``(B, P * ps)`` for ``ops/mla.py step_mask`` — with 0.0 at and
    past a lane's length.  Same operands, same types and one MXU pass as
    the einsum; only the order of the sum over the heads may differ.

    A key is read once: at 64 heads of 128 it needs 256 B and 16,512
    FLOP."""
    import jax.numpy as jnp

    if idx_pool.ndim != 4 or q_idx.shape[-1] != idx_pool.shape[-1]:
        raise ValueError(
            "index_scores_decode reads the whole key pool as a 4-d (layers, "
            f"pages, page_size, d) array of q's width, got {idx_pool.shape} "
            f"for q {q_idx.shape}")
    if page_size != idx_pool.shape[2]:
        raise ValueError(
            f"page_size={page_size} does not match the pool's page dim "
            f"{idx_pool.shape[2]}")
    return _index_decode_jit()(
        q_idx, w_idx, idx_pool, block_tables, lengths,
        jnp.asarray(layer, jnp.int32), scale=float(scale),
        step_tokens=INDEX_STEP_TOKENS, interpret=interpret_mode())
