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
import logging
from typing import Tuple

import numpy as np

logger = logging.getLogger(__name__)

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
                  block_q: int, block_k: int, n_kv: int, causal: bool,
                  scale: float, valid_k: int):
    """Grid cell (batch*head, q-block, kv-block): the kv axis is the
    innermost grid dimension, so the online-softmax carry lives in VMEM
    scratch across kv steps — KV streams block-by-block from HBM and
    VMEM holds O(block_q * d + block_k * d), independent of sequence
    length (the standard TPU flash-attention shape)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: kv blocks entirely beyond this q block contribute nothing
    needed = jnp.logical_or(
        jnp.logical_not(causal), j * block_k <= (qi + 1) * block_q - 1
    )

    @pl.when(needed)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = q @ k.T
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            s = jnp.where(k_pos > q_pos, -jnp.inf, s)
        if valid_k % block_k:  # tail block carries sequence padding
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
    are block-padded (padded keys masked in-kernel); only cross-length
    causal falls back to the einsum path.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from seldon_core_tpu.parallel.ring_attention import plain_attention

    b, sq, h, d = q.shape
    sk = k.shape[1]
    if causal and sq != sk:
        # cross-length causal has no absolute-position convention here
        return plain_attention(q, k, v, causal=causal)
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
        _flash_kernel, block_q=block_q, block_k=block_k, n_kv=n_kv,
        causal=causal, scale=1.0 / float(np.sqrt(d)), valid_k=valid_k,
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


def flash_attn_fn(block_q: int = 128, block_k: int = 128):
    """Drop-in ``attn_fn`` for the transformer family."""

    def fn(q, k, v, causal: bool = False):
        return flash_attention(q, k, v, causal=causal, block_q=block_q, block_k=block_k)

    return fn


# ---------------------------------------------------------------------------
# paged attention decode (flash-decoding over a paged K/V pool)
# ---------------------------------------------------------------------------

def _paged_decode_kernel(tables_ref, lens_ref, *refs, page_size,
                         quantized=False):
    """One (slot, page) grid step of online-softmax decode attention.

    The page block arrives via a block-table-indexed BlockSpec (scalar
    prefetch), so each grid step DMAs exactly one page from HBM —
    the (B, P, ps, h, hd) gathered copy the XLA path materialises per
    layer per step never exists.  acc/m/l are outputs revisited across
    the page dimension (flash carry), emitted unnormalised for the
    caller to merge with the current-token term.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if quantized:
        sk_ref, sv_ref, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref = refs

    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    length = lens_ref[b]
    start = p * page_size

    @pl.when(start < length)
    def _step():
        q = q_ref[0].astype(jnp.float32)          # (h, hd), pre-scaled
        k = k_ref[0].astype(jnp.float32)          # (ps, h, hd)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # int8 pages dequantise in-register: one f32 scale per page
            # per k/v, scalar-prefetched next to the block table
            k = k * sk_ref[tables_ref[b, p]]
            v = v * sv_ref[tables_ref[b, p]]
        # Mosaic has no batched-dot lowering — broadcast-multiply-
        # reduce on the VPU instead; the (h, ps, hd) intermediate is
        # ~128 KB of VMEM and the page DMA dominates regardless
        kt = k.transpose(1, 0, 2)                 # (h, ps, hd)
        s = (q[:, None, :] * kt).sum(axis=2)      # (h, ps)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        s = jnp.where(pos < length, s, -jnp.inf)
        # m/l carries are lane-padded to (h, 128) — Mosaic requires the
        # last block dim be 128-divisible (or the full array dim);
        # column 0 is the value, the broadcast keeps every lane equal
        m_prev = m_ref[0, :, 0]                   # (h,)
        l_prev = l_ref[0, :, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        w = jnp.exp(s - m_new[:, None])           # (h, ps)
        m_ref[0] = jnp.broadcast_to(m_new[:, None], m_ref.shape[1:])
        l_ref[0] = jnp.broadcast_to(
            (l_prev * alpha + w.sum(axis=1))[:, None], l_ref.shape[1:]
        )
        vt = v.transpose(1, 0, 2)                 # (h, ps, hd)
        pv_dot = (w[:, :, None] * vt).sum(axis=1)  # (h, hd)
        acc_ref[0] = acc_ref[0] * alpha[:, None] + pv_dot


def _paged_decode_kernel_stream(tables_ref, lens_ref, layer_ref, *refs,
                                page_size, heads, head_dim, quantized=False,
                                fold_lora=False, q_scale=1.0):
    """One slot of streaming flash-decoding: grid=(B,), the WHOLE
    ``(L, pages, ps, h*hd)`` K/V pools stay in HBM and each slot's live
    pages arrive via double-buffered manual DMA of
    ``pool.at[layer, page]`` — the layer is a scalar-prefetch operand,
    so every layer of a model runs the same compiled kernel and no
    layer of the pool is ever sliced out for it.

    The design motivation vs the (B, P) grid kernel: that kernel pays a
    Mosaic grid-step per (slot, page) — B x P x layers ~ 1,000 grid
    steps per decode step — and its BlockSpec fetches every page in the
    sliced table even past ``length`` (pl.when skips the compute, not
    the DMA).  Here the page loop is ``pl.when``-guarded per slot, so
    short streams stop paying max-length HBM traffic, and the next
    page's DMA overlaps the current page's compute.  It compiles under
    Mosaic on the v5e with jax 0.9.0 and agrees with a host float64
    oracle in every variant (tools/probe_kernels.py); its speed against
    the grid kernel and XLA's gather is not measured on the current
    code (ROADMAP S1).

    Everything stays in the pool's flattened (ps, h*hd) layout — Mosaic
    supports neither value shape-casts nor batched dots, so the
    per-head score/weighted-sum contractions are done as block-diagonal
    MXU matmuls: ``s = k @ QB`` with QB[r, c] = q[c, r - c*hd] masked to
    its head's block, and the weighted value sum via ``w @ E`` where
    E[c, r] = [r // hd == c] expands per-head weights across lanes.

    r18 extensions, both trace-time static flags so the base program is
    byte-identical with them off:

    * ``quantized`` — the pool stores int8 pages with one f32 scale per
      page per k/v; the scale tables ride the scalar prefetch next to
      the block table and pages dequantise in-register after the DMA.
    * ``fold_lora`` — the per-lane qkv LoRA BGMV delta computes INSIDE
      this launch: the lane's adapter slot id (scalar prefetch) indexes
      the factor pools in HBM, one DMA brings the lane's (r, D)/(r, 3D)
      factors into VMEM, two VPU reductions produce the (3D,) delta,
      the q third folds into the scores in-register (``q_scale`` is the
      1/sqrt(hd) the caller already applied to q), and the RAW delta
      emits as a fourth output for the caller's self-term and pool
      write.  Slot 0 holds zero factors, so no-adapter lanes compute an
      exact 0.0 delta through the same program.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pos = 0
    if quantized:
        sk_ref, sv_ref = refs[0], refs[1]
        pos = 2
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
    delta_ref = refs[pos + 5] if fold_lora else None

    b = pl.program_id(0)
    layer = layer_ref[0]
    h, hd = heads, head_dim
    D = h * hd
    length = lens_ref[b]
    n_pages = jax.lax.div(length + page_size - 1, page_size)

    def body(k_scratch, v_scratch, sems, a_scr=None, b_scr=None, lsems=None):
        def dma(pool, scratch, slot, i, which):
            return pltpu.make_async_copy(
                pool.at[layer, tables_ref[b, i]], scratch.at[slot],
                sems.at[slot, which],
            )

        if fold_lora:
            # the lane's factor rows start streaming before the first
            # page DMA — the slot-index gather rides the same scalar
            # prefetch as the block table
            lane = adapter_ref[b]
            cp_a = pltpu.make_async_copy(
                a_hbm.at[layer, lane], a_scr, lsems.at[0])
            cp_b = pltpu.make_async_copy(
                b_hbm.at[layer, lane], b_scr, lsems.at[1])
            cp_a.start()
            cp_b.start()

        @pl.when(n_pages > 0)
        def _warmup():
            dma(pk_hbm, k_scratch, 0, 0, 0).start()
            dma(pv_hbm, v_scratch, 0, 0, 1).start()

        qflat = q_ref[0, 0].astype(jnp.float32)       # (D,), pre-scaled
        if fold_lora:
            cp_a.wait()
            cp_b.wait()
            xflat = x_ref[0, 0].astype(jnp.float32)   # (D,) block input
            # BGMV on the VPU: t = A[lane]^T x (rank,), delta = t B[lane]
            t = (a_scr[...].astype(jnp.float32) * xflat[None, :]).sum(axis=1)
            delta = (t[:, None] * b_scr[...].astype(jnp.float32)).sum(axis=0)
            delta_ref[0, 0] = delta                   # (3D,) raw, unscaled
            qflat = qflat + q_scale * delta[:D]
        # block-diagonal projectors, built once per slot
        r_over = jax.lax.broadcasted_iota(jnp.int32, (D, h), 0) // hd
        c_idx = jax.lax.broadcasted_iota(jnp.int32, (D, h), 1)
        qb = jnp.where(r_over == c_idx, qflat[:, None], 0.0)      # (D, h)
        e_r = jax.lax.broadcasted_iota(jnp.int32, (h, D), 1) // hd
        e_c = jax.lax.broadcasted_iota(jnp.int32, (h, D), 0)
        expand = jnp.where(e_r == e_c, 1.0, 0.0)                  # (h, D)

        max_pages = tables_ref.shape[1]

        def loop(i, carry):
            m_prev, l_prev, acc = carry               # (h,), (h,), (D,)
            slot = jax.lax.rem(i, 2)
            nxt = jax.lax.rem(i + 1, 2)

            @pl.when(i + 1 < n_pages)
            def _prefetch():
                dma(pk_hbm, k_scratch, nxt, i + 1, 0).start()
                dma(pv_hbm, v_scratch, nxt, i + 1, 1).start()

            @pl.when(i < n_pages)
            def _wait():
                dma(pk_hbm, k_scratch, slot, i, 0).wait()
                dma(pv_hbm, v_scratch, slot, i, 1).wait()

            k = k_scratch[slot].astype(jnp.float32)   # (ps, D)
            v = v_scratch[slot].astype(jnp.float32)
            if quantized:
                # per-page dequant in-register (scales scalar-prefetched)
                k = k * sk_ref[layer, tables_ref[b, i]]
                v = v * sv_ref[layer, tables_ref[b, i]]
            # HIGHEST: a default-precision f32 dot runs as bf16 MXU
            # passes and costs ~0.05 absolute score error (measured
            # against a float64 host reference; the grid kernel's VPU
            # reduce is exact) — these dots are tiny, so full precision
            # is free
            s = jnp.dot(k, qb, preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)  # (ps, h)
            pos = i * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (page_size, 1), 0)
            s = jnp.where(pos < length, s, -jnp.inf)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))         # (h,)
            alpha = jnp.exp(m_prev - m_new)
            w = jnp.exp(s - m_new[None, :])           # (ps, h); dead rows 0
            l_new = l_prev * alpha + w.sum(axis=0)
            w_exp = jnp.dot(w, expand, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
            alpha_exp = jnp.dot(alpha[None, :], expand,
                                preferred_element_type=jnp.float32,
                                precision=jax.lax.Precision.HIGHEST)[0]
            acc = acc * alpha_exp + (v * w_exp).sum(axis=0)         # (D,)
            return m_new, l_new, acc

        def guarded(i, carry):
            # static trip count (Mosaic pipelines it far better than a
            # data-dependent bound); masked iterations skip BOTH the
            # DMA and the flash update
            new = loop(i, carry)
            keep = i < n_pages
            return tuple(
                jnp.where(keep, n, c) for n, c in zip(new, carry)
            )

        init = (
            jnp.full((h,), -jnp.inf, jnp.float32),
            jnp.zeros((h,), jnp.float32),
            jnp.zeros((D,), jnp.float32),
        )
        m_fin, l_fin, acc_fin = jax.lax.fori_loop(0, max_pages, guarded, init)
        acc_ref[0, 0] = acc_fin
        # m/l lane-padded to (h, 128): Mosaic wants 128-divisible last
        # block dims; every lane carries the same value
        m_ref[0] = jnp.broadcast_to(m_fin[:, None], m_ref.shape[1:])
        l_ref[0] = jnp.broadcast_to(l_fin[:, None], l_ref.shape[1:])

    pool_dtype = pk_hbm.dtype
    scope = dict(
        k_scratch=pltpu.VMEM((2, page_size, D), pool_dtype),
        v_scratch=pltpu.VMEM((2, page_size, D), pool_dtype),
        sems=pltpu.SemaphoreType.DMA((2, 2)),
    )
    if fold_lora:
        rank = a_hbm.shape[2]
        scope.update(
            a_scr=pltpu.VMEM((rank, D), a_hbm.dtype),
            b_scr=pltpu.VMEM((rank, 3 * D), b_hbm.dtype),
            lsems=pltpu.SemaphoreType.DMA((2,)),
        )
    pl.run_scoped(body, **scope)


def paged_kernel_impl(heads: int, head_dim: int) -> str:
    """The decode-kernel implementation that will serve this geometry —
    the env choice (``SELDON_TPU_PAGED_KERNEL_IMPL``) plus the Mosaic
    alignment fallback: the stream kernel DMAs (ps, h*hd) page slices
    and Mosaic requires a 128-aligned minor dim, so tiny models
    (h*hd % 128 != 0) take the grid kernel on hardware.  Callers that
    gate stream-only features (the in-kernel LoRA fold) resolve through
    here so they cannot disagree with :func:`paged_attention_decode`."""
    from seldon_core_tpu.runtime import knobs

    impl = knobs.raw("SELDON_TPU_PAGED_KERNEL_IMPL", "stream")
    if impl == "stream" and (heads * head_dim) % 128 != 0 and not interpret_mode():
        logger.warning(
            "paged decode kernel: stream impl needs a 128-aligned h*hd, got "
            "%d x %d — serving this geometry with the grid impl",
            heads, head_dim,
        )
        return "grid"
    return impl


def paged_attention_decode(q, pk, pv, block_tables, lengths, *, layer,
                           page_size, kv_scales=None, lora=None):
    """Unnormalised flash state of decode attention over one layer of a
    paged pool, addressed IN the whole pool.

    ``q`` (B, h, hd) — current-step queries, already scaled;
    ``pk``/``pv`` — the WHOLE pools, in the layout the serving impl
    reads: ``(L, num_pages, ps, h*hd)`` flat for ``stream``,
    ``(L, num_pages, ps, h, hd)`` split for ``grid``
    (``models/paged.pool_is_flat`` makes the same choice for the pool
    at rest); ``layer`` — which layer of them to attend over, a python
    int or a traced int32 scalar; ``block_tables`` (B, P); ``lengths``
    (B,) cached token counts.  Returns ``(acc, m, l)`` f32 — merge with
    the in-segment term via the usual flash rule.

    Why the whole pool: on the v5e a ``pool[layer]`` slice is an 84 MB
    copy at GPT-2-large size, and re-laying a split layer to the flat
    ``(ps, h*hd)`` form the stream kernel DMAs is another (a minor-dims
    reshape is NOT free under the (8, 128) tiling: it was
    ``copy_bf16_513_64_1280_``, 12 % of device time; PERF.md §6, PR 25).
    The stream kernel therefore takes the pool in ``pl.ANY`` and the
    layer as a scalar-prefetch operand — one compiled kernel for every
    layer — and DMAs ``pool.at[layer, page]``.

    ``kv_scales`` (r18): ``(sk, sv)`` per-page f32 scale tables
    ``(L, num_pages)`` for an int8 pool, indexed ``[layer, page]`` like
    the pool — pages dequantise in-register inside the online-softmax
    loop (no dequantised copy of the cache ever exists in HBM).

    ``lora`` (r18, stream impl only): ``(x, a_T, b, adapter_idx,
    q_scale)`` folds the per-lane qkv BGMV delta into the same launch —
    ``x`` (B, d) block inputs, ``a_T`` (L, slots, r, d) TRANSPOSED first
    factors (the DMA wants the 128-aligned d minor), ``b`` (L, slots, r,
    3d), both indexed ``[layer, slot]``, ``adapter_idx`` (B,) int32 slot
    ids, ``q_scale`` the static 1/sqrt(hd) already applied to q.  The
    return grows a fourth element: the raw (B, 3d) f32 delta for the
    caller's self-term and pool write.

    TPU-first replacement for the ``pk[layer][block_tables]`` gather in
    ``PagedTransformerBlock`` (models/paged.py): the gather copies the
    whole live cache through HBM per layer per step; here pages stream
    HBM->VMEM, indexed by the scalar-prefetched block table
    (the vLLM paged-attention idea recast in pallas; reference has no
    counterpart — it is pre-LLM).

    Two implementations, selected by ``SELDON_TPU_PAGED_KERNEL_IMPL``:

    * ``stream`` (default) — grid=(B,), double-buffered manual DMA,
      page loop bounded by each slot's own length.
    * ``grid`` — the original (B, P) grid with block-table BlockSpecs
      over ONE layer of the split pool (sliced here, as its caller did
      before); kept for A/B measurement (tools/profile_paged_kernel.py).
    """
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, h, hd = q.shape
    P = block_tables.shape[1]
    ps = pk.shape[2]
    if page_size != ps:
        raise ValueError(
            f"page_size={page_size} does not match the pool's page dim {ps}"
        )

    quantized = kv_scales is not None
    if quantized:
        sk, sv = kv_scales
        sk = jnp.asarray(sk, jnp.float32)
        sv = jnp.asarray(sv, jnp.float32)

    impl = paged_kernel_impl(h, hd)
    if lora is not None and impl != "stream":
        raise ValueError(
            "paged_attention_decode: the in-kernel LoRA fold is a stream-impl "
            f"feature but paged_kernel_impl resolved to {impl!r} — callers "
            "must gate the fold on paged_kernel_impl(heads, head_dim)"
        )

    if impl not in ("stream", "grid"):
        raise ValueError(
            f"unknown SELDON_TPU_PAGED_KERNEL_IMPL {impl!r}: use 'stream' or 'grid'"
        )
    want_ndim = 4 if impl == "stream" else 5
    if pk.ndim != want_ndim or pv.ndim != want_ndim:
        raise ValueError(
            f"paged_attention_decode: the {impl} impl reads the whole pool "
            f"as a {want_ndim}-d array, got {pk.shape} — the pool rests in "
            "the layout models/paged.pool_is_flat picks for the serving impl"
        )

    if impl == "stream":
        D = h * hd
        fold = lora is not None
        scalar_args = [
            block_tables, lengths, jnp.asarray(layer, jnp.int32).reshape(1)]
        n_prefetch = 3
        if quantized:
            scalar_args += [sk, sv]
            n_prefetch += 2
        if fold:
            x, a_T, b_f, adapter_idx, q_scale = lora
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
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(B,),
            in_specs=in_specs,
            out_specs=out_specs,
        )
        kernel = functools.partial(
            _paged_decode_kernel_stream, page_size=ps, heads=h, head_dim=hd,
            quantized=quantized, fold_lora=fold,
            q_scale=float(q_scale) if fold else 1.0)
        outs = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret_mode(),
        )(*scalar_args, *tensor_args)
        acc, m, l = outs[0], outs[1], outs[2]
        res = (acc.reshape(B, h, hd), m[:, :, 0], l[:, :, 0])
        if fold:
            res = res + (outs[3].reshape(B, 3 * D),)
        return res

    pk, pv = pk[layer], pv[layer]
    scalar_args = [block_tables, lengths]
    n_prefetch = 2
    if quantized:
        scalar_args += [sk[layer], sv[layer]]
        n_prefetch += 2
    lane2 = lambda b, p, *prefetch: (b, 0, 0)  # noqa: E731
    page2 = lambda b, p, *prefetch: (prefetch[0][b, p], 0, 0, 0)  # noqa: E731
    pad2 = lambda b, p, *prefetch: (b, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, h, hd), lane2),
            pl.BlockSpec((1, ps, h, hd), page2),
            pl.BlockSpec((1, ps, h, hd), page2),
        ],
        out_specs=[
            pl.BlockSpec((1, h, hd), lane2),
            pl.BlockSpec((1, h, 128), pad2),
            pl.BlockSpec((1, h, 128), pad2),
        ],
    )
    kernel = functools.partial(
        _paged_decode_kernel, page_size=ps, quantized=quantized)
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, h, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, h, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, h, 128), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(*scalar_args, q, pk, pv)
    return acc, m[:, :, 0], l[:, :, 0]
