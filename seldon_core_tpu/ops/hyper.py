"""A residual of several rows, mixed round every sub-layer:
manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections, arXiv:2409.19606) as Xing4.0 configures them.

A token's residual is ``X`` in ``R^{n x C}`` (``n`` = ``hc_mult`` rows of
``d_model``), float32.  A sub-layer ``F`` (an attention or an FFN, its
own pre-norm inside it) with parameters ``phi`` ``(2n + n^2, nC)`` (a
coefficient a row: the paper's matrix transposed, so that no program
re-lays it), ``bias`` ``(2n + n^2,)`` and ``scale`` ``(3,)`` (alpha_pre,
alpha_post, alpha_res), all float32:

    x~     = vec(X) / sqrt(mean(vec(X)^2) + eps)          (no scale of its own)
    H~     = alpha * (x~ phi) + bias                      (pre n | post n | res n x n)
    H_pre  = sigmoid(H~_pre)
    H_post = 2 sigmoid(H~_post)
    H_res  = SK(clip(H~_res, lo, hi)):  M = exp(.), then ``iters`` times
             M <- M / (rowsum M + eps);  M <- M / (colsum M + eps)
    X'     = H_res X + H_post^T (x) F(H_pre X)

:func:`hyper_pre` gives ``(H_pre X, H_post, H_res)``, :func:`hyper_post`
``X'``.  Both take the rows **stream-major**, ``(n, ..., C)``: each row
is then a plain ``(positions, C)`` matrix under the TPU's (8, 128)
tiling, where ``n`` = 4 as a second-minor dim would pad to 8 sublanes.

Each reads the rows once.  ``x~ phi`` is ``(vec(X) phi)`` times the
norm's reciprocal, so the projection and the sum of squares share one
pass; on a TPU both are Pallas kernels tiled over positions
(``hyper_pre_mix`` / ``hyper_post_mix``): the projection on the MXU at
``HIGHEST`` with the positions on the lanes of its output, the Sinkhorn
of a 4 x 4 matrix as sixteen lane vectors in registers, the coefficients
handed on in one ``(positions, 128)`` array.  Elsewhere (the CPU, a
stream count whose coefficients pass 128) XLA's form of the same
arithmetic.  Their first outputs are three-dimensional, ``(1, positions,
C)`` and ``(n, positions, C)``: what the benchmark's readers know them
by (a two-dimensional Pallas output is a grouped matmul to them).
"""

from __future__ import annotations

import functools

PRE_SCOPE = "seldon.hyper.pre"
POST_SCOPE = "seldon.hyper.post"

# positions a grid step of either kernel takes: the lanes of one vreg
# row, and a square (128, 128) transpose of the coefficients
TILE_T = 128
# the post kernel also cuts the rows' width (its arithmetic is
# elementwise in it): four rows in and out of (128, 512) float32, twice
# for the pipeline, are 4 MB of VMEM
POST_TILE_C = 512
# lanes of the coefficients' array: n pre, n post, n x n res, zeros
COEF_LANES = 128


def coefficients(n: int) -> int:
    """Rows of ``phi``: ``n`` pre, ``n`` post and ``n x n`` res."""
    return 2 * n + n * n


def backend() -> str:
    """``jax.default_backend()``; a test answers ``"interpret"`` to run
    the kernels under the Pallas interpreter off a TPU."""
    import jax

    return jax.default_backend()


def hyper_impl(n: int, where=None) -> str:
    """``"pallas"`` (a TPU, or the interpreter where a test asks for it)
    | ``"xla"``."""
    where = backend() if where is None else where
    return ("pallas" if where in ("tpu", "interpret")
            and coefficients(n) <= COEF_LANES else "xla")


def _check(x):
    n = x.shape[0]
    if n < 2:
        raise ValueError(
            f"hyper-connections over {n} stream: a residual of one row has "
            "nothing to mix (hc_mult is at least 2)")
    return n


def sinkhorn(logits, iters: int, eps: float, lo: float, hi: float):
    """``(..., n, n)`` float32 logits -> the matrix after ``iters``
    row-then-column normalisations of ``exp(clip(logits))``."""
    import jax
    import jax.numpy as jnp

    m = jnp.exp(jnp.clip(logits.astype(jnp.float32), lo, hi))

    def step(_, m):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        return m / (m.sum(axis=-2, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, step, m)


def _columns(params, n: int):
    """``(alpha, bias)`` a coefficient each, float32 ``(2n + n^2,)``."""
    import jax.numpy as jnp
    import numpy as np

    alpha = jnp.repeat(params["scale"].astype(jnp.float32),
                       np.array([n, n, n * n]), total_repeat_length=coefficients(n))
    return alpha, params["bias"].astype(jnp.float32)


def hyper_pre(x, params, *, iters: int, eps: float, lo: float, hi: float,
              impl=None):
    """``x`` ``(n, ..., C)`` float32 -> ``(h (..., C), h_post (..., n),
    h_res (..., n, n))`` float32: the row the sub-layer reads and the
    coefficients it writes back through.  ``params``: ``phi`` ``(2n +
    n^2, nC)``, ``bias`` ``(2n + n^2,)``, ``scale`` ``(3,)``."""
    import jax
    import jax.numpy as jnp

    n = _check(x)
    lead, c = x.shape[1:-1], x.shape[-1]
    rows = x.astype(jnp.float32).reshape(n, -1, c)
    impl = hyper_impl(n) if impl is None else impl
    with jax.named_scope(PRE_SCOPE):
        if impl == "pallas":
            h, coef = _pre_pallas(rows, params, iters=iters, eps=eps, lo=lo,
                                  hi=hi, interpret=backend() != "tpu")
            h_post = coef[:, n:2 * n]
            h_res = coef[:, 2 * n:coefficients(n)].reshape(-1, n, n)
        else:
            h, h_post, h_res = _pre_xla(rows, params, iters, eps, lo, hi)
    return (h.reshape(*lead, c), h_post.reshape(*lead, n),
            h_res.reshape(*lead, n, n))


def hyper_post(x, y, h_post, h_res, *, impl=None):
    """``X' = H_res X + H_post^T (x) y``: ``x`` ``(n, ..., C)`` float32,
    ``y`` ``(..., C)`` the sub-layer's output (any float type), the
    coefficients :func:`hyper_pre`'s; ``(n, ..., C)`` float32."""
    import jax
    import jax.numpy as jnp

    n = _check(x)
    shape, c = x.shape, x.shape[-1]
    rows = x.astype(jnp.float32).reshape(n, -1, c)
    out = y.reshape(-1, c)
    post = h_post.astype(jnp.float32).reshape(-1, n)
    res = h_res.astype(jnp.float32).reshape(-1, n, n)
    impl = hyper_impl(n) if impl is None else impl
    with jax.named_scope(POST_SCOPE):
        if impl == "pallas":
            t = rows.shape[1]
            coef = jnp.concatenate([
                jnp.zeros((t, n), jnp.float32), post, res.reshape(t, n * n),
                jnp.zeros((t, COEF_LANES - coefficients(n)), jnp.float32)],
                axis=-1)
            mixed = _post_pallas(rows, out, coef, interpret=backend() != "tpu")
        else:
            mixed = jnp.stack([
                sum(res[:, i, j, None] * rows[j] for j in range(n))
                + post[:, i, None] * out.astype(jnp.float32)
                for i in range(n)])
    return mixed.reshape(shape)


def _pre_xla(rows, params, iters, eps, lo, hi):
    import jax
    import jax.numpy as jnp

    n, _t, c = rows.shape
    phi = params["phi"].astype(jnp.float32).reshape(coefficients(n), n, c)
    raw = jnp.einsum("ntc,knc->tk", rows, phi,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    inv = jax.lax.rsqrt((rows * rows).sum(axis=(0, 2)) / (n * c) + eps)
    alpha, bias = _columns(params, n)
    logits = raw * inv[:, None] * alpha + bias
    h_pre = jax.nn.sigmoid(logits[:, :n])
    h_post = 2.0 * jax.nn.sigmoid(logits[:, n:2 * n])
    h_res = sinkhorn(logits[:, 2 * n:].reshape(-1, n, n), iters, eps, lo, hi)
    h = sum(h_pre[:, i, None] * rows[i] for i in range(n))
    return h, h_post, h_res


def _tile(t: int) -> int:
    """Positions a grid step takes of ``t``: :data:`TILE_T`, or all of a
    call smaller than that in whole sublane tiles of 8."""
    return TILE_T if t >= TILE_T else -(-t // 8) * 8


def _pad_positions(a, axis: int, to: int):
    import jax.numpy as jnp

    short = to - a.shape[axis]
    if not short:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, short)
    return jnp.pad(a, pad)


def _pre_kernel(x_ref, phit_ref, alpha_ref, bias_ref, h_ref, coef_ref, *,
                n, iters, eps, lo, hi):
    """One tile of positions: ``x_ref`` ``(n, tT, C)``, ``phit_ref``
    ``(K, n C)``, ``alpha_ref`` / ``bias_ref`` ``(K, 1)``; writes the
    read row ``h_ref`` ``(1, tT, C)`` and the coefficients ``coef_ref``
    ``(tT, 128)`` (pre, post, res row-major, zeros)."""
    import jax
    import jax.numpy as jnp

    k = coefficients(n)
    tile, c = x_ref.shape[1], x_ref.shape[2]
    raw = jnp.zeros((k, tile), jnp.float32)
    sq = jnp.zeros((tile, 1), jnp.float32)
    for i in range(n):
        xi = x_ref[i]
        # positions on the lanes of the output: (K, C) x (tT, C)^T
        raw = raw + jax.lax.dot_general(
            phit_ref[:, i * c:(i + 1) * c], xi, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        sq = sq + jnp.sum(xi * xi, axis=-1, keepdims=True)
    # the norm's reciprocal, a lane a position like ``raw``
    inv = jax.lax.rsqrt(
        jnp.broadcast_to(sq, (tile, COEF_LANES)).T[0:1, :] / (n * c) + eps)
    logits = raw * inv * alpha_ref[...] + bias_ref[...]
    pre = jax.nn.sigmoid(logits[0:n])
    post = 2.0 * jax.nn.sigmoid(logits[n:2 * n])
    m = tuple(jnp.exp(jnp.clip(logits[2 * n + e:2 * n + e + 1], lo, hi))
              for e in range(n * n))

    def step(_, m):
        # (a reciprocal a sum and a product an entry: a float32 divide is
        # a dozen instructions on the VPU, and these vectors fill an
        # eighth of a register each)
        m = list(m)
        for i in range(n):      # a row's entries over their sum
            r = 1.0 / (sum(m[i * n:(i + 1) * n]) + eps)
            m[i * n:(i + 1) * n] = [v * r for v in m[i * n:(i + 1) * n]]
        for j in range(n):      # then a column's
            r = 1.0 / (sum(m[j::n]) + eps)
            m[j::n] = [v * r for v in m[j::n]]
        return tuple(m)

    m = jax.lax.fori_loop(0, iters, step, m)
    coef = jnp.concatenate(
        [pre, post, *m, jnp.zeros((COEF_LANES - k, tile), jnp.float32)],
        axis=0).T                                              # (tT, 128)
    coef_ref[...] = coef
    h = coef[:, 0:1] * x_ref[0]
    for i in range(1, n):
        h = h + coef[:, i:i + 1] * x_ref[i]
    h_ref[0] = h


def _pre_pallas(rows, params, *, iters, eps, lo, hi, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, t, c = rows.shape
    k = coefficients(n)
    tile = _tile(t)
    padded = -(-t // tile) * tile
    rows = _pad_positions(rows, 1, padded)
    phit = params["phi"].astype(jnp.float32)
    alpha, bias = _columns(params, n)
    # the rows' block and the read row twice (the pipeline's two
    # buffers), phi^T twice, the coefficients
    vmem = 4 * (2 * (n + 1) * tile * c + 2 * (-(-k // 8) * 8) * n * c
                + 8 * tile * COEF_LANES)
    h, coef = pl.pallas_call(
        functools.partial(_pre_kernel, n=n, iters=iters, eps=eps, lo=lo, hi=hi),
        grid=(padded // tile,),
        in_specs=[pl.BlockSpec((n, tile, c), lambda i: (0, i, 0)),
                  pl.BlockSpec((k, n * c), lambda i: (0, 0)),
                  pl.BlockSpec((k, 1), lambda i: (0, 0)),
                  pl.BlockSpec((k, 1), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((1, tile, c), lambda i: (0, i, 0)),
                   pl.BlockSpec((tile, COEF_LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, padded, c), jnp.float32),
                   jax.ShapeDtypeStruct((padded, COEF_LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=min(vmem + (16 << 20), 120 << 20)),
        interpret=interpret,
        name="hyper_pre_mix",
    )(rows, phit, alpha[:, None], bias[:, None])
    return h[0, :t], coef[:t]


def _post_kernel(x_ref, y_ref, coef_ref, o_ref, *, n):
    """``x_ref`` / ``o_ref`` ``(n, tT, tC)``, ``y_ref`` ``(tT, tC)``,
    ``coef_ref`` ``(tT, 128)``."""
    import jax.numpy as jnp

    coef = coef_ref[...]
    y = y_ref[...].astype(jnp.float32)
    xs = [x_ref[j] for j in range(n)]
    for i in range(n):
        acc = coef[:, n + i:n + i + 1] * y
        for j in range(n):
            at = 2 * n + i * n + j
            acc = acc + coef[:, at:at + 1] * xs[j]
        o_ref[i] = acc


def _post_pallas(rows, y, coef, *, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, t, c = rows.shape
    tile = _tile(t)
    padded = -(-t // tile) * tile
    rows, y, coef = (_pad_positions(rows, 1, padded), _pad_positions(y, 0, padded),
                     _pad_positions(coef, 0, padded))
    tile_c = POST_TILE_C if c % POST_TILE_C == 0 else c
    out = pl.pallas_call(
        functools.partial(_post_kernel, n=n),
        grid=(padded // tile, c // tile_c),
        in_specs=[pl.BlockSpec((n, tile, tile_c), lambda i, j: (0, i, j)),
                  pl.BlockSpec((tile, tile_c), lambda i, j: (i, j)),
                  pl.BlockSpec((tile, COEF_LANES), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((n, tile, tile_c), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((n, padded, c), jnp.float32),
        # the rows are rewritten where they rest: a block is read whole
        # before its own write, and no other step touches it
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=min(
                4 * (4 * n + 2) * tile * tile_c + (16 << 20), 120 << 20)),
        interpret=interpret,
        name="hyper_post_mix",
    )(rows, y, coef)
    return out[:, :t]


# ---- what a position-sub-layer needs, from the widths (the benchmark's
# ``layer_metrics/hyper_work.py`` states the same arithmetic for its
# readers; this copy is the program's own account) ----

def position_bytes(n: int, d_model: int) -> int:
    """Least bytes one position of one mixed sub-layer moves: the rows
    read once for ``pre``; read and written once, and the sub-layer's
    row read, for ``post`` (float32)."""
    return 4 * d_model * (n + 2 * n + 1)


def weight_bytes(n: int, d_model: int) -> int:
    """A sub-layer's mixing parameters at rest (float32)."""
    return 4 * (n * d_model * coefficients(n) + coefficients(n) + 3)
