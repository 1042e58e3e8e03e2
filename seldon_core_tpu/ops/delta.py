"""Gated-DeltaNet linear attention (arXiv:2412.06464, under the key names
HF's ``linear_*`` config keys and FLA's ``GatedDeltaNet(allow_neg_eigval=)``
use): what a ``"linear"`` layer of ``models/paged/blocks.py`` computes between
its projections, and the one thing it keeps a lane — a state ``S`` of
``d_k x d_v`` float32 a head, whatever the context.

With ``q_t`` and ``k_t`` a head's normalised query and key (``d_k``),
``v_t`` its value (``d_v``), ``alpha_t`` in (0, 1) its decay and
``beta_t`` in (0, 2) its write strength::

    S' = alpha_t S_{t-1}
    u  = beta_t (v_t - S'^T k_t)        the delta rule: what k_t reads
    S_t = S' + k_t u^T                  is replaced by v_t, beta_t of it
    o_t = S_t^T q_t

* :func:`step` — a decode step: one position a lane against the state as
  it rests, memory-bound (a lane-step reads and writes the state once:
  2 x H x d_k x d_v x 4 B a layer).
* :func:`chunked_scan` — a prefill: the same recurrence over a segment
  in chunks of :data:`CHUNK` positions (the WY form: within a chunk the
  ``u`` solve a unit lower-triangular system that does not involve the
  state's rows, so a chunk is a handful of ``(64, d_k) x (d_k, d_v)``
  matmuls and the state is carried chunk to chunk).  Two forms of one
  arithmetic (:func:`scan_impl`): **on a TPU one Pallas kernel**,
  ``delta_chunk_scan`` (:func:`_scan_pallas`) — the state resident in
  VMEM from a prompt's first chunk to its last, a chunk's decays, its
  ``(64, 64)`` matrices and their inverse never in HBM; **XLA's form**
  on the CPU, under a chunk that is not :data:`CHUNK` and at a key width
  that is not whole sublanes.
* :func:`conv` / :func:`conv_step` — the causal depthwise convolution of
  :data:`TAPS` taps and SiLU that q, k and v pass first, and the last
  ``TAPS - 1`` inputs a lane keeps for it.

**A decay a key channel** (Kimi Delta Attention, arXiv:2510.26692; FLA's
``KimiDeltaAttention``): ``alpha_t`` may be a VECTOR a head, one gate a
key channel, and then ``S' = Diag(alpha_t) S_{t-1}`` scales the state's
ROWS (``d_k``) where the scalar scales every entry alike.  Every function
here takes ``log_alpha`` either way — ``(..., H)`` a head or ``(..., H,
d_k)`` a channel — and which it got is a fact of the call's structure:
the scalar form traces what it traced before there was a second.  The
channel form's gate is bounded below (:func:`gates` ``floor``), which is
what lets the chunked scan factor its decays in blocks of :data:`SUB`
positions (:func:`chunked_scan`).

**The pad rule.**  A position with ``beta = 0`` and ``alpha = 1`` leaves
the state as it was (``u = 0``, ``S' = S``).  A prefill call padded to
its bucket passes those at every position past a prompt's own length, so
the state it leaves is the state at the prompt's LAST REAL position; a
decode chunk passes them for a lane that is not running.

**How the state rests** (:func:`pack_of`): ``(slots, H / p, d_k, p x
d_v)`` float32, ``p`` heads side by side in the lanes.  Under the TPU's
(8, 128) tiling a minor dim of 192 occupies 256 lanes of HBM whatever
the array is called; two heads' 384 are three whole tiles.  ``p`` is 1
wherever ``d_v`` is a multiple of 128 already (or the heads are odd):
then the packed form is the plain one.
"""

from __future__ import annotations

import functools

CHUNK = 64      # positions a chunk of the prefill's scan
SUB = 16        # rows a diagonal block of a chunk's triangular system
TAPS = 4        # the convolution's taps (``linear_conv_kernel_dim``)


def pack_of(heads: int, value_dim: int) -> int:
    """Heads side by side in the resting state's lanes: 2 where that
    makes the minor dim whole 128-lane tiles and one head's is not."""
    if value_dim % 128 and heads % 2 == 0 and (2 * value_dim) % 128 == 0:
        return 2
    return 1


def state_shape(slots: int, heads: int, key_dim: int, value_dim: int):
    p = pack_of(heads, value_dim)
    return (slots, heads // p, key_dim, p * value_dim)


def pack_state(state, pack: int):
    """``(B, H, d_k, d_v)`` -> ``(B, H / p, d_k, p x d_v)``."""
    if pack == 1:
        return state
    b, h, dk, dv = state.shape
    return (state.reshape(b, h // pack, pack, dk, dv).transpose(0, 1, 3, 2, 4)
            .reshape(b, h // pack, dk, pack * dv))


def unpack_state(state, pack: int):
    """:func:`pack_state`'s inverse."""
    if pack == 1:
        return state
    b, g, dk, w = state.shape
    return (state.reshape(b, g, dk, pack, w // pack).transpose(0, 1, 3, 2, 4)
            .reshape(b, g * pack, dk, w // pack))


def backend() -> str:
    """``jax.default_backend()``; a test answers ``"interpret"`` to run
    the kernel under the Pallas interpreter off a TPU."""
    import jax

    return jax.default_backend()


def step_impl(key_dim: int, lanes: int, where=None) -> str:
    """Which form a decode step's state update takes
    (``lane_report()["delta_step"]``): ``"pallas"`` on a TPU (or the
    interpreter where a test asks for it) where the resting state's last
    two dims are whole (8, 128) tiles, else ``"xla"``.  On the v5e XLA's
    form of :func:`step` reads the state three times and materialises
    ``k`` spread over a head's lanes (a ``(slots, 15, 96, 2, 192)``
    broadcast re-laid to 384 lanes): 27 % of the update's roofline, 60 %
    of a decode step (my chip run, PR 48); the kernel passes over the
    state once."""
    where = backend() if where is None else where
    return ("pallas" if where in ("tpu", "interpret")
            and key_dim % 8 == 0 and lanes % 128 == 0 else "xla")


def scan_impl(key_dim: int, chunk: int = CHUNK, where=None) -> str:
    """Which form a prefill's chunked scan takes
    (``lane_report()["delta_scan"]``): ``"pallas"`` on a TPU (or the
    interpreter where a test asks for it) where a chunk is whole tiles —
    :data:`CHUNK` positions of ``d_k % 8 == 0`` — else ``"xla"``; the CPU
    always traces XLA's form.  The kernel ``delta_chunk_scan`` pads a
    segment to whole chunks itself (the pad rule), so every prefill
    bucket takes it.  XLA's form ran at 2-4 % of the scan's roofline
    (ledger, PR 52): hundreds of small float32 fusions, every
    intermediate through HBM, the state read and written a chunk."""
    where = backend() if where is None else where
    return ("pallas" if where in ("tpu", "interpret") and chunk == CHUNK
            and key_dim % 8 == 0 else "xla")


# ---------------------------------------------------------------------------
# the convolution
# ---------------------------------------------------------------------------

def conv(x, taps, true_lens=None, *, bias=None, scope="seldon.delta.conv"):
    """Causal depthwise convolution and SiLU over a segment from position
    zero: ``x`` ``(B, L, C)``, ``taps`` ``(TAPS, C)`` (tap ``j`` weighs the
    input ``TAPS - 1 - j`` positions back), float32 out.  Also the tail a
    decode step continues from: the last ``TAPS - 1`` INPUTS of each row
    **at its real length** ``true_lens`` ``(B,)`` (zeros where the row is
    shorter than that), ``(B, TAPS - 1, C)`` in ``x``'s type.  ``bias``
    ``(C,)`` (a state-space layer's, ops/ssm.py) is added before the
    SiLU; a call that passes none traces what it traced without one."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope(scope):
        b, length, _c = x.shape
        n = taps.shape[0]
        padded = jnp.pad(x, [(0, 0), (n - 1, 0), (0, 0)])
        w = taps.astype(jnp.float32)
        out = sum(padded[:, j:j + length].astype(jnp.float32) * w[j]
                  for j in range(n))
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        if true_lens is None:
            true_lens = jnp.full((b,), length, jnp.int32)
        # padded[b, len : len + n - 1] = x[b, len - (n - 1) : len]
        tail = jax.vmap(lambda row, at: jax.lax.dynamic_slice_in_dim(
            row, at, n - 1, axis=0))(padded, true_lens.astype(jnp.int32))
        return jax.nn.silu(out), tail


def conv_step(tail, x, taps, active=None, *, bias=None,
              scope="seldon.delta.conv"):
    """One position: ``tail`` ``(B, TAPS - 1, C)`` the inputs before it,
    ``x`` ``(B, C)`` its own.  ``(out (B, C) float32, new tail)``; a lane
    ``active`` ``(B,)`` leaves out keeps its tail.  ``bias`` as
    :func:`conv`'s."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope(scope):
        window = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
        out = (window.astype(jnp.float32)
               * taps.astype(jnp.float32)[None]).sum(axis=1)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        new = window[:, 1:]
        if active is not None:
            new = jnp.where(active[:, None, None], new, tail)
        return jax.nn.silu(out), new


def l2norm(x, eps: float = 1e-6):
    """``x / ||x||`` over the last dim, float32 (FLA's ``l2norm``: the
    eps under the root)."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def gates(a, b, a_log, dt_bias, neg_eigval: bool = True, *, floor=None):
    """``(log alpha, beta)`` float32 from the two gate projections ``a``
    and ``b`` ``(..., H)``: ``alpha = exp(-exp(A_log) softplus(a +
    dt_bias))``, ``beta = sigmoid(b)`` times 2 under ``neg_eigval``
    (``linear_allow_neg_eigval``: the state's transition ``I - beta k
    k^T`` may then have an eigenvalue in (-1, 0)).

    ``floor`` (a negative number: ``kda_lower_bound`` under
    ``kda_safe_gate``) is the bounded gate of a decay a key channel: ``a``
    and ``dt_bias`` are ``(..., H x d_k)``, ``a_log`` ``(H,)`` a head's
    rate, and ``log alpha = floor x sigmoid(exp(A_log) (a + dt_bias))``
    in ``(floor, 0)`` comes back ``(..., H, d_k)``."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    if floor is None:
        log_alpha = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
            a.astype(f32) + dt_bias.astype(f32))
    else:
        heads = a_log.shape[0]
        z = (a.astype(f32) + dt_bias.astype(f32)).reshape(
            *a.shape[:-1], heads, a.shape[-1] // heads)
        log_alpha = float(floor) * jax.nn.sigmoid(
            jnp.exp(a_log.astype(f32))[:, None] * z)
    beta = jax.nn.sigmoid(b.astype(f32)) * (2.0 if neg_eigval else 1.0)
    return log_alpha, beta


# ---------------------------------------------------------------------------
# a decode step
# ---------------------------------------------------------------------------

def step(state, q, k, v, log_alpha, beta, *, pack: int = 1, active=None):
    """One position a lane against the resting state: ``state`` ``(B, H /
    p, d_k, p x d_v)`` float32, ``q`` / ``k`` ``(B, H, d_k)``, ``v`` ``(B,
    H, d_v)``, ``log_alpha`` ``(B, H)`` (a decay a head) or ``(B, H, d_k)``
    (a decay a key channel), ``beta`` ``(B, H)``.  ``(new state, o (B, H,
    d_v) float32)``.  ``active`` ``(B,)``: a lane it leaves out passes the
    pad rule's gates, so its state stays as it is, bit for bit.

    Every operation keeps the packed state's shape or its row's ``(B, H /
    p, p x d_v)``: elementwise products and sums over ``d_k``, one pass
    over the state where XLA fuses them."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("seldon.delta.step"):
        f32 = jnp.float32
        b, h, dk = q.shape
        dv = v.shape[-1]
        g = h // pack
        channel = log_alpha.ndim == 3
        alpha = jnp.exp(log_alpha.astype(f32))
        beta = beta.astype(f32)
        if active is not None:
            alpha = jnp.where(active[:, None, None] if channel
                              else active[:, None], alpha, 1.0)
            beta = jnp.where(active[:, None], beta, 0.0)

        def lanes(x):  # (B, H) -> (B, G, p x d_v): a head's value its lanes over
            return jnp.repeat(x.reshape(b, g, pack), dv, axis=-1)

        where = backend()
        if step_impl(dk, pack * dv, where) == "pallas":
            # (a decay a channel rides with the rows: it scales the
            # state's ROWS, as q and k weigh them)
            rows = jnp.concatenate(
                [q.astype(f32).reshape(b, g, pack, dk),
                 k.astype(f32).reshape(b, g, pack, dk)]
                + ([alpha.reshape(b, g, pack, dk)] if channel else []), axis=2)
            gates = jnp.stack(
                [v.astype(f32).reshape(b, g, pack * dv)]
                + ([] if channel else [lanes(alpha)]) + [lanes(beta)], axis=2)
            new, out = _step_pallas(state, rows, gates, pack=pack,
                                    interpret=where != "tpu")
            return new, out.reshape(b, h, dv)

        def rows(x):   # (B, H, d_k) -> (B, G, d_k, p x d_v)
            return jnp.repeat(
                x.astype(f32).reshape(b, g, pack, dk).transpose(0, 1, 3, 2),
                dv, axis=-1)

        kk = rows(k)
        decayed = (rows(alpha) if channel
                   else lanes(alpha)[:, :, None, :]) * state
        read = (kk * decayed).sum(axis=2)                      # S'^T k
        u = lanes(beta) * (v.astype(f32).reshape(b, g, pack * dv) - read)
        new = decayed + kk * u[:, :, None, :]
        out = (rows(q) * new).sum(axis=2)                      # S^T q
        return new, out.reshape(b, h, dv)


def _step_kernel(s_ref, rows_ref, gates_ref, s_out_ref, o_ref, *, pack):
    """One lane: ``s_ref`` / ``s_out_ref`` ``(1, G, d_k, W)`` the state as
    it rests (``W`` = ``pack`` heads' ``d_v`` side by side), ``rows_ref``
    ``(1, G, 2 pack, d_k)`` the pair's q rows then its k rows,
    ``gates_ref`` ``(1, G, 3, W)`` v, alpha and beta over the lanes,
    ``o_ref`` ``(1, G, 1, W)``.  A pair of heads at a time: the state's
    tile is read once, decayed, read against k, written through, read
    against q — elementwise products and sums over ``d_k``, float32 on the
    vector unit (no matmul unit: nothing is rounded to bfloat16).

    **A decay a key channel**: ``rows_ref`` is ``(1, G, 3 pack, d_k)`` —
    alpha's rows after k's — and ``gates_ref`` ``(1, G, 2, W)``, v and
    beta: alpha is spread down the state's rows as k is, where the scalar
    came spread over its lanes."""
    import jax
    import jax.numpy as jnp

    groups, dk, width = s_ref.shape[1], s_ref.shape[2], s_ref.shape[3]
    dv = width // pack
    channel = rows_ref.shape[2] == 3 * pack
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
    head = jax.lax.broadcasted_iota(jnp.int32, (dk, width), 1) // dv

    def spread(rows):  # (pack, d_k) -> (d_k, W): lane l holds head l // d_v's column
        out = None
        for i in range(pack):
            col = jnp.sum(jnp.where(eye, rows[i:i + 1], 0.0), axis=1,
                          keepdims=True)                        # (d_k, 1)
            col = jnp.broadcast_to(col, (dk, width))
            out = col if out is None else jnp.where(head == i, col, out)
        return out

    def pair(g, carry):
        s = s_ref[0, g]
        rows = rows_ref[0, g]
        gates = gates_ref[0, g]
        kk = spread(rows[pack:2 * pack])
        if channel:
            decayed = s * spread(rows[2 * pack:])
            read = jnp.sum(kk * decayed, axis=0, keepdims=True)
            u = gates[1:2] * (gates[0:1] - read)
        else:
            decayed = s * gates[1:2]
            read = jnp.sum(kk * decayed, axis=0, keepdims=True)  # S'^T k
            u = gates[2:3] * (gates[0:1] - read)
        new = decayed + kk * u
        s_out_ref[0, g] = new
        o_ref[0, g] = jnp.sum(spread(rows[:pack]) * new, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, groups, pair, 0)


def _step_pallas(state, rows, gates, *, pack, interpret):
    """:func:`step`'s state update as one kernel call: a grid step a
    lane, the lane's whole state (2.2 MB at 15 x 96 x 384) a block,
    rewritten where it rests.  The state is the first output: a trace
    names the call by it (``pallas_kernel_f32_<slots>_<G>_<d_k>_<W>_``)."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, g, dk, width = state.shape
    block = 4 * g * dk * width
    new, out = pl.pallas_call(
        functools.partial(_step_kernel, pack=pack),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, g, dk, width), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((1, g, rows.shape[2], dk),
                               lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((1, g, gates.shape[2], width),
                               lambda i: (i, 0, 0, 0))],
        out_specs=[pl.BlockSpec((1, g, dk, width), lambda i: (i, 0, 0, 0)),
                   pl.BlockSpec((1, g, 1, width), lambda i: (i, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, g, 1, width), jnp.float32)],
        # a lane's state is read whole before its own write, and no other
        # grid step touches it
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # the state's block in and out, each twice (the pipeline's
            # two buffers), and room for the rest
            vmem_limit_bytes=min(4 * block + (16 << 20), 100 << 20)),
        interpret=interpret,
        name="delta_state_step",
    )(state, rows, gates)
    return new, out[:, :, 0]


# ---------------------------------------------------------------------------
# a prefill: the chunked scan
# ---------------------------------------------------------------------------

def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` ``(..., n, n)`` strictly lower triangular,
    by forward substitution a row at a time (row ``i`` of the inverse is
    ``e_i - a[i] @ rows before it``): ``n - 1`` steps of one masked
    product each, exact to rounding whatever ``a`` holds."""
    import jax
    import jax.numpy as jnp

    n = a.shape[-1]
    eye = jnp.eye(n, dtype=a.dtype)

    def row(i, t):
        pick = (jnp.arange(n) == i).astype(a.dtype)            # (n,)
        a_i = (a * pick[:, None]).sum(axis=-2)                 # a[..., i, :]
        new = -jnp.einsum("...j,...jk->...k", a_i, t,
                          precision=jax.lax.Precision.HIGHEST)
        return t + pick[:, None] * new[..., None, :]

    return jax.lax.fori_loop(1, n, row, jnp.broadcast_to(eye, a.shape))


def _solve_unit_lower(a, rhs, sub: int):
    """``(I + a)^-1 rhs`` for ``a`` ``(..., C, C)`` strictly lower
    triangular: the diagonal blocks of ``sub`` rows inverted a row at a
    time, the blocks below them by substitution a block at a time."""
    import jax
    import jax.numpy as jnp

    c = a.shape[-1]
    nb = c // sub
    hi = jax.lax.Precision.HIGHEST
    lead = a.shape[:-2]
    blocks = a.reshape(*lead, nb, sub, nb, sub)
    diag = jnp.stack([blocks[..., j, :, j, :] for j in range(nb)], axis=-3)
    inv = _unit_lower_inverse(diag)                            # (..., nb, sub, sub)
    rhs = rhs.reshape(*lead, nb, sub, rhs.shape[-1])
    solved = []
    for j in range(nb):
        r = rhs[..., j, :, :]
        for i, x in enumerate(solved):
            r = r - jnp.matmul(blocks[..., j, :, i, :], x, precision=hi)
        solved.append(jnp.matmul(inv[..., j, :, :], r, precision=hi))
    return jnp.concatenate(solved, axis=-2)


def _chunks(length: int, chunk: int):
    """``(c, sub, pad, n)``: positions a chunk, rows a diagonal block,
    pad positions and chunks of a segment of ``length``."""
    if length >= chunk:
        c = chunk
    else:  # one chunk: the segment in whole diagonal blocks
        c = -(-length // SUB) * SUB if length > SUB else length
    sub = SUB if c % SUB == 0 else c
    pad = -length % c
    return c, sub, pad, (length + pad) // c


def _lay(x, n: int, c: int, pad: int):
    """``(B, L, H, ...)`` -> ``(B, H, N, C, ...)`` float32, pads passing
    the pad rule."""
    import jax.numpy as jnp

    x = jnp.pad(x.astype(jnp.float32),
                [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
    x = x.reshape(x.shape[0], n, c, *x.shape[2:])
    return jnp.moveaxis(x, 3, 1)


def _carry_chunks(state, chunks, shape, length: int):
    """The sequential part of a scan: ``chunks`` = ``(U0, W, M . QK^T, e^g
    Q, e^{g_C - g} K, e^{g_C})`` laid ``(B, H, N, ...)``, the state carried
    chunk to chunk (``e^{g_C}`` scales it whole, or its rows where it is a
    vector a head).  ``(o (B, L, H, d_v), final state)``."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    b, h, dk, dv = shape

    def one(s, xs):
        u0_n, w_n, qk_n, q_n, k_n, carry_n = xs
        u = u0_n - jnp.matmul(w_n, s, precision=hi)
        o = (jnp.matmul(q_n, s, precision=hi)
             + jnp.matmul(qk_n, u, precision=hi))
        s = carry_n * s + jnp.einsum("...td,...tv->...dv", k_n, u,
                                     precision=hi)
        return s, o

    if state is None:
        state = jnp.zeros((b, h, dk, dv), jnp.float32)
    xs = tuple(jnp.moveaxis(x, 2, 0) for x in chunks)
    state, out = jax.lax.scan(one, state.astype(jnp.float32), xs)
    n, c = out.shape[0], out.shape[3]
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, n * c, dv)[:, :, :length]
    return jnp.moveaxis(out, 1, 2), state


def chunked_scan(q, k, v, log_alpha, beta, *, state=None, chunk: int = CHUNK):
    """The recurrence over a segment: ``q`` / ``k`` ``(B, L, H, d_k)``,
    ``v`` ``(B, L, H, d_v)``, ``log_alpha`` / ``beta`` ``(B, L, H)``;
    ``state`` ``(B, H, d_k, d_v)`` before the first position (None:
    zeros).  ``(o (B, L, H, d_v), final state)`` float32.

    Within a chunk, with ``g_t`` the running sum of ``log alpha`` from the
    chunk's start and ``S_0`` the state before it, ``U`` (the rows ``u_t``)
    solves ``(I + A) U = beta V - beta e^g K S_0`` with ``A[t, i] = beta_t
    e^{g_t - g_i} (k_t . k_i)`` for ``i < t``: two right-hand sides that do
    not involve ``S_0`` are solved for every chunk at once (``U0``, ``W``)
    and the sequential part is ``U = U0 - W S_0``, ``O = e^g Q S_0 + (M . Q
    K^T) U``, ``S = e^{g_C} S_0 + (e^{g_C - g} K)^T U`` a chunk.  Decays
    enter as ``exp`` of differences that are never positive.  Every matmul
    is float32 at the highest precision: the state's error is the
    recurrence's own.

    **A decay a key channel** (``log_alpha`` ``(B, L, H, d_k)``): ``g_t``
    is a vector, ``A[t, i] = beta_t sum_c k_t[c] k_i[c] e^{g_t[c] -
    g_i[c]}``, and the one product ``(K e^g)(K e^{-g})^T`` a chunk that
    would make it cannot be formed: at a gate's floor of -5 a position
    ``e^{-g}`` reaches ``e^{320}`` within 64 positions and leaves float32.
    In blocks of :data:`SUB` = 16 positions it can — that is what the
    floor is FOR: with ``m_j`` the running sum at block ``j``'s middle
    position, the rows of block ``j`` carry ``e^{g_t - m_j}`` and the
    columns up to its end ``e^{m_j - g_i}``; inside the block both
    exponents stay within ``+-8 x 5`` and a masked pair's product within
    ``e^{16 x 5}`` = ``e^{80}`` (float32 holds ``e^{88}``), and a column
    before the block is never positive.  ``S_0`` enters through ``e^g K`` and ``e^g Q``,
    the carry scales the state's ROWS by ``e^{g_C}``; everything else is
    the scalar form's (:func:`_channel_scan`)."""
    import jax
    import jax.numpy as jnp

    where = backend()
    if scan_impl(q.shape[-1], chunk, where) == "pallas":
        with jax.named_scope("seldon.delta.scan"):
            return _scan_jit()(q, k, v, log_alpha, beta,
                               *(() if state is None else (state,)),
                               interpret=where != "tpu")
    if log_alpha.ndim == q.ndim:
        return _channel_scan(q, k, v, log_alpha, beta, state, chunk)
    with jax.named_scope("seldon.delta.scan"):
        f32 = jnp.float32
        hi = jax.lax.Precision.HIGHEST
        b, length, h, dk = q.shape
        dv = v.shape[-1]
        c, sub, pad, n = _chunks(length, chunk)

        def lay(x):
            return _lay(x, n, c, pad)

        q, k, v = lay(q), lay(k), lay(v)                       # (B, H, N, C, d)
        beta = lay(beta)                                       # (B, H, N, C)
        g = jnp.cumsum(lay(log_alpha), axis=-1)
        lower = jnp.tril(jnp.ones((c, c), bool))
        strict = jnp.tril(jnp.ones((c, c), bool), -1)
        decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                                  -jnp.inf))                   # e^{g_t - g_i}, i <= t
        kk = jnp.einsum("...td,...id->...ti", k, k, precision=hi)
        a = jnp.where(strict, beta[..., :, None] * decay * kk, 0.0)
        rhs = jnp.concatenate(
            [beta[..., None] * v, (beta * jnp.exp(g))[..., None] * k], axis=-1)
        solved = _solve_unit_lower(a, rhs, sub)
        u0, w = solved[..., :dv], solved[..., dv:]
        qk = jnp.where(lower, decay * jnp.einsum(
            "...td,...id->...ti", q, k, precision=hi), 0.0)
        q_in = q * jnp.exp(g)[..., None]
        g_end = g[..., -1:]
        k_out = k * jnp.exp(g_end - g)[..., None]
        carry = jnp.exp(g_end)[..., None]                      # (B, H, N, 1, 1)
        return _carry_chunks(state, (u0, w, qk, q_in, k_out, carry),
                             (b, h, dk, dv), length)


# the most a float32 exponent may be asked for inside a diagonal block
# (``e^{88.7}`` is float32's largest): what a gate's floor times SUB may
# not pass (``models/spec.py model_spec`` holds a spec to it)
BLOCK_EXPONENT_MAX = 85.0


def _channel_scan(q, k, v, log_alpha, beta, state, chunk):
    """:func:`chunked_scan` under a decay a key channel: the same chunks,
    the same triangular solve and sequential part, the decays factored a
    block of :data:`SUB` positions at a time."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("seldon.delta.scan"):
        f32 = jnp.float32
        hi = jax.lax.Precision.HIGHEST
        b, length, h, dk = q.shape
        dv = v.shape[-1]
        c, sub, pad, n = _chunks(length, chunk)
        nb = c // sub

        def lay(x):
            return _lay(x, n, c, pad)

        q, k, v = lay(q), lay(k), lay(v)                       # (B, H, N, C, d)
        beta = lay(beta)                                       # (B, H, N, C)
        # running sums a BLOCK at a time, and every difference of two of
        # them as a sum of its own non-positive parts: a difference of two
        # sums near -320 would carry their rounding (3e-5) into a decay
        # that is not small
        gb = jnp.cumsum(lay(log_alpha).reshape(b, h, n, nb, sub, dk), axis=-2)
        lead = gb.shape[:-3]
        tot = gb[..., -1, :]                                   # (B, H, N, nb, d_k)
        at = jnp.arange(nb)
        # r_j, the sum as block j starts; what lies after block m; and
        # what lies between block m's end and block j's start
        ref = jnp.einsum("...ld,jl->...jd", tot,
                         (at[None, :] < at[:, None]).astype(f32))
        after = jnp.einsum("...ld,ml->...md", tot,
                           (at[None, :] > at[:, None]).astype(f32))
        between = jnp.einsum(
            "...ld,jml->...jmd", tot,
            ((at[None, None, :] > at[None, :, None])
             & (at[None, None, :] < at[:, None, None])).astype(f32))
        g = (ref[..., :, None, :] + gb).reshape(*lead, c, dk)  # from the chunk's start
        to_block_end = tot[..., :, None, :] - gb               # (..., nb, sub, d_k)
        # a block's rows carry e^{g_t - g_mid}, mid the block's middle
        # position, and the columns e^{g_mid - g_i}: within a block both
        # exponents stay inside +-(sub / 2) x floor — a factor taken from the
        # block's START would reach e^{-80} at its last row and flush a
        # small component of k to zero where its partner's e^{+80} makes
        # the pair matter — and a column before the block, e^{g_mid - g_i},
        # is never positive; nothing after the block (those pairs are
        # above the diagonal)
        mid = gb[..., (sub - 1) // 2, :]                       # (..., nb, d_k)
        within = jnp.exp(gb - mid[..., None, :])
        k_rows = k.reshape(*lead, nb, sub, dk) * within
        q_rows = q.reshape(*lead, nb, sub, dk) * within
        before = at[None, :] < at[:, None]                     # [j, m]: m < j
        cols = mid[..., :, None, None, :] + jnp.where(
            before[:, :, None, None],
            to_block_end[..., None, :, :, :] + between[..., :, :, None, :],
            jnp.where(jnp.eye(nb, dtype=bool)[:, :, None, None],
                      -gb[..., None, :, :, :], -jnp.inf))      # (..., nb, nb, sub, d_k)
        k_cols = k[..., None, :, :] * jnp.exp(cols.reshape(*lead, nb, c, dk))
        lower = jnp.tril(jnp.ones((c, c), bool))
        strict = jnp.tril(jnp.ones((c, c), bool), -1)
        kk = jnp.einsum("...jsd,...jid->...jsi", k_rows, k_cols,
                        precision=hi).reshape(*lead, c, c)
        a = jnp.where(strict, beta[..., :, None] * kk, 0.0)
        e_g = jnp.exp(g)
        rhs = jnp.concatenate(
            [beta[..., None] * v, beta[..., None] * e_g * k], axis=-1)
        solved = _solve_unit_lower(a, rhs, sub)
        u0, w = solved[..., :dv], solved[..., dv:]
        qk = jnp.where(lower, jnp.einsum(
            "...jsd,...jid->...jsi", q_rows, k_cols,
            precision=hi).reshape(*lead, c, c), 0.0)
        q_in = q * e_g
        k_out = k * jnp.exp(
            to_block_end + after[..., :, None, :]).reshape(*lead, c, dk)
        carry = jnp.exp(tot.sum(axis=-2))[..., None]           # (B, H, N, d_k, 1)
        return _carry_chunks(state, (u0, w, qk, q_in, k_out, carry),
                             (b, h, dk, dv), length)


# ---------------------------------------------------------------------------
# a prefill: the chunked scan as one kernel
# ---------------------------------------------------------------------------

def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """A float32 product at the precision XLA's form uses: Mosaic takes
    HIGHEST for ``contract_precision<fp32>`` and anything unsaid for its
    own default, which is not float32's."""
    import jax
    import jax.numpy as jnp

    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b
_NEVER = -1e30                   # an exponent of a pair above the diagonal


def _iotas(shape):
    import jax
    import jax.numpy as jnp

    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _inverse_masks(c: int, side: int):
    """What :func:`_unit_lower_inverses_side_by_side` selects by, made once
    a grid step: the identity, the pairs of rows, each doubling's blocks
    below the diagonal, and the weights' block diagonal."""
    import jax.numpy as jnp

    row, col = _iotas((c, side * c))
    col = col & (c - 1)                      # the column within its matrix
    below, s = [], 1
    while (2 << s) <= c:                     # blocks of 2^s rows to 2^(s+1)
        below.append(((row >> (s + 1)) == (col >> (s + 1)))
                     & ((row >> s) != (col >> s)))
        s += 1
    same = None
    if side > 1:
        wr, wc = _iotas((side * c, side * c))
        same = (wr // c) == (wc // c)
    return (jnp.where(row == col, 1.0, 0.0), (row >> 1) == (col >> 1), below, same)


def _unit_lower_inverses_side_by_side(packs, masks):
    """``(I + A_p)^-1`` for strictly lower triangular ``(C, C)`` matrices
    side by side in the lanes, each of ``packs`` ``(C, side x C)``: two by
    two the inverse is ``I - A`` exactly, and a block of ``2s`` rows
    follows from its two of ``s`` — ``[[P, 0], [R, Q]]^-1 = [[P^-1, 0],
    [-Q^-1 R P^-1, Q^-1]]`` — five doublings to 64, each two products on
    the matmul unit with no cancellation the inverse itself does not have
    (forward substitution's accuracy).  Side by side, a product's right
    operand is the matrices down a block diagonal: one ``(side x
    C)``-square weight load serves every matrix where one of ``C`` would
    fill a quarter of the unit.  The ten products of one pack wait on one
    another; the packs are walked level by level so that one's products
    fill another's wait."""
    import jax.numpy as jnp

    eye, pairs, below, same = masks

    def mm(x, y):
        if same is not None:
            y = jnp.where(same, jnp.concatenate([y] * (y.shape[1] // y.shape[0]),
                                                axis=0), 0.0)
        return _dot(x, y)

    ts = [eye - jnp.where(pairs, a, 0.0) for a in packs]
    for level in below:
        firsts = [mm(t, jnp.where(level, a, 0.0)) for t, a in zip(ts, packs)]
        ts = [t - mm(first, t) for first, t in zip(firsts, ts)]
    return ts


def _scalar_chunk(q, k, la_row, beta_col, masks):
    """A chunk's matrices under a decay a head: ``la_row`` ``(1, C)`` the
    chunk's ``log alpha``.  ``(A, M . Q K^T, e^g (rows), e^{g_C - g}
    (rows), e^{g_C})``."""
    import jax.numpy as jnp

    lower, strict, eye = masks[:3]
    c = q.shape[0]
    # the running sum down the rows and, picked off the diagonal, along
    # the lanes: their difference is exactly zero where t = i
    g_col = jnp.sum(jnp.where(lower, la_row, 0.0), axis=1, keepdims=True)
    g_row = jnp.sum(jnp.where(eye, g_col, 0.0), axis=0, keepdims=True)
    decay = jnp.exp(jnp.where(lower, g_col - g_row, _NEVER))   # e^{g_t - g_i}, i <= t
    kq = _dot(jnp.concatenate([k, q], axis=0), k, _NT)          # (2C, C)
    a = jnp.where(strict, beta_col * decay * kq[:c], 0.0)
    qk = decay * kq[c:]
    g_end = g_col[c - 1:c]
    return a, qk, jnp.exp(g_col), jnp.exp(g_end - g_col), jnp.exp(g_end)


def _channel_chunk(q, k, la, beta_col, masks):
    """A chunk's matrices under a decay a key channel, ``la`` ``(C, d_k)``:
    :func:`_channel_scan`'s factoring — running sums a block of
    :data:`SUB` positions, every exponent a sum of its own non-positive
    parts, a block's rows about its middle position."""
    import jax.numpy as jnp

    lower, strict, _eye, block_lower, channels = masks
    c, dk = k.shape
    nb = c // SUB
    gb = _dot(block_lower, la)                                  # sums from a block's start
    tot = [gb[j * SUB + SUB - 1:(j + 1) * SUB] for j in range(nb)]
    mid = [gb[j * SUB + (SUB - 1) // 2:j * SUB + (SUB - 1) // 2 + 1]
           for j in range(nb)]

    def span(lo, hi):  # what lies in blocks lo .. hi - 1
        out = jnp.zeros_like(tot[0])
        for at in range(lo, hi):
            out = out + tot[at]
        return out

    def spread(parts):  # a row a block -> (C, d_k)
        return jnp.concatenate(
            [jnp.broadcast_to(part, (SUB, dk)) for part in parts], axis=0)

    within = jnp.exp(gb - spread(mid))
    to_block_end = spread(tot) - gb
    k_rows, q_rows = k * within, q * within
    kk, qk = [], []
    for j in range(nb):
        # block j's rows against the columns up to its end: e^{g_mid - g_i}
        parts = [mid[j] + (to_block_end[m * SUB:(m + 1) * SUB] + span(m + 1, j))
                 for m in range(j)]
        parts.append(mid[j] - gb[j * SUB:(j + 1) * SUB])
        hi = (j + 1) * SUB
        k_cols = k[:hi] * jnp.exp(jnp.concatenate(parts, axis=0))
        if hi < c:
            k_cols = jnp.concatenate(
                [k_cols, jnp.zeros((c - hi, dk), jnp.float32)], axis=0)
        kq = _dot(jnp.concatenate([k_rows[j * SUB:hi], q_rows[j * SUB:hi]], axis=0),
                  k_cols, _NT)                                   # (2 SUB, C)
        kk.append(kq[:SUB])
        qk.append(kq[SUB:])
    a = jnp.where(strict, beta_col * jnp.concatenate(kk, axis=0), 0.0)
    qk = jnp.where(lower, jnp.concatenate(qk, axis=0), 0.0)
    e_g = jnp.exp(spread([span(0, j) for j in range(nb)]) + gb)
    k_out = jnp.exp(to_block_end + spread([span(j + 1, nb) for j in range(nb)]))
    # the carry scales the state's ROWS: the channels down the sublanes
    carry = jnp.sum(jnp.where(channels, jnp.exp(span(0, nb)), 0.0),
                    axis=1, keepdims=True)                       # (d_k, 1)
    return a, qk, e_g, k_out, carry


def _scan_kernel(*refs, channel: bool, resume: bool, side: int):
    """One chunk of ``heads`` heads of one prompt: ``q_ref`` / ``k_ref``
    ``(1, heads, C, d_k)``, ``v_ref`` / ``o_ref`` ``(1, heads, C, d_v)``,
    ``beta_ref`` ``(1, 1, 1, heads, C)`` (a chunk's gates along the lanes),
    ``la_ref`` like it (a decay a head) or like ``k_ref`` (a decay a key
    channel), ``s_ref`` ``(1, heads, d_k, d_v)`` the state: its block does
    not move along the chunk axis, so it stays in VMEM from the first
    chunk (zeroed, or loaded from ``s0_ref``) to the last, after which
    the pipeline writes it out once.

    A chunk is :func:`chunked_scan`'s: ``A`` and ``M . Q K^T`` from the
    decays, ``T = (I + A)^-1`` for ``side`` heads at a time, then against
    the resident ``S``: ``U = T (beta V - beta e^g K S)``, ``O = e^g Q S +
    (M . Q K^T) U``, ``S = e^{g_C} S + (e^{g_C - g} K)^T U`` — ``beta e^g
    K`` and ``e^g Q`` stacked, so one load of ``S`` serves 128 rows.  The
    heads are walked in straight-line code, every index static: the
    scheduler lays one head's products over another's waits."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if resume:
        q_ref, k_ref, v_ref, la_ref, beta_ref, s0_ref, o_ref, s_ref = refs
    else:
        q_ref, k_ref, v_ref, la_ref, beta_ref, o_ref, s_ref = refs
    heads, c, dk = q_ref.shape[1:]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...] if resume else jnp.zeros_like(s_ref)

    row, col = _iotas((c, c))
    masks = (row >= col, row > col, row == col)
    if channel:
        drow, dcol = _iotas((dk, dk))
        masks += (jnp.where(masks[0] & ((row // SUB) == (col // SUB)), 1.0, 0.0),
                  drow == dcol)
    made = []
    for h in range(heads):
        q, k = q_ref[0, h], k_ref[0, h]
        beta_col = jnp.sum(jnp.where(masks[2], beta_ref[0, 0, 0, h:h + 1, :], 0.0),
                           axis=1, keepdims=True)                # (C, 1)
        if channel:
            pieces = _channel_chunk(q, k, la_ref[0, h], beta_col, masks)
        else:
            pieces = _scalar_chunk(q, k, la_ref[0, 0, 0, h:h + 1, :], beta_col, masks)
        made.append((q, k, beta_col) + pieces)
    inverses = _unit_lower_inverses_side_by_side(
        [jnp.concatenate([m[3] for m in made[at:at + side]], axis=1)
         for at in range(0, heads, side)], _inverse_masks(c, side))
    for h, (q, k, beta_col, _a, qk, e_g, k_out, s_carry) in enumerate(made):
        s = s_ref[0, h]
        from_s = _dot(jnp.concatenate([beta_col * e_g * k, e_g * q], axis=0), s)
        at = (h % side) * c
        u = _dot(inverses[h // side][:, at:at + c],
                 beta_col * v_ref[0, h] - from_s[:c])
        o_ref[0, h] = from_s[c:] + _dot(qk, u)
        s_ref[0, h] = s_carry * s + _dot(k_out * k, u, _TN)


def _heads_a_step(heads: int, side: int) -> int:
    """Heads a grid step of the scan's kernel: the most up to 6 that
    divide ``heads`` in whole ``side``s (6 of 30, 4 of 32).  Two packs'
    chains already fill each other's waits (4 of 32 reads 1.56 ms a
    2,048-position call where 8 reads 1.50 and 2 reads 2.01: my chip
    runs, PR 53) and the body is straight-line code: every head more is
    that much more to trace, compile and load in every prefill program."""
    return max(n for n in range(side, 7, side) if heads % n == 0)


@functools.lru_cache(maxsize=None)
def _scan_jit():
    """:func:`_scan_pallas` under a jit of its own: a program's linear
    layers call it at the same shapes and share ONE trace and ONE lowering
    of the kernel's straight-line body, where each call would cost 0.14 s
    of set-up before any compile cache is asked (six layers in each of
    six prefill programs in the Olmo-Hybrid cell; XLA inlines the call)."""
    import jax

    return jax.jit(_scan_pallas, static_argnames=("interpret", "heads_step", "side"))


def _scan_pallas(q, k, v, log_alpha, beta, *state, interpret,
                 heads_step=None, side=None):
    """:func:`chunked_scan` as one kernel call, ``delta_chunk_scan``: a
    grid of (prompt, block of heads, chunk), the chunk axis sequential.
    HBM sees q, k, v and the gates once on the way in — laid ``(B, H, L,
    d)``, a head's chunk a ``(64, d)`` tile —, the output once on the way
    out and the final state once (``state``: none, or the one ``(B, H, d_k,
    d_v)`` to continue from).  The output is the call's FIRST result,
    ``(B, H, L, d_v)``: a device trace names the call by it, which is how
    the benchmark's readers find the scan (``layer_metrics/delta_work.py
    is_scan``: four dims whose second is H)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    b, length, h, dk = q.shape
    dv = v.shape[-1]
    c = CHUNK
    pad = -length % c
    n = (length + pad) // c
    channel = log_alpha.ndim == q.ndim
    side = side or (2 if h % 2 == 0 else 1)
    hb = heads_step or _heads_a_step(h, side)
    groups = h // hb

    def rows(x):    # (B, L, H, d) -> (B, H, N x C, d): a head's chunk a tile
        x = jnp.pad(x.astype(f32), [(0, 0), (0, pad), (0, 0), (0, 0)])
        return jnp.moveaxis(x, 2, 1)

    def lanes(x):   # (B, L, H) -> (B, groups, N, hb, C): a chunk's gates a row a head
        x = jnp.pad(x.astype(f32), [(0, 0), (0, pad), (0, 0)])
        return x.reshape(b, n, c, groups, hb).transpose(0, 3, 1, 4, 2)

    def tile(d):
        return pl.BlockSpec((1, hb, c, d), lambda i, g, j: (i, g, j, 0))

    gate = pl.BlockSpec((1, 1, 1, hb, c), lambda i, g, j: (i, g, j, 0, 0))
    rests = pl.BlockSpec((1, hb, dk, dv), lambda i, g, j: (i, g, 0, 0))
    out, final = pl.pallas_call(
        functools.partial(_scan_kernel, channel=channel, resume=len(state) == 1,
                          side=side),
        grid=(b, groups, n),
        in_specs=[tile(dk), tile(dk), tile(dv), tile(dk) if channel else gate, gate]
        + [rests] * len(state),
        out_specs=[tile(dv), rests],
        out_shape=[jax.ShapeDtypeStruct((b, h, n * c, dv), f32),
                   jax.ShapeDtypeStruct((b, h, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # the blocks twice (2.6 MB at 8 heads of 128 x 128), the state
            # and every head's matrices in straight-line code: well
            # inside, and past the 16 MB a kernel gets unasked
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="delta_chunk_scan",
    )(rows(q), rows(k), rows(v),
      rows(log_alpha) if channel else lanes(log_alpha), lanes(beta),
      *(s0.astype(f32) for s0 in state))
    return jnp.moveaxis(out[:, :, :length], 1, 2), final


def recurrence(q, k, v, log_alpha, beta, *, state=None):
    """The same map position by position (``lax.scan`` over ``t``): what
    :func:`chunked_scan` is held to, and the form a reader of the
    equations at the top can check by eye."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    b, _length, h, dk = q.shape
    dv = v.shape[-1]
    if state is None:
        state = jnp.zeros((b, h, dk, dv), f32)

    def one(s, xs):
        q_t, k_t, v_t, la_t, b_t = xs                           # (B, H, ...)
        # (a decay a head scales the whole state, one a key channel its rows)
        s = (jnp.exp(la_t)[..., None] if la_t.ndim == 3
             else jnp.exp(la_t)[..., None, None]) * s
        read = jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=hi)
        u = b_t[..., None] * (v_t - read)
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=hi)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0)
               for x in (q, k, v, log_alpha, beta))
    state, out = jax.lax.scan(one, state.astype(f32), xs)
    return jnp.moveaxis(out, 0, 1), state
