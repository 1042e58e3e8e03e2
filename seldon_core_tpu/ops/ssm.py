"""A selective state-space layer (Mamba-1, arXiv:2312.00752, as HF's
``modeling_jamba.py JambaMambaMixer`` runs it): what an ``"ssm"`` layer of
``models/paged/blocks.py`` computes between its projections, and the one thing
it keeps a lane — a state ``h`` of ``N x E`` float32 (``N`` =
``mamba_d_state`` columns, ``E`` = ``mamba_expand x hidden`` channels),
whatever the context, beside the convolution's last inputs.

With ``x_t`` a position's ``E`` channels after the convolution and SiLU,
``Delta_t`` in R^E its step sizes, ``B_t`` and ``C_t`` in R^N its input
and output columns (all three read from ``x_t``: :func:`select`), ``A`` =
``-exp(A_log)`` ``(N, E)`` and ``D`` ``(E,)`` the layer's own::

    h_t = exp(Delta_t (x) 1 . A) . h_{t-1} + (Delta_t . x_t) (x) B_t
    y_t = h_t^T C_t + D . x_t

**No chunk of this is a matrix product**: the decay ``exp(Delta_t,c
A_n,c)`` is one number a channel AND a state column, so a run of
positions does not factor into a ``(positions, positions)`` matrix a head
as the delta rule's does (``ops/delta.py``, the WY form).  It is
multiply-adds and one exponential a state entry a position — the vector
and the transcendental units' work, beside the matrix unit's projections.

* :func:`step` — a decode step: one position a lane against the state as
  it rests, memory-bound (a lane-step reads and writes the state once: 2
  x N x E x 4 B a layer).
* :func:`scan` — a prefill: the same recurrence over a padded group of
  prompts from a state of zeros, the state carried position to position
  and **never laid out a position in HBM** (``(positions, N, E)`` float32
  would be 327,680 B a position at 16 x 5,120).
* :func:`select` — ``Delta``, ``B`` and ``C`` from ``x``: the low-rank
  projection ``W_x``, Jamba's three inner RMSNorms, ``W_dt`` and its
  bias, the softplus.
* :func:`recurrence` — the plain form, position by position
  (``lax.scan``), that the tests hold both to.

Two forms of one arithmetic each (:func:`step_impl`, :func:`scan_impl`):
**on a TPU a Pallas kernel** — ``ssm_state_step`` passes over eight
lanes' states a grid step, rewritten where they rest; ``ssm_scan`` keeps
a block of ``E`` channels' state ``(N, block)`` resident in VMEM from a
prompt's first position to its last, ``N`` = 16 sublanes a tile — and
**XLA's form** on the CPU and wherever the state is not whole (8, 128)
tiles.

**The pad rule.**  A position with ``Delta = 0`` leaves the state as it
was (``exp(0) = 1`` and ``0 . x (x) B = 0``), bit for bit.  A prefill call
padded to its bucket masks ``Delta`` **after** the softplus at every
position past a prompt's own length (``softplus(0)`` is ln 2, not 0), so
the state it leaves is the one at the prompt's LAST REAL position; a
decode chunk passes ``Delta = 0`` for a lane that is not running.

**How the state rests**: ``(slots, N, E)`` float32 — the columns the
sublanes, the channels the lanes: 16 x 5,120 is two sublane tiles by
forty lane tiles, nothing padded.  The convolution and its tail are
``ops/delta.py conv`` / ``conv_step`` (four taps, a bias, SiLU).
"""

from __future__ import annotations

import functools

STEP_LANES = 8      # lanes' states a grid step of ``ssm_state_step``
SCAN_BLOCK = 512    # channels a grid step of ``ssm_scan`` keeps a state of
SCAN_POSITIONS = 256  # positions a grid step of ``ssm_scan``
_SUB = 8            # positions unrolled inside the kernel: a sublane tile


def backend() -> str:
    """``jax.default_backend()``; a test answers ``"interpret"`` to run
    the kernels under the Pallas interpreter off a TPU."""
    import jax

    return jax.default_backend()


def _tiles(state_dim: int, channels: int, where) -> bool:
    where = backend() if where is None else where
    return (where in ("tpu", "interpret") and state_dim % 8 == 0
            and channels % 128 == 0)


def step_impl(state_dim: int, channels: int, where=None) -> str:
    """Which form a decode step's state update takes
    (``lane_report()["ssm_step"]``): ``"pallas"`` on a TPU (or the
    interpreter where a test asks for it) where a lane's state ``(N, E)``
    is whole (8, 128) tiles, else ``"xla"``."""
    return "pallas" if _tiles(state_dim, channels, where) else "xla"


def scan_impl(state_dim: int, channels: int, where=None) -> str:
    """Which form a prefill's scan takes (``lane_report()["ssm_scan"]``):
    ``"pallas"`` under :func:`step_impl`'s rule where the channels are
    also whole blocks of the kernel (:func:`_scan_block`), else
    ``"xla"``; the CPU always traces XLA's form."""
    return ("pallas" if _tiles(state_dim, channels, where)
            and _scan_block(channels) else "xla")


def _scan_block(channels: int) -> int:
    """Channels a grid step of the scan's kernel: :data:`SCAN_BLOCK`
    where it divides them, else the most whole 128s under it that do."""
    for block in range(min(SCAN_BLOCK, channels), 0, -128):
        if block % 128 == 0 and channels % block == 0:
            return block
    return 0


def rms_norm(x, scale, eps: float):
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def select(x, w_x, dt_scale, b_scale, c_scale, w_dt, dt_bias, *, eps: float,
           dtype):
    """``(Delta (..., E), B (..., N), C (..., N))`` float32 from ``x``
    ``(..., E)``: ``[delta ; B ; C] = x W_x`` (``R + N + N`` wide, no
    bias), each through an RMSNorm of its own with a learned scale
    (Jamba's ``dt_layernorm`` / ``b_layernorm`` / ``c_layernorm``),
    ``Delta = softplus(delta W_dt + dt_bias)``.  The two matrices rest
    and multiply in ``dtype``, their products accumulate in float32; the
    norms, the bias and the softplus are float32."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("seldon.ssm.select"):
        f32 = jnp.float32
        state_dim = b_scale.shape[0]
        rank = w_x.shape[1] - 2 * state_dim
        low = jnp.einsum("...e,er->...r", x.astype(dtype), w_x.astype(dtype),
                         preferred_element_type=f32)
        delta = rms_norm(low[..., :rank], dt_scale, eps)
        b = rms_norm(low[..., rank:rank + state_dim], b_scale, eps)
        c = rms_norm(low[..., rank + state_dim:], c_scale, eps)
        dt = jnp.einsum("...r,re->...e", delta.astype(dtype),
                        w_dt.astype(dtype), preferred_element_type=f32)
        return jax.nn.softplus(dt + dt_bias.astype(f32)), b, c


# ---------------------------------------------------------------------------
# a decode step
# ---------------------------------------------------------------------------

def step(state, x, dt, b, c, a, d, *, active=None):
    """One position a lane against the resting state: ``state`` ``(B, N,
    E)`` float32, ``x`` / ``dt`` ``(B, E)``, ``b`` / ``c`` ``(B, N)``,
    ``a`` ``(N, E)`` (``-exp(A_log)``), ``d`` ``(E,)``.  ``(new state, y
    (B, E) float32)``.  ``active`` ``(B,)``: a lane it leaves out passes
    ``Delta = 0``, so its state stays as it is, bit for bit."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("seldon.ssm.step"):
        x, dt, b, c, a, d = (v.astype(jnp.float32) for v in (x, dt, b, c, a, d))
        if active is not None:
            dt = jnp.where(active[:, None], dt, 0.0)
        where = backend()
        if step_impl(state.shape[1], state.shape[2], where) == "pallas":
            return _step_pallas(state, x, dt, b, c, a, d,
                                interpret=where != "tpu")
        new = (jnp.exp(dt[:, None, :] * a) * state
               + (dt * x)[:, None, :] * b[:, :, None])
        return new, (new * c[:, :, None]).sum(axis=1) + d * x


def _step_kernel(h_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                 h_out_ref, y_ref):
    """A block of lanes: ``h_ref`` / ``h_out_ref`` ``(lanes, N, E)`` the
    states as they rest, ``x_ref`` / ``dt_ref`` / ``y_ref`` ``(lanes,
    E)``, ``b_ref`` / ``c_ref`` ``(lanes, N, 1)`` the columns down the
    sublanes, ``a_ref`` ``(N, E)``, ``d_ref`` ``(1, E)``.  A lane at a
    time: its state is read once, decayed, written through and read
    against ``C`` — elementwise float32 on the vector unit, one
    exponential an entry."""
    import jax.numpy as jnp

    a, d = a_ref[...], d_ref[...]
    xs, dts = x_ref[...], dt_ref[...]
    rows = []
    for j in range(h_ref.shape[0]):
        x_j, dt_j = xs[j:j + 1], dts[j:j + 1]                  # (1, E)
        h = jnp.exp(dt_j * a) * h_ref[j] + (dt_j * x_j) * b_ref[j]
        h_out_ref[j] = h
        rows.append(jnp.sum(h * c_ref[j], axis=0, keepdims=True) + d * x_j)
    y_ref[...] = jnp.concatenate(rows, axis=0)


def _step_pallas(state, x, dt, b, c, a, d, *, interpret):
    """:func:`step`'s state update as one kernel call: a grid step a
    block of :data:`STEP_LANES` lanes (2.6 MB of state at 16 x 5,120),
    rewritten where it rests.  The state is the first output: a trace
    names the call by it (``pallas_kernel_f32_<slots>_<N>_<E>_``)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes, n, e = state.shape
    bb = STEP_LANES if lanes % STEP_LANES == 0 else lanes
    block = 4 * bb * n * e

    def whole(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    rests = pl.BlockSpec((bb, n, e), lambda i: (i, 0, 0))
    row = pl.BlockSpec((bb, e), lambda i: (i, 0))
    col = pl.BlockSpec((bb, n, 1), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _step_kernel,
        grid=(lanes // bb,),
        in_specs=[rests, row, row, col, col, whole((n, e)), whole((1, e))],
        out_specs=[rests, row],
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((lanes, e), jnp.float32)],
        # a block's states are read whole before their own write, and no
        # other grid step touches them
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # the states' block in and out, each twice (the pipeline's
            # two buffers), and room for the rest
            vmem_limit_bytes=min(4 * block + (16 << 20), 100 << 20)),
        interpret=interpret,
        name="ssm_state_step",
    )(state, x, dt, b[:, :, None], c[:, :, None], a, d[None, :])


# ---------------------------------------------------------------------------
# a prefill: the scan
# ---------------------------------------------------------------------------

def scan(x, dt, b, c, a, d, *, true_lens=None):
    """The recurrence over a group of prompts from a state of zeros:
    ``x`` / ``dt`` ``(B, L, E)``, ``b`` / ``c`` ``(B, L, N)``, ``a`` ``(N,
    E)``, ``d`` ``(E,)``.  ``(y (B, L, E) float32, state (B, N, E))``.
    ``true_lens`` ``(B,)``: the pad rule — ``dt`` (already through its
    softplus) is zeroed at every position past a row's own length, so
    the state that comes back is the one at its last real position (and
    ``y`` there is ``C^T h + D x`` of a state that no longer moves:
    nobody reads it)."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("seldon.ssm.scan"):
        x, dt, b, c, a, d = (v.astype(jnp.float32) for v in (x, dt, b, c, a, d))
        if true_lens is not None:
            real = jnp.arange(x.shape[1])[None, :] < true_lens[:, None]
            dt = jnp.where(real[..., None], dt, 0.0)
        where = backend()
        if scan_impl(a.shape[0], a.shape[1], where) == "pallas":
            return _scan_jit()(x, dt, b, c, a, d, interpret=where != "tpu")
        return recurrence(x, dt, b, c, a, d)


def recurrence(x, dt, b, c, a, d, *, state=None):
    """The plain form, position by position (``lax.scan`` over ``t`` with
    the ``(B, N, E)`` state as carry): what :func:`scan` and :func:`step`
    are held to, the form a reader of the equations at the top can check
    by eye — and XLA's form of the scan."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    a, d = a.astype(f32), d.astype(f32)
    if state is None:
        state = jnp.zeros((x.shape[0], *a.shape), f32)

    def one(h, xs):
        x_t, dt_t, b_t, c_t = xs                      # (B, E) x 2, (B, N) x 2
        h = (jnp.exp(dt_t[:, None, :] * a) * h
             + (dt_t * x_t)[:, None, :] * b_t[:, :, None])
        return h, (h * c_t[:, :, None]).sum(axis=1) + d * x_t

    xs = tuple(jnp.moveaxis(v.astype(f32), 1, 0) for v in (x, dt, b, c))
    state, y = jax.lax.scan(one, state.astype(f32), xs)
    return jnp.moveaxis(y, 0, 1), state


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_ref):
    """One prompt's block of channels over a run of positions: ``x_ref``
    / ``dt_ref`` / ``y_ref`` ``(1, T, block)``; ``b_ref`` / ``c_ref``
    ``(1, T / 8, N, 8)`` — eight positions' columns side by side, the
    state's columns down the sublanes as the state has them; ``a_ref``
    ``(N, block)``, ``d_ref`` ``(1, block)``; ``h_ref`` ``(1, N, block)``
    the state, resident from the prompt's first run to its last (the
    position axis of the grid is the innermost and sequential) and the
    call's second result.  Eight positions a loop step, unrolled: a
    position's exponentials do not wait for the one before it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    a, d = a_ref[...], d_ref[...]

    def eight(i, h):
        at = pl.multiple_of(i * _SUB, _SUB)
        xs, dts = x_ref[0, pl.ds(at, _SUB), :], dt_ref[0, pl.ds(at, _SUB), :]
        bs, cs = b_ref[0, i], c_ref[0, i]                      # (N, 8)
        rows = []
        for j in range(_SUB):
            x_j, dt_j = xs[j:j + 1], dts[j:j + 1]              # (1, block)
            h = jnp.exp(dt_j * a) * h + (dt_j * x_j) * bs[:, j:j + 1]
            rows.append(jnp.sum(h * cs[:, j:j + 1], axis=0, keepdims=True)
                        + d * x_j)
        y_ref[0, pl.ds(at, _SUB), :] = jnp.concatenate(rows, axis=0)
        return h

    h_ref[0] = jax.lax.fori_loop(0, x_ref.shape[1] // _SUB, eight, h_ref[0])


@functools.lru_cache(maxsize=None)
def _scan_jit():
    """:func:`_scan_pallas` under a jit of its own: a program's
    state-space layers call it at the same shapes and share ONE trace and
    ONE lowering of the kernel (``ops/delta.py _scan_jit``'s reason)."""
    import jax

    return jax.jit(_scan_pallas, static_argnames=("interpret",))


def _scan_pallas(x, dt, b, c, a, d, *, interpret):
    """:func:`scan` as one kernel call, ``ssm_scan``: a grid of (prompt,
    block of channels, run of positions), the run axis sequential.  HBM
    sees ``x`` and ``dt`` once on the way in, ``B`` and ``C`` once a
    block of channels (laid ``(B, L / 8, N, 8)``), ``y`` once on the way
    out and the final state once.  ``y`` is the call's FIRST result,
    ``(B, L, E)`` float32: a device trace names the call by it
    (``pallas_kernel_f32_<prompts>_<L>_<E>_``)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    k, length, e = x.shape
    n = a.shape[0]
    block = _scan_block(e)
    run = min(SCAN_POSITIONS, -(-length // _SUB) * _SUB)
    pad = -length % run
    runs = (length + pad) // run

    def rows(v):    # (B, L, E): padded to whole runs (Delta 0: the pad rule)
        return jnp.pad(v, [(0, 0), (0, pad), (0, 0)])

    def cols(v):    # (B, L, N) -> (B, L / 8, N, 8): eight positions' columns
        v = jnp.pad(v, [(0, 0), (0, pad), (0, 0)])
        return v.reshape(k, -1, _SUB, n).transpose(0, 1, 3, 2)

    row = pl.BlockSpec((1, run, block), lambda i, g, j: (i, j, g))
    col = pl.BlockSpec((1, run // _SUB, n, _SUB), lambda i, g, j: (i, j, 0, 0))
    y, state = pl.pallas_call(
        _scan_kernel,
        grid=(k, e // block, runs),
        in_specs=[row, row, col, col,
                  pl.BlockSpec((n, block), lambda i, g, j: (0, g)),
                  pl.BlockSpec((1, block), lambda i, g, j: (0, g))],
        out_specs=[row, pl.BlockSpec((1, n, block), lambda i, g, j: (i, 0, g))],
        out_shape=[jax.ShapeDtypeStruct((k, length + pad, e), f32),
                   jax.ShapeDtypeStruct((k, n, e), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(rows(x), rows(dt), cols(b), cols(c), a, d[None, :])
    return y[:, :length], state
