"""The pool argument's format — what a compiled program is handed as
"the cache" — and the one class that owns what rests there.  Nothing
outside this module knows whether that argument is a bare array, an
int8 ``(pages, scales)`` bundle, a dict of kinds or a dict that
carries a state a lane beside the pages."""

from __future__ import annotations

from collections import OrderedDict, deque
from typing import (
    Any,
    Callable,
    Deque,
    Dict,
    List,
    MutableMapping,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

from seldon_core_tpu.utils import faults as _faults


def kv_split(pool):
    """Split a pool argument into ``(pages, scales)`` — the r18 int8
    bundle is a 2-tuple ``(int8 pages, f32 per-page scales)``; a bare
    array (the native-dtype pool) splits to ``(pool, None)``.  Program
    functions call this at entry so ONE argument convention covers both
    pool dtypes (jit treats the tuple as a pytree; donating it donates
    both leaves)."""
    if isinstance(pool, tuple):
        return pool
    return pool, None


def kv_join(pages, scales):
    """Inverse of :func:`kv_split`."""
    if scales is None:
        return pages
    return (pages, scales)


def state_split(pool):
    """``(K pool, (states, tails))`` of a K-pool argument that carries a
    linear spec's state a lane (``PagedEngine._kv_args``: ``{"kv",
    "state", "conv"}``), and ``(pool, None)`` of any other."""
    if isinstance(pool, dict) and "state" in pool:
        return pool["kv"], (pool["state"], pool["conv"])
    return pool, None


def state_join(pool, delta):
    """:func:`state_split`'s inverse."""
    if delta is None:
        return pool
    return {"kv": pool, "state": tuple(delta[0]), "conv": tuple(delta[1])}


def state_prefill_kwarg(delta, true_lens):
    """``{"delta": ...}`` for a prefill from zero of a spec with linear
    layers (the rows' real lengths: the pad rule's edge), ``{}`` for any
    other — like :func:`window_kwarg`, a helper so that a jitted program
    spells no branch on what is a fact of the call's structure."""
    if delta is None:
        return {}
    return {"delta": {"true_lens": true_lens}}


def state_written(delta, hist, slots):
    """A prefill's ``(resting state, what is left of hist)``: each row's
    state and tail as of its last real position (``hist``: the LM's
    ``(states, tails)``) written at ``slots`` over whatever the slot's
    last stream left — a pad row names a slot past the last, which the
    scatter drops.  ``(None, hist)`` for a spec without linear layers."""
    if delta is None:
        return None, hist
    states, tails, *routing = hist  # (a routed spec's histogram follows)
    return ([rest.at[slots].set(new, mode="drop")
             for rest, new in zip(delta[0], states)],
            [rest.at[slots].set(new.astype(rest.dtype), mode="drop")
             for rest, new in zip(delta[1], tails)]), tuple(routing)


def state_step_kwarg(delta, active, order):
    """``{"delta": ...}`` for a decode step: the state as it rests, the
    lanes that run (in slot order) and the lanes' order, or ``{}``."""
    if delta is None:
        return {}
    return {"delta": {
        "state": delta[0], "conv": delta[1], "order": order,
        "active": active if order is None else active[order[0]]}}


def state_carried(delta, hist):
    """A decode step's ``(state to carry, what is left of hist)``: the
    LM's ``(states, tails)`` take the resting ones' place."""
    if delta is None:
        return None, hist
    return hist[:2], tuple(hist[2:])


def window_kwarg(window):
    """``{"window": window}`` for a cache of kinds' window tables, ``{}``
    for None — the keyword a program passes on to the LM and to the
    write; like :func:`kv_scales_arg`, a helper so that jitted callers
    spell no ternary on what is a fact of the call's structure."""
    if window is None:
        return {}
    return {"window": window}


def kv_scales_arg(sk, sv):
    """The ``kv_scales=`` argument for a split pool: ``None`` for a
    native pool, ``(sk, sv)`` for the int8 bundle.  ``sk is None`` is a
    pytree-STRUCTURE fact fixed at trace time, not a traced value — a
    helper so jitted callers don't spell a ternary the jit-purity
    linter cannot tell apart from tracer control flow."""
    if sk is None:
        return None
    return (sk, sv)


def write_kv(pk, pv, new_k, new_v, block_tables, start, valid, *, page_size, max_len,
             from_zero: bool = False):
    """Write one call's K/V — ``(layers, B, L, d)``, or ``(layers, B,
    L, h, hd)`` as the gather lane and the ring chunk still hand them
    over — into the paged pool ``(layers, pages, ps, d)``, in place.

    ``start``: (B,) absolute position of each row's first token;
    invalid lanes are redirected to trash page 0.  Shared by the
    continuous-batching engine and the speculative decoder.

    Lowering matters enormously on TPU: an arbitrary-index scatter
    serialises (measured ~0.22 ms per index row at d512 — it dominated
    both the decode chunk at 16 slots and the batched prefill at
    16x128 tokens), while ``dynamic_update_slice`` stays in place on
    scan carries.  So every path here is DUS:

    * **decode steps (seg_len == 1)** — one DUS per slot.
    * **prefill (``from_zero=True``, static flag)** — writes always
      begin at position 0, so each (row, page) pair is one CONTIGUOUS
      page-block DUS; rows x pages unrolled statically.  Whole pages
      are written (pad positions land in the row's own page or, for
      rows without that page, in trash page 0 via the zero block-table
      entry) — attention masks by length, and later tokens overwrite.
    * **short segments (speculative verify)** — token-wise DUS,
      seg_len x rows unrolled.

    In place is not the same as cheap: what a DUS costs is set by the
    pool's layout.  On this pool an update is ``[L, 1, 1, d]`` or
    ``[L, 1, ps, d]`` against a page-major ``(…, ps, d)`` tiling and
    touches L short runs: 7 us a decode token, 15-18 us a page block on
    the v5e at GPT-2-large size.  A pool split ``(…, ps, h, hd)``, as
    it rested until PR 25, XLA laid out page-minor on the v5e (a
    64-wide minor dim would pad 2x under the (8, 128) tile): every
    element of an update landed in a tile of its own, and one update
    cost 0.16 ms (decode token) or 8.4-9.7 ms (page block) — 37-47 % of
    device time (PERF.md §6, PR 25).  New K/V should arrive in the
    pool's own form: the kernel lane's block hands them back flat,
    because the ``(h, hd) -> d`` reshape done here is a re-lay (a copy
    per page block) on the chip, not a free collapse.
    """
    import jax
    import jax.numpy as jnp

    # r18 int8 pool: the bundled ``(pages, scales)`` form takes the
    # quantising write path — pages are (re)quantised whole, one f32
    # scale per page per k/v kept exact in the sibling table
    pk_pages, sk = kv_split(pk)
    pv_pages, sv = kv_split(pv)
    if sk is not None:
        pk_pages, sk, pv_pages, sv = _write_kv_int8(
            pk_pages, sk, pv_pages, sv, new_k, new_v, block_tables, start,
            valid, page_size=page_size, max_len=max_len, from_zero=from_zero,
        )
        return (pk_pages, sk), (pv_pages, sv)

    # A lane that hands over split K/V (every lane but the kernel
    # lane's) has them merged here — logically contiguous, a re-lay on
    # the chip.
    if new_k.ndim == 5:
        new_k = new_k.reshape(*new_k.shape[:3], -1)
        new_v = new_v.reshape(*new_v.shape[:3], -1)

    # a latent cache is ONE pool of rows (models/spec.py cache_pools):
    # pv and new_v are None, and every write below is the K write alone
    two = pv is not None
    seg_len = new_k.shape[2]
    B = new_k.shape[1]
    if seg_len == 1:
        pos = jnp.minimum(start, max_len - 1)  # (B,)
        page_idx = pos // page_size
        offs = pos % page_size
        for s in range(B):
            page = jnp.where(
                valid[s, 0], jnp.take(block_tables[s], page_idx[s]), 0
            )
            pk = jax.lax.dynamic_update_slice(
                pk, new_k[:, s][:, None], (0, page, offs[s], 0)
            )
            if two:
                pv = jax.lax.dynamic_update_slice(
                    pv, new_v[:, s][:, None], (0, page, offs[s], 0)
                )
        return pk, pv

    if from_zero:
        # rows x pages of contiguous block writes; pages a row never
        # allocated hold 0 in its block table -> the block lands in the
        # trash page, same redirection the scatter's valid-mask gave
        for s in range(B):
            for j in range(-(-seg_len // page_size)):
                lo = j * page_size
                blen = min(page_size, seg_len - lo)
                page = block_tables[s, j]
                pk = jax.lax.dynamic_update_slice(
                    pk, new_k[:, s, lo : lo + blen][:, None], (0, page, 0, 0)
                )
                if two:
                    pv = jax.lax.dynamic_update_slice(
                        pv, new_v[:, s, lo : lo + blen][:, None],
                        (0, page, 0, 0)
                    )
        return pk, pv

    # short mid-sequence segments (draft_k+1 wide): token-wise DUS
    pos = start[:, None] + jnp.arange(seg_len)[None, :]  # (B, L)
    pos = jnp.minimum(pos, max_len - 1)
    page_idx = pos // page_size
    offs = pos % page_size
    for s in range(B):
        for t in range(seg_len):
            page = jnp.where(
                valid[s, t], jnp.take(block_tables[s], page_idx[s, t]), 0
            )
            pk = jax.lax.dynamic_update_slice(
                pk, new_k[:, s, t][:, None, None], (0, page, offs[s, t], 0)
            )
            if two:
                pv = jax.lax.dynamic_update_slice(
                    pv, new_v[:, s, t][:, None, None],
                    (0, page, offs[s, t], 0)
                )
    return pk, pv


def _write_kv_int8(pk, sk, pv, sv, new_k, new_v, block_tables, start, valid, *,
                   page_size, max_len, from_zero):
    """The quantising twin of :func:`write_kv` for the int8 pool.

    Same DUS lowering discipline and trash-page redirection as the
    native path, with one structural difference: int8 quantisation is a
    PAGE-granular property (one f32 scale per page per k/v), so every
    write touches whole pages —

    * **prefill (``from_zero``)** — each (row, page) block quantises
      fresh: per-layer abs-max over the block, scale = amax/127, pad
      positions zero (they contribute nothing to the abs-max, so a
      partial last page quantises at its live tokens' dynamic range).
    * **decode / speculative segments** — read-modify-write requant:
      dequantise the page at its old scale, ZERO the stale tail at or
      past the write offset (a recycled page's dead values must not
      inflate the new scale), insert the token, recompute the scale,
      requantise the whole page.  NUMERIC CAVEAT: a page filling token
      by token requantises up to ``page_size`` times, so earlier tokens'
      dequantised values can drift by ±scale/2 as the page's dynamic
      range grows — this is the int8 lane's documented regime
      (docs/architecture.md §5b), bounded by the top-1 agreement test.
    """
    import jax
    import jax.numpy as jnp

    if new_k.ndim == 5:
        new_k = new_k.reshape(*new_k.shape[:3], -1)
        new_v = new_v.reshape(*new_v.shape[:3], -1)
    L, d = pk.shape[0], pk.shape[3]

    def _quant(pagef):
        # pagef: (L, 1, ps, d) f32 — one scale per LAYER (the page
        # axis is the sliced singleton)
        amax = jnp.max(jnp.abs(pagef), axis=(1, 2, 3))
        scale = jnp.maximum(amax / 127.0, 1e-8)  # (L,)
        q = jnp.clip(
            jnp.round(pagef / scale.reshape(L, 1, 1, 1)), -127, 127,
        ).astype(jnp.int8)
        return q, scale

    def _rmw_token(pool, scales, tok, page, off):
        # tok: (L, d) f32 — requant one page with ``tok`` at ``off``
        oldq = jax.lax.dynamic_slice(
            pool, (0, page, 0, 0), (L, 1, page_size, d)
        )
        olds = jax.lax.dynamic_slice(scales, (0, page), (L, 1))
        pagef = oldq.astype(jnp.float32) * olds.reshape(L, 1, 1, 1)
        live = (jnp.arange(page_size) < off).reshape(1, 1, page_size, 1)
        pagef = jnp.where(live, pagef, 0.0)
        pagef = jax.lax.dynamic_update_slice(
            pagef, tok[:, None, None], (0, 0, off, 0)
        )
        q, scale = _quant(pagef)
        pool = jax.lax.dynamic_update_slice(pool, q, (0, page, 0, 0))
        scales = jax.lax.dynamic_update_slice(
            scales, scale[:, None], (0, page)
        )
        return pool, scales

    seg_len = new_k.shape[2]
    B = new_k.shape[1]
    new_kf = new_k.astype(jnp.float32)
    new_vf = new_v.astype(jnp.float32)

    if from_zero:
        for s in range(B):
            for j in range(-(-seg_len // page_size)):
                lo = j * page_size
                blen = min(page_size, seg_len - lo)
                page = block_tables[s, j]
                for pool_name, pool, scales, new in (
                    ("k", pk, sk, new_kf), ("v", pv, sv, new_vf)
                ):
                    blk = new[:, s, lo:lo + blen][:, None]  # (L,1,blen,*)
                    if blen < page_size:
                        pad = [(0, 0)] * blk.ndim
                        pad[2] = (0, page_size - blen)
                        blk = jnp.pad(blk, pad)
                    q, scale = _quant(blk)
                    pool = jax.lax.dynamic_update_slice(
                        pool, q, (0, page, 0, 0)
                    )
                    scales = jax.lax.dynamic_update_slice(
                        scales, scale[:, None], (0, page)
                    )
                    if pool_name == "k":
                        pk, sk = pool, scales
                    else:
                        pv, sv = pool, scales
        return pk, sk, pv, sv

    if seg_len == 1:
        pos = jnp.minimum(start, max_len - 1)  # (B,)
        page_idx = pos // page_size
        offs = pos % page_size
        for s in range(B):
            page = jnp.where(
                valid[s, 0], jnp.take(block_tables[s], page_idx[s]), 0
            )
            pk, sk = _rmw_token(pk, sk, new_kf[:, s, 0], page, offs[s])
            pv, sv = _rmw_token(pv, sv, new_vf[:, s, 0], page, offs[s])
        return pk, sk, pv, sv

    # short mid-sequence segments (speculative verify): token-wise RMW
    pos = start[:, None] + jnp.arange(seg_len)[None, :]  # (B, L)
    pos = jnp.minimum(pos, max_len - 1)
    page_idx = pos // page_size
    offs = pos % page_size
    for s in range(B):
        for t in range(seg_len):
            page = jnp.where(
                valid[s, t], jnp.take(block_tables[s], page_idx[s, t]), 0
            )
            pk, sk = _rmw_token(pk, sk, new_kf[:, s, t], page, offs[s, t])
            pv, sv = _rmw_token(pv, sv, new_vf[:, s, t], page, offs[s, t])
    return pk, sk, pv, sv


def write_kinds(pools, new, block_tables, start, valid, window, *, page_size,
                max_len, from_zero: bool = False, pools_v=None, new_v=None):
    """:func:`write_kv` for a cache of row kinds (models/spec.py
    ``cache_kinds``): ``pools`` and ``new`` are ``{"full", "index",
    "window"}``.  The full layers' rows and their indexer keys land where
    the block table says, as any latent row.  The window layers' rows
    land through ``window`` = ``(tables (B, P_w), base (B,))``: a lane's
    table covers positions ``base .. base + P_w * page_size``, so a
    decode step's row is written at ``start - base`` of it, and a
    prefill from zero writes the table's span of its rows — ``P_w`` page
    blocks from position ``base`` (whole pages: ``base`` is a page's
    first position) — and nothing of the prompt behind the window.
    K/V kinds (a multi-head spec's ``{"full", "window"}``): ``pools_v``
    and ``new_v`` hold V under the same names and ride every write
    beside K.  Returns ``(pools, V pools)``, the second None for a
    latent cache, which has no V."""
    import jax
    import jax.numpy as jnp

    w_tables, w_base = window
    out, out_v = {}, {}

    def of(name):  # the kind's V pool and V rows, or None twice
        if pools_v is None:
            return None, None
        return pools_v[name], new_v[name]

    span = w_tables.shape[1] * page_size

    def windowed(rows):  # (layers, B, L, W): the table's span of them
        if not from_zero:
            return rows
        rows = jnp.pad(rows, [(0, 0), (0, 0), (0, span), (0, 0)])
        return jnp.stack([
            jax.lax.dynamic_slice_in_dim(rows[:, s], w_base[s], span, axis=1)
            for s in range(rows.shape[1])], axis=1)

    for name in pools:
        pool_v, rows_v = of(name)
        if name == "window":
            at = jnp.zeros_like(start) if from_zero else start - w_base
            out[name], out_v[name] = write_kv(
                pools[name], pool_v, windowed(new[name]),
                None if rows_v is None else windowed(rows_v), w_tables, at,
                valid, page_size=page_size, max_len=span, from_zero=from_zero)
        else:
            out[name], out_v[name] = write_kv(
                pools[name], pool_v, new[name], rows_v, block_tables, start,
                valid, page_size=page_size, max_len=max_len,
                from_zero=from_zero)
    return out, (None if pools_v is None else out_v)



# Chain root for the prefix index: page i's key is
# ``prefix_chain_key(key_{i-1}, page_tokens)`` with key_0 chained off
# this constant, so one key identifies the ENTIRE token prefix up to
# and including its page (vLLM's hash-chained block keying).  Lookup
# walks root -> leaf and stops at the first miss, which is what makes
# an evicted interior page safely sever its (now unreachable)
# descendants instead of corrupting them.
_PREFIX_ROOT = 0x9E3779B97F4A7C15


def prefix_chain_key(parent: int, tokens: Tuple[int, ...]) -> int:
    """Key of the prefix ending at a full page: ``parent`` is the key of
    the preceding page (``_PREFIX_ROOT`` for page 0), ``tokens`` the
    page's token ids.  Module-level so tests can monkeypatch it into a
    colliding hash — entries verify token equality before sharing, so a
    collision must degrade to a private prefill, never to cross-stream
    KV contamination."""
    return hash((parent, tokens))


class _CachedPrefix:
    """One registered full prompt page in the prefix index.

    The page's KV bytes are a pure function of the token chain the key
    encodes (greedy prefill is deterministic), which is why any stream
    whose prompt starts with that chain can map the page read-only."""

    __slots__ = ("key", "page", "tokens", "parent")

    def __init__(self, key: int, page: int, tokens: Tuple[int, ...], parent: int):
        self.key = key
        self.page = page
        self.tokens = tokens
        self.parent = parent


class PagedCache:
    """What rests on the device between an engine's programs, and the
    host-side books that say whose it is.

    * the K and V pools ``(layers, pages, page_size, width)`` — or one
      latent pool and no V, or one pool a row kind (``kinds``: the full
      layers', their indexer keys', the window layers' over pages of
      their own), int8 with a scale a page beside them (``int8``);
    * the state a lane and its convolution tail (``state`` / ``conv``,
      one array a recurrent layer, whatever the recurrence);
    * the block tables and the window tables, the free lists and the
      reference counts, and the prefix index.

    :meth:`args` / :meth:`store` / :meth:`write` are the only host-side
    code that knows how those travel as a program's pool argument (the
    module's functions take it apart inside the program).

    The cache takes no lock: every method that touches the books is
    called with the engine's lock held, as the ``_locked`` methods they
    were.  A stream is handed in as the object it is — the cache reads
    and writes its ``pages`` / ``wpages`` / ``wfirst`` / ``slot`` and
    knows nothing else of it.

    ``sharding`` — ``(pool_shape, pool_dtype) -> (pool_k, pool_v)`` —
    makes the full pools where the owner places them on a mesh; None
    makes them here, unsharded.  ``counters`` is the mapping whose
    ``prefix_evictions`` and ``window_pages_released`` the allocator
    counts in; ``on_evict(entry)`` sees a cached page the moment it goes
    back to the free list (the host tier stages it).
    """

    def __init__(self, spec, *, num_layers: int, d_model: int, num_pages: int,
                 page_size: int, max_len: int, max_slots: int, max_steps: int,
                 dtype: Any, kv_dtype: str = "bf16",
                 sharding: Optional[Callable[[Tuple[int, ...], Any],
                                             Tuple[Any, Any]]] = None,
                 prefix_cache: bool = False,
                 counters: Optional[MutableMapping[str, Any]] = None,
                 on_evict: Optional[Callable[["_CachedPrefix"], None]] = None):
        import jax
        import jax.numpy as jnp

        self.spec = spec
        self.num_layers = int(num_layers)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_len = int(max_len)
        self.max_slots = int(max_slots)
        self.pages_per_stream = self.max_len // self.page_size
        self.dtype = dtype
        # the cache's geometry is the model's (models/spec.py): K and V
        # of d_model each, or one latent row of kv_rank + rope_dim
        self.width = int(spec.cache_width(d_model))
        # r18 int8 KV pool: pages rest int8 with ONE f32 scale per page
        # per k/v in a sibling (layers, num_pages) table — half the pool
        # bytes, dequantised in-register by the decode kernel and right
        # after the fetch by the gather lane
        self.int8 = kv_dtype == "int8"
        self.pool_dtype = jnp.int8 if self.int8 else dtype
        # a spec with layer kinds: the three pools' (name, layers, lanes)
        self.kinds = spec.cache_kinds(num_layers) if spec.kinds else ()
        # (the leading axis counts attention sub-layers — a double layer
        # has two — not layers)
        pool_shape = (self.kinds[0][1] if self.kinds
                      else spec.cache_layers(num_layers), self.num_pages,
                      self.page_size, self.width)
        if sharding is None:
            self.pages_k = jnp.zeros(pool_shape, self.pool_dtype)
            self.pages_v = (jnp.zeros(pool_shape, self.pool_dtype)
                            if spec.cache_pools == 2 else None)
        else:
            self.pages_k, self.pages_v = sharding(pool_shape, self.pool_dtype)
        # a cache of kinds: one pool a row kind.  The full layers' rows
        # and their indexer keys share the block table (and so the page
        # count); the window layers' rows have a pool, a free list and a
        # table of their own, of fixed width: what a window and one chunk
        # can touch.  The pool holds every slot's table full (and page 0,
        # the trash): a stream holds window pages only while it holds a
        # slot, so the pool never runs short and no lane waits for it
        self.window_pages = 0
        self.num_window_pages = 1
        if self.kinds:
            self.window_pages = spec.window_table_pages(
                self.page_size, int(max_steps))
            self.num_window_pages = self.max_slots * self.window_pages + 1

            def kind_pools(full):
                """One pool a kind: the full layers' (made above), and
                zeros for every other kind — the window layers' over
                their own pages."""
                return {name: full if name == "full" else jnp.zeros(
                    (layers, self.num_window_pages if name == "window"
                     else self.num_pages, self.page_size, lanes),
                    self.pool_dtype)
                    for name, layers, lanes in self.kinds}

            self.pages_k = kind_pools(self.pages_k)
            if self.pages_v is not None:  # K/V kinds: V's pools beside K's
                self.pages_v = kind_pools(self.pages_v)
        self.free_wpages: Deque[int] = deque(
            range(1, self.num_window_pages))  # 0 = trash
        self.wtables = np.zeros((self.max_slots, self.window_pages), np.int32)
        self.wbase = np.zeros((self.max_slots,), np.int32)
        # recurrent layers: a state a lane, beside the pages.  One array
        # a layer — ``spec.state_shape`` float32 over the slots (a linear
        # layer's ``ops/delta.py state_shape``, a state-space layer's
        # ``(slots, N, E)``: ops/ssm.py), and the convolution's last
        # inputs ``(slots, taps - 1, channels)`` in the compute type — so
        # that a layer's update replaces its own array and nothing of the
        # others moves; a prefill writes its slots' rows, a chunk carries
        # them all
        self.state_layers = spec.state_layers(num_layers)
        self.state: Tuple[Any, ...] = ()
        self.conv: Tuple[Any, ...] = ()
        if spec.recurrent:
            self.state = tuple(
                jnp.zeros(spec.state_shape(self.max_slots), jnp.float32)
                for _ in range(self.state_layers))
            self.conv = tuple(
                jnp.zeros((self.max_slots, spec.state_taps - 1,
                           spec.state_channels), dtype)
                for _ in range(self.state_layers))
        # ... in bytes as it rests, every slot's (what the tiling pads
        # counted)
        self.state_bytes = self.max_slots * spec.state_bytes(num_layers)
        # sibling per-page scale tables (int8 pool only): one f32 per
        # page per k/v, indexed exactly like the pool's page axis — the
        # export/migration/import paths slice them with the same page
        # index lists the pages use
        self.scales_k = self.scales_v = None
        if self.int8:
            self.scales_k = jnp.zeros((num_layers, self.num_pages), jnp.float32)
            self.scales_v = jnp.zeros((num_layers, self.num_pages), jnp.float32)
        # the bytes ONE device holds of the pools (the number HBM planning
        # cares about — a pool sharded over heads or pages is sliced, an
        # unshardable one reports full bytes honestly)
        self.pool_shard_bytes = spec.cache_pools * sum(
            int(pool.addressable_shards[0].data.nbytes)
            for pool in jax.tree_util.tree_leaves(self.pages_k))
        if self.int8:
            self.pool_shard_bytes += 2 * int(self.scales_k.nbytes)
        # refcounted page allocator (r9).  The free list is a deque —
        # alloc/free are popleft/append.  Page states (docs §5d state
        # machine):
        #   free   — on free_pages, refcount 0
        #   mapped — refcount == number of live streams whose block
        #            table points at it (shared prompt pages count once
        #            per stream)
        #   cached — refcount 0 BUT registered in the prefix index:
        #            parked on the lru OrderedDict (oldest first) and
        #            reclaimed by alloc under pressure instead of being
        #            freed eagerly on stream finish
        self.free_pages: Deque[int] = deque(range(1, self.num_pages))  # 0 = trash
        self.page_ref = np.zeros((self.num_pages,), np.int32)
        self.tables = np.zeros((self.max_slots, self.pages_per_stream), np.int32)
        # prefix index: chain key -> _CachedPrefix (page registered as
        # the canonical holder of that token prefix; may be mapped or
        # LRU-cached), plus the reverse page -> entry map the release
        # path and the invariant checker need
        self.prefix_index: Dict[int, _CachedPrefix] = {}
        self.page_entry: Dict[int, _CachedPrefix] = {}
        self.lru: "OrderedDict[int, _CachedPrefix]" = OrderedDict()
        self.prefix_enabled = bool(prefix_cache)
        self.counters = counters if counters is not None else {
            "prefix_evictions": 0, "window_pages_released": 0}
        self.on_evict = on_evict

    # ---- the pool argument (host side) -------------------------------------

    def args(self):
        """The pool arguments every jitted program takes: bare arrays
        for the native pool, ``(pages, scales)`` bundles for the int8
        pool (r18), a dict a kind for a cache of kinds — one argument
        convention, the programs split at entry (:func:`kv_split`)."""
        if self.int8:
            return (self.pages_k, self.scales_k), (self.pages_v, self.scales_v)
        if self.state:
            # the state a lane rides with the K pool: donated with it,
            # carried by a chunk's scan with it, stored back with it
            # (:func:`state_split`)
            return ({"kv": self.pages_k, "state": self.state,
                     "conv": self.conv}, self.pages_v)
        return self.pages_k, self.pages_v

    def store(self, pk, pv) -> None:
        """Inverse of :meth:`args` for a program's returned pools."""
        if self.int8:
            (self.pages_k, self.scales_k), (self.pages_v, self.scales_v) = pk, pv
        elif self.state:
            self.pages_k, self.pages_v = pk["kv"], pv
            self.state, self.conv = pk["state"], pk["conv"]
        else:
            self.pages_k, self.pages_v = pk, pv

    def write(self, pk, pv, new_k, new_v, block_row_or_tables, start, valid,
              from_zero: bool = False, window=None):
        """One call's new rows into the pools as a program holds them
        (traced: ``pk`` / ``pv`` are the program's split arguments)."""
        if self.kinds:  # (a latent cache's pv and new_v are None)
            return write_kinds(
                pk, new_k, block_row_or_tables, start, valid, window,
                page_size=self.page_size, max_len=self.max_len,
                from_zero=from_zero, pools_v=pv, new_v=new_v)
        return write_kv(
            pk, pv, new_k, new_v, block_row_or_tables, start, valid,
            page_size=self.page_size, max_len=self.max_len, from_zero=from_zero,
        )

    # ---- refcounted page allocator + prefix cache (r9) --------------------

    def pages_of(self, tokens: int) -> int:
        """Pages that hold ``tokens`` positions."""
        return -(-tokens // self.page_size)

    def allocatable(self) -> int:
        """Pages available right now: the free list plus the LRU-cached
        set (refcount-0 prefix pages are reclaimable on demand, so
        capacity accounting must count them as available)."""
        return len(self.free_pages) + len(self.lru)

    def evict_cached(self) -> None:
        """Reclaim the least-recently-used cached page: unregister it
        from the prefix index and return it to the free list.  With the
        KV tier on (r22) ``on_evict`` STAGES the page for host demotion
        first: its KV stays valid until the next pool-writing device
        call, and every such call is preceded by a flush that gathers
        the staged pages host-side — demote instead of discard, off the
        allocation hot path."""
        page, entry = self.lru.popitem(last=False)  # oldest first
        self.prefix_index.pop(entry.key, None)
        self.page_entry.pop(page, None)
        if self.on_evict is not None:
            self.on_evict(entry)
        self.free_pages.append(page)
        self.counters["prefix_evictions"] += 1

    def alloc(self, n: int) -> Optional[List[int]]:
        """Take ``n`` fresh pages (refcount 1 each), evicting LRU-cached
        pages under pressure.  Stack-discipline deque: O(1) per page.

        Fault point ``paged.alloc`` (utils/faults.py): an armed
        injection reports exhaustion exactly as a genuinely full pool
        would, driving the caller's stall/evict/rollback machinery."""
        if _faults.fire("paged.alloc"):
            return None
        if self.allocatable() < n:
            return None
        while len(self.free_pages) < n:
            self.evict_cached()
        out = [self.free_pages.popleft() for _ in range(n)]
        for p in out:
            self.page_ref[p] = 1
        return out

    def free(self, pages: List[int]) -> None:
        """Release one stream's mapping of ``pages``.  A page whose
        refcount drops to zero either parks on the LRU cached set (it
        is a registered prefix page — its KV stays valid and a later
        admission can remap it) or returns to the free list.  Reversed
        iteration inserts a stream's DEEPEST prefix pages into the LRU
        first (oldest), so under pressure leaves evict before the
        parents their chain lookups walk through."""
        for p in reversed(pages):
            r = int(self.page_ref[p]) - 1
            self.page_ref[p] = max(r, 0)
            if r > 0:
                continue
            entry = self.page_entry.get(p)
            if entry is not None and self.prefix_enabled:
                self.lru[p] = entry  # most-recent end
            else:
                if entry is not None:  # registered but caching disabled
                    self.prefix_index.pop(entry.key, None)
                    self.page_entry.pop(p, None)
                self.free_pages.append(p)

    def seat(self, stream, length: int) -> None:
        """Write ``stream``'s pages into its slot's row of the block
        table (admission), and start its window pages at ``length``."""
        row = np.zeros((self.pages_per_stream,), np.int32)
        row[: len(stream.pages)] = stream.pages
        self.tables[stream.slot] = row
        if self.kinds:
            stream.wpages, stream.wfirst = [], self.window_first(length)
            self.window_ensure(stream, length, length)

    def ensure_pages(self, stream, length: int, horizon: int) -> bool:
        """Grow the stream's block table to cover positions up to
        ``horizon`` (a chunk from ``length``); False when the allocator
        has no page left."""
        slot = stream.slot
        need = self.pages_of(horizon)
        while len(stream.pages) < need:
            got = self.alloc(1)
            if got is None:
                return False
            self.tables[slot, len(stream.pages)] = got[0]
            stream.pages.extend(got)
        if self.kinds:
            self.window_ensure(stream, length, horizon)
        return True

    # ---- the window layers' pages (a spec with layer kinds) ----------------

    def window_first(self, length: int) -> int:
        """The first logical page a step at position ``length`` (and so
        any later one) still reads in a window layer."""
        return max(0, length - (self.spec.window - 1)) // self.page_size

    def window_ensure(self, stream, length: int, horizon: int) -> None:
        """Move ``stream``'s window pages to what steps from position
        ``length`` up to ``horizon`` touch: the pages wholly behind the
        window at ``length`` go back to the allocator (no later step
        reads them; a wave still in flight reads them before anything
        enqueued after this can write them — programs run in order),
        pages up to the horizon are taken, and the lane's table and base
        are rewritten.  The pool backs every slot's whole table, and
        only a stream in a slot holds pages: none is ever missing."""
        first = self.window_first(length)
        drop = min(first - stream.wfirst, len(stream.wpages))
        if drop > 0:
            self.free_wpages.extend(stream.wpages[:drop])
            del stream.wpages[:drop]
            self.counters["window_pages_released"] += drop
        stream.wfirst = max(stream.wfirst, first)
        need = self.pages_of(horizon) - stream.wfirst
        while len(stream.wpages) < need:
            stream.wpages.append(self.free_wpages.popleft())
        row = self.wtables[stream.slot]
        row[:] = 0
        row[:len(stream.wpages)] = stream.wpages
        self.wbase[stream.slot] = stream.wfirst * self.page_size

    def free_window(self, stream, slots: Sequence[Any]) -> None:
        """Every window page ``stream`` holds goes back (finish,
        eviction, failure); ``slots``: the stream in each slot."""
        if stream.wpages:
            self.free_wpages.extend(stream.wpages)
            stream.wpages = []
            if stream.slot is not None and slots[stream.slot] in (stream, None):
                # (a predicted finisher's slot may hold a joiner by now)
                self.wtables[stream.slot] = 0
                self.wbase[stream.slot] = 0
        stream.wfirst = 0

    def release(self, stream, slots: Sequence[Any]) -> None:
        """Everything ``stream`` holds goes back: its pages' mappings
        (:meth:`free`) and its window pages (:meth:`free_window`)."""
        self.free(stream.pages)
        self.free_window(stream, slots)
        stream.pages = []

    def chunk_tables(self) -> Dict[str, Any]:
        """What a chunk program is handed beside the block tables: the
        window layers' tables as this wave reads them — copies, the next
        wave's planning rewrites the host's — and ``{}`` for a cache of
        one kind."""
        import jax.numpy as jnp

        if not self.kinds:
            return {}
        return {"window": (jnp.asarray(self.wtables.copy()),
                           jnp.asarray(self.wbase.copy()))}

    def prefill_tables(self, slots: Sequence[int], k: int) -> Dict[str, Any]:
        """What a from-zero prefill call of ``k`` rows is handed beside
        its block rows (``slots``: the real rows' slots, in row order)."""
        import jax.numpy as jnp

        out: Dict[str, Any] = {}
        if self.kinds:
            # the window layers' write tables (pad rows: the trash page)
            w_rows = np.zeros((k, self.window_pages), np.int32)
            w_base = np.zeros((k,), np.int32)
            for i, slot in enumerate(slots):
                w_rows[i] = self.wtables[slot]
                w_base[i] = self.wbase[slot]
            out["window"] = (jnp.asarray(w_rows), jnp.asarray(w_base))
        if self.state:
            # where each row's state rests: its stream's slot (a pad
            # row: past the last, dropped by the write)
            at = np.full((k,), self.max_slots, np.int32)
            at[:len(slots)] = slots
            out["slots"] = jnp.asarray(at)
        return out

    # ---- the prefix index ---------------------------------------------------

    def match_prefix(self, prompt, root: int) -> List[_CachedPrefix]:
        """Longest cached prefix of FULL prompt pages, walked root →
        leaf through the chain-keyed index in O(pages).  The last
        prompt page is always private — even when the prompt is an
        exact page multiple — so the suffix prefill always has at least
        one token to produce the next-token logits from.  Colliding
        keys verify parent AND token equality before sharing: a hash
        collision (including an adapter root colliding with another's)
        degrades to a miss, never to foreign KV.  No LRU touching
        here: :meth:`map_prefix` pops every matched refcount-0 page off
        the LRU (and :meth:`unmap_prefix` re-inserts deepest first), so
        the leaves-evict-before-parents ordering is maintained entirely
        by insertion discipline."""
        if not self.prefix_enabled:
            return []
        ps = self.page_size
        n_full = (len(prompt) - 1) // ps
        matched: List[_CachedPrefix] = []
        parent = root
        for i in range(n_full):
            toks = tuple(int(t) for t in prompt[i * ps:(i + 1) * ps])
            key = prefix_chain_key(parent, toks)
            entry = self.prefix_index.get(key)
            if entry is None or entry.parent != parent or entry.tokens != toks:
                break
            matched.append(entry)
            parent = key
        return matched

    def map_prefix(self, matched: List[_CachedPrefix]) -> None:
        """One more stream maps the matched pages (off the LRU where
        none did)."""
        for e in matched:
            if int(self.page_ref[e.page]) == 0:
                self.lru.pop(e.page, None)
            self.page_ref[e.page] += 1

    def unmap_prefix(self, matched: List[_CachedPrefix]) -> None:
        """:meth:`map_prefix` rolled back (the admission found no fresh
        pages): the deepest page re-parked first."""
        for e in reversed(matched):
            self.page_ref[e.page] -= 1
            if int(self.page_ref[e.page]) == 0:
                self.lru[e.page] = e

    def register_prefix(self, stream, root: int) -> List[int]:
        """Publish a prefilled stream's full prompt pages into the
        prefix index (called once the prefill device call owning their
        KV has been issued — later programs read the pool through the
        threaded pool arrays, so the data dependency orders any shared
        read after this write); the keys newly registered.  Pages whose
        key is already registered stay private: either they ARE the
        registered page (matched at admission), a concurrent identical
        prompt got there first (its page is canonical, ours frees
        normally), or the key collides with different tokens (never
        share unverified content — and stop, since lookups cannot walk
        past a collision either)."""
        fresh: List[int] = []
        if not self.prefix_enabled:
            return fresh
        ps = self.page_size
        prompt = stream.prompt
        n_full = (len(prompt) - 1) // ps
        parent = root
        for i in range(n_full):
            toks = tuple(int(t) for t in prompt[i * ps:(i + 1) * ps])
            key = prefix_chain_key(parent, toks)
            entry = self.prefix_index.get(key)
            if entry is None:
                page = stream.pages[i]
                if page not in self.page_entry:
                    e = _CachedPrefix(key, page, toks, parent)
                    self.prefix_index[key] = e
                    self.page_entry[page] = e
                    fresh.append(key)
            elif entry.parent != parent or entry.tokens != toks:
                break  # collision: descendants are unreachable anyway
            parent = key
        return fresh

    # ---- the audit ------------------------------------------------------------

    def check_invariants(self, slots: Sequence[Any] = (),
                         live: Sequence[Any] = ()) -> List[str]:
        """SELDON_TPU_PAGED_DEBUG=1 audit (chunk boundaries), as the
        problems found: the non-trash pages partition into free ∪ cached
        ∪ mapped, refcounts equal the number of live block tables holding
        each page, and every LRU entry is consistent with the prefix
        index.  ``slots``: the stream in each slot (or None); ``live``:
        every stream that may hold window pages."""
        problems: List[str] = []
        free = list(self.free_pages)
        free_set = set(free)
        if len(free_set) != len(free):
            problems.append("duplicate pages on the free list")
        cached = set(self.lru)
        mapped: Dict[int, int] = {}
        for s in slots:
            if s is None:
                continue
            for i, p in enumerate(s.pages):
                mapped[p] = mapped.get(p, 0) + 1
                if int(self.tables[s.slot, i]) != p:
                    problems.append(
                        f"slot {s.slot} block table col {i} != stream page {p}"
                    )
        for a, b, name in (
            (free_set, cached, "free∩cached"),
            (free_set, set(mapped), "free∩mapped"),
            (cached, set(mapped), "cached∩mapped"),
        ):
            if a & b:
                problems.append(f"pages simultaneously {name}: {sorted(a & b)}")
        every = free_set | cached | set(mapped)
        want = set(range(1, self.num_pages))
        if every != want:
            problems.append(
                f"leaked pages {sorted(want - every)} / phantom {sorted(every - want)}"
            )
        for p in want:
            if int(self.page_ref[p]) != mapped.get(p, 0):
                problems.append(
                    f"page {p} refcount {int(self.page_ref[p])} != "
                    f"{mapped.get(p, 0)} live mappings"
                )
        for p, entry in self.lru.items():
            if entry.page != p or self.prefix_index.get(entry.key) is not entry \
                    or self.page_entry.get(p) is not entry:
                problems.append(f"LRU entry for page {p} inconsistent with index")
        if self.kinds:
            # the window pool: a page is free or held by one stream, and
            # a stream's pages are the ones its lane's table names
            held: Dict[int, int] = {}
            for st in live:
                for pg in st.wpages:
                    held[pg] = held.get(pg, 0) + 1
                if st.slot is not None and slots[st.slot] is st and (
                        list(self.wtables[st.slot, :len(st.wpages)])
                        != st.wpages):
                    problems.append(
                        f"stream {st.req_id}: window table != its pages")
            free_w = list(self.free_wpages)
            if any(n > 1 for n in held.values()) or set(free_w) & set(held):
                problems.append("a window page is held twice or free and held")
            if len(free_w) + len(held) != self.num_window_pages - 1 or 0 in held:
                problems.append(
                    f"window pages: {len(free_w)} free + {len(held)} held != "
                    f"{self.num_window_pages - 1}")
        return problems

    # ---- what the reports read ----------------------------------------------

    @property
    def pool_pages_used(self) -> int:
        """Pages mapped by a live stream (neither free nor cached)."""
        return self.num_pages - 1 - len(self.free_pages) - len(self.lru)

    @property
    def full_pages_held(self) -> int:
        """A cache of kinds' full-layer pages off the free list (0 for
        any other cache)."""
        return self.num_pages - 1 - len(self.free_pages) if self.kinds else 0

    @property
    def window_pages_held(self) -> int:
        return self.num_window_pages - 1 - len(self.free_wpages)

    @property
    def window_pages_total(self) -> int:
        return self.num_window_pages - 1

    @property
    def prefix_pages_cached(self) -> int:
        return len(self.lru)
