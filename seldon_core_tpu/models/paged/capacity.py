"""Bytes as a function of a spec and sizes: what one prefill call may
take of the HBM left beside the weights and the pool, and what a
deployment of so many streams holds.  Pure host arithmetic — no jax
array is made here."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple


# A prefill call pays for ``k * bucket`` positions (the group rounded up
# to a power of two) and its temporaries grow with them.  Admission
# groups only merge: whoever waits in the queue when a wave starts is
# prefilled with it, so a burst of arrivals used to become ONE call of
# any size — 32 prompts of 512 needed 18.43 GB of GPT-2-large's 15.75
# (PERF.md §6 PR 26, ROADMAP S0c), 16 of 2,048 needed 20.42 GB of
# GigaChat3.1's and failed all 16 (my chip run, PR 30).  So a call's
# positions are capped at what its temporaries may take of the HBM left
# beside the weights and the pool; a larger group is served as several
# calls in the same wave, in arrival order.
#
# The share of that HBM one call's temporaries may take: the chunk
# enqueued behind a prefill holds its own temporaries at the same time
# (the runtime hands a program its buffers at dispatch, PERF.md §5), and
# the allocator cannot use every gap.
PREFILL_TEMP_SHARE = 0.5


def prefill_position_bytes(spec, d_model: int, vocab_size: int,
                           num_heads: int) -> int:
    """Bytes of temporaries a prefill program keeps per padded position
    where it keeps most, counted from the widths (an estimate of XLA's
    buffer assignment good to a third: 8,192 positions of GigaChat3.1
    were 3.0 GB by the compiler's count, 2.4 GB by this one):

    * ``4 * vocab_size``: what float32 logits at every position took.
      No program holds them since PR 49 (a prefill unembeds the one row
      a prompt it returns, ``_unembed``); the term is kept so that no
      cell's ``prefill_positions_max`` moves in the same PR as the
      program — larger groups would form than the cells' traffic warms
      — and its removal is queued with their ``warm_group_max``
      (ROADMAP S3 c′);
    * the float32 residual stream beside its normed bf16 copy — under a
      residual
      of ``spec.hc_mult`` rows (ops/hyper.py) those rows twice, the
      ones a sub-layer's mixing reads and the ones it writes, beside
      the one row the sub-layer reads and its normed copy;
    * the wider of the attention's rows — q, k, v in bf16 and the
      attended values in float32; the naive latent path makes K and V
      per head — and the FFN's: a dense layer's hidden rows (float32
      and bf16; gate, up and their product for SwiGLU), or a routed
      layer's rows for the assignments a token brings to the experts
      held here (at ``moe.HELD_ROWS_HEADROOM`` even shares: what
      :func:`moe.held_rows_cap` holds under the ridge and the bound on
      what it holds over it, where a pass is smaller), each its bf16
      input, gate, up, product and float32 output, beside the shared
      expert's; a double layer's dense and routed rows together."""
    from seldon_core_tpu.ops import moe

    kept = 4 * vocab_size + 6 * d_model
    if spec.hc_mult:
        kept += 2 * 4 * spec.hc_mult * d_model
    if spec.double_layer:
        # the shortcut's float32 input and output wait out a half-layer
        kept += 8 * d_model
    if spec.kv_heads:
        # grouped-query heads: q and the attended values are num_heads x
        # head_dim wide (bf16 q, float32 and bf16 values), k and v
        # kv_heads x head_dim each
        attn = (8 * num_heads + 4 * spec.kv_heads) * spec.head_dim
    elif spec.kinds:
        # the wider of the two kinds' rows, and under an indexed layer the
        # scores of ops/mla.py INDEX_QUERY_BLOCK queries against every
        # position: the attention's in float32 and bf16, the indexer's in
        # float32 (what a position adds to each block's (heads, block,
        # positions) arrays)
        from seldon_core_tpu.ops import mla

        def rows(heads, qk, v):
            return 2 * heads * (2 * qk + v) + 4 * heads * v

        attn = max(
            rows(num_heads, spec.nope_dim + spec.rope_dim, spec.v_dim)
            + mla.INDEX_QUERY_BLOCK * (6 * num_heads + 4 * spec.index_heads),
            rows(spec.win_heads, spec.win_nope_dim + spec.win_rope_dim,
                 spec.win_v_dim))
    elif spec.latent:
        qk = spec.nope_dim + spec.rope_dim
        attn = 2 * num_heads * (2 * qk + spec.v_dim) + 4 * num_heads * spec.v_dim
    else:
        attn = 10 * d_model
    if spec.linear:
        # a linear layer's rows: q, k, v after the convolution and as the
        # scan lays them (float32, twice), the scan's two solved right-hand
        # sides and its output, and a chunk's three (64, 64) matrices a
        # head (ops/delta.py CHUNK positions share them)
        from seldon_core_tpu.ops import delta

        qkv = 2 * spec.lin_key_dim + spec.lin_value_dim
        # (a decay a key channel: the gate's projection, the running sums
        # and their exponentials a channel, and k once more a diagonal
        # block of the chunk — its columns at each block's own reference)
        channel = ((6 + delta.CHUNK // delta.SUB) * spec.lin_key_dim
                   if spec.lin_gate == "channel" else 0)
        attn = max(attn, 4 * spec.lin_heads * (
            2 * qkv + 2 * (spec.lin_key_dim + spec.lin_value_dim)
            + 3 * delta.CHUNK + channel))
    if spec.ssm:
        # a state-space layer's rows: the in projection's two halves (bf16)
        # and x after the convolution, Delta, y and the gated y (float32);
        # the scan carries the state and never lays it out a position
        attn = max(attn, (2 * 2 + 4 * 4) * spec.ssm_inner
                   + 4 * (spec.ssm_dt_rank + 4 * spec.ssm_state))
    if spec.ffn == "swiglu":
        ffn = 10 * spec.dense_width  # gate, up and their product
    elif not spec.routed:
        ffn = 6 * 4 * d_model  # the GELU MLP's hidden rows
    else:
        swiglu = 10  # bytes a hidden value: gate, up, their product
        dense = spec.dense_layers or spec.double_layer
        ffn = swiglu * spec.dense_width if dense else 0
        rows = spec.experts_per_tok * min(
            1.0, moe.HELD_ROWS_HEADROOM * spec.held / spec.router_outputs)
        routed = (int(rows * (6 * d_model + swiglu * spec.expert_width))
                  + swiglu * spec.shared_experts * spec.expert_width)
        # a double layer's routed shortcut runs beside its dense
        # half-layer, not in another layer's place (the chip compiler:
        # 363 KB a position at LongCat-Flash's widths, 332 KB by this
        # count; b1024_k4 1.73 GiB, b512_k4 0.89)
        ffn = ffn + routed if spec.double_layer else max(ffn, routed)
    return kept + max(attn, ffn)


def prefill_positions_max(free_bytes: Optional[int], position_bytes: int
                          ) -> Optional[int]:
    """The most positions one prefill call may pay for: the largest
    power of two whose temporaries fit :data:`PREFILL_TEMP_SHARE` of
    ``free_bytes``, at least one; None (no cap) where the device does
    not say what it holds (the CPU)."""
    if free_bytes is None:
        return None
    cap = 1
    while 2 * cap * position_bytes <= PREFILL_TEMP_SHARE * max(free_bytes, 0):
        cap *= 2
    return cap


def prefill_group_max(bucket: int, positions_max: Optional[int]) -> int:
    """Prompts of ``bucket`` one prefill call takes under a cap of
    ``positions_max`` positions (a power of two | None): at least one
    (a bucket past the cap is still one prompt a call)."""
    if positions_max is None:
        return 1 << 30
    return max(1, positions_max // bucket)


# A prefill call's rows round up to a power of two (one program a
# (bucket, k)).  Whole empty rows cost what full ones do once a row
# alone fills the MXU, so a call is padded with fewer positions than
# this and a group that would need more is cut at the power of two
# below: three prompts of 1,024 run as two and one, not as four.
PREFILL_PAD_POSITIONS = 1024


def prefill_group_cuts(rows: int, bucket: int, most: int) -> List[int]:
    """The prefill calls a group of ``rows`` same-bucket prompts is cut
    into, as rows a call: at most ``most`` (:func:`prefill_group_max`),
    and no call padded with ``PREFILL_PAD_POSITIONS`` positions of empty
    rows or more."""
    cuts = []
    while rows:
        n = min(rows, most)
        k = 1 << (n - 1).bit_length()
        if (k - n) * bucket >= PREFILL_PAD_POSITIONS:
            n = k // 2
        cuts.append(n)
        rows -= n
    return cuts



def paged_hbm_accounting(
    *,
    streams: int,
    ctx_len: int,
    d_model: int,
    num_layers: int,
    page_size: int = 64,
    steps_per_call: int = 8,
    dtype_bytes: int = 2,
    chunk_impl: str = "ring",
    donated: bool = True,
    split_tile_pad: float = 2.0,
    cached_prefix_pages: int = 0,
    tp_degree: int = 1,
    dp_degree: int = 1,
    num_pool_pages: Optional[int] = None,
    num_heads: Optional[int] = None,
    inflight_prefill_tokens: int = 0,
    adapter_bytes: int = 0,
    reclaimable_weight_bytes: int = 0,
    kv_dtype: str = "bf16",
    host_tier_gib: float = 0.0,
    weight_bytes: int = 0,
    cache_pools: int = 2,
    cache_kinds: Sequence[Tuple[int, int, int]] = (),
    state_bytes: int = 0,
) -> Dict[str, int]:
    """Pool-HBM bytes for ``streams`` concurrent streams at ``ctx_len``
    tokens — the capacity model the bench certifies (VERDICT r5 #3/#5).

    Terms, each measured in earlier rounds rather than assumed:

    * **pool (at rest)** — pages x page_size x d_model x 2 (K+V) x
      layers: the logical bytes of the ``(layers, pages, page_size,
      d_model)`` pool, which the v5e holds unpadded (``hbm_peak_gib``
      8.92 = f32 weights + their bf16 cast + 6.05 GB of pool; PERF.md
      §4, ledger PR 25).
    * **donated vs copied** — the chunk program donates pk/pv
      (``donate_argnums``), so exactly ONE pool copy is live during a
      chunk; without donation XLA keeps input AND output pools and the
      at-rest term doubles.  ``donated=False`` prices that world — the
      accounting the capacity claim must state.
    * **working set (ring impl only)** — the once-per-chunk ctx copy
      (split in flight: charged ``split_tile_pad``, 2.0x, an r5 reading
      of the (8,128) tile that no chip run since has re-taken) plus the
      step-indexed ring;
      the pool impl reads the pool per step and carries no copy.
      Under the r6 length-bucketed gather this is the WORST case
      (uniform ctx_len); mixed traffic gathers less.

    * **cached prefix pages (r9)** — LRU-parked prefix-cache pages are
      RECLAIMABLE: allocation evicts them on demand, so they never
      reduce admissible capacity.  ``cached_prefix_pages`` prices the
      bytes they occupy *between* reclaims (``reclaimable_bytes``)
      without adding to ``peak_bytes`` — the accounting the admission
      guard and ``paged_capacity_streams`` rely on.

    * **tensor parallelism (r11)** — ``tp_degree > 1`` prices the
      PER-SHARD bytes one device holds: the pool and the in-flight
      working set are sharded over heads on the ``model`` axis, so
      every KV term divides by the degree (tables/lengths replicate
      but are KBs against the pool's GBs and stay out of scope like
      the host runtime).  Capacity under a fixed per-chip budget
      therefore SCALES with the degree — the accounting
      ``paged_capacity_streams`` certifies.  Pass ``num_heads`` to
      carry the head-sharding constraint: an indivisible head count
      leaves the pool REPLICATED at engine load
      (``shard_decode_state``'s WARN fallback), so the accounting
      prices FULL bytes rather than certifying capacity the fallback
      cannot deliver.

    * **in-flight prefill scratch (r15)** — under chunked prefill a
      stream admitted but still chunking holds ALL its prompt pages
      mapped (admission allocates the whole prompt's block table up
      front; slices fill it over several waves) while contributing no
      decode.  ``inflight_prefill_tokens`` prices those mapped pages
      (``inflight_prefill_bytes``, included in ``peak_bytes``) so
      :func:`paged_capacity_streams` cannot over-admit during the
      chunking window — the over-admission bug the r15 satellite
      fixed.

    * **adapter pool (r16)** — multi-LoRA serving preallocates a
      slot-granular factor pool next to the KV pool
      (``LoraPool.hbm_bytes`` — already per-shard under TP, since each
      target's sharded factor follows its base layer's megatron
      sharding).  ``adapter_bytes`` prices it into ``peak_bytes``: the
      pool is resident whether or not slots are full, so capacity
      planning must reserve it off the top like in-flight prefill.
      ``reclaimable_weight_bytes`` prices the weight registry's CACHED
      (refcount-0) sets next to the prefix cache's reclaimable pages —
      capacity, never cost.

    * **data axis / sequence sharding (r19)** — ``dp_degree > 1``
      prices the 2-D serving mesh: the pool's PAGE dim is sharded over
      ``data`` (on top of the ``model`` heads sharding), so per-device
      pool bytes divide by BOTH degrees — this is the long-context
      claim: a 32k stream whose full pool bytes exceed one chip's
      budget admits when its per-shard slice fits
      (:func:`paged_max_context` inverts this).  Pass
      ``num_pool_pages`` (the engine's dp-rounded pool) to carry the
      page-divisibility constraint: an indivisible pool leaves the
      page dim REPLICATED at engine load (``shard_decode_state``'s
      WARN fallback), so the accounting prices full page bytes rather
      than certifying capacity the fallback cannot deliver.  The ring
      working set divides with the lane sharding (slot-major arrays
      batch-shard over ``data``); tables/lengths stay out of scope as
      under TP.

    * **int8 KV pool (r18)** — ``kv_dtype="int8"`` prices pages at ONE
      byte per element plus the sibling scale table's 8 bytes per page
      (one f32 per page per k/v per layer): ~2x
      ``paged_capacity_streams`` at equal budget vs bf16.  In-flight
      prefill scratch and reclaimable prefix pages are pool pages, so
      they reprice the same way; the ring working set does NOT — the
      gathered ctx/ring copies hold the engine's compute dtype (and the
      int8 pool is pool-impl-only regardless).

    * **host KV tier (r22)** — ``host_tier_gib`` prices the
      ``SELDON_TPU_KV_OFFLOAD`` host-RAM container budget as its own
      section: ``host_tier_bytes`` is HOST memory (never added to
      ``peak_bytes`` — the tier exists so HBM can shed), and the whole
      budget is ``host_reclaimable_bytes`` because every entry is a
      re-derivable cache the OS may reclaim by dropping demoted pages
      (they re-prefill on miss, exactly as without the tier).

    * **base weights** — ``weight_bytes``: the served tree **as it
      rests** (``ops/surgery.tree_hbm_bytes``; an engine's is
      ``lane_report()["weight_bytes"]``), a fixed term like the adapter
      pool.  A routed spec's tree rests in
      bf16 (norm scales and the router f32) and is read as it is:
      OLMoE at 8 layers is 7.13 GB, no more.  GPT-2's rests in f32 and
      every program holds a bf16 cast of it beside that while it runs
      (PERF.md §4): price that lane's transient on top yourself.

    * **a latent pool** — ``cache_pools=1`` with ``d_model`` the row's
      lanes (``spec.cache_width``: 640 for 576 values) and
      ``num_layers`` the pool's leading axis, attention sub-layers
      (``spec.cache_layers``: two a LongCat-Flash layer); the default 2
      is K and V of ``d_model`` a layer.

    * **a state a lane** — ``state_bytes``: what ONE stream's
      linear-attention state takes as it rests (``ModelSpec.state_bytes``:
      every linear layer's float32 state and convolution inputs; 0
      without such layers), whatever its context: ``streams`` of them are
      a term of ``peak_bytes`` and of ``per_stream_bytes`` beside the
      pages (``num_layers`` then counts the layers that keep pages).

    * **a cache of row kinds** — ``cache_kinds``: ``(layers, lanes,
      window)`` a kind (``spec.cache_kinds`` with the window layers'
      ``spec.window``, 0 for a kind whose pages grow with the stream), in
      place of ``num_layers`` x ``d_model`` x ``cache_pools``.  A kind
      with a window holds a stream's last ``window`` positions and one
      chunk's growth, in whole pages whose first need not start the
      window — ``ceil((window - 1 + steps_per_call) / page_size) + 1``
      pages at most (the engine's ``window_table_pages``), however long
      the stream: past that its pages go back to the allocator, so a
      stream's bytes stop growing in those layers (``window_bytes``, in
      ``pool_bytes`` and ``peak_bytes``).  In-flight prefill scratch and
      the prefix residue price the growing kinds alone (a spec with kinds
      takes neither lane); the native pool type and the pool chunk only.

    Activations and the host runtime stay out of scope.
    """
    shard = max(1, int(tp_degree))
    if num_heads is not None and num_heads % shard:
        # mirror shard_decode_state: this configuration serves with a
        # replicated pool, so one device really holds the full bytes
        shard = 1
    dshard = max(1, int(dp_degree))
    if num_pool_pages is not None and num_pool_pages % dshard:
        # mirror shard_decode_state's page-dim guard: an indivisible
        # pool replicates over `data`, so price the full page bytes
        dshard = 1
    kv_shard = shard * dshard
    pages = -(-ctx_len // page_size)
    kv_int8 = kv_dtype == "int8"
    pool_elt_bytes = 1 if kv_int8 else dtype_bytes
    tok_bytes = num_layers * d_model * cache_pools * pool_elt_bytes
    window_bytes = 0
    if cache_kinds:
        tok_bytes = sum(layers * lanes for layers, lanes, window in cache_kinds
                        if not window) * pool_elt_bytes
        for layers, lanes, window in cache_kinds:
            if window:
                held = min(pages, -(-(window - 1 + steps_per_call)
                                    // page_size) + 1)
                window_bytes += int(streams * held * page_size * layers
                                    * lanes * pool_elt_bytes)
    # sibling scale table: one f32 per page per k/v per layer
    page_scale_bytes = num_layers * 2 * 4 if kv_int8 else 0
    page_bytes = page_size * tok_bytes + page_scale_bytes
    pool = int(streams * pages * page_bytes + window_bytes) // kv_shard
    ws = 0
    if chunk_impl == "ring":
        # the ring impl's gathered working set holds the COMPUTE dtype
        ws = int(
            streams * (pages * page_size + steps_per_call)
            * num_layers * d_model * cache_pools * dtype_bytes * split_tile_pad
        ) // kv_shard
    at_rest = pool if donated else 2 * pool
    state = int(streams) * int(state_bytes)
    inflight_pages = -(-int(inflight_prefill_tokens) // page_size)
    inflight = int(inflight_pages * page_bytes) // kv_shard
    return {
        "pool_bytes": pool,
        "window_bytes": window_bytes // kv_shard,
        "working_set_bytes": ws,
        "peak_bytes": (at_rest + ws + inflight + int(adapter_bytes)
                       + int(weight_bytes) + state),
        "weight_bytes": int(weight_bytes),
        "state_bytes": state,
        "per_stream_bytes": (at_rest + ws + state) // max(1, streams),
        "reclaimable_bytes": int(
            cached_prefix_pages * page_bytes
        ) // kv_shard + int(reclaimable_weight_bytes),
        "inflight_prefill_bytes": inflight,
        "adapter_bytes": int(adapter_bytes),
        "reclaimable_weight_bytes": int(reclaimable_weight_bytes),
        "tp_degree": shard,
        "dp_degree": dshard,
        # host KV tier (r22): HOST bytes, never HBM — always present
        # (0 when the tier is off) so capacity dashboards need no
        # key-existence branch
        "host_tier_bytes": int(float(host_tier_gib) * (1 << 30)),
        "host_reclaimable_bytes": int(float(host_tier_gib) * (1 << 30)),
    }


def paged_capacity_streams(
    budget_bytes: int, ctx_len: int, *, donated: bool = True,
    inflight_prefill_tokens: int = 0, adapter_bytes: int = 0, **model_kw
) -> int:
    """Max concurrent streams whose paged KV peak fits ``budget_bytes``
    at ``ctx_len`` tokens each (per-stream cost is linear in streams,
    so this is one division over the single-stream accounting).

    Prefix-cache residue never prices into this: LRU-cached pages are
    reclaimable on demand (``cached_prefix_pages`` above contributes
    ``reclaimable_bytes``, not ``peak_bytes``), so a warm cache holds
    the same number of admissible streams as a cold pool.

    In-flight prefill scratch DOES price into this (r15 bugfix):
    ``inflight_prefill_tokens`` — prompt tokens of streams admitted
    but still chunking their prefill — reserves its mapped pages off
    the top of the budget BEFORE the per-stream division, because
    those pages are neither free nor reclaimable while the slices run.
    Without the term, chunked prefill let the planner admit streams
    whose pages the chunking prompts already held.

    The multi-LoRA adapter pool (r16) reserves off the top the same
    way: ``adapter_bytes`` (per-shard, ``LoraPool.hbm_bytes``) is
    resident regardless of stream count, so it must come out of the
    budget BEFORE the per-stream division — otherwise enabling
    adapters would silently certify KV capacity the factor pool
    already occupies."""
    one = paged_hbm_accounting(
        streams=1, ctx_len=ctx_len, donated=donated,
        inflight_prefill_tokens=inflight_prefill_tokens,
        adapter_bytes=adapter_bytes, **model_kw
    )
    fixed = (one["inflight_prefill_bytes"] + one["adapter_bytes"]
             + one["weight_bytes"])  # (weight_bytes= rides model_kw)
    per_stream = max(1, one["peak_bytes"] - fixed)
    usable = max(0, int(budget_bytes) - fixed)
    return int(usable // per_stream)


def paged_max_context(
    budget_bytes: int, *, page_size: int = 64, max_len_cap: int = 1 << 20,
    **model_kw,
) -> int:
    """Largest page-aligned context ONE stream can hold under a
    per-chip HBM budget — :func:`paged_capacity_streams` inverted over
    ``ctx_len`` instead of ``streams`` (the ``longctx_max_len`` bench
    key).  Per-stream peak bytes grow monotonically with context, so a
    binary search over page counts suffices; ``dp_degree > 1`` in
    ``model_kw`` is the whole point — sequence sharding divides the
    per-shard bytes, so the admissible context multiplies with the
    data axis (the 2-D mesh's long-context claim, priced not assumed).
    Returns 0 when not even one page fits."""
    def fits(ctx_len: int) -> bool:
        one = paged_hbm_accounting(
            streams=1, ctx_len=ctx_len, page_size=page_size, **model_kw
        )
        return one["peak_bytes"] <= int(budget_bytes)

    lo, hi = 0, max_len_cap // page_size
    if not fits(page_size):
        return 0
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if fits(mid * page_size):
            lo = mid
        else:
            hi = mid - 1
    return lo * page_size
