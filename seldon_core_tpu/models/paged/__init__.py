"""Paged KV-cache + continuous batching for autoregressive serving.

The contiguous cache in :mod:`seldon_core_tpu.models.generate` allocates
``batch x max_len`` K/V slots per request batch and requires every
prompt in a batch to share one length.  This module replaces that with
the memory model long-running generation services need (the reference
serving stack has no generation path at all — this extends the
framework the direction its GPU successors went):

* **Paged pool** — K/V live in one shared pool of fixed-size pages
  ``(layers, num_pages, page_size, heads, head_dim)``; each stream owns
  a *block table* mapping its logical positions to pages.  HBM scales
  with tokens actually generated, not ``slots x max_len``.
* **Continuous batching** — streams join and leave between decode
  chunks; one compiled decode program of static shape ``(max_slots,)``
  serves every mix of prompt lengths, sampling settings and
  ``max_new_tokens``.  Finished slots free their pages immediately and
  the next queued request takes over the slot — no head-of-line
  blocking on the longest generation in a batch.
* **Static shapes throughout** — page reads are one gather, writes one
  scatter; EOS/stall handling is mask-based; the per-chunk inner loop
  is a ``lax.scan`` with sampling on device, so ``steps_per_call``
  tokens cost one host round-trip.

``PagedTransformerLM`` mirrors :class:`TransformerLM`'s parameter tree
exactly (same module names in the same order), so a trained
TransformerLM checkpoint drives paged decoding unchanged — tested by
structural equality in tests/test_paged.py.

Page 0 is reserved as a *trash page*: writes for masked-out lanes
(padding, finished or stalled slots) are redirected there and no block
table ever legitimately reads past its stream's length, so scatters
need no dynamic control flow.
"""

from .cache import (
    PagedCache,
    kv_join,
    kv_split,
    prefix_chain_key,
    write_kinds,
    write_kv,
)
from .capacity import (
    PREFILL_PAD_POSITIONS,
    PREFILL_TEMP_SHARE,
    paged_capacity_streams,
    paged_hbm_accounting,
    paged_max_context,
    prefill_group_cuts,
    prefill_group_max,
    prefill_position_bytes,
    prefill_positions_max,
)
from .component import StreamingLM
from .engine import (
    TPU_COMPILER_OPTIONS,
    PagedEngine,
    _Stream,
    get_chunk_lm_class,
    get_paged_lm_class,
    journal_entry,
)
from .lanes import (
    paged_kernel_explicit,
    paged_kernel_mode,
    paged_kernel_requested,
    paged_kernel_static_eligible,
    paged_kv_dtype_mode,
)
from .seam import _DeliveryTally, _DeviceClock, _WaveSeam

__all__ = [
    "PREFILL_PAD_POSITIONS",
    "PREFILL_TEMP_SHARE",
    "PagedCache",
    "TPU_COMPILER_OPTIONS",
    "PagedEngine",
    "StreamingLM",
    "get_chunk_lm_class",
    "get_paged_lm_class",
    "journal_entry",
    "kv_join",
    "kv_split",
    "paged_capacity_streams",
    "paged_hbm_accounting",
    "paged_kernel_explicit",
    "paged_kernel_mode",
    "paged_kernel_requested",
    "paged_kernel_static_eligible",
    "paged_kv_dtype_mode",
    "paged_max_context",
    "prefill_group_cuts",
    "prefill_group_max",
    "prefill_position_bytes",
    "prefill_positions_max",
    "prefix_chain_key",
    "write_kinds",
    "write_kv",
]
