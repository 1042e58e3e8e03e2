"""What ``dots3-note``'s learned sparse attention and its window layers
need, computed from shapes, and which of a trace's operations are
theirs: shared by the ``index_*``, ``sparse_*`` and ``window_*`` readers
(not a metric itself).

**Bytes and FLOPs** (work *needed*: one pass, values and not the lanes
they rest in, bfloat16).

* Scoring one cached indexer key for one lane-step: the key's
  ``index_head_dim`` values (128 x 2 = 256 B) and ``2 x index_n_heads x
  index_head_dim`` FLOP for the heads' products, ``+ 2 x index_n_heads``
  for the ReLU-weighted sum (16,512 FLOP): 64 FLOP a byte against the
  v5e's ridge of 240, so the bytes bind.
* Attending one chosen row of a full layer: ``kv_lora_rank +
  qk_rope_head_dim`` values (576 x 2 = 1,152 B) and ``2 x heads x (576 +
  512)`` FLOP at 128 heads (278,528): 242 FLOP a byte, level with the
  ridge — the larger of the two is taken, as ``mla_work`` does.
* Attending one row of a window layer: 1,088 x 2 = 2,176 B and ``2 x 64
  x (1088 + 1024)`` = 270,336 FLOP.

**Which operations are whose** (``trace["ops"]`` is keyed by opcode plus
the first output's type and shape; ``moe_work.py`` says why a reader has
nothing else; XLA flattens a gather's leading dims, so ``lanes x topk``
and ``lanes x pages`` stand as one number).  With ``lanes`` a decode
bucket (``max_slots`` or half), ``pages`` a block table's width that can
hold over ``index_topk`` positions (a power of two, or the per-stream
table), ``span`` = ``pages x page_size``, ``topk`` = ``index_topk``, by
**whole shape** (names from my traced run, PR 38, of bucket 64 x 112):

* **indexer scoring**: the gather of the cached keys ``(lanes x pages,
  page_size, index lanes)`` (``fusion_bf16_7168_64_128_``), the heads'
  products and their ReLU-weighted sum — any shape that starts with
  ``lanes`` and holds ``index_n_heads`` and a ``span`` — and ``(lanes,
  span)`` itself in any type but a sort's (``fusion_f32_64_7168_``: the
  sum, the masks that cut at the length and put the own score in its
  slot); **or a ``pallas_kernel``** whose first output is those scores,
  ``(lanes, span)`` or ``(lanes, pages, page_size)``: scoring fused over
  the key pages is the same work;
* **top-k**: a ``sort`` of ``(lanes, span)`` (``sort_f32_64_7168_``: the
  scores with positions and pool rows carried) and what is cut from its
  outputs, ``(lanes, topk)``;
* **the attention that reads chosen rows**: the gather of the rows
  ``(lanes x topk, row lanes)`` or ``(lanes, topk, row lanes)``
  (``fusion_bf16_131072_640_``), their scores and weights ``(lanes,
  heads, topk)``, the weighted rows ``(lanes, heads, rank)``
  (``fusion_bf16_64_128_512_``) and the flash statistics and merge
  with the step's own row ``(lanes, heads)`` in float32 at the full
  layers' head count (``fusion_f32_64_128_``); **or a ``pallas_kernel``**
  whose first output is ``(lanes, heads, rank)``, three dims — the flash
  state ``ops/kernels.py latent_attention_decode`` returns for a full
  layer (``pallas_kernel_f32_64_128_512_``; ``mla_work.is_latent_kernel``
  spells the same rule): a kernel that walks a lane's pages under a mask
  of the chosen positions does the work of the gather, the scores and
  the weighted rows, and is taken for what it does.  The needed work
  stays the chosen set's cached members (``sparse_rows_read``), not the
  rows such a kernel moves.  **Where a bucket has as
  many lanes as the layer has heads** (128 x 128) that shape is also the
  indexed prefill's softmax statistics of one group's block of 128
  queries (``fusion_f32_128_128_``: 0.064 s a 3,072-position prompt, 2 %
  of busy), so it is counted only when the trace ran a chunk program
  with a bucket of that many lanes (``trace["modules"]``:
  ``jit_paged_chunk_s8_128x112``), and then holds the prefill's part too:
  PERF.md section 5 says which chunk programs a traced reading saw;
* **the window kernel**: a ``pallas_kernel`` whose first output is
  ``(lanes, swa heads, swa rank)`` (``pallas_kernel_f32_128_64_1024_``).

A prefill's indexed attention (blocks of queries in XLA) and its
windowed kernel are prefill time (``prefill_time_share_pct``) and are not
in these.
"""

from __future__ import annotations

from layer_metrics.pool_move_share_pct import shape_of as dims_of

BYTES = 2


def sizes(config: dict):
    """The configuration's sizes a reader needs, or None for a
    configuration without an indexer and window layers."""
    model, engine = config.get("model") or {}, config.get("engine") or {}
    try:
        out = {k: int(model[k]) for k in (
            "index_topk", "index_n_heads", "index_head_dim", "num_attention_heads",
            "kv_lora_rank", "qk_rope_head_dim", "swa_num_attention_heads",
            "swa_kv_lora_rank", "swa_qk_rope_head_dim", "sliding_window_size",
            "num_hidden_layers")}
        kinds = model["layer_types"][:out["num_hidden_layers"]]
        out["window_layers"] = sum(k == "sliding_attention" for k in kinds)
        out["full_layers"] = len(kinds) - out["window_layers"]
        slots, ps = int(engine["max_slots"]), int(engine["page_size"])
        cap = int(engine["max_len"]) // ps
    except (KeyError, TypeError, ValueError):
        return None
    out["lanes"] = {slots, slots // 2, slots - slots // 2}
    pages, p = {cap}, 1
    while p < cap:
        pages.add(p)
        p *= 2
    out["pages"], out["page_size"] = pages, ps
    out["spans"] = {n * ps for n in pages}
    out["steps"] = int(engine["steps_per_call"])
    return out


def lanes_of(n: int) -> int:
    return -(-n // 128) * 128


def index_key_bytes(z: dict) -> float:
    return float(z["index_head_dim"] * BYTES)


def index_key_flops(z: dict) -> float:
    return 2.0 * z["index_n_heads"] * z["index_head_dim"] + 2.0 * z["index_n_heads"]


def full_row_bytes(z: dict) -> float:
    return float((z["kv_lora_rank"] + z["qk_rope_head_dim"]) * BYTES)


def full_row_flops(z: dict) -> float:
    return 2.0 * z["num_attention_heads"] * (
        2 * z["kv_lora_rank"] + z["qk_rope_head_dim"])


def window_row_bytes(z: dict) -> float:
    return float((z["swa_kv_lora_rank"] + z["swa_qk_rope_head_dim"]) * BYTES)


def window_row_flops(z: dict) -> float:
    return 2.0 * z["swa_num_attention_heads"] * (
        2 * z["swa_kv_lora_rank"] + z["swa_qk_rope_head_dim"])


def least_seconds(n: float, item_bytes: float, item_flops: float, peaks: dict) -> float:
    return max(n * item_bytes / peaks["hbm_bytes_per_s"],
               n * item_flops / peaks["bf16_flops"])


def selecting(z: dict):
    """``(pages, span)`` of the block tables wide enough to select."""
    return [(p, p * z["page_size"]) for p in sorted(z["pages"])
            if p * z["page_size"] > z["index_topk"]]


def plain(key: str):
    """The dims of a non-kernel operation without its 1s, or None."""
    return None if key.startswith("pallas_kernel") else [d for d in dims_of(key) if d != 1]


def kernel_dims(key: str):
    """The dims of a ``pallas_kernel``'s first output, or None."""
    return dims_of(key) if key.startswith("pallas_kernel") else None


def is_index_score(key: str, z: dict) -> bool:
    kernel = kernel_dims(key)
    if kernel:
        return any(kernel in ([lanes, span], [lanes, pages, z["page_size"]])
                   for lanes in z["lanes"] for pages, span in selecting(z))
    dims = plain(key)
    if not dims or key.startswith("sort"):
        return False
    ilanes, ps = lanes_of(z["index_head_dim"]), z["page_size"]
    for lanes in z["lanes"]:
        for pages, span in selecting(z):
            if dims in ([lanes * pages, ps, ilanes], [lanes, pages, ps, ilanes],
                        [lanes, span, ilanes], [lanes, span]):
                return True
            if dims[0] == lanes and z["index_n_heads"] in dims[1:] and span in dims[1:]:
                return True
    return False


def is_topk(key: str, z: dict) -> bool:
    dims = plain(key)
    if not dims or len(dims) != 2 or dims[0] not in z["lanes"]:
        return False
    if key.startswith("sort"):
        return any(dims[1] == span for _p, span in selecting(z))
    return dims[1] == z["index_topk"]


def is_sparse_attention(key: str, z: dict) -> bool:
    heads, rank, topk = z["num_attention_heads"], z["kv_lora_rank"], z["index_topk"]
    kernel = kernel_dims(key)
    if kernel:
        return len(kernel) == 3 and kernel[0] in z["lanes"] and kernel[1:] == [heads, rank]
    dims = plain(key)
    if not dims:
        return False
    row = lanes_of(rank + z["qk_rope_head_dim"])
    for lanes in z["lanes"]:
        if dims in ([lanes * topk, row], [lanes, topk, row], [lanes * topk, rank],
                    [lanes, topk, rank], [lanes, heads, topk], [lanes, heads, rank]):
            return True
        if dims == [lanes, heads] and "_f32_" in key and (
                lanes != heads or lanes in z.get("lanes_run", ())):
            return True
    return False


def is_window_kernel(key: str, z: dict) -> bool:
    dims = dims_of(key)
    return (key.startswith("pallas_kernel") and len(dims) == 3 and dims[0] in z["lanes"]
            and dims[1:] == [z["swa_num_attention_heads"], z["swa_kv_lora_rank"]])


def seconds_of(trace: dict, z: dict, rule) -> float:
    return sum(v["seconds"] for k, v in trace["ops"].items() if rule(k, z))


def lanes_run(trace: dict) -> set:
    """The lane counts of the buckets of the chunk programs the trace
    ran (``jit_paged_chunk_s8_64x64_64x112``: 64)."""
    out = set()
    for name in trace.get("modules") or {}:
        if "_chunk_" in name:
            out |= {int(b.split("x")[0]) for b in name.split("_") if "x" in b
                    and b.replace("x", "").isdigit()}
    return out


def context(ctx):
    """``(trace, sizes)`` where both exist, else None."""
    trace, z = ctx.get("trace"), sizes(ctx.get("config") or {})
    if not trace or not trace.get("ops") or not z:
        return None
    z["lanes_run"] = lanes_run(trace)
    return trace, z
