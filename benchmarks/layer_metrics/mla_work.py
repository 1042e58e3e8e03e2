"""What latent attention (MLA) and one replica's share of an
expert-parallel layer need, computed from shapes, and which of a
trace's operations are theirs: shared by the ``mla_*``,
``expert_share_*``, ``experts_held_*`` and ``routed_local_*`` readers
(not a metric itself).

**Bytes and FLOPs.**  A decode step's absorbed attention reads each
cached row once for all heads: ``kv_lora_rank + qk_rope_head_dim``
values in bfloat16 (576 x 2 = 1,152 B at the published widths) and, per
row, a ``(heads, 576) x (576,)`` score product and a ``(heads,) x
(512,)`` value product: ``2 * heads * (576 + 512)`` = 139,264 FLOP.
Work *needed*: one pass, rows and not whole pages, the 576 values and
not the 640 lanes they rest in.  The least time is the larger of bytes
over the chip's HBM bytes/s and FLOPs over its bf16 FLOP/s; at 121
FLOP/B against the v5e's ridge of 240 that is the bytes.

A decode layer-step of the held experts must read the gate, up and down
matrices of every held expert a lane was routed to: ``3 x hidden x
moe_intermediate_size`` x 2 B each (88.08 MB at the published widths),
and the shared expert's.

**Which operations are whose** (``trace["ops"]`` is keyed by opcode
plus the first output's type and shape; see ``moe_work.py`` for why a
reader has nothing else).  By **whole shape**, from the configuration's
own sizes and what the program says of itself:

* the **latent kernel**: a ``pallas_kernel`` whose first output is
  ``(lanes, heads, kv_lora_rank)``, three dims, with ``lanes`` a decode
  bucket (``max_slots`` or half of it);
* the **held experts' grouped matmuls**: every ``pallas_kernel`` with a
  2-D output (``moe_work``'s rule); **of a decode step**: those whose
  rows are the engine's ``moe_held_pass_rows`` (``engine_stats()``: the
  rows one pass takes at ``max_slots`` tokens), so a program that does
  not say it has none;
* the **shared expert of a decode step**: the ``(max_slots,
  n_shared x moe_intermediate_size)`` outputs of its gate and up
  projections.  Its down projection is a float32 ``(rows, hidden)``
  like much else and cannot be found, so the roofline reader leaves its
  bytes out as well as its seconds.

Decode's MLA projections (W_qa, W_qb, W_kva, W_uk, W_uv, W_o) are not
found this way: XLA fuses W_qa and W_kva with the norms' statistics
under another output's name, and a rule on first outputs read 3 % where
the weight bytes alone put them near 12 % (PERF.md section 7, PR 30).
"""

from __future__ import annotations

from layer_metrics.pool_move_share_pct import shape_of as dims_of

BYTES = 2  # rows and matrices rest and stream in bfloat16


def latent(config: dict):
    """``(heads, rank, rope, nope, v, q_rank, hidden, layers)`` of a
    latent-attention configuration's ``model`` block, or None."""
    model = config.get("model") or {}
    try:
        return tuple(int(model[k]) for k in (
            "num_attention_heads", "kv_lora_rank", "qk_rope_head_dim",
            "qk_nope_head_dim", "v_head_dim", "q_lora_rank", "hidden_size",
            "num_hidden_layers"))
    except (KeyError, TypeError, ValueError):
        return None


def row_bytes(config: dict) -> float:
    _h, rank, rope, *_ = latent(config)
    return float((rank + rope) * BYTES)


def row_flops(config: dict) -> float:
    heads, rank, rope, *_ = latent(config)
    return 2.0 * heads * ((rank + rope) + rank)


def least_seconds(config: dict, rows: float, peaks: dict) -> float:
    """The least time the chip could take to attend ``rows`` cached
    rows: the larger of their bytes over HBM bytes/s and their FLOPs
    over bf16 FLOP/s."""
    return max(rows * row_bytes(config) / peaks["hbm_bytes_per_s"],
               rows * row_flops(config) / peaks["bf16_flops"])


def lane_counts(config: dict) -> set:
    slots = int(config["engine"]["max_slots"])
    return {slots, slots // 2, slots - slots // 2}


def is_latent_kernel(key: str, config: dict) -> bool:
    heads, rank, *_ = latent(config)
    dims = dims_of(key)
    return (key.startswith("pallas_kernel") and len(dims) == 3
            and dims[0] in lane_counts(config) and dims[1:] == [heads, rank])


def latent_kernel_seconds(trace: dict, config: dict):
    """``(calls, seconds)`` of the latent decode kernel."""
    hits = [v for k, v in trace["ops"].items() if is_latent_kernel(k, config)]
    return sum(v["count"] for v in hits), sum(v["seconds"] for v in hits)


def share(config: dict):
    """``(held, published, top-k, hidden, width, shared)`` of a
    configuration that holds a share of its routed experts, or None."""
    model = config.get("model") or {}
    try:
        return (int(model["n_routed_experts"]),
                int(model.get("n_routed_experts_published", model["n_routed_experts"])),
                int(model["num_experts_per_tok"]), int(model["hidden_size"]),
                int(model["moe_intermediate_size"]), int(model["n_shared_experts"]))
    except (KeyError, TypeError, ValueError):
        return None


def matrix_bytes(config: dict) -> float:
    """Bytes of one of an expert's three matrices."""
    _held, _pub, _k, hidden, width, _shared = share(config)
    return float(hidden * width * BYTES)


def pass_rows(ctx: dict):
    """The rows of one pass of a decode step's held experts, as the
    engine says them (``moe_held_pass_rows``), or None."""
    pair = (ctx.get("engine") or {}).get("trace") or (ctx.get("engine") or {}).get("window")
    rows = pair[1].get("moe_held_pass_rows") if pair and pair[1] else None
    return int(rows) if rows else None


def grouped_matmul_seconds(trace: dict) -> float:
    """Seconds of every grouped matmul, a prefill's and a decode
    step's."""
    return sum(v["seconds"] for k, v in trace["ops"].items()
               if k.startswith("pallas_kernel") and len(dims_of(k)) == 2)


def decode_expert_keys(trace: dict, config: dict, rows: int) -> list:
    """The grouped matmuls of a decode step's held experts (``rows`` a
    pass) and its shared expert's gate and up projections."""
    _held, _pub, _top_k, _hidden, width, shared = share(config)
    slots = int(config["engine"]["max_slots"])
    out = []
    for key in trace["ops"]:
        dims = dims_of(key)
        if key.startswith("pallas_kernel"):
            if len(dims) == 2 and dims[0] == rows:
                out.append(key)
        elif shared and tuple(d for d in dims if d != 1) == (slots, shared * width):
            out.append(key)
    return out


def decode_expert_seconds(trace: dict, config: dict, rows: int) -> float:
    return sum(trace["ops"][k]["seconds"] for k in decode_expert_keys(trace, config, rows))
