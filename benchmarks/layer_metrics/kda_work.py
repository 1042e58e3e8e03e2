"""What the Kimi-Delta-Attention layers of a hybrid model over a latent
pool (Ling-3.0-flash; the program's ``ops/delta.py`` under a decay a key
channel) need, computed from shapes, and which of a trace's operations
are theirs: shared by the ``kda_*`` readers and ``ling3_step_mfu_pct``
(not a metric itself).  Sizes come from the configuration's own keys
(``num_attention_heads`` heads of ``head_dim`` for q, k and v alike,
``short_conv_kernel_size`` taps, ``layer_types`` as derived).

**Bytes and FLOPs**, one KDA layer, H heads of ``d`` x ``d``:

* **a decode lane-step** reads and writes the lane's state once: ``2 x H
  x d x d x 4 B`` (float32) = 4,194,304 B at 32 x 128 x 128.  Its FLOPs
  (``7 H d d``) are 0.9 FLOP/B: the bytes bound it.  A lane that is not
  running, and a second pass over the state, are not needed work.
* **a prefill position** reads q, k, v and writes the output once, in the
  compute type's 2 B: ``2 x 4 H d`` = 32,768 B; the recurrence is ``7 H d
  d`` = 3,670,016 FLOP.  At the v5e's peaks the bytes take 40 ns and the
  FLOPs 19: the larger bounds it.  The chunked form's extra products (the
  triangular solve, the block-wise decays, the ``(64, 64)`` masks) and
  the gate's ``H d`` exponentials a position are not needed work: the
  share under-reads and cannot over-read.

**Which operations are theirs** (``trace["ops"]`` is keyed by opcode plus
the first output's type and shape).  By whole shape — at 32 heads of 128
the ``delta_*`` readers' row rule ``(slots, H, d_v)`` is also the shape of
latent attention's attended values, so this cell has rules of its own:

* **the decode state update**: FOUR dims ``(slots, H, ., .)`` — the state
  kernel by its first output ``(slots, H, d, d)`` and the operands made
  for it, ``(slots, H, 3, d)`` rows (q, k, the decay) and ``(slots, H, 2,
  d)`` (v, beta over the lanes).  Latent attention's arrays of a decode
  step have three dims ``(slots, H, .)``; a decode step's q, k, v are
  ``(slots, 1, H, d)``.
* **the prefill's scan**: four or more dims whose first is NOT the slots
  and whose second is H (the scan lays everything ``(prompts, H, chunks,
  64, ...)``), or five or more whose third is H (the chunk axis first, as
  ``lax.scan`` walks them).  A latent layer's prefill arrays carry their
  heads third of four dims and the fused causal kernel's output has
  three.
* **the convolution**: a float32 output whose last dim is the q, k, v
  channels ``3 H d`` (the projection's own output is bf16), or any output
  ``(..., taps | taps - 1, channels)`` (the tail).
* **the gate**: a float32 output of at most three dims whose last is ``H
  d`` — the decay's projection (bf16 operands, float32 out) and what XLA
  fuses behind it.  The output projection's input has the same width in
  bf16.
"""

from __future__ import annotations

from layer_metrics.delta_work import seconds_of  # noqa: F401 — the readers' one sum
from layer_metrics.pool_move_share_pct import shape_of as dims_of
from layer_metrics.step_work import causal_pairs, head_flops, mean_prompt

F32, BF16 = 4, 2
# the counters the whole step's FLOPs are made of
COUNTERS = ("prefill_tokens", "prefills", "decode_lane_steps", "latent_kv_tokens",
            "moe_local_assignments")


def sizes(config: dict):
    """The configuration's sizes a reader needs, or None for a
    configuration without KDA layers beside latent attention."""
    model, engine = config.get("model") or {}, config.get("engine") or {}
    try:
        if model.get("model_type") != "bailing_hybrid":
            return None
        z = {k: int(model[k]) for k in (
            "hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_attention_heads", "head_dim", "num_hidden_layers", "vocab_size",
            "short_conv_kernel_size", "first_k_dense_replace", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "num_experts_published", "num_shared_experts")}
        kinds = model["layer_types"][:z["num_hidden_layers"]]
        z["kda_layers"] = sum(1 for k in kinds if k == "linear_attention")
        z["mla_layers"] = len(kinds) - z["kda_layers"]
        z["slots"] = int(engine["max_slots"])
        z["page_size"] = int(engine["page_size"])
    except (KeyError, TypeError, ValueError):
        return None
    if not z["kda_layers"]:
        return None
    z["channels"] = 3 * z["num_attention_heads"] * z["head_dim"]
    return z


def state_values(z: dict) -> int:
    return z["num_attention_heads"] * z["head_dim"] * z["head_dim"]


def step_bytes(z: dict) -> float:
    """A lane-step of one KDA layer: the state read and written."""
    return 2.0 * F32 * state_values(z)


def position_bytes(z: dict) -> float:
    """A prefill position of one KDA layer: q, k, v in, the output out."""
    return float(BF16 * 4 * z["num_attention_heads"] * z["head_dim"])


def position_flops(z: dict) -> float:
    return 7.0 * state_values(z)


def step_least_seconds(z: dict, lane_steps: float, peaks: dict) -> float:
    return lane_steps * step_bytes(z) / peaks["hbm_bytes_per_s"]


def scan_least_seconds(z: dict, positions: float, peaks: dict) -> float:
    """The larger of a position's bytes over HBM bytes/s and the
    recurrence's FLOPs over bf16 FLOP/s, times ``positions``."""
    return positions * max(position_bytes(z) / peaks["hbm_bytes_per_s"],
                           position_flops(z) / peaks["bf16_flops"])


def page_bytes(z: dict) -> float:
    """One mapped page: a latent row's lanes (the values in whole
    128-lane tiles) in every latent layer."""
    lanes = -(-(z["kv_lora_rank"] + z["qk_rope_head_dim"]) // 128) * 128
    return float(z["mla_layers"] * z["page_size"] * lanes * BF16)


def row_flops(z: dict) -> float:
    """A cached latent row a decode lane-step reads (the absorbed form):
    the score over ``rank + rope`` values and the value over ``rank``,
    for every head (``mla_work.row_flops``)."""
    rank, rope = z["kv_lora_rank"], z["qk_rope_head_dim"]
    return 2.0 * z["num_attention_heads"] * ((rank + rope) + rank)


def needed_flops(config: dict, c: dict) -> float:
    """The FLOPs the tokens of an interval need (``COUNTERS`` as deltas):
    every matrix a token passes in each layer kind (a KDA layer's q, k, v,
    decay, beta, gate and output projections, its recurrence and
    convolution; a latent layer's plain q, W_kva, the up-projections, the
    gate and the output), the dense SwiGLU of the leading layers, the
    router and the shared expert of the others, an assignment served here
    three expert matrices, the head once a decode lane-step and once a
    prompt, the latent rows the program says it read and the prompts'
    causal pairs in the latent layers."""
    z = sizes(config)
    hidden, heads, d = z["hidden_size"], z["num_attention_heads"], z["head_dim"]
    rank, rope = z["kv_lora_rank"], z["qk_rope_head_dim"]
    nope, v = z["qk_nope_head_dim"], z["v_head_dim"]
    layers, dense = z["num_hidden_layers"], z["first_k_dense_replace"]
    width = z["moe_intermediate_size"]
    tokens = c["prefill_tokens"] + c["decode_lane_steps"]
    kda = (2.0 * hidden * (z["channels"] + heads * d + 2 * heads + heads * d)
           + position_flops(z) + 2.0 * z["short_conv_kernel_size"] * z["channels"])
    mla = 2.0 * (hidden * heads * (nope + rope) + hidden * (rank + rope)
                 + heads * rank * (nope + v) + heads * v * hidden + hidden * heads)
    ffn = (dense * 6.0 * hidden * z["intermediate_size"]
           + (layers - dense) * (2.0 * hidden * z["num_experts_published"]
                                 + z["num_shared_experts"] * 6.0 * hidden * width))
    pairs = causal_pairs(mean_prompt(c)) * c["prefills"]
    return (tokens * (z["kda_layers"] * kda + z["mla_layers"] * mla + ffn)
            + 6.0 * hidden * width * c["moe_local_assignments"]
            + head_flops(hidden, z["vocab_size"], c)
            + row_flops(z) * c["latent_kv_tokens"]
            + z["mla_layers"] * 2.0 * heads * (nope + rope + v) * pairs)


def is_step(key: str, z: dict) -> bool:
    dims = dims_of(key)
    return len(dims) == 4 and dims[:2] == [z["slots"], z["num_attention_heads"]]


def is_scan(key: str, z: dict) -> bool:
    heads, dims = z["num_attention_heads"], dims_of(key)
    if is_step(key, z):
        return False
    return (len(dims) >= 4 and dims[1] == heads) or (
        len(dims) >= 5 and dims[2] == heads)


def is_conv(key: str, z: dict) -> bool:
    dims, taps = dims_of(key), z["short_conv_kernel_size"]
    if len(dims) < 2 or dims[-1] != z["channels"]:
        return False
    return "_f32_" in key or (len(dims) >= 3 and dims[-2] in (taps, taps - 1))


def is_gate(key: str, z: dict) -> bool:
    dims = dims_of(key)
    return ("_f32_" in key and 2 <= len(dims) <= 3
            and dims[-1] == z["num_attention_heads"] * z["head_dim"])


def context(ctx):
    """``(trace, sizes)`` where both exist, else None."""
    trace, z = ctx.get("trace"), sizes(ctx.get("config") or {})
    if not trace or not trace.get("ops") or not z:
        return None
    return trace, z
