"""What SmallThinker's grouped-query attention over K/V pools of kinds,
its ReLU-gated experts and its whole step need, computed from the
configuration's sizes and the program's counters, and which of a trace's
operations are theirs: shared by the ``gqa_*``, ``relu_experts_*`` and
``smallthinker_step_mfu_pct`` readers (not a metric itself).  The keys
are the source's own (``moe_num_primary_experts``, ``head_dim``, ...):
``step_work.family()``, ``moe_work.routed()`` and ``mla_work.share()``
know none of them and return None for this configuration, as they must —
their arithmetic assumes K and V of ``hidden_size``, every expert held,
or a latent row.

**Bytes and FLOPs** (work *needed*: one pass, bfloat16, no padding, no
masked position).

* Attending one cached row of one layer for one decode lane-step: the
  row's K and V, ``2 x kv_heads x head_dim`` values (4 x 128 x 2 x 2 =
  2,048 B), and ``4 x heads x head_dim`` FLOP (28 heads x 128
  multiply-adds for the score and as many for the value: 14,336): 7
  FLOP a byte against the v5e's ridge of 240, so the bytes bind.  The
  rows are the program's count, ``gqa_kv_rows_read`` (a full layer's
  every cached row, a window layer's live ones, summed over layers and
  lane-steps).
* A prompt's attention: a full layer scores every causal pair, a window
  layer at most ``sliding_window_size`` keys a query
  (``step_work.causal_pairs``, at the traced interval's mean prompt: the
  pairs are convex in the length, so the mean understates them), each
  pair ``4 x heads x head_dim`` FLOP.
* A decode step's held experts: the three matrices of every held expert
  actually hit (``moe_held_active_expert_steps``), ``3 x hidden x width
  x 2`` B each (11.8 MB).
* The whole step (``smallthinker_step_mfu_pct``): a token passes q, k,
  v and the output projection at the grouped widths (``2 x (2 x hidden x
  heads x head_dim + 2 x hidden x kv_heads x head_dim)`` FLOP a layer),
  the router (``2 x hidden x published experts``), ``6 x hidden x
  width`` FLOP for each assignment served here
  (``moe_local_assignments``), the head over the slice once a decode
  lane-step and once a prompt, and attention as above.

**Which operations are whose** (``trace["ops"]`` is keyed by opcode plus
the first output's type and shape; ``moe_work.py`` says why a reader has
nothing else).  With ``lanes`` a decode bucket (``max_slots`` or half):

* **the decode page loop**: a ``pallas_kernel`` whose first output is
  the step's attended values for every query head — ``(lanes, heads in
  whole sublane tiles, head_dim)`` as ``ops/kernels.py
  paged_attention_decode`` returns them for grouped heads
  (``pallas_kernel_f32_64_32_128_``), or ``(lanes, 1, heads x
  head_dim)`` / ``(lanes, heads, head_dim)`` should a later kernel lay
  them flat — **or**, where no kernel runs, XLA's gather lane: an
  operation whose output holds a bucket's lanes, the K/V heads and the
  query heads of a group (``ops/gqa.py ctx_state``: ``(lanes, kv_heads,
  heads / kv_heads, span)`` scores and weights, ``(lanes, kv_heads,
  heads / kv_heads, head_dim)`` values).  Whichever does the work, the
  rows needed are the program's count;
* **the prefill attention**: a ``pallas_kernel`` whose first output is
  ``(prompts x heads, bucket, head_dim)`` (``ops/kernels.py
  causal_attention``: ``pallas_kernel_bf16_28_8192_128_``).  A bucket
  under one query block (128) attends in XLA and is not found: the cell's
  prompts start at 1,025;
* **a decode step's grouped matmuls**: ``pallas_kernel``s with a 2-D
  output of ``moe_held_pass_rows`` rows (``moe_work``'s rule at the rows
  the engine says one pass holds).
"""

from __future__ import annotations

from layer_metrics.pool_move_share_pct import shape_of as dims_of
from layer_metrics.step_work import causal_pairs, mean_prompt

BYTES = 2
# the counters the whole step's FLOPs are made of
COUNTERS = ("prefill_tokens", "prefills", "decode_lane_steps",
            "moe_local_assignments", "gqa_kv_rows_read")


def sizes(config: dict):
    """The configuration's sizes a reader needs, or None for a
    configuration that is not of this family."""
    model, engine = config.get("model") or {}, config.get("engine") or {}
    try:
        z = {k: int(model[k]) for k in (
            "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_ffn_hidden_size", "moe_num_primary_experts",
            "moe_num_active_primary_experts", "sliding_window_size",
            "num_hidden_layers", "vocab_size")}
        z["published"] = int(model.get("moe_num_primary_experts_published",
                                       z["moe_num_primary_experts"]))
        layout = model["sliding_window_layout"][:z["num_hidden_layers"]]
        z["window_layers"] = sum(1 for w in layout if w)
        z["full_layers"] = len(layout) - z["window_layers"]
        slots = int(engine["max_slots"])
        z["buckets"] = {int(b) for b in engine["prompt_buckets"]}
    except (KeyError, TypeError, ValueError):
        return None
    z["lanes"] = {slots, slots // 2, slots - slots // 2}
    return z


def row_bytes(z: dict) -> float:
    """One cached row of one layer: K and V."""
    return float(2 * z["num_key_value_heads"] * z["head_dim"] * BYTES)


def pair_flops(z: dict) -> float:
    """One (query, key) pair over every query head: score and value."""
    return 4.0 * z["num_attention_heads"] * z["head_dim"]


def expert_bytes(z: dict) -> float:
    """One expert's gate, up and down matrices."""
    return 3.0 * z["hidden_size"] * z["moe_ffn_hidden_size"] * BYTES


def prefill_pairs(z: dict, c: dict) -> float:
    """(query, key) pairs x layers the prompts of an interval need."""
    n = mean_prompt(c)
    return c["prefills"] * (
        z["full_layers"] * causal_pairs(n)
        + z["window_layers"] * causal_pairs(n, z["sliding_window_size"]))


def needed_flops(config: dict, c: dict) -> float:
    """The FLOPs the tokens of an interval need (``COUNTERS`` as deltas)."""
    z = sizes(config)
    hidden, q_w = z["hidden_size"], z["num_attention_heads"] * z["head_dim"]
    kv_w = z["num_key_value_heads"] * z["head_dim"]
    tokens = c["prefill_tokens"] + c["decode_lane_steps"]
    layers = z["full_layers"] + z["window_layers"]
    matrices = 2.0 * (2 * hidden * q_w + 2 * hidden * kv_w) + 2.0 * hidden * z["published"]
    experts = 6.0 * hidden * z["moe_ffn_hidden_size"] * c["moe_local_assignments"]
    head = 2.0 * hidden * z["vocab_size"] * (c["decode_lane_steps"] + c["prefills"])
    attention = pair_flops(z) * (c["gqa_kv_rows_read"] + prefill_pairs(z, c))
    return tokens * layers * matrices + experts + head + attention


def kernel_dims(key: str):
    return dims_of(key) if key.startswith("pallas_kernel") else None


def is_decode_attention(key: str, z: dict) -> bool:
    heads, kv, hd = z["num_attention_heads"], z["num_key_value_heads"], z["head_dim"]
    padded = -(-heads // 8) * 8
    kernel = kernel_dims(key)
    if kernel:
        return len(kernel) == 3 and kernel[0] in z["lanes"] and kernel[1:] in (
            [padded, hd], [heads, hd], [1, heads * hd])
    dims = [d for d in dims_of(key) if d != 1]
    return (len(dims) == 4 and dims[0] in z["lanes"]
            and dims[1:3] == [kv, heads // kv])


def is_prefill_attention(key: str, z: dict) -> bool:
    kernel = kernel_dims(key)
    return bool(kernel) and len(kernel) == 3 and not is_decode_attention(key, z) and (
        kernel[0] % z["num_attention_heads"] == 0 and kernel[1] in z["buckets"]
        and kernel[2] == z["head_dim"])


def seconds_of(trace: dict, z: dict, rule) -> float:
    return sum(v["seconds"] for k, v in trace["ops"].items() if rule(k, z))


def pass_rows(ctx: dict):
    """The rows of one pass of a decode step's held experts, as the
    engine says them (``moe_held_pass_rows``), or None."""
    engine = ctx.get("engine") or {}
    pair = engine.get("trace") or engine.get("window")
    rows = pair[1].get("moe_held_pass_rows") if pair and pair[1] else None
    return int(rows) if rows else None


def decode_expert_seconds(trace: dict, rows: int) -> float:
    """Summed seconds of the 2-D Pallas kernels over ``rows`` rows."""
    total = 0.0
    for key, slot in trace["ops"].items():
        dims = kernel_dims(key)
        if dims and len(dims) == 2 and dims[0] == rows:
            total += slot["seconds"]
    return total


def context(ctx):
    """``(trace, sizes)`` where both exist, else None."""
    trace, z = ctx.get("trace"), sizes(ctx.get("config") or {})
    if not trace or not trace.get("ops") or not z:
        return None
    return trace, z
