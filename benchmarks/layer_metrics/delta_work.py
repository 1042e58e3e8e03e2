"""What the linear-attention layers of a hybrid model (Gated DeltaNet;
the program's ``ops/delta.py``) need, computed from shapes, and which of
a trace's operations are theirs: shared by the ``delta_*`` readers and by
``olmo_hybrid_step_mfu_pct`` (not a metric itself).

**Bytes and FLOPs**, one linear layer, H heads of ``d_k`` (q, k) against
``d_v`` (v):

* **a decode lane-step** reads and writes the lane's state once:
  ``2 x H x d_k x d_v x 4 B`` (float32) = 4,423,680 B at 30 x 96 x 192.
  Its FLOPs (``7 H d_k d_v``) are 0.9 FLOP/B: the bytes bound it.  The
  tiling's padding, a lane that is not running and a second pass over
  the state are not needed work.
* **a prefill position** reads q, k, v and writes the output once, in the
  compute type's 2 B: ``2 x (2 H d_k + 2 H d_v)`` = 34,560 B; the
  recurrence is ``7 H d_k d_v`` = 3,870,720 FLOP (decay, read, delta,
  write, output: 2 + 2 + 1 + 2 ... of ``d_k x d_v`` a head, rounded to
  the recurrence's seven passes).  At the v5e's peaks the bytes take 42
  ns and the FLOPs 20: the larger bounds it.  The chunked form's extra
  products (the triangular solve, the ``(64, 64)`` masks) are not needed
  work: the share under-reads and cannot over-read.

**Which operations are theirs** (``trace["ops"]`` is keyed by opcode plus
the first output's type and shape; ``moe_work.py`` says why a reader has
nothing else).  By whole shape:

* **the decode state update**: an output shaped like the resting state
  ``(slots, H / p, d_k, p x d_v)`` or like its row ``(slots, H / p, p x
  d_v)`` (``p`` heads side by side in whole 128-lane tiles: 2 where ``d_v``
  is not a multiple of 128).  No other operation of the cell has 15 as a
  second dim.
* **the prefill's scan**: an output of four or more dims whose second is
  H (the scan lays everything ``(prompts, H, chunks, 64, ...)``), or of
  five or more whose third is H (the same arrays with the chunk axis
  first, as ``lax.scan`` walks them).  A full layer's arrays carry their
  30 heads third of four dims, or fourth.
* **the convolution**: a float32 output whose last dim is the q, k, v
  channels ``H (2 d_k + d_v)`` (the projections' own outputs are bf16),
  or any output ``(..., taps | taps - 1, channels)`` (the tail).
"""

from __future__ import annotations

from layer_metrics.pool_move_share_pct import shape_of as dims_of
from layer_metrics.step_work import causal_pairs, mean_prompt

F32, BF16 = 4, 2
# the counters the whole step's FLOPs are made of
COUNTERS = ("prefill_tokens", "prefills", "decode_lane_steps", "decode_kv_tokens")


def sizes(config: dict):
    """The configuration's sizes a reader needs, or None for a
    configuration without linear-attention layers."""
    model, engine = config.get("model") or {}, config.get("engine") or {}
    try:
        z = {k: int(model[k]) for k in (
            "hidden_size", "intermediate_size", "num_attention_heads",
            "num_hidden_layers", "vocab_size", "linear_num_key_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim")}
        kinds = model["layer_types"][:z["num_hidden_layers"]]
        z["linear_layers"] = sum(1 for k in kinds if k == "linear_attention")
        z["full_layers"] = len(kinds) - z["linear_layers"]
        z["slots"] = int(engine["max_slots"])
        z["page_size"] = int(engine["page_size"])
    except (KeyError, TypeError, ValueError):
        return None
    if not z["linear_layers"]:
        return None
    heads, dv = z["linear_num_key_heads"], z["linear_value_head_dim"]
    z["pack"] = 2 if dv % 128 and heads % 2 == 0 and (2 * dv) % 128 == 0 else 1
    z["channels"] = heads * (2 * z["linear_key_head_dim"] + dv)
    return z


def state_values(z: dict) -> int:
    return z["linear_num_key_heads"] * z["linear_key_head_dim"] * z["linear_value_head_dim"]


def step_bytes(z: dict) -> float:
    """A lane-step of one linear layer: the state read and written."""
    return 2.0 * F32 * state_values(z)


def position_bytes(z: dict) -> float:
    """A prefill position of one linear layer: q, k, v in, the output out."""
    heads = z["linear_num_key_heads"]
    return float(BF16 * heads * (2 * z["linear_key_head_dim"]
                                 + 2 * z["linear_value_head_dim"]))


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
    """One mapped page: K and V of every full layer."""
    return float(2 * z["full_layers"] * z["page_size"] * z["hidden_size"] * BF16)


def needed_flops(config: dict, c: dict) -> float:
    """The FLOPs the tokens of an interval need (``COUNTERS`` as deltas):
    the matrices a token passes in each layer kind, the recurrence and the
    convolution, the head once a decode lane-step and once a prompt, the
    full layers' attention by the rows the program says it read and the
    prompts' causal pairs."""
    z = sizes(config)
    hidden, heads = z["hidden_size"], z["linear_num_key_heads"]
    dv = z["linear_value_head_dim"]
    tokens = c["prefill_tokens"] + c["decode_lane_steps"]
    ffn = 6.0 * hidden * z["intermediate_size"]
    linear = (2.0 * hidden * (z["channels"] + 2 * heads * dv + 2 * heads)
              + position_flops(z) + 2.0 * z["linear_conv_kernel_dim"] * z["channels"]
              + ffn)
    full = 8.0 * hidden * hidden + ffn
    head = 2.0 * hidden * z["vocab_size"] * (c["decode_lane_steps"] + c["prefills"])
    pairs = c["decode_kv_tokens"] + c["prefills"] * causal_pairs(mean_prompt(c))
    attention = 4.0 * hidden * z["full_layers"] * pairs
    return (tokens * (z["linear_layers"] * linear + z["full_layers"] * full)
            + head + attention)


def is_step(key: str, z: dict) -> bool:
    groups = z["linear_num_key_heads"] // z["pack"]
    lanes = z["pack"] * z["linear_value_head_dim"]
    dims = dims_of(key)
    return dims in ([z["slots"], groups, z["linear_key_head_dim"], lanes],
                    [z["slots"], groups, lanes])


def is_scan(key: str, z: dict) -> bool:
    heads, dims = z["linear_num_key_heads"], dims_of(key)
    if is_step(key, z):
        return False
    return (len(dims) >= 4 and dims[1] == heads) or (
        len(dims) >= 5 and dims[2] == heads)


def is_conv(key: str, z: dict) -> bool:
    dims, taps = dims_of(key), z["linear_conv_kernel_dim"]
    if len(dims) < 2 or dims[-1] != z["channels"]:
        return False
    return "_f32_" in key or (len(dims) >= 3 and dims[-2] in (taps, taps - 1))


def seconds_of(trace: dict, z: dict, rule) -> float:
    return sum(v["seconds"] for k, v in trace["ops"].items() if rule(k, z))


def context(ctx):
    """``(trace, sizes)`` where both exist, else None."""
    trace, z = ctx.get("trace"), sizes(ctx.get("config") or {})
    if not trace or not trace.get("ops") or not z:
        return None
    return trace, z
