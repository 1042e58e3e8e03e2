"""What the state-space layers of a hybrid model (Mamba-1 as Jamba runs
it; the program's ``ops/ssm.py``) need, computed from shapes, and which of
a trace's operations are theirs: shared by the ``ssm_*`` readers and by
``jamba_step_mfu_pct`` (not a metric itself).

**Bytes and FLOPs**, one state-space layer of ``E`` channels (``mamba_expand
x hidden``) over ``N`` state columns (``mamba_d_state``):

* **a decode lane-step** reads and writes the lane's state once: ``2 x N
  x E x 4 B`` (float32) = 655,360 B at 16 x 5,120.  Its FLOPs (``9 E N``)
  are 1.1 FLOP/B: the bytes bound it.  A lane that is not running and a
  second pass over the state are not needed work.
* **a prefill position** reads x~ and z (the in projection's halves, 2 B
  each), ``Delta`` (4 B), ``B`` and ``C`` (4 B x N each) and writes y (4
  B), in their resting types: ``12 E + 8 N`` = 61,568 B; the recurrence
  is ``9 E N`` = 737,280 FLOP (``Delta A``, the decay's product, ``Delta
  x``, its product with ``B``, the sum, the product with ``C`` and its
  sum: 2 + 1 + 1 + 1 + 1 + 2 + 1 of ``E x N``, an exponential counted as
  one).  At the v5e's peaks the bytes take 75 ns and the FLOPs, over the
  matrix unit's bf16 peak, 3.7: **no vector-unit peak is published**, and
  none of these FLOPs can run on the matrix unit, so the bound is far
  below what any program can reach: the share under-reads and cannot
  over-read.

**Which operations are theirs** (``trace["ops"]`` is keyed by opcode plus
the first output's type and shape; ``moe_work.py`` says why a reader has
nothing else).  Each rule says both forms it finds:

* **the decode state update**: an output shaped like the resting state
  ``(slots, N, E)`` — the kernel ``ssm_state_step`` (a ``pallas_kernel``
  whose first output is the state) and XLA's fusions over the state
  alike.  No other operation of the cell has that shape.
* **the prefill's scan**: the kernel ``ssm_scan``, a ``pallas_kernel``
  whose first output is ``y`` ``(prompts, L, E)`` float32 (no other
  kernel of the cell has ``E`` as a last dim), or in XLA's form the
  carried state ``(prompts, N, E)`` of its loop (the state's shape under
  a leading dim that is not the slots).
* **the convolution and the selection**: a float32 output of XLA whose
  last dim is ``E`` (the convolution, ``Delta``), ``R + 2 N`` or ``R``
  (the low-rank projection and its norms), or any output ``(..., taps |
  taps - 1, E)`` (the tail).  The in and out projections are matmuls any
  layer has (bf16 outputs) and are left out.
"""

from __future__ import annotations

from layer_metrics.pool_move_share_pct import shape_of as dims_of
from layer_metrics.step_work import causal_pairs, mean_prompt

F32, BF16 = 4, 2
# the counters the whole step's FLOPs are made of
COUNTERS = ("prefill_tokens", "prefills", "decode_lane_steps", "decode_kv_tokens")


def sizes(config: dict):
    """The configuration's sizes a reader needs, or None for a
    configuration without state-space layers."""
    model, engine = config.get("model") or {}, config.get("engine") or {}
    try:
        z = {k: int(model[k]) for k in (
            "hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "attn_layer_period", "attn_layer_offset", "mamba_d_state",
            "mamba_d_conv", "mamba_expand", "mamba_dt_rank")}
        z["slots"] = int(engine["max_slots"])
        z["page_size"] = int(engine["page_size"])
    except (KeyError, TypeError, ValueError):
        return None
    z["full_layers"] = sum(
        1 for i in range(z["num_hidden_layers"])
        if i % z["attn_layer_period"] == z["attn_layer_offset"])
    z["ssm_layers"] = z["num_hidden_layers"] - z["full_layers"]
    if not z["ssm_layers"]:
        return None
    z["channels"] = z["mamba_expand"] * z["hidden_size"]
    z["head_dim"] = z["hidden_size"] // z["num_attention_heads"]
    z["low_rank"] = z["mamba_dt_rank"] + 2 * z["mamba_d_state"]
    return z


def state_values(z: dict) -> int:
    return z["mamba_d_state"] * z["channels"]


def step_bytes(z: dict) -> float:
    """A lane-step of one state-space layer: the state read and written."""
    return 2.0 * F32 * state_values(z)


def position_bytes(z: dict) -> float:
    """A prefill position of one state-space layer: x~ and z (bf16),
    Delta, B and C in, y out (float32)."""
    return float((2 * BF16 + 2 * F32) * z["channels"]
                 + 2 * F32 * z["mamba_d_state"])


def position_flops(z: dict) -> float:
    return 9.0 * state_values(z)


def step_least_seconds(z: dict, lane_steps: float, peaks: dict) -> float:
    return lane_steps * step_bytes(z) / peaks["hbm_bytes_per_s"]


def scan_least_seconds(z: dict, positions: float, peaks: dict) -> float:
    """The larger of a position's bytes over HBM bytes/s and the
    recurrence's FLOPs over bf16 FLOP/s (no vector-unit peak is
    published: the bound is below any program's reach), times
    ``positions``."""
    return positions * max(position_bytes(z) / peaks["hbm_bytes_per_s"],
                           position_flops(z) / peaks["bf16_flops"])


def page_bytes(z: dict) -> float:
    """One mapped page: K and V of every attention layer, ``kv_heads x
    head_dim`` wide (65,536 B at 2 layers of one head of 128)."""
    return float(2 * z["full_layers"] * z["page_size"]
                 * z["num_key_value_heads"] * z["head_dim"] * BF16)


def needed_flops(config: dict, c: dict) -> float:
    """The FLOPs the tokens of an interval need (``COUNTERS`` as deltas):
    every matrix a token passes in each layer kind, the recurrence and the
    convolution, the tied head once a decode lane-step and once a prompt,
    the attention layers' products by the K/V rows the program says it
    read and the prompts' causal pairs."""
    z = sizes(config)
    hidden, channels = z["hidden_size"], z["channels"]
    q_w = z["num_attention_heads"] * z["head_dim"]
    kv_w = z["num_key_value_heads"] * z["head_dim"]
    tokens = c["prefill_tokens"] + c["decode_lane_steps"]
    ffn = 6.0 * hidden * z["intermediate_size"]
    ssm = (2.0 * (hidden * 2 * channels + channels * z["low_rank"]
                  + z["mamba_dt_rank"] * channels + channels * hidden)
           + position_flops(z) + 2.0 * z["mamba_d_conv"] * channels + ffn)
    full = 2.0 * hidden * (q_w + 2 * kv_w) + 2.0 * q_w * hidden + ffn
    head = 2.0 * hidden * z["vocab_size"] * (c["decode_lane_steps"] + c["prefills"])
    pairs = c["decode_kv_tokens"] + c["prefills"] * causal_pairs(mean_prompt(c))
    attention = 4.0 * q_w * z["full_layers"] * pairs
    return (tokens * (z["ssm_layers"] * ssm + z["full_layers"] * full)
            + head + attention)


def _state(z: dict) -> list:
    return [z["mamba_d_state"], z["channels"]]


def is_step(key: str, z: dict) -> bool:
    return dims_of(key) == [z["slots"], *_state(z)]


def is_scan(key: str, z: dict) -> bool:
    dims = dims_of(key)
    if len(dims) != 3 or is_step(key, z):
        return False
    if key.startswith("pallas_kernel"):
        return dims[-1] == z["channels"] and "_f32_" in key
    return dims[1:] == _state(z)


def is_mixer_rows(key: str, z: dict) -> bool:
    """The convolution's and the selection's XLA operations."""
    dims, taps = dims_of(key), z["mamba_d_conv"]
    if len(dims) < 2 or key.startswith("pallas_kernel") or is_step(
            key, z) or is_scan(key, z):
        return False
    if len(dims) >= 3 and dims[-1] == z["channels"] and dims[-2] in (taps, taps - 1):
        return True
    return "_f32_" in key and dims[-1] in (
        z["channels"], z["low_rank"], z["mamba_dt_rank"])


def seconds_of(trace: dict, z: dict, rule) -> float:
    return sum(v["seconds"] for k, v in trace["ops"].items() if rule(k, z))


def context(ctx):
    """``(trace, sizes)`` where both exist, else None."""
    trace, z = ctx.get("trace"), sizes(ctx.get("config") or {})
    if not trace or not trace.get("ops") or not z:
        return None
    return trace, z
