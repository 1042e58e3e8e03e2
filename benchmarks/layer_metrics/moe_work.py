"""What a routed expert layer needs, computed from shapes, and which of
a trace's operations are its: shared by the ``moe_*`` and
``paged_kernel_*`` readers (not a metric itself).

**Which operations are whose.**  ``trace["ops"]`` is keyed by opcode
plus the first output's type and shape
(``trace_reduce.stable_op_name``); the scopes the program names its
expert layer by (``moe_experts``, ``moe_router``) are in the HLO
metadata, which that key drops, and a reader is handed the reduced
trace only.  So the rule is by **whole shape**, from the
configuration's own sizes:

* the **grouped matmuls**: XLA's TPU backend lowers ``ragged_dot`` to
  Mosaic kernels of its own, which the trace shows as ``pallas_kernel``
  with a 2-D output ``(assignment rows, expert width | hidden)`` and a
  1-D group-metadata kernel before them: ``pallas_kernel`` with an
  output of at most two dims;
* the **paged-decode kernel**: the other ``pallas_kernel``, whose output
  ``(lanes, 1, hidden)`` has three dims;
* the **router**: an operation whose output is exactly ``(rows,
  experts)`` where ``rows`` is a number of tokens a program routes at
  once — ``max_slots`` (a decode step) or a prompt bucket times a
  group of 1, 2, 4, ... ``max_slots`` prompts, the sizes the engine
  pads a group to (logits, softmax, top-k's operand);
* the **routing counters**: exactly ``(layers, experts)`` (a prefill's
  histogram) or ``(layers, experts + 2)`` (the decode accumulator).

A last dim of 64 alone would not do: in ``olmoe-1b-7b`` a page is 64
tokens and half a head is 64 wide, so RoPE's ``(B, L, 64)`` angles and
page-shaped operations end in 64 too; they have three or more dims or
a first dim that is no row count.  The per-layer ``(experts,)``
histogram, the sort of the assignments, the gather of their rows and
the gated sum back to tokens have no shape of their own and are **not**
counted: the share leaves them out.  A decode step's grouped matmuls
are those with ``max_slots x experts_per_tok`` rows (a prefill group
has at least eight times a prompt bucket).
"""

from __future__ import annotations

from layer_metrics.pool_move_share_pct import shape_of as dims_of

BYTES_PER_WEIGHT = 2  # expert matrices rest and stream in bfloat16


def routed(config: dict):
    """``(experts, experts per token, hidden, expert width)`` of a routed
    configuration's ``model`` block, or None."""
    model = config.get("model") or {}
    try:
        return (int(model["num_experts"]), int(model["num_experts_per_tok"]),
                int(model["hidden_size"]), int(model["intermediate_size"]))
    except (KeyError, TypeError, ValueError):
        return None


def expert_weight_bytes(config: dict, experts: float) -> float:
    """Bytes of the gate, up and down matrices of ``experts`` experts:
    what one layer must read to apply them."""
    _e, _k, hidden, width = routed(config)
    return float(experts) * 3 * hidden * width * BYTES_PER_WEIGHT


def is_grouped_matmul(key: str) -> bool:
    return key.startswith("pallas_kernel") and len(dims_of(key)) <= 2


def grouped_matmul_seconds(trace: dict, rows=None) -> float:
    """Summed seconds of the grouped-matmul kernels; with ``rows`` only
    those over that many assignment rows (a decode step's)."""
    total = 0.0
    for key, slot in trace["ops"].items():
        dims = dims_of(key)
        if is_grouped_matmul(key) and (rows is None or (len(dims) == 2 and dims[0] == rows)):
            total += slot["seconds"]
    return total


def is_paged_kernel(key: str) -> bool:
    return key.startswith("pallas_kernel") and len(dims_of(key)) >= 3


def paged_kernel_seconds(trace: dict):
    """``(calls, seconds)`` of the paged-decode kernel."""
    hits = [v for k, v in trace["ops"].items() if is_paged_kernel(k)]
    return sum(v["count"] for v in hits), sum(v["seconds"] for v in hits)


def router_shapes(config: dict) -> set:
    """The whole output shapes the rule above calls the router's and
    the routing counters'."""
    experts = routed(config)[0]
    engine, layers = config["engine"], int(config["model"]["num_hidden_layers"])
    slots = int(engine["max_slots"])
    rows = {slots} | {int(b) << j for b in engine["prompt_buckets"]
                      for j in range(slots.bit_length())}
    return {(r, experts) for r in rows} | {(layers, experts), (layers, experts + 2)}


def expert_layer_keys(trace: dict, config: dict) -> list:
    """The traced operations the rule above calls the expert layer's."""
    shapes = router_shapes(config)
    return [key for key in trace["ops"]
            if is_grouped_matmul(key) or tuple(dims_of(key)) in shapes]


def expert_layer_seconds(trace: dict, config: dict) -> float:
    return sum(trace["ops"][key]["seconds"] for key in expert_layer_keys(trace, config))
