"""What a LongCat-Flash double layer needs, computed from shapes, and
which of a trace's operations are its routed shortcut's and its dense
pair's: shared by the ``zero_expert_*``, ``real_experts_*``,
``shortcut_moe_*`` and ``dense_pair_*`` readers (not a metric itself).

**Bytes and FLOPs** (bfloat16 at rest, 2 B a parameter; the functions
below, kept with the benchmark).  A decode layer-step streams, whatever
the batch: the **dense pair** — two SwiGLU FFNs of three ``hidden x
ffn_hidden_size`` matrices each (2 x 3 x 6144 x 12288 x 2 B = 905.97 MB
a layer) and ``2 x 6 x hidden x ffn_hidden_size`` FLOP a token; the
**routed shortcut** — the three ``hidden x expert_ffn_hidden_size``
matrices (75.50 MB) of every held expert a lane was routed to, and the
float32 router ``hidden x (n_routed_experts_published +
zero_expert_num)`` (18.87 MB).  An identity expert streams nothing:
every point of ``zero_expert_pick_share_pct`` is expert bytes not
streamed.

**Which operations are whose** (``trace["ops"]`` is keyed by opcode
plus the first output's type and shape; see ``moe_work.py`` for why a
reader has nothing else).  By **whole shape**, from the configuration's
own sizes:

* the **held experts' grouped matmuls**: every ``pallas_kernel`` with a
  2-D output (``mla_work``'s rule; the latent kernel's has three);
* the **router**: an operation whose output is exactly ``(rows, 768)``
  with ``rows`` a number of tokens a program routes at once
  (``moe_work``'s rows: ``max_slots`` or a prompt bucket times a group),
  and the **routing counters** ``(layers, 781)`` (a prefill's histogram:
  768 outputs and 13 bins of real picks) and ``(layers, 783 | 784)``
  (the decode accumulator);
* the **dense pair**: an operation that is no kernel and whose output is
  exactly two dims ``(rows, ffn_hidden_size)``: the gate and up
  projections and their product, which the program computes on ``(T,
  d)`` rows.  (``W_qb``'s output is as wide, 64 x 192 = 12,288, and has
  three dims ``(lanes | prompts, 1 | bucket, 12288)``.)  The down
  projection's output is a float32 ``(rows, hidden)`` like the norms',
  the gated sums' and the identity experts', and cannot be found: **its
  seconds are left out**, a third of the pair's weight bytes.

The sort of the assignments, the gathers of their rows and the gated
sum back to tokens have no shape of their own and are not counted.
"""

from __future__ import annotations

from layer_metrics.pool_move_share_pct import shape_of as dims_of

BYTES = 2  # matrices rest and stream in bfloat16


def double(config: dict):
    """``(hidden, dense width, expert width, router outputs, real
    outputs, picks a token, layers)`` of a double-layer configuration's
    ``model`` block, or None."""
    model = config.get("model") or {}
    try:
        real = int(model.get("n_routed_experts_published", model["n_routed_experts"]))
        return (int(model["hidden_size"]), int(model["ffn_hidden_size"]),
                int(model["expert_ffn_hidden_size"]), real + int(model["zero_expert_num"]),
                real, int(model["moe_topk"]), int(model["num_layers"]))
    except (KeyError, TypeError, ValueError):
        return None


def dense_pair_bytes(config: dict) -> float:
    """Bytes of one layer's two dense SwiGLU FFNs."""
    hidden, dense, *_ = double(config)
    return 2.0 * 3 * hidden * dense * BYTES


def dense_pair_flops(config: dict, tokens: float) -> float:
    hidden, dense, *_ = double(config)
    return 2.0 * 6 * hidden * dense * tokens


def expert_bytes(config: dict) -> float:
    """Bytes of one expert's gate, up and down matrices."""
    hidden, _dense, width, *_ = double(config)
    return 3.0 * hidden * width * BYTES


def expert_flops(config: dict, assignments: float) -> float:
    hidden, _dense, width, *_ = double(config)
    return 6.0 * hidden * width * assignments


def router_bytes(config: dict) -> float:
    hidden, _dense, _width, outputs, *_ = double(config)
    return 4.0 * hidden * outputs


def token_rows(config: dict) -> set:
    """Tokens a program routes at once: ``max_slots`` (a decode step) or
    a prompt bucket times a group of 1, 2, 4, ... prompts."""
    engine = config["engine"]
    slots = int(engine["max_slots"])
    return {slots} | {int(b) << j for b in engine["prompt_buckets"]
                      for j in range(slots.bit_length())}


def shortcut_keys(trace: dict, config: dict) -> list:
    """The held experts' grouped matmuls, the router and the routing
    counters."""
    _h, _d, _w, outputs, _real, picks, layers = double(config)
    rows = token_rows(config)
    counters = {(layers, outputs + picks + 1 + extra) for extra in (0, 2, 3)}
    out = []
    for key in trace["ops"]:
        dims = tuple(dims_of(key))
        if key.startswith("pallas_kernel"):
            if len(dims) == 2:
                out.append(key)
        elif dims in counters or (len(dims) == 2 and dims[1] == outputs and dims[0] in rows):
            out.append(key)
    return out


def dense_pair_keys(trace: dict, config: dict) -> list:
    """The dense FFNs' gate and up projections and their product."""
    _hidden, dense, *_ = double(config)
    rows = token_rows(config)
    return [key for key in trace["ops"]
            if not key.startswith("pallas_kernel")
            and len(dims_of(key)) == 2 and dims_of(key)[1] == dense
            and dims_of(key)[0] in rows]


def seconds(trace: dict, keys: list) -> float:
    return sum(trace["ops"][k]["seconds"] for k in keys)
