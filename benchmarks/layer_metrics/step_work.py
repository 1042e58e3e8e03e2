"""The FLOPs the tokens of an interval *need*, prefill positions and
decode lane-steps alike, computed from a configuration's sizes as its
cell cuts them and from the program's own counters: shared by
``step_mfu_pct`` (not a metric itself).  It reads no trace: what did the
work, a kernel or XLA's own operations, does not enter.

**What is counted** (two FLOPs a multiply-add, one pass):

* per token (a prefill position or a decode lane-step), every matrix the
  token passes on this replica: the q / kv projections and their
  up-projections, the indexer's projections, the headwise gate, the
  attention output, dense and shared FFNs, the router;
* the head once a decode lane-step and once a prompt (a prefill returns
  its last position's logits alone);
* routed experts by the assignments this replica served
  (``moe_local_assignments`` where it holds a share, ``moe_assignments``
  where it holds every expert), three matrices each; an identity expert
  and an assignment to an absent expert need none;
* decode attention by the cached rows the program says it read, at the
  per-row FLOPs the ``*_work`` modules hold;
* prefill attention as ``harness/peaks.py gpt2_prefill_flops`` takes it:
  the causal half of the square at the **mean** prompt length, which is
  never above the true mean of squares (every form below is convex in
  the length), a window layer's keys capped at its window, an indexed
  layer's at ``index_topk``.

**What is not**: padding to a bucket, logits nobody returns, masked
positions, recomputation, rows a kernel moved but the mathematics did not
ask for, a step's own row, biases, norms, softmax and activations.  Where
a term can only be bounded the lower bound stands: the share may
under-read, never over-read.

``COUNTERS`` names the ``engine_stats()`` counters a family's arithmetic
reads; a program that lacks one of them gives no reading, not a smaller
one.
"""

from __future__ import annotations

from harness.peaks import gpt2_prefill_flops
from layer_metrics import dots3_work, longcat_work, mla_work, moe_work

TOKENS = ("prefill_tokens", "prefills", "decode_lane_steps")
COUNTERS = {
    "gpt2": TOKENS + ("decode_kv_tokens",),
    "olmoe": TOKENS + ("decode_kv_tokens", "moe_assignments"),
    "latent_share": TOKENS + ("latent_kv_tokens", "moe_local_assignments"),
    "double_layer": TOKENS + ("latent_kv_tokens", "moe_local_assignments"),
    "dots3": TOKENS + ("moe_local_assignments", "sparse_rows_read", "window_rows_read",
                       "index_keys_scored"),
}


def family(config: dict):
    """Which arithmetic a configuration's ``model`` block takes, by the
    keys it holds (the most particular first), or None."""
    model = config.get("model") or {}
    if dots3_work.sizes(config):
        return "dots3"
    if longcat_work.double(config):
        return "double_layer"
    if mla_work.latent(config) and mla_work.share(config) and "first_k_dense_replace" in model:
        return "latent_share"
    if moe_work.routed(config):
        return "olmoe"
    if all(k in model for k in ("n_embd", "n_layer", "n_inner", "vocab_size")):  # a GPT-2 MLP
        return "gpt2"
    return None


def causal_pairs(length: float, cap: float = float("inf")) -> float:
    """(query, key) pairs of one prompt of ``length`` positions where a
    query sees itself and the keys before it, at most ``cap`` of them:
    ``n^2 / 2`` up to the cap, ``cap x n - cap^2 / 2`` past it (the sum
    of ``min(t + 1, cap)`` without its half-row).  Convex in ``n``."""
    if length <= cap:
        return length * length / 2.0
    return cap * length - cap * cap / 2.0


def mean_prompt(c: dict) -> float:
    return c["prefill_tokens"] / max(c["prefills"], 1.0)


def head_flops(hidden: int, vocab: int, c: dict) -> float:
    """The head once a decode lane-step and once a prompt."""
    return 2.0 * hidden * vocab * (c["decode_lane_steps"] + c["prefills"])


def share_ffn_flops(config: dict, c: dict) -> float:
    """The FFNs of a DeepSeek-V3-shaped stack on a replica that holds a
    share of the experts: a token passes ``first_k_dense_replace`` dense
    SwiGLUs of 3 x hidden x ``intermediate_size`` and, in each other
    layer, a router ``hidden x n_routed_experts_published`` and
    ``n_shared_experts`` SwiGLUs of ``moe_intermediate_size``; an
    assignment served here (``moe_local_assignments``) is one more such
    SwiGLU."""
    model = config["model"]
    _held, published, _k, hidden, width, shared = mla_work.share(config)
    layers, dense = int(model["num_hidden_layers"]), int(model["first_k_dense_replace"])
    per_token = 2.0 * (dense * 3 * hidden * int(model["intermediate_size"])
                       + (layers - dense) * (hidden * published + shared * 3 * hidden * width))
    return (per_token * (c["prefill_tokens"] + c["decode_lane_steps"])
            + 6.0 * hidden * width * c["moe_local_assignments"])


def gpt2_flops(config: dict, c: dict) -> float:
    """GPT-2: the prompts as ``harness/peaks.py gpt2_prefill_flops`` has
    them (24 d^2 a token and layer, 2 n^2 d a prompt and layer at the
    mean length, the head 2 d V a prompt); a decode lane-step the same 24
    d^2 a layer and the head, and 4 d a layer for each cached token it
    attends (scores and weighted values, ``d`` multiply-adds each over
    the heads)."""
    model = config["model"]
    d, layers, vocab = (model[k] for k in ("n_embd", "n_layer", "vocab_size"))
    return (gpt2_prefill_flops(model, c["prefill_tokens"], c["prefills"])
            + c["decode_lane_steps"] * (layers * 24.0 * d * d + 2.0 * d * vocab)
            + layers * 4.0 * d * c["decode_kv_tokens"])


def olmoe_flops(config: dict, c: dict) -> float:
    """OLMoE: a token and layer 2 x (3 d^2 qkv + d^2 projection + d x
    experts router); an assignment (every expert is held:
    ``moe_assignments``, counted over all layers) 2 x 3 d x width; head
    and attention as GPT-2's (16 heads of 128 = d)."""
    experts, _k, d, width = moe_work.routed(config)
    layers, vocab = (int(config["model"][k]) for k in ("num_hidden_layers", "vocab_size"))
    tokens = c["prefill_tokens"] + c["decode_lane_steps"]
    pairs = causal_pairs(mean_prompt(c)) * c["prefills"]
    return (layers * (2.0 * (4 * d * d + d * experts) * tokens
                      + 4.0 * d * (c["decode_kv_tokens"] + pairs))
            + 6.0 * d * width * c["moe_assignments"]
            + head_flops(d, vocab, c))


def latent_attention_macs(hidden, heads, q_rank, rank, nope, rope, v) -> float:
    """Multiply-adds a token needs in one latent attention's matrices:
    W_qa ``hidden x q_rank``, W_qb ``q_rank x heads (nope + rope)``,
    W_kva ``hidden x (rank + rope)``, the up-projections W_uk and W_uv
    ``heads x rank x (nope + v)`` (a prefill makes K and V with them, a
    decode step folds them into q and the output: the same products a
    token), W_o ``heads v x hidden``."""
    return float(hidden * q_rank + q_rank * heads * (nope + rope) + hidden * (rank + rope)
                 + heads * rank * (nope + v) + heads * v * hidden)


def latent_share_flops(config: dict, c: dict) -> float:
    """DeepSeek-V3 / GigaChat on a replica that holds a share of the
    experts: every layer a latent attention, the FFNs as
    ``share_ffn_flops``; a cached row a decode lane-step read
    (``latent_kv_tokens``, over all layers) ``mla_work.row_flops`` (the
    absorbed form); a prompt's attention 2 x heads x (nope + rope + v) x
    its causal pairs a layer (the naive form: the cheaper one there)."""
    heads, rank, rope, nope, v, q_rank, hidden, layers = mla_work.latent(config)
    attention = 2.0 * layers * latent_attention_macs(hidden, heads, q_rank, rank, nope, rope, v)
    return (attention * (c["prefill_tokens"] + c["decode_lane_steps"])
            + share_ffn_flops(config, c)
            + mla_work.row_flops(config) * c["latent_kv_tokens"]
            + layers * 2.0 * heads * (nope + rope + v) * causal_pairs(mean_prompt(c))
            * c["prefills"]
            + head_flops(hidden, int(config["model"]["vocab_size"]), c))


def double_layer_flops(config: dict, c: dict) -> float:
    """LongCat-Flash: a layer is two latent attentions, two dense SwiGLUs
    of ``ffn_hidden_size`` (``longcat_work.dense_pair_flops``) and a
    router over the published real experts and the identity experts; an
    assignment to a real expert held here (``moe_local_assignments``)
    ``longcat_work.expert_flops``; an identity expert needs none;
    ``latent_kv_tokens`` counts a row an attention (two a layer) and the
    prompt's attention is taken twice a layer."""
    hidden, _dense, _width, outputs, _real, _picks, layers = longcat_work.double(config)
    heads, rank, rope, nope, v, q_rank, *_ = mla_work.latent(config)
    per_token = (layers * 2.0 * (2 * latent_attention_macs(hidden, heads, q_rank, rank, nope,
                                                             rope, v) + hidden * outputs)
                 + layers * longcat_work.dense_pair_flops(config, 1.0))
    return (per_token * (c["prefill_tokens"] + c["decode_lane_steps"])
            + longcat_work.expert_flops(config, c["moe_local_assignments"])
            + mla_work.row_flops(config) * c["latent_kv_tokens"]
            + 2 * layers * 2.0 * heads * (nope + rope + v) * causal_pairs(mean_prompt(c))
            * c["prefills"]
            + head_flops(hidden, int(config["model"]["vocab_size"]), c))


def dots3_flops(config: dict, c: dict) -> float:
    """dots3-note: a full layer's latent attention at the published sizes
    with its headwise gate ``hidden x heads`` and its indexer (``q_rank x
    index heads x index dim``, ``hidden x index dim``, ``hidden x index
    heads``); a window layer's at the ``swa_*`` sizes with its gate;
    the FFNs as ``share_ffn_flops``.  Decode attention
    by the blocks' own account: ``sparse_rows_read`` x
    ``dots3_work.full_row_flops``, ``window_rows_read`` x
    ``window_row_flops``, ``index_keys_scored`` x ``index_key_flops``.  A
    prompt: a full layer's queries see at most ``index_topk`` keys and a
    window layer's ``sliding_window_size`` (``causal_pairs``), and its
    indexer must score every key before a query past ``index_topk``:
    ``(n^2 - topk^2) / 2`` keys, nothing for a shorter prompt."""
    model, z = config["model"], dots3_work.sizes(config)
    hidden, q_rank = int(model["hidden_size"]), int(model["q_lora_rank"])
    heads, wheads = z["num_attention_heads"], z["swa_num_attention_heads"]
    nope, rope, v = (int(model[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    wnope, wrope, wv = (int(model["swa_" + k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    full = (latent_attention_macs(hidden, heads, q_rank, z["kv_lora_rank"], nope, rope, v)
            + hidden * heads
            + q_rank * z["index_n_heads"] * z["index_head_dim"]
            + hidden * z["index_head_dim"] + hidden * z["index_n_heads"])
    window = (latent_attention_macs(hidden, wheads, int(model["swa_q_lora_rank"]),
                                    z["swa_kv_lora_rank"], wnope, wrope, wv)
              + hidden * wheads)
    attention = 2.0 * (z["full_layers"] * full + z["window_layers"] * window)
    mean, topk = mean_prompt(c), z["index_topk"]
    prompt = (
        z["full_layers"] * (2.0 * heads * (nope + rope + v) * causal_pairs(mean, topk)
                            + dots3_work.index_key_flops(z)
                            * max(mean * mean - topk * topk, 0.0) / 2.0)
        + z["window_layers"] * 2.0 * wheads * (wnope + wrope + wv)
        * causal_pairs(mean, z["sliding_window_size"]))
    return (attention * (c["prefill_tokens"] + c["decode_lane_steps"])
            + share_ffn_flops(config, c)
            + dots3_work.full_row_flops(z) * c["sparse_rows_read"]
            + dots3_work.window_row_flops(z) * c["window_rows_read"]
            + dots3_work.index_key_flops(z) * c["index_keys_scored"]
            + prompt * c["prefills"]
            + head_flops(hidden, int(model["vocab_size"]), c))


FLOPS = {"gpt2": gpt2_flops, "olmoe": olmoe_flops, "latent_share": latent_share_flops,
         "double_layer": double_layer_flops, "dots3": dots3_flops}


def needed_flops(config: dict, counters: dict):
    """FLOPs the work ``counters`` states needs under ``config`` (each
    counter a difference over the interval; one absent counts nothing),
    or None for a configuration no family takes."""
    kind = family(config)
    if not kind:
        return None
    c = {name: float(counters.get(name) or 0.0) for name in COUNTERS[kind]}
    return FLOPS[kind](config, c)
