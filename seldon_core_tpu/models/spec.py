"""What a decoder block is made of: the one description the paged
block, its decode-chunk twin, the LM around them, the KV pool and the
parameter initialiser all read.

The paged engine's contract with a model: per **attention sub-layer**
one cache row a token, ``cache_width`` values wide, in ``cache_pools``
pools of one shape ``(attention sub-layers, pages, page_size,
cache_width)``, written after whatever the model does to what it caches
(positions, norms, a constant scale), and next-token logits.  The
pool's leading axis counts attentions, not layers
(:meth:`ModelSpec.cache_layers`): one a layer for every block but
LongCat-Flash's double layer, which has two, so attention ``2 * layer +
i`` owns pool row ``2 * layer + i`` while the routing counters stay one
row a *layer*.  Multi-head attention caches K and V, each ``num_heads
x head_dim = d_model`` wide, in two pools; latent attention (MLA) caches
one row ``[c_kv ; RoPE(k_r)]`` of ``kv_rank + rope_dim`` values (in
whole 128-lane tiles: 576 values rest in 640 lanes) in one pool and no
V.  Everything around the cache is the model's: how
positions enter (a learned table added to the embedding | rotary
embedding before the cache write, plain or YaRN-scaled), which norm
(LayerNorm | RMSNorm), a norm on q and k, the attention's projections
(fused qkv | low-rank q and a shared latent for k and v, absorbed into
q and the output in a decode step), which layers are dense and which
routed (or, in a double layer, that every layer holds two dense FFNs
*and* routed experts on a shortcut round the second half), how the
router scores and picks (softmax top-k | sigmoid with a selection-only
bias and group-limited top-k, renormalised and scaled | softmax over
real and identity experts with a selection-only bias, scaled and not
renormalised), a shared expert beside the routed ones, identity
("zero-computation") experts after them, and **which routed experts this
replica holds** (``experts_held`` from ``expert_offset``, of
``num_experts`` the router scores: one chip's share of an
expert-parallel layer computes its own experts' part and nothing
stands in for the rest).

``GPT2``, ``OLMOE``, ``DEEPSEEK_V3``, ``LONGCAT_FLASH``, ``DOTS3_NOTE``,
``SMALLTHINKER``, ``XING4_0``, ``OLMO_HYBRID``, ``BAILING_HYBRID`` and ``JAMBA`` are the values served (the fifth added layer KINDS: ``layer_kinds``,
``attn_kind``, ``cache_kinds`` — layers that differ in their attention
and a cache of one pool a row kind); a new architecture is a new value (and new branches where the
block reads a field it has not met), not a new block.  The fourth value
added ``double_layer`` (two latent attentions and two dense SwiGLU FFNs
a layer, the routed experts computed from the first half's normed
stream and added after the second), ``zero_experts`` (identity experts
the router scores after the ``num_experts`` real ones),
``mla_lora_scale`` (constant scales on q and on the cached latent) and
``score="softmax_bias"``.

For multi-head attention ``kv_heads`` and ``head_dim`` are fields whose
0 means what GPT-2 and OLMoE have: as many K/V heads as query heads, of
``d_model / num_heads``, so the pool's element is ``d_model`` wide.  The
sixth value (``SMALLTHINKER``) sets both: 28 query heads of 128 (q and
the output projection's input are 3,584 wide, not ``d_model``) over 4
K/V heads, a cache row of ``kv_heads x head_dim`` = 512 values in each
of K and V (:meth:`ModelSpec.cache_width`), query head ``h`` reading K/V
head ``h // (num_heads / kv_heads)``.  It also made ``layer_kinds`` mean
something for a multi-head spec — "full" | "window" layers over K/V
pools of kinds (:meth:`ModelSpec.cache_kinds`), with **positions a
kind** (``full_positions="none"``: the window layers rotate q and k, the
full layers carry no positional encoding at all) — and added
``router_from="attn_input"`` (the routing logits come from the block's
normed input, the rows that feed q, k and v), ``expert_act="relu"`` and
``norm_topk`` / ``experts_held`` behind a softmax router.  Latent
attention's head widths (``nope_dim``, ``rope_dim``, ``v_dim``) and
ranks are fields: none follows from ``d_model``.

The seventh value (``XING4_0``) changed the RESIDUAL: ``hc_mult`` rows
of ``d_model`` a token in place of one (manifold-constrained
hyper-connections; ``ops/hyper.py`` has the equations), every attention
and every FFN reading a learned, input-dependent mix of the rows and
writing back through another.  The rows live inside a program: the
cache row, the pool and the allocator are DeepSeek-V3's.

The eighth value (``OLMO_HYBRID``) added a layer kind that keeps **no
pages**: ``layer_kinds`` may name a layer ``"linear"`` — Gated-DeltaNet
linear attention (``ops/delta.py``; the ``lin_*`` fields) whose whole
memory of a stream is a float32 state of ``lin_key_dim x lin_value_dim``
a head and the last inputs of a short convolution, resting with the
SLOT.  Such a spec is not a cache of kinds (:attr:`ModelSpec.kinds` is
false): its other layers are one kind, ``"full"`` multi-head layers over
one K/V pool whose leading axis counts them alone
(:meth:`ModelSpec.cache_layers`, :meth:`ModelSpec.state_layers`).  With
it came ``post_norm`` (the norm on a sub-layer's output) and ``ffn
"swiglu"`` (a dense SwiGLU in every layer of a multi-head spec).

The ninth value (``BAILING_HYBRID``: Ling-3.0-flash) put the linear
layers **beside a latent pool** — the layers that keep pages are
DeepSeek-V3's latent attention over ONE pool whose leading axis counts
them alone, with ``q_rank`` 0 (a plain q projection, no bottleneck) and
dots3's ``attn_gate`` — made the linear layer's VARIANT facts of the spec
(``lin_gate`` "head" | "channel": one decay a head or one a key channel,
Kimi Delta Attention; ``lin_gate_floor``: 0 = the softplus gate, a
negative number the bounded gate ``floor x sigmoid(.)``; ``lin_out_gate``
"silu" over every value | "sigmoid_head" one a head), and gave a linear
layer the FFN its place calls for: leading dense SwiGLU layers, then
DeepSeek-V3's sigmoid-routed experts of which the replica holds a share
(:meth:`ModelSpec.layer_routed` whatever the layer's kind).

The tenth value (``JAMBA``: AI21-Jamba2-3B) added a second layer kind
that keeps a state a lane, ``"ssm"`` — a Mamba-1 selective state-space
layer (``ops/ssm.py``; the ``ssm_*`` fields) whose state is ``ssm_state
x ssm_inner`` float32 a lane and whose recurrence is no delta rule: the
same manager (:attr:`ModelSpec.recurrent`, :meth:`ModelSpec.state_layers`,
:meth:`ModelSpec.state_bytes`) holds either, never both in one spec —
beside multi-query ``"full"`` layers (``kv_heads`` 1) without positions,
and ``tied_head``: the head is the embedding's transpose, one matrix at
rest.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, replace
from functools import lru_cache, partial
from typing import Any, Dict, Tuple


def lane_tiles(values: int) -> int:
    """``values`` rounded up to whole 128-lane tiles: the lanes a cache
    row of that many values rests in (576 -> 640)."""
    return -(-values // 128) * 128


@dataclass(frozen=True)
class AttnKind:
    """One layer's attention at its own sizes
    (:meth:`ModelSpec.attn_kind`): ``window`` 0 attends over every
    earlier position, ``topk`` over the best that many by the layer's
    indexer (0: no indexer).  A multi-head spec's kinds leave the latent
    ranks and widths 0 and say how positions enter the layer
    (``positions``: "rope" | "none")."""
    name: str
    heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    window: int
    topk: int
    positions: str = "rope"

    @property
    def lanes(self) -> int:
        """The cache row in whole 128-lane tiles."""
        return lane_tiles(self.kv_rank + self.rope_dim)

    @property
    def softmax_scale(self) -> float:
        return (self.nope_dim + self.rope_dim) ** -0.5


@dataclass(frozen=True)
class ModelSpec:
    name: str = "gpt2"
    positions: str = "learned"    # "learned" | "rope"
    norm: str = "layernorm"       # "layernorm" | "rmsnorm"
    norm_eps: float = 1e-6
    qk_norm: bool = False         # RMSNorm over the whole q / k projection
    ffn: str = "gelu"             # "gelu" (dense, 4x) | "moe" (routed SwiGLU)
    num_experts: int = 0
    experts_per_tok: int = 0
    expert_width: int = 0
    rope_theta: float = 10_000.0
    bias: bool = True             # projections and head carry a bias
    # the residual stream between blocks: the compute type (bf16 when
    # serving) | float32.  Every matmul still takes bf16 operands; what
    # float32 saves is one rounding to 8 bits of mantissa per residual
    # add, which is what a routed model's near-ties at the k-th expert
    # are decided by
    residual_f32: bool = False
    # the type ``init`` makes matrices and embeddings in: float32
    # (TransformerLM's trees, as its checkpoints and the reference hold
    # them) | the compute type (norm scales and a router are float32
    # either way).  Not how an engine holds them: it casts a float32
    # tree to its compute type once, as it takes it (:func:`rest_tree`)
    weights_f32: bool = True
    # ---- attention: "mha" (K and V of d_model each) | "mla" (one
    # latent row of kv_rank + rope_dim; q through a q_rank bottleneck;
    # heads of nope_dim + rope_dim against values of v_dim)
    attention: str = "mha"
    # multi-head attention's K/V heads and head width (0 = num_heads and
    # d_model / num_heads: K and V of d_model each).  Set, q and the
    # output projection's input are num_heads x head_dim wide, K and V
    # kv_heads x head_dim, and query head h reads K/V head
    # h // (num_heads / kv_heads)
    kv_heads: int = 0
    head_dim: int = 0
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    # YaRN (rope_factor 1 = plain RoPE): the inverse frequencies blend
    # interpolation and extrapolation between the beta_fast and
    # beta_slow correction dims of the original context, and the
    # softmax scale carries mscale_all_dim's m squared
    rope_factor: float = 1.0
    rope_orig_len: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # ---- feed-forward by layer: the first dense_layers are dense
    # SwiGLU of dense_width, the rest routed (ffn == "moe")
    dense_layers: int = 0
    dense_width: int = 0
    shared_experts: int = 0       # SwiGLU of shared_experts * expert_width
    # ---- the router: "softmax" (plain top-k, gates as they are) |
    # "sigmoid" (a selection-only bias; n_group groups scored by their
    # two best, topk_group of them kept; the chosen gates divided by
    # their sum when norm_topk, times routed_scale)
    score: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    norm_topk: bool = False
    routed_scale: float = 1.0
    # what the router reads: "ffn_input" (the post-attention norm, which
    # the experts act on) | "attn_input" (the block's normed input, the
    # rows that feed q, k and v: a router placed before the attention)
    router_from: str = "ffn_input"
    # the experts' gate activation: "silu" (SwiGLU) | "relu" (ReGLU)
    expert_act: str = "silu"
    # ---- the share: of the num_experts the router scores, this
    # replica holds experts_held (0 = all) starting at expert_offset
    experts_held: int = 0
    expert_offset: int = 0
    # ---- LongCat-Flash.  double_layer: a layer is two latent
    # attentions (each its own cache row) and two dense SwiGLU FFNs of
    # dense_width, with the routed experts on a shortcut: computed from
    # the first half's post-attention norm, added after the second half.
    # zero_experts: router outputs num_experts .. + zero_experts are
    # identity experts (gate * h: no matrices, nothing streamed).
    # score "softmax_bias": softmax over num_experts + zero_experts, a
    # selection-only bias, the chosen probabilities times routed_scale
    # as they are.  mla_lora_scale: q times (d_model / q_rank) ** 0.5
    # and the normed latent times (d_model / kv_rank) ** 0.5
    double_layer: bool = False
    zero_experts: int = 0
    mla_lora_scale: bool = False
    # ---- layer kinds (dots3-note).  layer_kinds[i] names layer i's
    # attention, "full" | "window" (() = every layer the one attention
    # the fields above describe).  A FULL layer is that attention plus
    # an indexer (DeepSeek sparse attention's: index_heads heads of
    # index_dim score every cached position, a row attends over the
    # index_topk best) and caches a second row a token, the indexer's
    # key.  A WINDOW layer is latent attention at widths of its own (the
    # win_* fields; its heads too, whatever the LM's num_heads says)
    # over the token and the ``window - 1`` positions before it.
    # attn_gate: one sigmoid gate a head, read from the layer's normed
    # input, on the attended values before the output projection
    layer_kinds: Tuple[str, ...] = ()
    window: int = 0
    win_heads: int = 0
    win_q_rank: int = 0
    win_kv_rank: int = 0
    win_nope_dim: int = 0
    win_rope_dim: int = 0
    win_v_dim: int = 0
    win_rope_theta: float = 10_000.0
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    attn_gate: bool = False
    # a multi-head spec with layer kinds: how positions enter a FULL
    # layer ("" = as ``positions`` says | "none": no positional encoding
    # at all; the window layers keep ``positions``)
    full_positions: str = ""
    # ---- the residual (Xing4.0: manifold-constrained hyper-connections,
    # ops/hyper.py).  hc_mult rows of d_model a token (0 = the one row
    # every other arch carries); each sub-layer reads sigmoid(H_pre) of
    # them, and writes H_res X + 2 sigmoid(H_post) x its output, H_res the
    # exp of its clipped logits after hc_sinkhorn_iters row-then-column
    # normalisations with hc_eps in each divisor (and in the RMSNorm of
    # the flattened rows the coefficients are read from)
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_min: float = -30.0
    hc_res_max: float = 30.0
    # ---- linear-attention layers (Olmo-Hybrid: Gated DeltaNet,
    # ops/delta.py).  A layer_kinds entry "linear" is a layer whose
    # attention keeps NO pages: lin_heads heads of lin_key_dim (q, k)
    # against lin_value_dim (v), each through a causal depthwise
    # convolution of lin_conv taps, and a state of lin_key_dim x
    # lin_value_dim float32 a head and a lane that rests with the SLOT
    # beside the convolution's last lin_conv - 1 inputs.  lin_neg_eigval:
    # beta = 2 sigmoid(.) (linear_allow_neg_eigval).  post_norm: RMSNorm
    # on each sub-layer's OUTPUT before the residual add (Olmo 2 / 3),
    # none on its input.  ffn "swiglu": a dense SwiGLU of dense_width in
    # every layer
    lin_heads: int = 0
    lin_key_dim: int = 0
    lin_value_dim: int = 0
    lin_conv: int = 4
    lin_neg_eigval: bool = True
    post_norm: bool = False
    # ---- the linear layer's variant (Ling-3.0-flash: Kimi Delta
    # Attention).  lin_gate: the decay is one number a head ("head") | one
    # a key CHANNEL ("channel": a full matrix ``a`` of lin_heads x
    # lin_key_dim outputs, and the state's rows decay each at its own
    # rate).  lin_gate_floor: 0 = alpha = exp(-exp(A_log) softplus(.)) |
    # negative = the bounded gate, log alpha = floor x sigmoid(exp(A_log)
    # (.)) in (floor, 0) (kda_safe_gate / kda_lower_bound).  lin_out_gate:
    # "silu" (a gate a value, lin_heads x lin_value_dim wide) |
    # "sigmoid_head" (one sigmoid gate a head).  The two swiglu limit
    # lists are a clamp on the experts' hidden rows whose form no config
    # key gives: kept so that a non-zero entry is REFUSED by name
    lin_gate: str = "head"
    lin_gate_floor: float = 0.0
    lin_out_gate: str = "silu"
    expert_swiglu_limits: Tuple[float, ...] = ()
    shared_swiglu_limits: Tuple[float, ...] = ()
    # ---- state-space layers (Jamba: Mamba-1, ops/ssm.py).  A layer_kinds
    # entry "ssm" is a layer that keeps NO pages: ssm_inner channels
    # (mamba_expand x hidden) through a causal depthwise convolution of
    # ssm_conv taps (with a bias where ssm_conv_bias), step sizes, input
    # and output columns read from them through a projection of
    # ssm_dt_rank + 2 ssm_state and three inner RMSNorms, and a state of
    # ssm_state x ssm_inner float32 a lane that rests with the SLOT beside
    # the convolution's last ssm_conv - 1 inputs.  ssm_proj_bias (a bias
    # on the in and out projections) is kept so that a true one is REFUSED
    # by name.  tied_head: logits = h . embedding^T, one matrix at rest
    ssm_inner: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_dt_rank: int = 0
    ssm_conv_bias: bool = True
    ssm_proj_bias: bool = False
    tied_head: bool = False

    @property
    def routed(self) -> bool:
        return self.ffn == "moe"

    @property
    def latent(self) -> bool:
        return self.attention == "mla"

    @property
    def cache_pools(self) -> int:
        """Pools of ``(layers, pages, page_size, cache_width)`` a
        model's cache takes: K and V | one latent row."""
        return 1 if self.latent else 2

    def cache_width(self, d_model: int) -> int:
        """Lanes a token's cache row takes, per layer and pool:
        ``d_model`` (``kv_heads x head_dim`` where a multi-head spec sets
        them), or a latent row's :attr:`cache_values` rounded up
        to whole 128-lane tiles (576 -> 640, the tail zero).  Under the
        TPU's (8, 128) tiling a 576-wide minor dim occupies 640 lanes of
        HBM whatever the array is called, and the decode kernel can
        only cut HBM in whole tiles; so the padding is in the shape,
        where the allocator's byte accounting sees it."""
        if not self.latent:
            return self.kv_heads * self.head_dim or d_model
        return lane_tiles(self.cache_values)

    def head_sizes(self, num_heads: int, d_model: int) -> Tuple[int, int]:
        """``(kv_heads, head_dim)`` of multi-head attention with
        ``num_heads`` query heads."""
        return (self.kv_heads or num_heads,
                self.head_dim or d_model // num_heads)

    @property
    def cache_values(self) -> int:
        """Values a latent row holds: ``[c_kv ; RoPE(k_r)]``."""
        return self.kv_rank + self.rope_dim

    def layer_routed(self, layer: int) -> bool:
        return self.routed and layer >= self.dense_layers

    @property
    def attn_sublayers(self) -> int:
        """Attentions, and so cache rows a token, in one layer."""
        return 2 if self.double_layer else 1

    def cache_layers(self, num_layers: int) -> int:
        """The pool's leading axis: attention sub-layers, not layers —
        and of a spec with linear layers only the layers that keep
        pages."""
        return (num_layers - self.state_layers(num_layers)) * self.attn_sublayers

    @property
    def kinds(self) -> bool:
        """Whether the layers differ in their attention's ROWS: then the
        cache is one pool a row kind (:meth:`cache_kinds`), not one
        element.  (Linear layers keep no rows at all: what is left of a
        spec with them is one kind, in one pool.)"""
        return bool(self.layer_kinds) and not self.recurrent

    @property
    def linear(self) -> bool:
        """Whether some layers are linear attention: a state a lane
        beside the pages (:meth:`state_layers`)."""
        return "linear" in self.layer_kinds

    @property
    def ssm(self) -> bool:
        """Whether some layers are state-space layers: a state a lane of
        another recurrence (``ops/ssm.py``)."""
        return "ssm" in self.layer_kinds

    @property
    def recurrent(self) -> bool:
        """Whether some layers keep a state a lane and no pages, of
        either recurrence: what the engine's state-a-lane manager asks."""
        return self.linear or self.ssm

    @property
    def state_kind(self) -> str:
        """The layer kind that keeps the state: "linear" | "ssm" | ""."""
        return "linear" if self.linear else "ssm" if self.ssm else ""

    def state_layers(self, num_layers: int) -> int:
        """The layers that keep a state a lane and no pages."""
        return self.layer_kinds[:num_layers].count(self.state_kind)

    def state_shape(self, slots: int) -> Tuple[int, ...]:
        """One layer's resting state over ``slots`` (float32): a linear
        layer's ``ops/delta.py state_shape``, a state-space layer's
        ``(slots, ssm_state, ssm_inner)``."""
        if self.ssm:
            return (slots, self.ssm_state, self.ssm_inner)
        from seldon_core_tpu.ops import delta

        return delta.state_shape(slots, self.lin_heads, self.lin_key_dim,
                                 self.lin_value_dim)

    @property
    def state_channels(self) -> int:
        """The channels a state layer convolves, and its taps: the tail a
        lane keeps is ``(taps - 1, channels)`` in the compute type."""
        return self.ssm_inner if self.ssm else self.lin_channels

    @property
    def state_taps(self) -> int:
        return self.ssm_conv if self.ssm else self.lin_conv

    def state_bytes(self, num_layers: int) -> int:
        """Bytes one lane's state takes as it rests, of either
        recurrence: the float32 state (what the (8, 128) tiling pads is
        counted) and the convolution's inputs in bf16."""
        if not self.recurrent:
            return 0
        *groups, rows, lanes = self.state_shape(1)[1:]
        state = 4 * (groups[0] if groups else 1) * (-(-rows // 8) * 8) * lane_tiles(lanes)
        conv = 2 * (self.state_taps - 1) * self.state_channels
        return self.state_layers(num_layers) * (state + conv)

    @property
    def lin_channels(self) -> int:
        """The channels a linear layer convolves: q, k and v side by
        side."""
        return self.lin_heads * (2 * self.lin_key_dim + self.lin_value_dim)

    def layer_kind(self, layer: int) -> str:
        return self.layer_kinds[layer] if self.layer_kinds else "full"

    def kind_index(self, layer: int) -> int:
        """Layer ``layer``'s place among the layers of its kind: the
        leading index of its rows in that kind's pool."""
        kind = self.layer_kind(layer)
        return sum(k == kind for k in self.layer_kinds[:layer])

    def attn_kind(self, layer: int, num_heads: int) -> "AttnKind":
        """Layer ``layer``'s attention at its own sizes."""
        if not self.latent:
            window = self.layer_kind(layer) == "window"
            return AttnKind(
                "window" if window else "full", num_heads, 0, 0, 0, 0, 0,
                self.rope_theta, self.window if window else 0, 0,
                positions=(self.positions if window or not self.full_positions
                           else self.full_positions))
        if self.layer_kind(layer) == "window":
            return AttnKind(
                "window", self.win_heads, self.win_q_rank, self.win_kv_rank,
                self.win_nope_dim, self.win_rope_dim, self.win_v_dim,
                self.win_rope_theta, self.window, 0)
        return AttnKind(
            "full", num_heads, self.q_rank, self.kv_rank, self.nope_dim,
            self.rope_dim, self.v_dim, self.rope_theta, 0,
            self.index_topk if self.kinds else 0)

    def cache_kinds(self, num_layers: int) -> Tuple[Tuple[str, int, int], ...]:
        """``(name, layers, lanes)`` of each pool a spec with layer kinds
        keeps: the full layers' latent rows and their indexer keys (both
        addressed by a stream's block table, which grows with its
        length) and the window layers' rows (addressed by a table of
        fixed width: pages behind the window go back to the allocator).
        Lanes are values in whole 128-lane tiles, as
        :meth:`cache_width`.  A multi-head spec keeps two kinds, the full
        and the window layers' K/V rows of ``kv_heads x head_dim``, each
        in :attr:`cache_pools` pools (K and V)."""
        kinds = self.layer_kinds[:num_layers]
        full, win = kinds.count("full"), kinds.count("window")
        if not self.latent:
            width = self.kv_heads * self.head_dim
            return (("full", full, width), ("window", win, width))
        return (("full", full, lane_tiles(self.kv_rank + self.rope_dim)),
                ("index", full, lane_tiles(self.index_dim)),
                ("window", win, lane_tiles(self.win_kv_rank + self.win_rope_dim)))

    def window_table_pages(self, page_size: int, steps: int) -> int:
        """Columns of a lane's window table: the pages ``window``
        positions can touch (the first need not start a page) and those
        a chunk of ``steps`` positions grows into."""
        return -(-(self.window - 1 + steps) // page_size) + 1

    @property
    def router_outputs(self) -> int:
        """Outputs the router scores: real experts, then identity ones."""
        return self.num_experts + self.zero_experts

    @property
    def hist_width(self) -> int:
        """Columns of a layer's routing histogram: assignments per
        router output and, where some outputs are identity experts, the
        tokens by how many REAL experts they chose (``0 ..
        experts_per_tok``: a token's expert work varies)."""
        return self.router_outputs + (
            self.experts_per_tok + 1 if self.zero_experts else 0)

    def lora_scales(self, d_model: int, kind: "AttnKind" = None):
        """``(s_q, s_kv)``: the constants on q and on the normed latent
        (1, 1 unless ``mla_lora_scale``), at ``kind``'s ranks where the
        layers differ."""
        if not self.mla_lora_scale:
            return 1.0, 1.0
        q_rank, kv_rank = ((kind.q_rank, kind.kv_rank) if kind
                           else (self.q_rank, self.kv_rank))
        return ((d_model / q_rank) ** 0.5, (d_model / kv_rank) ** 0.5)

    @property
    def held(self) -> int:
        """Routed experts whose matrices this replica holds."""
        return self.experts_held or self.num_experts

    @property
    def softmax_scale(self) -> float:
        """Latent attention's score scale: ``(nope + rope) ** -0.5``,
        times YaRN's ``m ** 2`` with ``m = 0.1 * mscale_all_dim *
        ln(factor) + 1``."""
        import math

        scale = (self.nope_dim + self.rope_dim) ** -0.5
        if self.rope_factor > 1.0 and self.rope_mscale_all_dim:
            m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
            scale *= m * m
        return scale

    @property
    def transformer_lm(self) -> bool:
        """Whether ``models/transformer.py TransformerLM`` builds this
        block.  It builds one — learned positions, LayerNorm, biased
        dense GELU MLP, made in float32 — and then its ``init`` makes
        the tree, values included, as every GPT-2 test, checkpoint and
        reference expects; any other block's tree is
        :func:`init_params`'s."""
        return (self.positions, self.norm, self.qk_norm, self.ffn, self.bias,
                self.weights_f32) == ("learned", "layernorm", False, "gelu",
                                      True, True)

    @property
    def rope(self) -> bool:
        return self.positions == "rope"


GPT2 = ModelSpec()

# allenai/OLMoE-1B-7B-0125-Instruct config.json: RoPE theta 10,000,
# RMSNorm eps 1e-5, 64 experts of width 1024, top-8, gates not
# renormalised; QK-norm is in modeling_olmoe.py, not a config key
OLMOE = ModelSpec(
    name="olmoe", positions="rope", norm="rmsnorm", norm_eps=1e-5,
    qk_norm=True, ffn="moe", num_experts=64, experts_per_tok=8,
    expert_width=1024, rope_theta=10_000.0, bias=False, residual_f32=True,
    weights_f32=False,
)

# ai-sage/GigaChat3.1-702B-A36B config.json (model_type deepseek_v3):
# MLA with q rank 1536, latent 512 + 64 rope, heads of 128 + 64 against
# values of 192; YaRN factor 64 over 4096, theta 100,000; 3 leading
# dense SwiGLU layers of 18,432, then 256 sigmoid-routed experts of
# 2048 in 8 groups (4 kept), top-8 renormalised and scaled 2.5, beside
# one shared expert; RMSNorm eps 1e-6, no biases
DEEPSEEK_V3 = ModelSpec(
    name="deepseek_v3", positions="rope", norm="rmsnorm", norm_eps=1e-6,
    ffn="moe", num_experts=256, experts_per_tok=8, expert_width=2048,
    rope_theta=100_000.0, bias=False, residual_f32=True, weights_f32=False,
    attention="mla", q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
    v_dim=192, rope_factor=64.0, rope_orig_len=4096, rope_beta_fast=32.0,
    rope_beta_slow=1.0, rope_mscale_all_dim=1.0, dense_layers=3,
    dense_width=18_432, shared_experts=1, score="sigmoid", n_group=8,
    topk_group=4, norm_topk=True, routed_scale=2.5,
)

# meituan-longcat/LongCat-Flash-Omni config.json (the language model;
# HF modeling_longcat_flash.py): 28 double layers of two MLA attentions
# (q rank 1536, latent 512 + 64 rope, heads of 128 + 64 against values
# of 128, q and the latent scaled by (6144 / rank) ** 0.5, theta 1e7, no
# scaling) and two dense SwiGLU FFNs of 12,288; 512 experts of 2048 and
# 256 identity experts behind one softmax router with a selection-only
# bias, top-12 times 6, not renormalised; RMSNorm eps 1e-5, no biases,
# no shared expert, no leading dense layers
LONGCAT_FLASH = ModelSpec(
    name="longcat_flash", positions="rope", norm="rmsnorm", norm_eps=1e-5,
    ffn="moe", num_experts=512, experts_per_tok=12, expert_width=2048,
    rope_theta=10_000_000.0, bias=False, residual_f32=True, weights_f32=False,
    attention="mla", q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
    v_dim=128, dense_width=12_288, score="softmax_bias", routed_scale=6.0,
    double_layer=True, zero_experts=256, mla_lora_scale=True,
)

# dots-studio/dots3-note-prev config.json (model_type dots3_note; the
# language model): 46 layers whose attention layer_types names — 13
# "full_attention" (MLA: 128 heads, q rank 1024, latent 512 + 64 rope,
# heads of 128 + 64 against values of 128, theta 8e7, no scaling; an
# indexer of 64 heads x 128 picks the 2,048 positions a row attends)
# and 33 "sliding_attention" (MLA at the swa_* sizes: 64 heads, q rank
# 1024, latent 1024 + 64, heads of 192 + 64 against 128, theta 50,000,
# over 513 positions) in the period full, window x 3 after two leading
# full layers — each with a headwise output gate and q and the latent
# rescaled by (5120 / rank) ** 0.5 (apply_mla_qkv_lora_rescale, read as
# LongCat-Flash's pair); one leading dense SwiGLU layer of 13,824, then
# 256 sigmoid-routed experts of 1536 (noaux_tc: a selection-only bias,
# one group), top-8 renormalised, scale 1, beside one shared expert;
# RMSNorm eps 1e-5, no biases
DOTS3_NOTE = ModelSpec(
    name="dots3_note", positions="rope", norm="rmsnorm", norm_eps=1e-5,
    ffn="moe", num_experts=256, experts_per_tok=8, expert_width=1536,
    rope_theta=80_000_000.0, bias=False, residual_f32=True, weights_f32=False,
    attention="mla", q_rank=1024, kv_rank=512, nope_dim=128, rope_dim=64,
    v_dim=128, dense_layers=1, dense_width=13_824, shared_experts=1,
    score="sigmoid", n_group=1, topk_group=1, norm_topk=True, routed_scale=1.0,
    mla_lora_scale=True,
    layer_kinds=("full", "full") + ("window", "window", "window", "full") * 11,
    window=513, win_heads=64, win_q_rank=1024, win_kv_rank=1024,
    win_nope_dim=192, win_rope_dim=64, win_v_dim=128, win_rope_theta=50_000.0,
    index_heads=64, index_dim=128, index_topk=2048, attn_gate=True,
)

# PowerInfer/SmallThinker-21BA3B-Instruct config.json: 52 layers, each
# routed; 28 query heads of 128 over 4 K/V heads (q 3,584 wide beside a
# hidden size of 2,560); sliding_window_layout = rope_layout = 0, 1, 1, 1
# x 13: a layer with 1 attends over 4,096 positions and rotates q and k
# (rotate-half, theta 1,500,000, no scaling), a layer with 0 attends
# over every earlier position and has no positional encoding at all;
# 64 ReLU-gated experts of 768 ("sparse ReGLU"), softmax top-6
# renormalised (norm_topk_prob), the router reading the attention's
# input ("router placed before attention"); RMSNorm eps 1e-6, no biases,
# no QK-norm, no shared expert, untied embedding and head
SMALLTHINKER = ModelSpec(
    name="smallthinker", positions="rope", norm="rmsnorm", norm_eps=1e-6,
    ffn="moe", num_experts=64, experts_per_tok=6, expert_width=768,
    rope_theta=1_500_000.0, bias=False, residual_f32=True, weights_f32=False,
    kv_heads=4, head_dim=128, norm_topk=True, router_from="attn_input",
    expert_act="relu", layer_kinds=("full", "window", "window", "window") * 13,
    window=4096, full_positions="none",
)

# XingChen-AGI/Xing4.0-29B-A4B config.json (model_type xing4_0): the
# DeepSeek-V3 block at other sizes — MLA with q rank 768, latent 512 + 64
# rope, heads of 128 + 64 against values of 128, YaRN factor 64 over
# 4,096 at theta 10,000; 2 leading dense SwiGLU layers of 9,216, then 64
# sigmoid-routed experts of 1,024 in one group (noaux_tc), top-4
# renormalised and scaled 2.0, beside one shared expert; RMSNorm eps
# 1e-6 — under a residual of hc_mult = 4 rows mixed round every
# attention and every FFN (hc_sinkhorn_iters 20, hc_eps 1e-6,
# mhc_h_res_clamp_min / _max -30 / 30)
XING4_0 = replace(
    DEEPSEEK_V3, name="xing4_0", num_experts=64, experts_per_tok=4,
    expert_width=1024, rope_theta=10_000.0, q_rank=768, v_dim=128,
    dense_layers=2, dense_width=9216, n_group=1, topk_group=1,
    routed_scale=2.0, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    hc_res_min=-30.0, hc_res_max=30.0,
)

# allenai/Olmo-Hybrid-7B config.json (model_type olmo_hybrid): 32 layers,
# layer_types = (linear_attention x 3, full_attention) x 8.  A full layer:
# 30 heads = 30 K/V heads of 128 (q, k and v of d_model each), RMSNorm
# over the whole q and k (the Olmo 2 / 3 QK-norm), rope_theta null read
# as NO rotary embedding (the recurrent layers carry position).  A linear
# layer: Gated DeltaNet (ops/delta.py), 30 heads of 96 (q, k) against
# 192 (v), a convolution of 4 taps, allow_neg_eigval.  Every layer a
# dense SwiGLU of 11,008; RMSNorm eps 1e-6 on each sub-layer's output
# (post-norm), no biases, untied embedding and head
OLMO_HYBRID = ModelSpec(
    name="olmo_hybrid", positions="rope", norm="rmsnorm", norm_eps=1e-6,
    qk_norm=True, ffn="swiglu", dense_width=11_008, bias=False,
    residual_f32=True, weights_f32=False, kv_heads=30, head_dim=128,
    layer_kinds=("linear", "linear", "linear", "full") * 8,
    full_positions="none", lin_heads=30, lin_key_dim=96, lin_value_dim=192,
    lin_conv=4, lin_neg_eigval=True, post_norm=True,
)

# inclusionAI/Ling-3.0-flash config.json (model_type bailing_hybrid): 42
# layers, layer_group_size 6 — layer i is latent attention where (i + 1) %
# 6 == 0 and Kimi Delta Attention elsewhere (35 : 7).  A KDA layer: 32
# heads of 128 (q, k) against 128 (v), a convolution of 4 taps, one decay
# a key channel under the bounded gate (kda_safe_gate, kda_lower_bound
# -5), beta = sigmoid(.) (no allow_neg_eigval), a sigmoid output gate a
# head (head_wise).  An MLA layer: DeepSeek-V3's with NO q bottleneck
# (q_lora_rank null), latent 512 + 64 rope, heads of 128 + 64 against
# values of 128, theta 6e6, no scaling, a sigmoid gate a head.  2 leading
# dense SwiGLU layers of 6,144, then 512 sigmoid-routed experts of 768 in
# 8 groups (4 kept), top-8 renormalised and scaled 2.5, beside one shared
# expert; RMSNorm eps 1e-6 on each sub-layer's input, no biases
BAILING_HYBRID = ModelSpec(
    name="bailing_hybrid", positions="rope", norm="rmsnorm", norm_eps=1e-6,
    ffn="moe", num_experts=512, experts_per_tok=8, expert_width=768,
    rope_theta=6_000_000.0, bias=False, residual_f32=True, weights_f32=False,
    attention="mla", q_rank=0, kv_rank=512, nope_dim=128, rope_dim=64,
    v_dim=128, dense_layers=2, dense_width=6144, shared_experts=1,
    score="sigmoid", n_group=8, topk_group=4, norm_topk=True,
    routed_scale=2.5, attn_gate=True,
    layer_kinds=(("linear",) * 5 + ("full",)) * 7,
    lin_heads=32, lin_key_dim=128, lin_value_dim=128, lin_conv=4,
    lin_neg_eigval=False, lin_gate="channel", lin_gate_floor=-5.0,
    lin_out_gate="sigmoid_head",
)

# ai21labs/AI21-Jamba2-3B config.json (model_type jamba; HF
# modeling_jamba.py): 28 layers, attn_layer_period 14 / attn_layer_offset
# 7 — layer i is attention where i % 14 == 7 (layers 7 and 21) and a
# Mamba-1 mixer elsewhere (26 : 2).  A Mamba layer: mamba_expand 2 x 2,560
# = 5,120 channels, a convolution of 4 taps WITH a bias, step sizes and
# the input and output columns from a projection of 160 + 16 + 16 with an
# RMSNorm each, a state of 16 x 5,120 float32 a lane; no bias on the in
# and out projections.  An attention layer: 20 query heads of 128 over
# ONE K/V head, no positional encoding of any kind (the Mamba layers carry
# position), no QK-norm, no window.  num_experts 1: every layer's FFN is
# the dense SwiGLU of 8,192; RMSNorm eps 1e-6 on each sub-layer's input,
# no biases, the head tied to the embedding
def jamba_layer_kinds(num_layers: int, period: int = 14, offset: int = 7
                      ) -> Tuple[str, ...]:
    """The ``jamba`` family's rule: layer ``i`` is attention where ``i %
    attn_layer_period == attn_layer_offset`` and a Mamba mixer elsewhere."""
    return tuple("full" if i % period == offset else "ssm"
                 for i in range(num_layers))


JAMBA = ModelSpec(
    name="jamba", positions="rope", norm="rmsnorm", norm_eps=1e-6,
    ffn="swiglu", dense_width=8192, bias=False, residual_f32=True,
    weights_f32=False, kv_heads=1, head_dim=128,
    layer_kinds=jamba_layer_kinds(28), full_positions="none",
    ssm_inner=5120, ssm_state=16, ssm_conv=4, ssm_dt_rank=160,
    ssm_conv_bias=True, ssm_proj_bias=False, tied_head=True,
)

_ARCHS = {"gpt2": GPT2, "olmoe": OLMOE, "deepseek_v3": DEEPSEEK_V3,
          "longcat_flash": LONGCAT_FLASH, "dots3_note": DOTS3_NOTE,
          "smallthinker": SMALLTHINKER, "xing4_0": XING4_0,
          "olmo_hybrid": OLMO_HYBRID, "bailing_hybrid": BAILING_HYBRID,
          "jamba": JAMBA}
# the sizes any routed arch has; a replica's share of the experts; those
# only DeepSeek-V3's expert layer and attention have; and the two every
# arch has
_EXPERT_SIZES = ("num_experts", "experts_per_tok", "expert_width")
_SHARE_SIZES = ("experts_held", "expert_offset")
_LATENT_SIZES = _SHARE_SIZES + (
    "dense_width", "routed_scale",
    "q_rank", "kv_rank", "nope_dim", "rope_dim", "v_dim")
_DEEPSEEK_SIZES = _LATENT_SIZES + (
    "dense_layers", "shared_experts", "n_group", "topk_group", "rope_factor",
    "rope_orig_len", "rope_beta_fast", "rope_beta_slow", "rope_mscale_all_dim")
# ... and the one LongCat-Flash's router has beside the latent ones
_LONGCAT_SIZES = _LATENT_SIZES + ("zero_experts",)
# ... and those of a spec whose layers differ in kind: the window
# layers' own widths, the indexer's, and which layer is which
_KIND_SIZES = (
    "layer_kinds", "window", "win_heads", "win_q_rank", "win_kv_rank",
    "win_nope_dim", "win_rope_dim", "win_v_dim", "win_rope_theta",
    "index_heads", "index_dim", "index_topk")
_DOTS3_SIZES = _LATENT_SIZES + (
    "dense_layers", "shared_experts", "n_group", "topk_group") + _KIND_SIZES
# ... and those of grouped-query heads over K/V pools of kinds, with a
# share of the experts behind a softmax router
_SMALLTHINKER_SIZES = _SHARE_SIZES + (
    "kv_heads", "head_dim", "layer_kinds", "window")
# ... and those of a residual of several rows over DeepSeek-V3's block
_HYPER_SIZES = ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "hc_res_min",
                "hc_res_max")
# ... and those of linear-attention layers beside full multi-head ones
_LINEAR_SIZES = ("kv_heads", "head_dim", "layer_kinds", "dense_width",
                 "lin_heads", "lin_key_dim", "lin_value_dim", "lin_conv",
                 "lin_neg_eigval")
# the sizes an arch has beside the expert sizes and the two every arch
# has: what ``model_spec`` lets a caller set, by the arch's name (a share
# of the experts and layer kinds belong to the archs whose block was
# built for them, latent or not)
# ... and those of linear layers of a variant beside a latent pool, under
# DeepSeek-V3's expert layer
_VARIANT_SIZES = ("lin_gate", "lin_gate_floor", "lin_out_gate",
                  "expert_swiglu_limits", "shared_swiglu_limits")
_BAILING_SIZES = _LATENT_SIZES + (
    "dense_layers", "shared_experts", "n_group", "topk_group", "layer_kinds",
    "lin_heads", "lin_key_dim", "lin_value_dim", "lin_conv",
    "lin_neg_eigval") + _VARIANT_SIZES
# ... and those of state-space layers beside multi-query full ones
_SSM_SIZES = ("kv_heads", "head_dim", "layer_kinds", "dense_width",
              "ssm_inner", "ssm_state", "ssm_conv", "ssm_dt_rank",
              "ssm_conv_bias", "ssm_proj_bias")
_OWN_SIZES = {"gpt2": (), "olmoe": (), "deepseek_v3": _DEEPSEEK_SIZES,
              "longcat_flash": _LONGCAT_SIZES, "dots3_note": _DOTS3_SIZES,
              "smallthinker": _SMALLTHINKER_SIZES,
              "xing4_0": _DEEPSEEK_SIZES + _HYPER_SIZES,
              "olmo_hybrid": _LINEAR_SIZES, "bailing_hybrid": _BAILING_SIZES,
              "jamba": _SSM_SIZES}
_SIZES = tuple(dict.fromkeys(
    _EXPERT_SIZES + sum(_OWN_SIZES.values(), ()) + ("rope_theta", "norm_eps")))
# a size that may be given as 0 and mean it, for an arch that takes
# sizes of its own (0 elsewhere = as published; GPT-2 and OLMoE, which
# have none of these, take a 0 as "not given")
_ZERO_MEANS_ZERO = ("dense_layers", "shared_experts", "expert_offset",
                    "experts_held", "zero_experts", "lin_neg_eigval",
                    "ssm_conv_bias", "ssm_proj_bias")


def model_spec(arch: str = "gpt2", **sizes: Any) -> ModelSpec:
    """The spec named ``arch`` with the given sizes in place of the
    published ones (tests serve an 8-expert OLMoE).  A size of 0/None
    keeps the published value; an unknown ``arch`` or a size the arch
    does not have is a ``ValueError``."""
    try:
        spec = _ARCHS[arch or "gpt2"]
    except KeyError:
        raise ValueError(
            f"arch={arch!r}: the paged engine serves {sorted(_ARCHS)}"
        ) from None
    own = set(_OWN_SIZES[spec.name])
    if spec.ssm and (int(sizes.get("num_experts") or 1) > 1
                     or int(sizes.get("experts_per_tok") or 1) > 1):
        raise ValueError(
            f"arch={spec.name!r}, num_experts {sizes.get('num_experts')} / "
            f"experts_per_tok {sizes.get('experts_per_tok')}: a routed FFN "
            "inside a state-space stack is not built — every layer's FFN is "
            "the dense SwiGLU (num_experts 1)")
    if spec.ssm:  # (one expert chosen always is the dense FFN: not a size)
        sizes = {k: v for k, v in sizes.items()
                 if k not in ("num_experts", "experts_per_tok")}
    given = {k: v for k, v in sizes.items()
             if v or (v is not None and k in _ZERO_MEANS_ZERO and own)}
    unknown = sorted(set(given) - set(_SIZES))
    if unknown:
        raise ValueError(f"model_spec: unknown sizes {unknown}")
    if not spec.routed and given.keys() & set(_EXPERT_SIZES):
        raise ValueError(f"arch={spec.name!r} has no experts to size")
    foreign = given.keys() & set(sum(_OWN_SIZES.values(), ())) - own
    if foreign:
        raise ValueError(f"arch={spec.name!r} has no {sorted(foreign)}")
    if given:
        floats = ("rope_theta", "norm_eps", "routed_scale", "rope_factor",
                  "rope_beta_fast", "rope_beta_slow", "rope_mscale_all_dim",
                  "win_rope_theta", "hc_eps", "hc_res_min", "hc_res_max",
                  "lin_gate_floor")
        limits = ("expert_swiglu_limits", "shared_swiglu_limits")
        spec = replace(spec, **{
            k: (float(v) if k in floats
                else tuple(str(x) for x in v) if k == "layer_kinds"
                else tuple(float(x) for x in v) if k in limits
                else str(v) if k in ("lin_gate", "lin_out_gate")
                else bool(v) if k in ("lin_neg_eigval", "ssm_conv_bias",
                                      "ssm_proj_bias") else int(v))
            for k, v in given.items()
        })
    if spec.ssm and (spec.linear or not spec.ssm_inner
                     or set(spec.layer_kinds) - {"full", "ssm"}):
        raise ValueError(
            f"arch={spec.name!r}, layer_kinds {spec.layer_kinds}: state-space "
            "layers stand beside 'full' layers (multi-head over K/V pages) of "
            "an arch that has their sizes (ssm_inner, ssm_state, "
            "ssm_dt_rank); 'linear' and 'ssm' layers in one spec are two "
            "states a lane, which no manager holds")
    if spec.ssm and (min(spec.ssm_inner, spec.ssm_state, spec.ssm_dt_rank,
                         spec.ssm_conv - 1) < 1 or spec.ssm_proj_bias):
        raise ValueError(
            f"ssm_inner {spec.ssm_inner}, ssm_state {spec.ssm_state}, "
            f"ssm_dt_rank {spec.ssm_dt_rank}, ssm_conv {spec.ssm_conv}, "
            f"ssm_proj_bias {spec.ssm_proj_bias}: each size at least 1 (two "
            "taps), and a bias on the mixer's in and out projections "
            "(mamba_proj_bias) is not built")
    if spec.linear and (not spec.lin_heads
                        or set(spec.layer_kinds) - {"full", "linear"}):
        raise ValueError(
            f"arch={spec.name!r}, layer_kinds {spec.layer_kinds}: linear "
            "layers stand beside 'full' layers (multi-head over K/V pages, "
            "or latent attention over one latent pool) of an arch that has "
            "their sizes (lin_heads, lin_key_dim, lin_value_dim)")
    if (spec.lin_gate not in ("head", "channel")
            or spec.lin_out_gate not in ("silu", "sigmoid_head")
            or spec.lin_gate_floor > 0
            or (spec.lin_gate == "channel") != (spec.lin_gate_floor < 0)):
        raise ValueError(
            f"lin_gate {spec.lin_gate!r}, lin_gate_floor "
            f"{spec.lin_gate_floor}, lin_out_gate {spec.lin_out_gate!r}: a "
            "decay is one a 'head' under the softplus gate (floor 0) or one "
            "a 'channel' under the bounded gate (a negative floor), and the "
            "output gate 'silu' or 'sigmoid_head'")
    if spec.lin_gate_floor < 0:
        from seldon_core_tpu.ops import delta

        if -spec.lin_gate_floor * delta.SUB > delta.BLOCK_EXPONENT_MAX:
            raise ValueError(
                f"lin_gate_floor {spec.lin_gate_floor}: the chunked scan "
                f"factors a decay a channel in blocks of {delta.SUB} "
                f"positions, which holds down to a floor of "
                f"{-delta.BLOCK_EXPONENT_MAX / delta.SUB} a position")
    if any(spec.expert_swiglu_limits) or any(spec.shared_swiglu_limits):
        raise ValueError(
            f"arch={spec.name!r}: expert_swiglu_limit_list "
            f"{spec.expert_swiglu_limits} / share_expert_swiglu_limit_list "
            f"{spec.shared_swiglu_limits} name a clamp on the experts' "
            "hidden rows in some served layer; the clamp's form is not in "
            "the configuration and is not built — serve layers whose "
            "entries are 0")
    if spec.linear and min(spec.lin_heads, spec.lin_key_dim,
                           spec.lin_value_dim, spec.lin_conv - 1) < 1:
        raise ValueError(
            f"lin_heads {spec.lin_heads}, lin_key_dim {spec.lin_key_dim}, "
            f"lin_value_dim {spec.lin_value_dim}, lin_conv {spec.lin_conv}")
    if spec.kinds and set(spec.layer_kinds) - {"full", "window"}:
        raise ValueError(
            f"layer_kinds {spec.layer_kinds}: a layer is 'full' or 'window'")
    if not spec.latent and bool(spec.kv_heads) != bool(spec.head_dim):
        raise ValueError(
            f"kv_heads {spec.kv_heads} and head_dim {spec.head_dim}: "
            "grouped-query heads set both")
    if spec.hc_mult == 1 or spec.hc_mult < 0:
        raise ValueError(
            f"hc_mult {spec.hc_mult}: a residual of one row has nothing to "
            "mix (a stream count is 0 or at least 2)")
    if spec.hc_mult and (spec.hc_sinkhorn_iters < 1
                         or spec.hc_res_min >= spec.hc_res_max):
        raise ValueError(
            f"hc_sinkhorn_iters {spec.hc_sinkhorn_iters}, clamp "
            f"[{spec.hc_res_min}, {spec.hc_res_max}]")
    if spec.routed and not 0 < spec.experts_per_tok <= spec.router_outputs:
        raise ValueError(
            f"experts_per_tok {spec.experts_per_tok} of {spec.router_outputs} "
            "experts")
    if spec.routed and (
            spec.num_experts % spec.n_group
            or not 0 < spec.topk_group <= spec.n_group
            or spec.experts_per_tok
            > spec.topk_group * (spec.router_outputs // spec.n_group)):
        raise ValueError(
            f"{spec.num_experts} experts in {spec.n_group} groups, "
            f"{spec.topk_group} kept, top-{spec.experts_per_tok}")
    if spec.routed and not (
            0 <= spec.expert_offset
            and spec.expert_offset + spec.held <= spec.num_experts):
        raise ValueError(
            f"experts_held {spec.held} from {spec.expert_offset} of "
            f"{spec.num_experts} experts")
    return spec


def rope(x, positions, theta: float):
    """Rotary embedding, rotate-half form: ``x`` ``(B, L, heads, hd)``,
    ``positions`` ``(B, L)`` absolute token indices.  Computed in f32."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv  # (B, L, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def yarn_inv_freq(spec: ModelSpec):
    """RoPE's inverse frequencies ``(rope_dim // 2,)`` float32 for a
    latent spec: plain ``theta ** (-2i / dim)`` at ``rope_factor`` 1,
    else YaRN's blend (HF ``_compute_yarn_parameters``) — dim ``i``
    keeps its frequency (extrapolation) below the ``beta_fast``
    correction dim, divides it by the factor (interpolation) above the
    ``beta_slow`` one, and ramps linearly between.  ``mscale /
    mscale_all_dim`` is 1 in the served configuration, so cos and sin
    are not scaled; ``m`` enters the softmax scale instead
    (:attr:`ModelSpec.softmax_scale`)."""
    import math

    import numpy as np

    dim, base = spec.rope_dim, spec.rope_theta
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation = 1.0 / pos_freqs
    if spec.rope_factor <= 1.0:
        return extrapolation.astype(np.float32)
    interpolation = 1.0 / (spec.rope_factor * pos_freqs)

    def correction_dim(rotations):
        return (dim * math.log(spec.rope_orig_len / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(spec.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(spec.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001  # HF's guard against a zero-width ramp
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    keep = 1.0 - ramp  # the share of extrapolation
    return (interpolation * (1.0 - keep) + extrapolation * keep).astype(np.float32)


def rope_interleaved(x, positions, inv_freq):
    """HF ``modeling_deepseek_v3.py``'s rotary embedding: ``x`` ``(...,
    L, heads, dim)`` arrives with its pairs interleaved ``(x0, x1, x2,
    x3, ...)``, is re-laid to halves ``(x0, x2, ... ; x1, x3, ...)`` and
    rotated in rotate-half form.  ``positions`` ``(..., L)``.  f32."""
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# the parameter initialiser: the tree is the module's own
# ---------------------------------------------------------------------------

def declared_tree(spec: ModelSpec, config: Dict[str, int], dtype=None):
    """The parameter tree the paged LM declares for ``spec`` at
    ``config``'s sizes — names, shapes and the type each leaf rests in,
    as ``jax.ShapeDtypeStruct``s: ``jax.eval_shape`` of the module's own
    ``init``, so nothing is allocated and a new field of the spec needs
    no second description.  ``dtype`` is the compute type (bf16 when
    serving).  Kept by its arguments: a process asks the same
    declaration again for every engine it builds."""
    import jax.numpy as jnp

    return _declared(spec, tuple(sorted(config.items())),
                     jnp.dtype(dtype or jnp.bfloat16).name)


@lru_cache(maxsize=32)
def _declared(spec, sizes, dtype_name):
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.paged import get_paged_lm_class

    config, dtype = dict(sizes), jnp.dtype(dtype_name)
    # the gather lane's module: same tree as every other lane's, and it
    # takes one layer's pool at a time, so any small pool will do
    lm = get_paged_lm_class()(dtype=dtype, spec=spec, decode_kernel=False,
                              **config)
    i32 = partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    if spec.kinds:
        # a pool a row kind, a segment from position zero (a table of no
        # width), and the window layers' table beside it
        pools = {name: jax.ShapeDtypeStruct((layers, 2, 8, lanes), dtype)
                 for name, layers, lanes in spec.cache_kinds(
                     config["num_layers"])}
        return jax.eval_shape(
            lm.init, jax.random.key(0), i32((1, 8)), i32((1, 8)), pools,
            None if spec.latent else pools,  # K and V pools of kinds
            i32((1, 0)), i32((1,)),
            window=(i32((1, 0)), i32((1,))))["params"]
    pool = jax.ShapeDtypeStruct(
        (spec.cache_layers(config["num_layers"]), 2, 8,
         spec.cache_width(config["d_model"])), dtype)
    return jax.eval_shape(
        lm.init, jax.random.key(0), i32((1, 8)), i32((1, 8)), pool,
        pool if spec.cache_pools == 2 else None,
        # (grouped heads without kinds prefill from zero: a table of no
        # width, and the linear layers' state starts at zero)
        i32((1, 0 if spec.recurrent else 1)), i32((1,)))["params"]


def rest_tree(params, spec: ModelSpec, config: Dict[str, int], dtype=None):
    """``params`` as an engine whose programs compute in ``dtype``
    holds them: every leaf the module would cast whole where it is used
    — what :func:`declared_tree` puts in the compute type for a spec
    whose tree is made in it: the projections' kernels and the biases
    added to their outputs, the embeddings, the head, expert matrices —
    cast **once, here**, by the rounding ``promote_dtype`` would apply
    in every program call: the matmuls' operands are the same values,
    and no program converts a matrix again.  A norm's scale and bias
    and a router stay float32 (they are multiplied in float32; casting
    them would change the result).

    What is done is a function of each leaf's type beside ``dtype``: a
    leaf already in the type it is declared in — a tree made in the
    compute type, any tree under a float32 engine — and a leaf the
    module does not declare are returned as they are, no copy; a tree
    with nothing to cast is returned itself.  The caller's arrays are
    never deleted: whoever owns the wide tree lets go of it."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype or jnp.bfloat16)

    def wide(leaf):
        kind = getattr(leaf, "dtype", None)
        return (kind is not None and jnp.issubdtype(kind, jnp.floating)
                and jnp.dtype(kind).itemsize > dtype.itemsize)

    if not any(wide(leaf) for leaf in jax.tree_util.tree_leaves(params)):
        return params
    from flax.traverse_util import flatten_dict

    declared = flatten_dict(
        declared_tree(replace(spec, weights_f32=False), config, dtype))

    cast = []

    def rest(path, leaf):
        want = declared.get(tuple(getattr(p, "key", None) for p in path))
        if want is None or want.dtype != dtype or not wide(leaf):
            return leaf
        cast.append(path)
        return jnp.asarray(leaf).astype(dtype)

    rested = jax.tree_util.tree_map_with_path(rest, params)
    return rested if cast else params


def init_params(spec: ModelSpec, config: Dict[str, int], seed: int,
                dtype=None):
    """Seeded random weights of the block ``spec`` describes, whatever
    it is made of: the tree (names, shapes, at-rest types) is what the
    paged LM itself declares — ``jax.eval_shape`` of its ``init``, so a
    new field of the spec needs no second description here — and every
    leaf is made on the default device **in the type it rests in**
    (``spec.weights_f32`` false: matrices and embeddings in the compute
    type ``dtype``, bf16 when serving; norm scales and the router f32).
    No f32 copy of a matrix is ever resident (at OLMoE's size one would
    be 14 GB).

    Every leaf is uniform — bits, a scale and a shift, so the CPU (the
    benchmark's reference) and the chip (the server) make the same tree
    from the same seed — over a range its name picks: a norm's
    ``scale`` in [0.5, 1.5) and a ``bias`` (a sigmoid router's
    ``score_bias`` too) within ±0.1, so that one left out shows — a
    softmax router's ``score_bias`` within ± the mean probability ``1 /
    outputs``, which is to probabilities that add up to 1 what ±0.1 is
    to scores in (0, 1): a tenth of unity would choose the experts by
    itself; an ``embedding``
    ±sqrt(3) (unit variance); any other leaf is a matrix, ±sqrt(3 /
    fan_in) with the fan-in its second-to-last dim, experts' included
    (unit-variance outputs) — its last for a ``phi`` (a residual of
    several rows' mixing, ops/hyper.py: the matrix rests transposed, a
    coefficient a row; its ``scale``, alpha, in [0.5, 1.5) and its
    ``bias`` within ±0.1 then move every coefficient by tenths).  A leaf's stream is keyed by its path, not
    its place in the tree.

    Where the spec scales its low-rank paths (``mla_lora_scale``), the
    matrices a rank feeds — ``q_b``, ``kv_b_k``, ``kv_b_v`` — take the
    fan-in ``d_model``, not their own: the constants ``(d_model / rank)
    ** 0.5`` exist because the source draws every matrix at one
    deviation, which leaves those paths ``(rank / d_model) ** 0.5``
    small, and restore q, k and v to unit variance.  Drawn at their own
    fan-in they would come out 2 and 3.46 times too large, the scores
    6.9 times, and attention near-argmax: a step function that bfloat16
    rounding moves at half the positions (49.6 % of 512 served tokens
    off by over 0.09 deviations on the CPU, tools/precision_readings.py,
    PERF.md section 6 PR 32).

    Where a replica holds a share of the routed experts, the
    ``score_bias`` is **ordered by the seed, not redrawn**
    (:func:`share_bias`): among near-unit-variance logits a ±0.1 bias
    decides which experts are chosen, so eight held experts drawn low
    stream less and answer faster than eight drawn high — 5,750 to
    6,100 tokens/s by seed on the chip (PERF.md §6, PR 30) — and a
    seed would set the amount of work, which is the configuration's to
    set."""
    import zlib

    import jax
    import jax.numpy as jnp
    from flax.traverse_util import flatten_dict, unflatten_dict

    declared = declared_tree(spec, config, dtype)
    uniform = _uniform()
    root = jax.random.key(int(seed) % (1 << 63))
    tree = {}
    for path, leaf in flatten_dict(declared, sep="/").items():
        name = path.rsplit("/", 1)[-1]
        if name == "scale" or name.endswith("_norm"):
            # (a state-space layer's three inner norms are leaves of the
            # block itself: dt_norm, b_norm, c_norm)
            lo, hi = 0.5, 1.5
        elif spec.ssm and name in ("a_log", "dt_bias", "d_skip"):
            # a state-space layer's own initial ranges (Mamba's): A =
            # -exp(a_log) in (-16, -1], a step size softplus(dt_bias) in
            # [1e-3, 1e-1] (the bias uniform between their inverse
            # softplus), so that exp(Delta A) spreads over (0.2, 1) and
            # does not sit at 1, where the decay would test nothing; the
            # skip D in [0.5, 1.5) so that one left out shows
            lo, hi = {"a_log": (0.0, 2.772588722239781),
                      "dt_bias": (-6.907255, -2.252168),
                      "d_skip": (0.5, 1.5)}[name]
        elif name == "a_log":
            # a linear layer's decay rate exp(a_log) in (0.05, 1): with
            # the gate's softplus of order 1 the decay alpha spreads over
            # (0, 1) and does not sit at 1, where it would test nothing
            lo, hi = -3.0, 0.0
            if spec.lin_gate_floor:
                # the bounded gate's rate exp(a_log) in (0.37, 1.65) ...
                lo, hi = -1.0, 0.5
        elif name == "dt_bias" and spec.lin_gate_floor:
            # ... and a bias a key channel in [-6, -1): with the projection
            # of order 1, log alpha = floor x sigmoid(rate (a + dt_bias))
            # spreads a head's channels from alpha 0.99 (a memory of a
            # hundred positions) down to 0.1, most of them slow — a gate
            # that sat at one value a head would test Olmo's rule again
            lo, hi = -6.0, -1.0
        else:
            hi = (0.1 if name == "bias" or name.endswith("_bias")
                  else (3.0 if name == "embedding" else 3.0 / leaf.shape[
                      -1 if name == "phi" else -2]) ** 0.5)
            lo = -hi
        if name == "score_bias" and spec.score == "softmax_bias":
            lo, hi = -1.0 / leaf.shape[0], 1.0 / leaf.shape[0]
        if name == "embedding" and spec.tied_head:
            # the one matrix is also the head: drawn as a head's, fan-in
            # d_model (unit-variance logits; the source's own
            # initializer_range 0.02 is 1 / sqrt(2,500)).  At +-sqrt(3) a
            # token's own logit would stand 7 deviations over the rest
            # and every served token would repeat the last
            hi = (3.0 / leaf.shape[-1]) ** 0.5
            lo = -hi
        if spec.mla_lora_scale and _RANK_FED.search(path):
            hi = (3.0 / config["d_model"]) ** 0.5
            lo = -hi
        key = jax.random.fold_in(root, zlib.crc32(path.encode()))
        if (name == "score_bias" and spec.experts_held
                and leaf.shape[0] % spec.held == 0):
            tree[path] = share_bias(key, leaf.shape[0], spec.held, hi)
        else:
            tree[path] = uniform(key, leaf.shape, lo, hi, jnp.dtype(leaf.dtype))
    return unflatten_dict(tree, sep="/")


@lru_cache(maxsize=None)
def _uniform():
    """The jitted draw of one leaf, made once a process: one compiled
    program per distinct (shape, range, type), which the layers' leaves
    share and every later ``init_params`` finds again."""
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnums=(1, 2, 3, 4))
    def uniform(key, shape, lo, hi, leaf_dtype):
        return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(leaf_dtype)

    return uniform


# leaves whose input is a low-rank latent: q_b's kernel, kv_b_k, kv_b_v
# (a double layer's carry a sub-layer suffix)
_RANK_FED = re.compile(r"/(q_b(_\d)?/kernel|kv_b_[kv](_\d)?)$")


def share_bias(key, num_experts: int, held: int, most: float):
    """A router's correction bias ``float32[num_experts]`` for replicas
    that hold ``held`` experts each: every replica's block holds the
    same ``held`` values, evenly spaced within ±``most`` (8: ∓0.0875,
    ∓0.0625, ∓0.0375, ∓0.0125), in an order ``key`` draws per block.
    Every share of every seed then has the same experts to offer, and
    every group of blocks the same: the seed permutes the load, it does
    not size it."""
    import jax
    import jax.numpy as jnp

    values = most * (2.0 * (jnp.arange(held, dtype=jnp.float32) + 0.5) / held - 1.0)
    keys = jax.random.split(key, num_experts // held)
    return jax.vmap(lambda k: jax.random.permutation(k, values))(keys).reshape(-1)
