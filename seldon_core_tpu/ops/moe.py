"""Routed experts: a router and a grouped expert matmul over the routed
rows only.

``route``: logits, softmax and the chosen gates in float32 (on a TPU an
f32 matmul runs in bf16 passes unless told otherwise, so the router's
is ``HIGHEST``); top-k of the probabilities, gates **not** renormalised
(OLMoE's ``norm_topk_prob: false``).

``expert_ffn``: the ``T x k`` (token, expert) assignments are sorted by
expert and the three SwiGLU matmuls run as ``jax.lax.ragged_dot`` over
the sorted rows with the experts' group sizes: every routed pair is
computed, none is dropped (no capacity factor), and an expert nobody
chose costs nothing but its place in the group table.  XLA's TPU
backend lowers ``ragged_dot`` to a Mosaic grouped-matmul kernel of its
own (``ragged-dot-*`` custom calls: visible in the device trace as
Mosaic kernels with a 2-D output), so one code path serves a 32-row
decode step inside a scan and a 4,096-row prefill group.  Matmuls take
the activations' type (bf16 when serving) and accumulate in f32.

``route_grouped`` is the DeepSeek-V3 router: sigmoid scores in float32,
a correction bias that takes part in the *selection* alone, groups of
experts scored by their two best of which the best few are kept, top-k
among those, and the chosen experts' own sigmoid scores divided by
their sum and scaled.

``expert_ffn_held`` is one replica's share of an expert-parallel layer:
the router scores every expert, this replica holds ``w_gate.shape[0]``
of them from ``offset`` on, and the result is the part those experts
give.  An assignment to an absent expert costs no expert FLOPs and no
weight bytes, and what it would have added is left out; nothing stands
in for the absent replicas or for their exchange.

``EXPERTS_SCOPE`` names the expert layer's operations in the HLO
metadata (``jax.named_scope``), whatever the compiler calls them.
"""

from __future__ import annotations

EXPERTS_SCOPE = "moe_experts"
ROUTER_SCOPE = "moe_router"


def route(h, w_router, top_k: int):
    """``h`` ``(T, d)``, ``w_router`` ``(d, E)`` -> ``(gates (T, k) f32,
    experts (T, k) int32)``: the top-k softmax probabilities as they
    are, and whose they are."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope(ROUTER_SCOPE):
        logits = jnp.dot(
            h.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, top_k)
    return gates, experts.astype(jnp.int32)


def expert_ffn(h, w_gate, w_up, w_down, gates, experts):
    """``sum_k gates[t, k] * W_down[e] (silu(h W_gate[e]) * (h W_up[e]))``
    with ``e = experts[t, k]``: ``h`` ``(T, d)``, ``w_gate``/``w_up``
    ``(E, d, f)``, ``w_down`` ``(E, f, d)`` -> ``(T, d)`` float32."""
    import jax
    import jax.numpy as jnp

    tokens, top_k = experts.shape
    num_experts = w_gate.shape[0]
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)       # assignments, by expert
    sizes = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1)
    rows = h.astype(w_gate.dtype)[order // top_k]           # (T*k, d)
    inner = jnp.float32 if h.dtype == jnp.float32 else w_gate.dtype
    with jax.named_scope(EXPERTS_SCOPE):
        gate = jax.lax.ragged_dot(rows, w_gate, sizes, preferred_element_type=inner)
        up = jax.lax.ragged_dot(rows, w_up, sizes, preferred_element_type=inner)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(w_down.dtype)
        # what leaves the layer stays float32 up to the residual add
        out = jax.lax.ragged_dot(act, w_down, sizes,
                                 preferred_element_type=jnp.float32)
    # back to (token, k) order, then the gated sum over a token's k
    out = out[jnp.argsort(order)].reshape(tokens, top_k, -1)
    return jnp.einsum("tkd,tk->td", out, gates)


def route_grouped(h, w_router, bias, top_k: int, n_group: int,
                  topk_group: int, norm: bool, scale: float):
    """``h`` ``(T, d)``, ``w_router`` ``(d, E)``, ``bias`` ``(E,)`` ->
    ``(gates (T, k) f32, experts (T, k) int32)``, as HF
    ``modeling_deepseek_v3.py`` routes: ``s = sigmoid(h W)``; selection
    scores ``s + bias``; a group's score is the sum of its two largest;
    the experts of all but the ``topk_group`` best groups are set to 0
    (not -inf) and the ``top_k`` largest chosen; the gates are ``s``
    (never ``s + bias``) of the chosen, over their sum (+ 1e-20) when
    ``norm``, times ``scale``.  Ties go to the lower index."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope(ROUTER_SCOPE):
        logits = jnp.dot(
            h.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        scores = jax.nn.sigmoid(logits)                       # (T, E)
        choice = scores + bias.astype(jnp.float32)
        tokens, num_experts = choice.shape
        grouped = choice.reshape(tokens, n_group, num_experts // n_group)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)   # (T, G)
        _, kept = jax.lax.top_k(group_score, topk_group)          # (T, g)
        keep = jax.nn.one_hot(kept, n_group, dtype=jnp.int32).sum(axis=1) > 0
        choice = jnp.where(keep[:, :, None], grouped, 0.0).reshape(
            tokens, num_experts)
        _, experts = jax.lax.top_k(choice, top_k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
        if norm:
            gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
        gates = gates * scale
    return gates, experts.astype(jnp.int32)


# A pass of ``expert_ffn_held`` holds this many times the rows an even
# router would send here: routing is not even (PERF.md section 6, PR 30:
# a share's load swings 2.5 to 3.4 % about 3.125), and a second pass
# streams the held experts' matrices again, where empty rows cost only
# their MXU time.
HELD_ROWS_HEADROOM = 4
# ... and never fewer than this: a pass's sort slice, gathers and three
# kernel launches are not worth a smaller one.
HELD_ROWS_MIN = 64


def held_rows_cap(tokens: int, top_k: int, held: int, num_experts: int) -> int:
    """Rows one pass of :func:`expert_ffn_held` computes: the share of
    the ``tokens x top_k`` assignments that ``held`` of ``num_experts``
    experts get when routing is even, :data:`HELD_ROWS_HEADROOM` times
    over, rounded up to a power of two.  More local assignments than
    that are computed in further passes, none dropped."""
    even = tokens * top_k * held / num_experts
    cap = HELD_ROWS_MIN
    while cap < HELD_ROWS_HEADROOM * even:
        cap *= 2
    return cap


def expert_ffn_held(h, w_gate, w_up, w_down, gates, experts, offset: int,
                    num_experts: int):
    """The held experts' part of :func:`expert_ffn`'s sum: ``w_gate`` /
    ``w_up`` ``(H, d, f)`` and ``w_down`` ``(H, f, d)`` are experts
    ``offset .. offset + H`` of the ``num_experts`` that ``experts``
    ``(T, k)`` names.

    The assignments are sorted local-first by expert and computed
    :func:`held_rows_cap` rows a pass (``ragged_dot`` over the rows'
    group sizes, as ``expert_ffn``), in a ``while_loop`` that runs as
    many passes as the local assignments need: one, unless routing
    piles onto this share.  A pass's rows go back to their tokens by
    ``top_k`` gathers of ``(T, d)`` (a token's j-th assignment reads its
    row, or a zero row when it is absent or another pass's) — no
    scatter, which serialises on a TPU."""
    import jax
    import jax.numpy as jnp

    tokens, top_k = experts.shape
    held = w_gate.shape[0]
    d_model = h.shape[-1]
    cap = held_rows_cap(tokens, top_k, held, num_experts)
    local = experts - offset
    is_local = (local >= 0) & (local < held)
    key = jnp.where(is_local, local, held).reshape(-1)      # absent sort last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rank = jnp.argsort(order).astype(jnp.int32).reshape(tokens, top_k)
    n_local = is_local.sum().astype(jnp.int32)
    pad = jnp.full((cap,), tokens * top_k, jnp.int32)
    order = jnp.concatenate([order, pad])
    key_sorted = jnp.concatenate(
        [key, jnp.full((1,), held, key.dtype)])[order]
    gates = jnp.where(is_local, gates, 0.0)
    rows_in = h.astype(w_gate.dtype)
    inner = jnp.float32 if h.dtype == jnp.float32 else w_gate.dtype

    def one_pass(state):
        base, y = state
        idx = jax.lax.dynamic_slice(order, (base,), (cap,))
        which = jax.lax.dynamic_slice(key_sorted, (base,), (cap,))
        sizes = (which[:, None] == jnp.arange(held)[None, :]).sum(
            axis=0).astype(jnp.int32)
        rows = rows_in[jnp.minimum(idx // top_k, tokens - 1)]       # (cap, d)
        with jax.named_scope(EXPERTS_SCOPE):
            gate = jax.lax.ragged_dot(rows, w_gate, sizes,
                                      preferred_element_type=inner)
            up = jax.lax.ragged_dot(rows, w_up, sizes,
                                    preferred_element_type=inner)
            act = (jax.nn.silu(gate.astype(jnp.float32))
                   * up.astype(jnp.float32)).astype(w_down.dtype)
            out = jax.lax.ragged_dot(act, w_down, sizes,
                                     preferred_element_type=jnp.float32)
        # rows past the groups hold whatever the kernel left there
        out = jnp.where((which < held)[:, None], out, 0.0)
        out = jnp.concatenate([out, jnp.zeros((1, d_model), out.dtype)])
        at = rank - base
        at = jnp.where(is_local & (at >= 0) & (at < cap), at, cap)
        for j in range(top_k):
            y = y + out[at[:, j]] * gates[:, j, None]
        return base + cap, y

    _, y = jax.lax.while_loop(
        lambda state: state[0] < n_local, one_pass,
        (jnp.int32(0), jnp.zeros((tokens, d_model), jnp.float32)))
    return y


def swiglu(h, w_gate, w_up, w_down):
    """``W_down (silu(h W_gate) * (h W_up))``: a dense SwiGLU FFN or a
    shared expert; ``h`` ``(T, d)`` in the matmuls' type, f32 out."""
    import jax
    import jax.numpy as jnp

    inner = jnp.float32 if h.dtype == jnp.float32 else w_gate.dtype
    gate = jnp.dot(h, w_gate, preferred_element_type=inner)
    up = jnp.dot(h, w_up, preferred_element_type=inner)
    act = (jax.nn.silu(gate.astype(jnp.float32))
           * up.astype(jnp.float32)).astype(w_down.dtype)
    return jnp.dot(act, w_down, preferred_element_type=jnp.float32)


def expert_histogram(experts, num_experts: int, mask=None):
    """Assignments per expert, ``int32[E]``, over the rows ``mask``
    (``(T,)`` bool) keeps: what the engine's routing counters add up."""
    import jax
    import jax.numpy as jnp

    hit = jax.nn.one_hot(experts, num_experts, dtype=jnp.int32)   # (T, k, E)
    if mask is not None:
        hit = hit * mask.astype(jnp.int32)[:, None, None]
    return hit.sum(axis=(0, 1))
