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


def expert_histogram(experts, num_experts: int, mask=None):
    """Assignments per expert, ``int32[E]``, over the rows ``mask``
    (``(T,)`` bool) keeps: what the engine's routing counters add up."""
    import jax
    import jax.numpy as jnp

    hit = jax.nn.one_hot(experts, num_experts, dtype=jnp.int32)   # (T, k, E)
    if mask is not None:
        hit = hit * mask.astype(jnp.int32)[:, None, None]
    return hit.sum(axis=(0, 1))
