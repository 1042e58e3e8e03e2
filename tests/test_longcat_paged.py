"""LongCat-Flash (as LongCat-Flash-Omni's language model configures it)
on the paged engine (PR 32): a double layer — two latent attentions,
each with its own cache row, and two dense SwiGLU FFNs, the routed
experts on a shortcut round the second half — behind a softmax router
over real and identity experts, compared on **logits** with the
benchmark's plain float32 reference (``benchmarks/reference/
longcat_flash.py``: naive attention, a loop over the held experts).

Small size, CPU: d 64, 4 heads, ranks 24 / 16, heads of 8 nope + 4 rope
against values of 8, 8 real experts of 32 and 4 identity experts, top-4
times 6, dense FFNs of 96, 2 layers (4 attentions); the replica holds 4
of the 8 real experts from the third on.  The engine's own compiled
programs are driven through the seams its other tests use, on the
kernel lane (Pallas in interpret mode) and the XLA gather lane.

This file: the logits of the three programs on the module's engines.
``test_longcat_model.py`` has the wrong programs the tolerances tell
apart, the front door, the router, the share and the sizes (PR 44 split
one file of 671 s along its sections).
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paged_harness as harness
from paged_harness import PAGE, PROMPT, cached_suffix, prefill, run_program
from seldon_core_tpu.ops import moe

ref, MODEL = harness.MODELS["longcat"]
SPEC, SIZES = harness.spec_and_sizes("longcat")
LANES = ("gather", "kernel")  # the ring chunk refuses a latent pool

# float32 compute against a float32 reference: what is left is the order
# of sums (absorbed against naive attention, a paged softmax merged by
# the flash rule, a grouped matmul, the shortcut added last).  Logits
# have unit spread; the largest difference seen over the six lane x
# program cases is 3e-6.  1e-4 is ~30x that, and under a hundredth of
# what the mildest of the wrong programs below moves them by.
F32_ATOL = 1e-4


# bfloat16 compute through 2 double layers at d = 64 (as the DeepSeek
# block's test: every matmul output, the latent rows in the pool, q with
# W_uk folded in and the softmax weights are rounded).  Largest
# difference seen over weight seeds 3-8 and the three programs: 0.05 of
# the logits' spread; a wrong program moves them by 0.1-1 of it.
BF16_ATOL, BF16_SEED = 0.08, 4


engines, own_engine = harness.fixtures(SPEC, SIZES)


def _reference(params, tokens, model=MODEL):
    return np.asarray(ref.logits(params, model, tokens))


@pytest.mark.parametrize("program", ["prefill", "decode", "cached"])
@pytest.mark.parametrize("lane", sorted(LANES))
def test_logits_match_the_reference_f32(engines, lane, program):
    eng, params = engines(lane)
    assert eng._kernel_active == (lane == "kernel")
    assert eng._chunk_impl == "pool" and eng.cache.pages_v is None
    # the pool's leading axis: 2 layers x 2 attentions; 20 values in 128 lanes
    assert eng.cache.pages_k.shape == (4, eng.num_pages, PAGE, 128)
    rows, tokens, at = run_program(eng, program)
    want = _reference(params, tokens)[at: at + len(rows)]
    assert np.abs(rows - want).max() < F32_ATOL


@pytest.mark.parametrize("program", ["prefill", "decode", "cached"])
@pytest.mark.parametrize("lane,experts", [
    *((lane, "ragged_dot") for lane in sorted(LANES)), ("kernel", "stream")])
def test_logits_match_the_reference_bf16(monkeypatch, engines, own_engine, lane,
                                         program, experts):
    """The serving precision: matrices at rest in bf16 (router, its
    correction bias and norm scales f32), a bf16 latent pool, f32 router
    and residual stream; the held experts through ``ragged_dot`` (what a
    CPU traces) and through the streaming kernel (what a TPU traces at a
    decode pass's rows: here under the interpreter: an engine of its
    own, traced under the patch)."""
    if experts == "stream":
        monkeypatch.setattr(moe, "matmul_backend", lambda: "interpret")
        eng, params = own_engine(lane, jnp.bfloat16, seed=BF16_SEED)
    else:
        eng, params = engines(lane, jnp.bfloat16, BF16_SEED)
    assert set(eng.lane_report()["expert_matmul"].values()) >= {experts}
    block = params["block_1"]
    assert block["experts_gate"].dtype == block["kv_b_k_1"].dtype == jnp.bfloat16
    assert block["router"].dtype == block["score_bias"].dtype == jnp.float32
    rows, tokens, at = run_program(eng, program)
    want = _reference(params, tokens)[at: at + len(rows)]
    assert np.abs(rows - want).max() < BF16_ATOL * want.std()


def test_a_cached_suffix_sees_what_a_whole_prefill_sees(engines):
    eng, _params = engines("gather")
    whole, _hist = prefill(eng, PROMPT)
    for cached in (PAGE, 3 * PAGE):
        assert np.abs(cached_suffix(eng, PROMPT, cached) - whole).max() < 1e-5
