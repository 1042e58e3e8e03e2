"""The wrong mixings ``reference/xing4.py`` is told apart from, each the
reference itself with one of its functions replaced: what the CPU tests
(``tests/test_xing4_paged.py``) hold the logits' tolerance against and
``tools/precision_readings.py --config xing4.0-29b-a4b`` puts through
the cell's judgement at the published widths.

    with wrong("no_input_dependent_term", model) as other:
        logits = xing4.logits(params, other, tokens)

``module`` is the reference to change where it was loaded under another
name (``harness/manifest.py module`` loads a file as a module of its
own).
"""

from __future__ import annotations

import contextlib

from reference import xing4


def _one_iteration(model, ref):
    return {}, dict(model, hc_sinkhorn_iters=1)


def _post_without_its_2(model, ref):
    right = ref.coefficients

    def halved(x, p, m):
        pre, post, res = right(x, p, m)
        return pre, post / 2, res

    return {"coefficients": halved}, model


def _no_dynamic_term(model, ref):
    import jax.numpy as jnp

    right = ref.coefficients
    return {"coefficients": lambda x, p, m: right(
        x, {**p, "scale": jnp.zeros_like(p["scale"])}, m)}, model


def _res_the_identity(model, ref):
    import jax.numpy as jnp

    return {"sinkhorn": lambda logits, _m: jnp.broadcast_to(
        jnp.eye(logits.shape[-1]), logits.shape)}, model


def _pre_after_the_norm(model, ref):
    import jax.numpy as jnp

    return {"read": lambda x, h_pre, scale, eps: jnp.einsum(
        "tn,tnc->tc", h_pre, ref.rms_norm(x, scale, eps))}, model


def _bf16_coefficients(model, ref):
    import jax.numpy as jnp

    right = ref.coefficients

    def low(a):
        return jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32)

    def rounded(x, p, m):
        pre, post, res = right(low(x), {k: low(v) for k, v in p.items()}, m)
        return low(pre), low(post), low(res)

    return {"coefficients": rounded}, model


def _exit_a_mean(model, ref):
    return {"leave": lambda x: x.mean(axis=1)}, model


# name -> (the functions it replaces, the model it hands the reference)
WRONG = {"sinkhorn_stopped_at_1": _one_iteration,
         "h_post_without_its_2": _post_without_its_2,
         "no_input_dependent_term": _no_dynamic_term,
         "h_res_the_identity": _res_the_identity,
         "h_pre_after_the_norm": _pre_after_the_norm,
         "bf16_coefficients": _bf16_coefficients}
# ... and the one no comparison of logits can tell: the final RMSNorm
# divides the constant out again
SAME_LOGITS = {"exit_a_mean": _exit_a_mean}


@contextlib.contextmanager
def wrong(name: str, model: dict, module=xing4):
    """``reference/xing4.py`` (``module``) computing ``name`` until the
    block ends; yields the ``model`` block to hand it."""
    replaced, other = {**WRONG, **SAME_LOGITS}[name](model, module)
    kept = {attr: getattr(module, attr) for attr in replaced}
    for attr, fn in replaced.items():
        setattr(module, attr, fn)
    try:
        yield other
    finally:
        for attr, fn in kept.items():
            setattr(module, attr, fn)
