"""XLA recompilation sentinel for the engine's jit entry points.

Silent recompiles are the #1 invisible tail-latency source on TPU: a
request arriving with a shape the compiled-program cache has never seen
pays seconds of XLA compilation *inside its serving path*, and nothing
in the process said so.  ``instrument`` wraps a jitted callable with a
shape-signature tracker: the first call under each distinct argument
signature is a (re)compile event — it increments the canonical
``seldon_tpu_jit_compiles_total{program=...}`` counter and WARNs with
the exact signature that triggered it, so the operator can map a tail
spike to the shape that caused it (and warm it at deploy time).

The tracker is signature-based rather than hooking jax internals: it
costs one pytree walk per call (microseconds against a chunk program's
milliseconds), works on every jax version, and — unlike cache-size
probing — can NAME the offending signature.  ``SELDON_TPU_JIT_SENTINEL=0``
disables it (the wrap then returns the function untouched).
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any, Callable, Set, Tuple

logger = logging.getLogger(__name__)

JIT_COMPILES_METRIC = "seldon_tpu_jit_compiles_total"


def sentinel_enabled() -> bool:
    from seldon_core_tpu.runtime import knobs

    return knobs.flag("SELDON_TPU_JIT_SENTINEL")


# dtype -> its name.  ``str(np.dtype)`` is a dozen python-level calls; a
# walk over a 36-layer parameter tree pays it per leaf, per jit call,
# for the handful of dtypes a model has.
_DTYPE_NAMES: dict = {}


def _leaf_sig(x: Any) -> Any:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        name = _DTYPE_NAMES.get(dtype)
        if name is None:
            name = _DTYPE_NAMES[dtype] = str(dtype)
        return (tuple(shape), name)
    # weak_type-irrelevant python scalars: jit re-traces on dtype class,
    # not value — collapse to the type name
    return type(x).__name__


def signature_of(args: tuple, kwargs: dict) -> Tuple:
    """The abstract (shape, dtype) signature jit keys its cache on —
    static python values collapse to their type."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (tuple(_leaf_sig(leaf) for leaf in leaves), str(treedef))


def _count_compile(program: str, sig: Tuple, static: str) -> None:
    logger.warning(
        "jit compile: program=%s%s signature=%s — a new argument-shape "
        "signature reached this entry point; if this happened under "
        "traffic the request paid the compile",
        program, f" [{static}]" if static else "", sig[0],
    )
    try:
        from seldon_core_tpu.utils.metrics import _cache_for

        _cache_for(None).get(
            "counter", JIT_COMPILES_METRIC, ("program",),
            "XLA compilations triggered at an engine jit entry point "
            "(first call per distinct argument-shape signature)",
        ).labels(program=program).inc()
    except Exception:  # noqa: BLE001 — the sentinel never breaks serving
        logger.exception("jit compile counter failed for %s", program)


class JitSentinel:
    """Per-program signature memory shared by all wrapped callables of
    one logical program (e.g. every (steps, buckets) chunk variant)."""

    def __init__(self, program: str):
        self.program = program
        self._seen: Set[Tuple] = set()
        self._lock = threading.Lock()

    @property
    def compiles(self) -> int:
        return len(self._seen)

    def wrap(self, fn: Callable, static: str = "") -> Callable:
        """Wrap a jitted callable; ``static`` names the static part of
        the cache key (the chunk's (steps, buckets) spec) so two
        variants with identical array shapes still count separately."""
        if not sentinel_enabled():
            return fn
        import functools

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            try:
                sig = (static, *signature_of(args, kwargs))
                with self._lock:
                    new = sig not in self._seen
                    if new:
                        self._seen.add(sig)
                if new:
                    _count_compile(self.program, sig[1:], static)
            except Exception:  # noqa: BLE001 — the sentinel never breaks serving
                logger.exception("jit sentinel failed for %s", self.program)
            return fn(*args, **kwargs)

        return wrapped
