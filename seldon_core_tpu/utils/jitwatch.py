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

What the sentinel cannot see is a compile no entry point of the engine
made: an eager ``.at[].set`` or gather whose index shape is new compiles
a small program of its own, inside a wave.  ``watch_backend_compiles``
hears every one of them from ``jax.monitoring`` (the backend-compile
event, with its seconds and the function's name) at no cost to a call
that does not compile; it counts them all, keeps the last 32 with where
the compiling thread stood, and adds those no sentinel-wrapped call made
to the same ``seldon_tpu_jit_compiles_total{program=<fun_name>}``.
"""

from __future__ import annotations

import logging
import os
import threading
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

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


def _count_compile(program: str, sig: Optional[Tuple], static: str) -> None:
    """One more compile of ``program``: the sentinel's (``sig`` is the
    signature that was new, and is logged) or the backend listener's
    (None: its own line is logged where it fires)."""
    if sig is not None:
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
            # a backend compile under this call is the sentinel's to count
            _inside.depth = getattr(_inside, "depth", 0) + 1
            try:
                return fn(*args, **kwargs)
            finally:
                _inside.depth -= 1

        return wrapped


# ---------------------------------------------------------------------------
# every backend compile of the process, where it happens
# ---------------------------------------------------------------------------

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_RING = 32

_inside = threading.local()  # .depth: sentinel-wrapped calls open on this thread
_watch_lock = threading.Lock()
_watching = False
# (compiles, seconds) so far: ONE tuple, replaced whole under the lock
_totals: Tuple[int, float] = (0, 0.0)
_ring: deque = deque(maxlen=COMPILE_RING)
# thread ident -> a callable giving (where, wave) of the wave loop that
# runs on that thread (models/paged/seam.py _WaveSeam.compile_context), or None
_contexts: Dict[int, Callable[[], Optional[Tuple[str, int]]]] = {}


def _on_duration(event: str, duration: float, **kwargs: Any) -> None:
    """``jax.monitoring``'s listener, on the compiling thread.  Never
    raises into the compile that fired it."""
    if event != BACKEND_COMPILE_EVENT:
        return
    global _totals
    try:
        fun_name = str(kwargs.get("fun_name", ""))
        context = _contexts.get(threading.get_ident())
        where, wave = (context() if context is not None else None) or ("", 0)
        with _watch_lock:
            _totals = (_totals[0] + 1, _totals[1] + float(duration))
            _ring.append({"fun_name": fun_name, "seconds": float(duration),
                          "where": where, "wave": wave})
        if not getattr(_inside, "depth", 0):
            logger.info(
                "xla compile outside every sentinel: fun_name=%s %.1f ms, "
                "where=%s wave=%s", fun_name, 1e3 * duration, where or "-", wave)
            _count_compile(fun_name or "unnamed", None, "")
    except Exception:  # noqa: BLE001 — the listener never breaks a compile
        logger.exception("backend-compile listener failed")


def watch_backend_compiles() -> None:
    """Register the process's one listener for backend compiles.  Called
    wherever an engine is built; every call but the first is a flag
    test."""
    global _watching
    if _watching:
        return
    with _watch_lock:
        if _watching:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _watching = True


def compile_context(ident: int, context: Callable[[], Optional[Tuple[str, int]]]) -> None:
    """The wave loop that runs on thread ``ident`` says where it stands
    through ``context()`` (the newest to claim a thread holds it)."""
    _contexts[ident] = context


def compile_totals() -> Tuple[int, float]:
    """(backend compiles, their seconds) of the process since
    :func:`watch_backend_compiles`."""
    return _totals


def compile_ring() -> List[Dict[str, Any]]:
    """The last ``COMPILE_RING`` compiles, oldest first: ``fun_name``,
    ``seconds``, and the ``where`` (the seam's open phase) and ``wave``
    of the wave loop on the compiling thread ('' and 0 on any other)."""
    with _watch_lock:
        return [dict(entry) for entry in _ring]
