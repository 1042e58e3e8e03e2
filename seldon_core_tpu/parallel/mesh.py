"""Device-mesh construction.

The reference's scale-out unit is the pod replica behind a Service
(reference: SURVEY §2 request-level parallelism); the TPU-native unit is
the **device mesh**: ICI-connected chips addressed by named axes, over
which models are sharded with ``NamedSharding`` and XLA inserts the
collectives.  DCN (multi-host) edges stay at the graph/transport layer.

Conventions used across the framework:

* ``data``  — batch-dimension sharding (throughput scaling)
* ``model`` — tensor-parallel parameter sharding (fit + latency scaling)
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

DATA_AXIS = "data"
MODEL_AXIS = "model"


def create_mesh(
    axes: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence] = None,
):
    """Build a ``jax.sharding.Mesh``.

    ``axes`` maps axis name -> size; a size of -1 means "everything
    left" (at most one axis).  Axis ORDER is the device-grid order:
    list ``data`` before ``model`` (the :func:`resolve_mesh`
    convention) so each model group spans adjacent devices — the fast
    ICI neighbours tensor-parallel collectives want — while data
    groups stride across them.

    Default (no ``axes``): every device on ``data`` — the pure
    replica/batch mesh the trainer uses.  Serving callers never rely
    on this default: they go through :func:`resolve_mesh` (or its
    1-D front :func:`tp_mesh`), THE precedence home that builds
    ``{"data": D, "model": M}`` — dropping either axis at size 1 so a
    degenerate request lowers byte-identically to the 1-D (or
    single-chip) program.
    """
    import jax
    import numpy as np

    devices = list(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {DATA_AXIS: len(devices)}

    sizes = dict(axes)
    wildcards = [k for k, v in sizes.items() if v == -1]
    if len(wildcards) > 1:
        raise ValueError("at most one mesh axis may be -1")
    fixed = math.prod(v for v in sizes.values() if v != -1)
    if wildcards:
        if len(devices) % fixed:
            raise ValueError(f"{len(devices)} devices not divisible by {fixed}")
        sizes[wildcards[0]] = len(devices) // fixed
    total = math.prod(sizes.values())
    if total > len(devices):
        raise ValueError(f"mesh {sizes} needs {total} devices, have {len(devices)}")
    mesh_devices = np.asarray(devices[:total]).reshape(tuple(sizes.values()))
    return jax.sharding.Mesh(mesh_devices, tuple(sizes.keys()))


def single_device_mesh():
    """Degenerate 1-device mesh so sharded code paths run anywhere."""
    return create_mesh({DATA_AXIS: 1})


def mesh_from_axes(mesh_axes):
    """``{"model": 4}`` -> Mesh, or None when ``mesh_axes`` is falsy.

    The one-liner every component with a ``mesh_axes`` config knob
    (StreamingLM, SpeculativeLM) shares."""
    return create_mesh(dict(mesh_axes)) if mesh_axes else None


def resolve_tp(tp: Optional[int] = None) -> int:
    """Tensor-parallel degree for the serving lanes: an explicit
    ``tp`` argument wins (``1`` forces single-chip even with the env
    var exported); ``None``/``0`` defers to ``SELDON_TPU_TP``, where
    unset/empty/``0`` all spell OFF (= 1), matching every other
    ``SELDON_TPU_*=0``-disables knob.  The ONE place the knob's
    precedence lives, so the paged engine, the contiguous generator,
    and the speculative lane cannot disagree about what a deployment
    asked for."""
    import os

    if tp is None or int(tp) == 0:
        from seldon_core_tpu.runtime import knobs

        raw = (knobs.raw("SELDON_TPU_TP", "") or "").strip()
        tp = int(raw) if raw else 1
        if tp == 0:
            tp = 1
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"tensor-parallel degree must be >= 1, got {tp}")
    return tp


def resolve_dp(dp: Optional[int] = None) -> int:
    """Data-parallel degree for the serving lanes — :func:`resolve_tp`'s
    twin over the ``data`` axis: an explicit ``dp`` argument wins
    (``1`` forces one replica group even with the env var exported);
    ``None``/``0`` defers to ``SELDON_TPU_DP``, where unset/empty/``0``
    all spell OFF (= 1), the fleet-wide ``=0``-disables convention."""
    if dp is None or int(dp) == 0:
        from seldon_core_tpu.runtime import knobs

        raw = (knobs.raw("SELDON_TPU_DP", "") or "").strip()
        dp = int(raw) if raw else 1
        if dp == 0:
            dp = 1
    dp = int(dp)
    if dp < 1:
        raise ValueError(f"data-parallel degree must be >= 1, got {dp}")
    return dp


def resolve_mesh(
    mesh=None,
    mesh_axes: Optional[Dict[str, int]] = None,
    tp: Optional[int] = None,
    dp: Optional[int] = None,
    *,
    strict: bool = False,
    data_axis: str = DATA_AXIS,
    model_axis: str = MODEL_AXIS,
):
    """THE serving-mesh precedence home: ``{"data": D, "model": M}``.

    Precedence (first hit wins, the one ordering every engine shares):

    1. an explicit ``mesh`` object — returned verbatim;
    2. ``mesh_axes`` (the StreamingLM/SpeculativeLM config spelling) —
       built as given via :func:`create_mesh`;
    3. constructor ``tp=`` / ``dp=`` integers;
    4. the ``SELDON_TPU_TP`` / ``SELDON_TPU_DP`` env knobs
       (:func:`resolve_tp` / :func:`resolve_dp`; unset/``0`` = 1).

    A size-1 axis is DROPPED: ``dp=1`` yields the exact ``{model: tp}``
    mesh :func:`tp_mesh` builds (so 1-D programs stay byte-identical),
    and ``dp=tp=1`` yields ``None`` (the single-chip engine, no
    annotation objects at all).  Axis order is data-major — each model
    group spans adjacent devices (fast ICI neighbours for the per-layer
    all-reduces), data groups stride across them.

    Degrade is deterministic and shrinks the DATA axis first: a host
    with fewer than ``dp*tp`` devices keeps the full model degree and
    drops ``dp`` to what fits (``devices // tp``); only when even
    ``tp`` alone cannot fit does the mesh degrade to single-chip —
    both steps WARN naming BOTH axes, so one serving config rolls out
    across pod and dev hosts unchanged.  ``strict=True`` raises
    instead (dry-run / bench lanes, where a silent degrade would
    certify the wrong thing)."""
    if mesh is not None:
        return mesh
    if mesh_axes:
        return create_mesh(dict(mesh_axes))
    tp = resolve_tp(tp)
    dp = resolve_dp(dp)
    if dp <= 1:
        return tp_mesh(tp, axis=model_axis, strict=strict)
    import jax

    devices = jax.devices()
    avail = len(devices)
    if tp > avail:
        msg = (
            f"serving mesh ({data_axis}={dp}, {model_axis}={tp}) needs "
            f"{dp * tp} devices but the host exposes {avail} and even "
            f"the model axis alone does not fit — degrading to "
            f"single-chip ({data_axis}=1, {model_axis}=1)"
        )
        if strict:
            raise ValueError(msg)
        import logging

        logging.getLogger(__name__).warning(msg)
        return None
    if dp * tp > avail:
        fit = max(1, avail // tp)
        msg = (
            f"serving mesh ({data_axis}={dp}, {model_axis}={tp}) needs "
            f"{dp * tp} devices but the host exposes {avail} — "
            f"shrinking the data axis first: "
            f"({data_axis}={fit}, {model_axis}={tp})"
        )
        if strict:
            raise ValueError(msg)
        import logging

        logging.getLogger(__name__).warning(msg)
        dp = fit
        if dp <= 1:
            return tp_mesh(tp, axis=model_axis, strict=strict)
    axes = {data_axis: dp}
    if tp > 1:
        axes[model_axis] = tp
    return create_mesh(axes, devices=devices[: dp * tp])


def tp_mesh(
    tp: Optional[int] = None,
    *,
    axis: str = MODEL_AXIS,
    strict: bool = False,
):
    """``{"model": tp}`` serving mesh, or ``None`` when TP is off.

    ``tp=None``/``0`` defers to ``SELDON_TPU_TP`` (:func:`resolve_tp`).
    When the host exposes fewer devices than the requested degree the
    knob DEGRADES to single-chip (returns ``None``) with a WARN instead
    of failing engine load — one serving config can roll out across
    v5e-8 pods and single-chip dev hosts unchanged.  ``strict=True``
    raises instead (the multichip dry-run / bench lanes, where a silent
    degrade would certify the wrong thing)."""
    tp = resolve_tp(tp)
    if tp <= 1:
        return None
    import jax

    devices = jax.devices()
    if len(devices) < tp:
        msg = (
            f"tensor-parallel degree {tp} needs {tp} devices but the host "
            f"exposes {len(devices)} — degrading to single-chip (tp=1)"
        )
        if strict:
            raise ValueError(msg)
        import logging

        logging.getLogger(__name__).warning(msg)
        return None
    return create_mesh({axis: tp}, devices=devices[:tp])


def mesh_shape(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def device_report() -> Dict[str, object]:
    """What this process computes on, as jax reports it — the
    ``device`` block of a serving component's ``/health/status``, so a
    client can tell a chip from the CPU backend, a compiled Pallas lane
    from the interpreter, and whether every device of a mesh holds its
    share.  Initialises the backend."""
    import jax

    from seldon_core_tpu.ops.kernels import interpret_mode

    devices = jax.devices()
    out = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "pallas_interpret": interpret_mode(),
    }
    stats = [d.memory_stats() for d in devices]
    if all(stats):  # the CPU backend reports none
        out["bytes_in_use"] = [int(s["bytes_in_use"]) for s in stats]
        out["peak_bytes_in_use"] = [
            int(s.get("peak_bytes_in_use", s["bytes_in_use"])) for s in stats
        ]
    return out
