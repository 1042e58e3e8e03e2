"""Device placement — graph nodes onto TPU chips.

The reference's scheduler is Kubernetes: one container per graph node,
kube-scheduler picks machines.  Here the schedulable resource is the
TPU device set of this host: each predictor gets a device group sized
by its ``mesh_axes`` request (or one device), chosen round-robin.

The plan is BOOKKEEPING: no component consumes it yet (every
``JAX_SERVER`` computes on ``jax.devices()[0]``; generation components
take ``tp``/``dp``/``mesh_axes`` as their own parameters), which is why
``default_and_validate`` refuses predictor-level ``meshAxes`` and
``deviceIds``.  It is planned over the device ids the operator passes
and never asks jax for them: a process that initialises the backend
holds the chip, and the deployer may be about to spawn the worker that
needs it.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from seldon_core_tpu.controlplane.spec import DeploymentSpecError, TpuDeployment


@dataclass
class PredictorPlacement:
    predictor: str
    device_ids: List[int]
    mesh_axes: Optional[Dict[str, int]] = None


@dataclass
class PlacementPlan:
    placements: Dict[str, PredictorPlacement] = field(default_factory=dict)

    def for_predictor(self, name: str) -> Optional[PredictorPlacement]:
        return self.placements.get(name)


def plan_placement(dep: TpuDeployment, device_ids: Optional[List[int]] = None) -> PlacementPlan:
    """Assign device groups to predictors.

    Explicit ``deviceIds`` on a predictor are honoured (after checking
    they exist and don't collide); others are packed round-robin.
    A ``mesh_axes`` request sizes the group to the mesh volume.
    ``device_ids=None`` (the CLI's default) plans nothing.
    """
    plan = PlacementPlan()
    if device_ids is None:
        return plan
    available = list(device_ids)

    # explicit claims first
    for p in dep.predictors:
        if p.device_ids:
            missing = [i for i in p.device_ids if i not in available]
            if missing:
                raise DeploymentSpecError(
                    f"predictor {p.name!r} claims unavailable devices {missing}"
                )
            for i in p.device_ids:
                available.remove(i)
            plan.placements[p.name] = PredictorPlacement(p.name, list(p.device_ids), p.mesh_axes)

    # size-derived assignment for the rest; wrap around (time-sliced
    # sharing) when demand exceeds supply — chips multiplex predictors
    cursor = 0
    pool = available if available else list(device_ids)
    for p in dep.predictors:
        if p.name in plan.placements:
            continue
        want = math.prod(p.mesh_axes.values()) if p.mesh_axes else 1
        if want > len(pool):
            raise DeploymentSpecError(
                f"predictor {p.name!r} wants {want} devices, only {len(pool)} available"
            )
        ids = [pool[(cursor + i) % len(pool)] for i in range(want)]
        cursor = (cursor + want) % len(pool)
        plan.placements[p.name] = PredictorPlacement(p.name, ids, p.mesh_axes)
    return plan
