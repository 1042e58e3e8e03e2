"""Sharding layouts: how parameters and activations map onto the mesh.

The recipe (How to Scale Your Model): pick a mesh, annotate shardings
on jit inputs/outputs, and let XLA insert the collectives over ICI —
never hand-write NCCL-style point-to-point (the reference's only
"collective" layer is gRPC over the pod network,
reference: InternalPredictionService.java:192-467; here that role is
played by XLA collectives inside one jit program).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from seldon_core_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def data_sharded(mesh, axis: str = DATA_AXIS):
    """Batch dim sharded, everything else replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(axis))


def infer_param_specs(
    params: Any,
    mesh,
    model_axis: str = MODEL_AXIS,
    min_weight_size: int = 16_384,
):
    """Tensor-parallel partition specs for a parameter tree.

    Heuristic: for each weight at least ``min_weight_size`` elements,
    shard its largest dimension that divides the model-axis size; small
    weights (biases, norm scales) replicate.  This is the standard
    Megatron-style layout expressed as PartitionSpecs — XLA turns the
    matmuls into reduce-scatter/all-gather pairs over ICI as needed.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from seldon_core_tpu.ops.surgery import QuantizedKernel

    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get(model_axis, 1)

    def dense_spec(shape, prefer_last: bool = False) -> P:
        if axis_size <= 1 or not shape or int(np.prod(shape)) < min_weight_size:
            return P()
        order = sorted(range(len(shape)), key=lambda d: shape[d], reverse=True)
        if prefer_last:
            order.remove(len(shape) - 1)
            order.insert(0, len(shape) - 1)
        for dim in order:
            if shape[dim] % axis_size == 0 and shape[dim] >= axis_size:
                entries: list = [None] * len(shape)
                entries[dim] = model_axis
                return P(*entries)
        return P()

    def spec_for(x):
        # a QuantizedKernel is one unit: its (N,) scale must follow the
        # q layout, so prefer sharding q on the last (output-channel)
        # dim — then scale shards the same axis and the fused dequant
        # needs no resharding collective.  q sharded on an input dim
        # keeps scale replicated (broadcast over sharded rows is free).
        if isinstance(x, QuantizedKernel):
            q_spec = dense_spec(x.q.shape, prefer_last=True)
            entries = tuple(q_spec)
            if entries and entries[-1] == model_axis:
                return QuantizedKernel(q_spec, P(model_axis))
            return QuantizedKernel(q_spec, P())
        return dense_spec(getattr(x, "shape", ()))

    return jax.tree.map(
        spec_for, params, is_leaf=lambda x: isinstance(x, QuantizedKernel)
    )


def shard_params(
    params: Any,
    mesh,
    specs: Optional[Any] = None,
    model_axis: str = MODEL_AXIS,
    min_weight_size: int = 16_384,
):
    """device_put a parameter tree with tensor-parallel shardings.

    Un-annotatable leaves DEGRADE instead of failing engine load: a
    leaf whose device_put rejects its inferred spec falls back to
    replicated with a WARN, and a leaf that cannot be placed at all
    passes through host-side (the jit tracing it will replicate it).
    A checkpoint with one odd auxiliary leaf must not take the whole
    serving engine down."""
    import logging

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    log = logging.getLogger(__name__)
    if specs is None:
        specs = infer_param_specs(params, mesh, model_axis=model_axis, min_weight_size=min_weight_size)

    def put(x, spec):
        # ONLY spec/placement rejections (ValueError: rank mismatch,
        # indivisible dim; TypeError: non-array leaf) degrade — a
        # device OOM (RESOURCE_EXHAUSTED RuntimeError) must propagate:
        # retrying it replicated needs MORE memory, and a host-side
        # fallback would hide a fatal capacity misconfiguration behind
        # a per-call re-upload cliff
        try:
            return jax.device_put(x, NamedSharding(mesh, spec))
        except (TypeError, ValueError):
            if tuple(spec) != ():
                log.warning(
                    "parameter leaf %s (shape %s) rejected spec %s — "
                    "falling back to replicated",
                    type(x).__name__, getattr(x, "shape", "?"), spec,
                )
                try:
                    return jax.device_put(x, NamedSharding(mesh, P()))
                except (TypeError, ValueError):
                    pass
            log.warning(
                "parameter leaf %s is not device-placeable — leaving it "
                "host-side (jit will replicate it)", type(x).__name__,
            )
            return x

    return jax.tree.map(put, params, specs)


def shard_decode_state(
    params: Any,
    mesh,
    *,
    pool_shape,
    dtype,
    model_axis: str = MODEL_AXIS,
    data_axis: str = DATA_AXIS,
    min_weight_size: int = 16_384,
    num_heads: int,
    seq_shard: bool = True,
    pools: int = 2,
):
    """Serving-mesh layout for the paged-decode lanes: megatron param
    specs + K/V pools sharded on BOTH mesh axes.

    * ``model`` axis — the heads, as dim 3 of the pool ``(layers,
      pages, page_size, d_model)``: d_model is head-major contiguous,
      so a head-boundary-aligned partition of it shards the heads.
      ``num_heads`` carries the divisibility constraint (dim 3's size
      is d_model, but shards must align to head boundaries).
    * ``data`` axis — the PAGE dim (dim 1): every data shard owns
      ``num_pages // dp`` pages of the global pool, which is both the
      throughput story (each replica group's streams write their own
      pages) and the long-context story (one 32k stream's pages spread
      across the axis, so contexts one chip's pool cannot admit stay
      servable).  Requires ``num_pages % dp == 0`` (the engine rounds
      its pool up); ``seq_shard=False`` (``SELDON_TPU_SEQ_SHARD=0``)
      replicates the pool over ``data`` — pure throughput replicas,
      no capacity claim.

    Params replicate over ``data`` implicitly: megatron specs only
    name the ``model`` axis, so one weight residency is shared by all
    D replica groups in the process — the whole point vs N processes
    x N full copies.

    Pools are created ALREADY SHARDED (jit with out_shardings) — a
    ``jnp.zeros`` then ``device_put`` would materialise the full pool
    on one device first, defeating the memory win sharding buys.

    ``mesh=None`` is the single-device case: params untouched, plain
    unsharded pools — so callers need no conditional.

    ``pools=1`` (a latent cache: one row a token, no V; single device
    only) makes no second pool and returns None in its place.

    Returns ``(params, pool_k, pool_v)``.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from seldon_core_tpu.parallel.mesh import mesh_shape

    if mesh is None:
        # pin params on device: trees straight from surgery/msgpack are
        # host numpy, and numpy args to jit re-upload EVERY call
        return (
            jax.device_put(params),
            jnp.zeros(pool_shape, dtype),
            jnp.zeros(pool_shape, dtype) if pools == 2 else None,
        )
    if pools != 2:
        raise ValueError("a one-pool (latent) cache has no sharding rule")

    params = shard_params(
        params, mesh, model_axis=model_axis, min_weight_size=min_weight_size
    )
    shape = mesh_shape(mesh)
    axis_size = shape.get(model_axis, 1)
    dp_size = shape.get(data_axis, 1)
    if axis_size > 1 and num_heads % axis_size == 0:
        heads_entry = model_axis
    else:
        if axis_size > 1:
            import logging

            logging.getLogger(__name__).warning(
                "KV pool NOT sharded over (%r, %r): num_heads=%d is not "
                "divisible by mesh axis %r size %d — every device will "
                "hold the full head dim (no per-device memory win). Pick "
                "a head count divisible by the model-axis size.",
                data_axis, model_axis, num_heads, model_axis, axis_size,
            )
        heads_entry = None
    num_pages = pool_shape[1]
    if dp_size > 1 and seq_shard and num_pages % dp_size == 0:
        pages_entry = data_axis
    else:
        if dp_size > 1 and seq_shard:
            import logging

            logging.getLogger(__name__).warning(
                "KV pool NOT sharded over (%r, %r): num_pages=%d is not "
                "divisible by mesh axis %r size %d — every device will "
                "hold the full page dim (no long-context capacity win). "
                "Pick a pool size divisible by the data-axis size.",
                data_axis, model_axis, num_pages, data_axis, dp_size,
            )
        pages_entry = None
    # a 1-D model mesh yields the exact historical
    # P(None, None, None, model) spelling
    pool_spec = P(None, pages_entry, None, heads_entry)
    make_pool = jax.jit(
        lambda: jnp.zeros(pool_shape, dtype),
        out_shardings=NamedSharding(mesh, pool_spec),
    )
    return params, make_pool(), make_pool()


def sharding_tree(specs: Any, mesh):
    import jax
    from jax.sharding import NamedSharding

    return jax.tree.map(lambda spec: NamedSharding(mesh, spec), specs,
                        is_leaf=lambda x: hasattr(x, "index_sizes") or type(x).__name__ == "PartitionSpec")
