"""Device-mesh construction from a ResourceSpec.

Replaces the reference's device resolver + ClusterSpec
(``/root/reference/autodist/kernel/device/resolver.py:26-67``,
``cluster.py:70-82``): AutoDist device strings resolved into a
``jax.sharding.Mesh`` instead of TF ``DeviceSpecV2`` job/task strings. On real
TPU slices the mesh uses ``mesh_utils.create_device_mesh`` so logical axes map
onto physical ICI rings; on the host platform (tests) it is a plain reshape.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh

from autodist_tpu import const
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.utils import logging

DEFAULT_AXES = (const.MESH_AXIS_DATA, const.MESH_AXIS_MODEL)


def build_mesh(
    resource_spec: Optional[ResourceSpec] = None,
    axes: Sequence[str] = DEFAULT_AXES,
    devices=None,
    slice_of=None,
) -> Mesh:
    """Build the logical mesh the strategy lowers onto.

    The axis sizes come from the resource spec (``mesh:`` override or
    all-chips-on-data default); the concrete devices come from the local JAX
    runtime. The spec's chip count must match the visible device count —
    the analog of the reference's cluster_spec/worker agreement.

    ``slice_of`` maps a device to its slice/ICI-domain id (None = single
    domain). Defaults to the runtime's ``slice_index`` attribute; tests and
    the driver dryrun inject a fake assignment to exercise the multi-slice
    hybrid layout on the host-platform mesh.
    """
    if devices is None:
        devices = jax.devices()
    injected_slices = slice_of is not None
    if slice_of is None:
        slice_of = lambda d: getattr(d, "slice_index", None)  # noqa: E731
    if resource_spec is None:
        shape: Dict[str, int] = {ax: 1 for ax in axes}
        shape[list(axes)[0]] = len(devices)
    else:
        shape = resource_spec.mesh_shape(tuple(axes))
    n = math.prod(shape.values())
    if n != len(devices):
        raise ValueError(
            f"mesh shape {shape} needs {n} devices but the runtime has "
            f"{len(devices)} — resource spec and runtime disagree"
        )
    axis_names = tuple(shape.keys())
    dims = [shape[ax] for ax in axis_names]

    slice_ids = {slice_of(d) for d in devices}
    slice_ids.discard(None)
    n_slices = max(len(slice_ids), 1)
    if n_slices > 1:
        # The DCN-crossing axis is the DATA axis *by role*, not positionally:
        # a mesh override may list axes in any order. Resolved only when
        # multi-slice placement needs it — a role-only mesh (no batch-capable
        # axis) must still build on a single slice.
        data_ix = None
        try:
            data_ix = axis_names.index(_data_axis_name(axis_names, shape))
        except ValueError:
            logging.warning(
                "multi-slice runtime (%d slices) but the mesh has no "
                "data-capable axis — collectives may cross DCN", n_slices,
            )
        if data_ix is not None and dims[data_ix] % n_slices == 0:
            # Multi-slice pod: only the DATA axis crosses DCN — its
            # gradient all-reduce tolerates the slower hops via
            # hierarchical reduce-scatter — while model/seq/expert
            # axes stay inside a slice so their per-layer collectives
            # ride ICI (the scaling-book layout; the reference's analog
            # was `network_bandwidth` steering PS placement).
            try:
                return Mesh(
                    _hybrid_arrangement(
                        devices, dims, data_ix, n_slices, slice_of,
                        honor_slice_of=injected_slices,
                    ),
                    axis_names,
                )
            except Exception as e:  # noqa: BLE001 - ICI-aware path still next
                logging.warning(
                    "hybrid mesh arrangement failed (%s); falling back to "
                    "create_device_mesh", e,
                )
        elif data_ix is not None:
            logging.warning(
                "multi-slice runtime (%d slices) but data axis %d does "
                "not divide by the slice count — model-axis collectives "
                "may cross DCN", n_slices, dims[data_ix],
            )
    if devices and devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        # A failure here raises: device order on a TPU decides which
        # collectives ride ICI neighbours, so a silent reshape in id order
        # would be a different (slower) mesh than the one asked for.
        return Mesh(mesh_utils.create_device_mesh(dims, devices=devices),
                    axis_names)
    return Mesh(np.asarray(devices).reshape(dims), axis_names)


def _hybrid_arrangement(devices, dims, data_ix: int, n_slices: int, slice_of,
                        honor_slice_of: bool = False):
    """Device array for a multi-slice mesh: DCN-major along the data axis.

    The data axis splits into ``n_slices`` contiguous DCN blocks, each filled
    by exactly one slice's devices, so fixing a data coordinate pins a slice
    (model/seq/expert fibers never leave their ICI domain) and the gradient
    all-reduce decomposes into in-slice reduce-scatter + cross-slice
    exchange + in-slice all-gather (XLA does this given the layout). On TPU
    with the runtime's own slice notion the arrangement delegates to
    ``mesh_utils.create_hybrid_device_mesh`` (physical-topology-aware within
    each slice); with a caller-injected ``slice_of`` (``honor_slice_of``) or
    off-TPU, each slice block is ordered by a plain reshape — the injected
    assignment is the contract, so it must not be silently re-derived from
    hardware attributes that may disagree.
    """
    groups: Dict[object, list] = {}
    for d in devices:
        groups.setdefault(slice_of(d), []).append(d)
    sizes = {len(g) for g in groups.values()}
    if len(sizes) != 1:
        raise ValueError(
            f"uneven slices: {sorted((k, len(v)) for k, v in groups.items())}"
        )
    if devices[0].platform == "tpu" and not honor_slice_of:
        from jax.experimental import mesh_utils

        dcn = [1] * len(dims)
        dcn[data_ix] = n_slices
        ici = list(dims)
        ici[data_ix] = dims[data_ix] // n_slices
        return mesh_utils.create_hybrid_device_mesh(ici, dcn, devices=devices)
    per_slice = list(dims)
    per_slice[data_ix] //= n_slices
    blocks = [
        np.asarray(groups[sid]).reshape(per_slice) for sid in sorted(groups)
    ]
    return np.concatenate(blocks, axis=data_ix)


def _data_axis_name(names: Sequence[str], sizes: Dict[str, int]) -> str:
    """Resolve which axis carries the batch (shared by :func:`data_axis`
    and :func:`build_mesh`'s DCN-placement logic).

    ``data`` when present with degree > 1. When a mesh override uses a
    custom axis name (e.g. ``{"x": 8}``), ``mesh_shape`` still setdefaults a
    size-1 ``data`` axis — there, the batch axis is the custom-named axis
    (degree > 1, not a known model/seq/expert/pipe role), not the vestigial
    ``data``. Known non-data roles are never picked even when ``data`` has
    degree 1: ``{"model": 8}`` means the user asked for pure model
    parallelism with a replicated batch.
    """
    non_data_roles = set(const.ALL_MESH_AXES) - {const.MESH_AXIS_DATA}
    if const.MESH_AXIS_DATA not in names:
        for ax in names:
            if ax not in non_data_roles:
                return ax
        # Every axis is a known non-data role (e.g. axes=("model",)):
        # putting the batch on any of them would silently corrupt training
        # (each model shard would see different examples). Pure model
        # parallelism is spelled with a size-1 data axis — the default
        # mesh_axes includes one automatically.
        raise ValueError(
            f"mesh axes {tuple(names)} contain no axis that can carry the "
            f"batch; include '{const.MESH_AXIS_DATA}' (size 1 for pure "
            f"model parallelism) in mesh_axes"
        )
    if sizes[const.MESH_AXIS_DATA] > 1:
        return const.MESH_AXIS_DATA
    for ax in names:
        if ax not in non_data_roles and sizes[ax] > 1:
            return ax
    return const.MESH_AXIS_DATA


def data_axis(mesh: Mesh) -> str:
    """The batch axis name (see :func:`_data_axis_name`)."""
    return _data_axis_name(
        mesh.axis_names, dict(zip(mesh.axis_names, mesh.devices.shape))
    )


def data_sharding(mesh: Mesh, rank: int, dim: int = 0):
    """NamedSharding for a rank-``rank`` array batch-sharded on ``dim``.

    The generic "this dimension is per-example/per-slot work" placement:
    training batches use dim 0 (``ShardingPlan.batch_shardings``), the
    serving engine's KV-cache pools use dim 1 (``[layers, slots, ...]``).
    Replicates when the data axis is trivial — a size-1 axis in the spec
    would be legal but noisier to read in sharding dumps.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    ax = data_axis(mesh)
    if dict(zip(mesh.axis_names, mesh.devices.shape))[ax] <= 1:
        return NamedSharding(mesh, PartitionSpec())
    spec = [None] * rank
    spec[dim] = ax
    return NamedSharding(mesh, PartitionSpec(*spec))
