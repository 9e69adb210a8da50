"""Mesh construction for single-pod and multi-pod production runs.

Everything is a *function* (never module-level device state) so importing
this module touches no jax backend — required for the dry-run's
``xla_force_host_platform_device_count`` trick to work.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = [
    "make_mesh",
    "make_production_mesh",
    "mesh_axis_sizes",
    "mesh_topology",
    "DATA_AXES",
    "MODEL_AXIS",
    "POD_AXIS",
]

POD_AXIS = "pod"
DATA_AXIS = "data"
MODEL_AXIS = "model"
DATA_AXES = (POD_AXIS, DATA_AXIS)  # gradient-sync (DP) axes when present


def make_mesh(shape, axes):
    """Mesh over the first prod(shape) devices (Auto axis types).

    Unlike ``jax.make_mesh`` this tolerates a process exposing *more*
    devices than the mesh uses — the dry-run builds the 256-chip
    single-pod mesh inside a 512-virtual-device process.
    """
    shape = tuple(shape)
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"mesh {shape} needs {n} devices, have {len(devs)}"
        )
    return Mesh(
        np.asarray(devs[:n]).reshape(shape),
        tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's production meshes.

    single-pod: 16 x 16 = 256 chips, axes ("data", "model")
    multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model");
    the "pod" axis is the slow (inter-pod DCI) domain — the paper's
    "inter-node network" — while "data"/"model" live on intra-pod ICI.
    """
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel (gradient sync) axes present in this mesh."""
    return tuple(ax for ax in (POD_AXIS, DATA_AXIS) if ax in mesh.axis_names)


def hierarchy_axes(mesh) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """(inter, intra) split of the DP axes for node-aware collectives.

    With a "pod" axis the slow domain is the pod boundary; single-pod
    meshes have no slow domain and the split is ((), ("data",)).
    """
    names = mesh.axis_names
    if POD_AXIS in names:
        return (POD_AXIS,), tuple(
            ax for ax in (DATA_AXIS,) if ax in names
        )
    return (), tuple(ax for ax in (DATA_AXIS,) if ax in names)


def mesh_topology(mesh, *, params=None):
    """The :class:`repro.core.comm.Topology` of a production mesh.

    The mesh→topology entry point of the topology-first collective API:
    the DP hierarchy split comes from :func:`hierarchy_axes` (a "pod"
    axis is the slow domain), the grid shape from the mesh axis sizes,
    and ``params`` optionally overrides the machine constants.  Lazy
    import keeps this module free of jax-backend state at import time.
    """
    from ..core.comm import Topology

    return Topology.from_mesh(mesh, params=params)
