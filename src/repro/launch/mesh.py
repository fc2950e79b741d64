"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state (the dry-run must set
XLA_FLAGS before any jax initialization).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.models.moe import MeshInfo


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment mesh: one v5e pod = (16, 16) = (data, model);
    two pods = (2, 16, 16) = (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (elastic scaling / tests), every axis ``Auto``."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def use_mesh(mesh):
    """Context manager activating ``mesh``."""
    return jax.set_mesh(mesh)


def mesh_info_for(mesh, global_batch: Optional[int] = None) -> MeshInfo:
    """MeshInfo with batch-divisibility-aware data axes.

    If the global batch does not divide across all data axes (long_500k has
    batch 1), fall back to fewer axes or replication — shard_map requires
    even sharding.
    """
    names = mesh.axis_names
    model_axis = "model" if "model" in names else None
    cand = tuple(a for a in ("pod", "data") if a in names)
    if global_batch is not None:
        while cand:
            size = 1
            for a in cand:
                size *= mesh.shape[a]
            if global_batch % size == 0:
                break
            cand = cand[1:]  # drop the pod axis first
    return MeshInfo(mesh=mesh, data_axes=cand, model_axis=model_axis)
