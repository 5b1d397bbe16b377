"""Mesh and shard_map construction, in one place.

The repo pins jax 0.9.0 (``pyproject.toml``). Mesh and shard_map
construction goes through this module so that call sites share one
spelling of axis types and of the manual-axes argument.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["AxisType", "axis_size", "make_mesh", "mesh_from_devices",
           "shard_map", "single_device_mesh"]


def make_mesh(
    axis_shapes: Sequence[int], axis_names: Sequence[str], *,
    axis_types: Optional[Sequence[Any]] = None, devices=None,
) -> Mesh:
    """``jax.make_mesh``; ``axis_types`` defaults to jax's own."""
    kw = {} if axis_types is None else {"axis_types": tuple(axis_types)}
    return jax.make_mesh(axis_shapes, axis_names, devices=devices, **kw)


def mesh_from_devices(
    device_array, axis_names: Sequence[str], *,
    axis_types: Optional[Sequence[Any]] = None,
) -> Mesh:
    """``Mesh(devices, names)``; ``axis_types`` defaults to jax's own."""
    kw = {} if axis_types is None else {"axis_types": tuple(axis_types)}
    return Mesh(device_array, tuple(axis_names), **kw)


def single_device_mesh(device=None,
                       axis_names: Sequence[str] = ("data", "model"),
                       *, axis_types: Optional[Sequence[Any]] = None,
                       ) -> Mesh:
    """A (1, ..., 1) mesh pinned to one device (default: devices()[0]).

    The HDP trainer anchors its model mesh here so tables, state and the
    key schedule are built on the same single device at every lane
    count — the lane sweeps (core/streaming.py) place work per-device
    themselves and never widen this mesh.
    """
    if device is None:
        device = jax.devices()[0]
    arr = np.asarray([device]).reshape((1,) * len(axis_names))
    return mesh_from_devices(arr, tuple(axis_names), axis_types=axis_types)


def axis_size(axis_name) -> Any:
    """Size of a mapped mesh axis from inside a shard_map region."""
    return jax.lax.axis_size(axis_name)


def shard_map(
    f, *, mesh: Mesh, in_specs, out_specs,
    axis_names: Optional[frozenset] = None, check_vma: bool = False,
):
    """``jax.shard_map``; ``axis_names`` is the set of mesh axes that are
    manual inside ``f`` (all axes when None)."""
    kw: dict[str, Any] = {"check_vma": check_vma}
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)
