"""Mesh construction for the production targets.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.  The dry-run driver
(launch/dryrun.py) forces 512 host platform devices *before* importing
anything; everything else (tests, benches) sees the real single CPU
device.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices but only {len(devs)} are "
            f"available — the dry-run must set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            f"importing jax (see launch/dryrun.py)")
    return jax.make_mesh(shape, axes, devices=devs[:need],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def mesh_for_target(target) -> Mesh:
    """Build the mesh a TargetSpec describes (first N devices)."""
    return _mesh(tuple(target.mesh_shape), tuple(target.mesh_axes))


def degraded_mesh(target, *, lost_data_slices: int = 1) -> Mesh:
    """Elastic-scaling mesh: drop `lost_data_slices` rows of the data axis
    (node failure) and rebuild — TP ('model') state needs no resharding."""
    shape = list(target.mesh_shape)
    axes = tuple(target.mesh_axes)
    di = axes.index("data")
    if shape[di] - lost_data_slices < 1:
        raise ValueError("cannot degrade below one data slice")
    shape[di] -= lost_data_slices
    return _mesh(tuple(shape), axes)
