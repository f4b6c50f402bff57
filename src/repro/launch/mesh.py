"""Production mesh construction.

Functions (not module constants) so importing never touches jax device
state.  Single pod = 16x16 = 256 chips (TPU v5e pod); multi-pod = 2 pods =
512 chips with a leading "pod" axis (data parallelism across the
inter-pod DCN/ICI links).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.models.api import MeshAxes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU smoke tests (same axis names as production)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_axes(mesh) -> MeshAxes:
    if "pod" in mesh.axis_names:
        return MeshAxes(batch=("pod", "data"), model="model")
    return MeshAxes(batch=("data",), model="model")


def batch_extent(mesh) -> int:
    """Product of DP axis sizes."""
    import math

    ax = mesh_axes(mesh)
    return math.prod(mesh.shape[a] for a in ax.batch) if ax.batch else 1
