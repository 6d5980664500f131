"""Production mesh construction (kept as functions — importing this module
never touches jax device state)."""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes. The sharding rules constrain
    activations with bare ``PartitionSpec``s, which JAX accepts only on
    Auto axes (``jax.make_mesh`` defaults to Explicit ones)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2×16×16 = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1×1 mesh for CPU-scale smoke runs through the same code paths."""
    return make_mesh((1, 1), ("data", "model"))


def mesh_axes(mesh) -> dict:
    """Convenience: axis-role names present in ``mesh``."""
    names = mesh.axis_names
    dp_axes = tuple(n for n in names if n in ("pod", "data"))
    return {"dp_axes": dp_axes, "tp_axis": "model",
            "dp_total": math.prod(mesh.shape[n] for n in dp_axes),
            "tp": mesh.shape.get("model", 1)}
