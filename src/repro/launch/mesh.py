"""Production mesh definitions.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax call).

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model); the pod axis
is the slow DCN/ICI boundary -- only (compressed) gradients cross it.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto-typed (GSPMD propagation)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Whatever this host has (CPU smoke tests: 1 device)."""
    n = len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))


def mesh_dict(mesh) -> dict:
    return {name: size for name, size in
            zip(mesh.axis_names, mesh.devices.shape)}
