"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips across DCN.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before any jax import; smoke
tests and benches must keep seeing 1 device).
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "dp_axes", "MESH_AXES"]

MESH_AXES = {"single": ("data", "model"), "multi": ("pod", "data", "model")}


def make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (GSPMD-propagated shardings), the
    axis type all of the repo's sharding rules assume."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    """Axes that carry the batch (pod composes with data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)

