"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — `pod` is the
outer data-parallel axis (gradient reduction across pods rides the DCI).

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType


def _build_mesh(shape, axes):
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _build_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small ones, e.g. (2, 4))."""
    return _build_mesh(shape, axes)


def make_abstract_mesh(shape, axes):
    """Device-free mesh of the given shape and axis names."""
    return AbstractMesh(tuple(shape), tuple(axes))


def data_axes(mesh) -> tuple:
    """The (possibly hierarchical) batch axes of a mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"
