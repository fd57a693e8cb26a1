"""Mesh construction, activation and introspection.

* ``make_mesh`` builds every mesh with ``Auto`` axes.  ``jax.make_mesh``
  defaults to ``Explicit`` axes, and ``with_sharding_constraint`` rejects
  a spec that names an Explicit axis, so a mesh from the bare call breaks
  every constraint in :mod:`repro.parallel.sharding`.
* ``use_mesh`` activates a mesh with ``jax.set_mesh``.
* ``current_mesh`` reads it back through ``jax.sharding.get_abstract_mesh``.
"""

from __future__ import annotations

import contextlib
from typing import Sequence

import jax
from jax.sharding import AxisType

_GET_ABSTRACT_MESH = getattr(jax.sharding, "get_abstract_mesh", None)
_SET_MESH = getattr(jax, "set_mesh", None)

INTROSPECTION_BRANCH = ("get_abstract_mesh" if _GET_ABSTRACT_MESH is not None
                        else None)
ACTIVATION_BRANCH = "set_mesh" if _SET_MESH is not None else None


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """A mesh of ``shape`` over ``axes`` whose axes are all ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def current_mesh():
    """The active (abstract) mesh, or None when no mesh is active."""
    mesh = _GET_ABSTRACT_MESH()
    if mesh is None or mesh.empty:
        return None
    return mesh


@contextlib.contextmanager
def use_mesh(mesh):
    """Activate ``mesh`` for the block."""
    with _SET_MESH(mesh):
        yield mesh


def sharding_constraint(x, sharding):
    """Single entry point for with_sharding_constraint."""
    return jax.lax.with_sharding_constraint(x, sharding)


def mesh_axis_sizes(mesh) -> dict:
    """{axis name: size} for a concrete or abstract mesh (``.shape`` is
    the one accessor both expose; ``.devices`` is concrete-only)."""
    return dict(mesh.shape)
