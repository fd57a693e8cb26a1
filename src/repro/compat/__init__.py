"""JAX version-compatibility layer — the ONLY place version-sensitive
JAX API usage is allowed.

The repo targets the JAX 0.9 series.  Its version-sensitive surfaces:

* mesh construction: ``jax.make_mesh`` defaults to ``Explicit`` axes,
  which ``with_sharding_constraint`` rejects; :func:`make_mesh` builds
  ``Auto`` axes,
* mesh activation and introspection: ``jax.set_mesh`` and
  ``jax.sharding.get_abstract_mesh``; see :mod:`repro.compat.meshes`,
* AOT cost analysis, whose return shape changed across releases; see
  :mod:`repro.compat.aot`.

Pallas kernels index refs directly (``ref[...]``, ``ref[0, pl.ds(i, n)]``),
the one spelling the installed JAX supports.

Everything outside this package imports the stable names below; the
pinned-API canary in ``tests/test_compat.py`` fails in one obvious place
when a JAX bump shifts the surface again.
"""

from repro.compat.aot import flatten_cost_analysis
from repro.compat.meshes import (
    current_mesh,
    make_mesh,
    sharding_constraint,
    use_mesh,
)
from repro.compat.version import (
    JAX_VERSION,
    SUPPORTED_MAX,
    SUPPORTED_MIN,
    api_report,
    check_pinned_api,
    supported,
)

__all__ = [
    "JAX_VERSION",
    "SUPPORTED_MAX",
    "SUPPORTED_MIN",
    "api_report",
    "check_pinned_api",
    "current_mesh",
    "flatten_cost_analysis",
    "make_mesh",
    "sharding_constraint",
    "supported",
    "use_mesh",
]
