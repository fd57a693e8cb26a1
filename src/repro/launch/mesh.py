"""Production meshes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state.  Single-pod: a
16 x 16 v5e pod (256 chips) as (data, model).  Multi-pod: 2 pods = 512
chips as (pod, data, model); batch shards over (pod, data), params'
tensor dims over model and fsdp dims over data.
"""

from __future__ import annotations

import jax

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a 1-D (data,) mesh (tests)."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))


# TPU v5e hardware constants for the roofline model (per chip).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link (~per-axis share used)
