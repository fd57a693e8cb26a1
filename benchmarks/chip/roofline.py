"""The chip's peaks, and the bytes a fused call moves.

Peaks live in ``peaks.json``, keyed by ``device_kind`` as JAX reports it,
with their source.  A device that is not in the table is an error.

A fused step of the served path is elementwise int32 work over whole
planes, so its floor on the chip is the HBM traffic of its operands and
results: :func:`call_bytes` counts them from the arrays the call receives
and returns (shapes and dtypes only, never contents).  Counted from the
call's own shapes, the roofline reads the same work whichever engine
(jnp or Pallas) runs the step.
"""

from __future__ import annotations

import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def call_bytes(args, out) -> int:
    """Bytes of a call's array operands and results."""
    import jax
    arrays = [a for a in args if hasattr(a, "nbytes")]
    arrays += jax.tree.leaves(out)
    return sum(int(a.nbytes) for a in arrays)


def share(n_calls: int, bytes_per_call: float, device_s: float,
          bytes_per_s: float) -> float:
    """Percent of the HBM roofline: bytes moved over device time, over
    the peak rate."""
    return 100.0 * n_calls * bytes_per_call / device_s / bytes_per_s
