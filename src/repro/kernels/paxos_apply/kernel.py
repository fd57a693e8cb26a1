"""Pallas TPU kernel: batched Paxos message application (protocol hot path).

TPU adaptation of the paper's many-core scaling (§3): per-key protocol state
machines are independent, so the receiver-side hot loop is data-parallel
across keys.  Lanes live in HBM as struct-of-arrays ``(rows, 128)`` int32
planes; each grid step streams a ``(block_rows, 128)`` tile of every plane
into VMEM, runs the branch-free Table-1 select network on the VPU (the op is
entirely element-wise — no MXU work), and writes back the updated state and
reply planes.

The kernel body *is* the oracle (`repro.core.vector.apply_batch`) applied to
VMEM tiles: the select network is identical by construction, and the tests
still verify kernel-vs-oracle over shape/dtype sweeps in interpret mode.

Arithmetic intensity: ~60 int32 planes r/w per lane for a few hundred VPU
ops — memory-bound by design (the paper's CPU version is equally
state-bound: §8.6 "we are bottlenecked by the CPU and not the network").
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.vector import KVTable, MsgBatch, ReplyBatch, apply_batch
from repro.runtime import kernel_interpret

N_KV = len(KVTable._fields)          # 18 state planes
N_MSG = len(MsgBatch._fields)        # 11 message planes
N_REP = len(ReplyBatch._fields)      # 11 reply planes (kind + opcode + payload)

LANE = 128                           # TPU lane width (minor dim)


def _paxos_apply_kernel(*refs):
    """refs = kv[18], msg[11], is_reg, out_kv[18], out_rep[11], out_mask."""
    kv_refs = refs[:N_KV]
    msg_refs = refs[N_KV:N_KV + N_MSG]
    reg_ref = refs[N_KV + N_MSG]
    out = refs[N_KV + N_MSG + 1:]
    out_kv_refs = out[:N_KV]
    out_rep_refs = out[N_KV:N_KV + N_REP]
    out_mask_ref = out[N_KV + N_REP]

    kv = KVTable(*[r[...] for r in kv_refs])
    msg = MsgBatch(*[r[...] for r in msg_refs])
    is_reg = reg_ref[...] != 0

    new_kv, replies, reg_mask = apply_batch(kv, msg, is_reg)

    for r, v in zip(out_kv_refs, new_kv):
        r[...] = v
    for r, v in zip(out_rep_refs, replies):
        r[...] = v
    out_mask_ref[...] = reg_mask.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret"))
def paxos_apply(kv: KVTable, msg: MsgBatch, is_registered: jnp.ndarray,
                *, block_rows: int = 32, interpret: Optional[bool] = None):
    """Apply a conflict-free message batch on TPU via Pallas.

    All lane arrays must be 1-D of equal length; the wrapper in ``ops.py``
    handles padding to a multiple of ``block_rows * 128`` and un-padding.
    ``interpret=None`` runs compiled on a TPU and interpreted elsewhere
    (:func:`repro.runtime.kernel_interpret`).
    """
    if interpret is None:
        interpret = kernel_interpret()
    n = kv.state.shape[0]
    if n % (block_rows * LANE) != 0:
        raise ValueError(
            f"paxos_apply: lane count {n} is not a multiple of "
            f"block_rows * LANE = {block_rows} * {LANE} = "
            f"{block_rows * LANE}. Padding contract: every KVTable/MsgBatch "
            f"plane must be 1-D, all of one equal length, padded with NOOP "
            f"lanes (kind=0) up to a tile multiple — use "
            f"repro.kernels.paxos_apply.ops.replica_step, which owns the "
            f"padding/un-padding.")
    rows = n // LANE
    grid = (rows // block_rows,)

    def plane(a):
        return a.reshape(rows, LANE)

    inputs = ([plane(a) for a in kv] + [plane(a) for a in msg]
              + [plane(is_registered.astype(jnp.int32))])

    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    out_shapes = ([jax.ShapeDtypeStruct((rows, LANE), jnp.int32)]
                  * (N_KV + N_REP + 1))

    outs = pl.pallas_call(
        _paxos_apply_kernel,
        grid=grid,
        in_specs=[spec] * len(inputs),
        out_specs=[spec] * len(out_shapes),
        out_shape=out_shapes,
        interpret=interpret,
    )(*inputs)

    new_kv = KVTable(*[o.reshape(n) for o in outs[:N_KV]])
    replies = ReplyBatch(*[o.reshape(n)
                           for o in outs[N_KV:N_KV + N_REP]])
    reg_mask = outs[N_KV + N_REP].reshape(n)
    return new_kv, replies, reg_mask
