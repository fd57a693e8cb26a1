"""Jitted public wrapper for the paxos_propose kernel.

Handles lane padding and parameter-plane broadcasting, and exposes the
issuer step with the same ``use_kernel`` switch the receiver step has
(:func:`repro.kernels.paxos_apply.ops.replica_step`): ``use_kernel=False``
runs the pure-jnp oracle (:func:`repro.core.proposer_vector.proposer_core`)
on the same planes, bit-identically.

Padding contract (enforced with a ``ValueError`` inside
:func:`repro.kernels.paxos_propose.kernel.paxos_propose`):

* every ``ProposerTable`` and ``IssuerReplyBatch`` plane is 1-D with one
  shared lane count ``n`` (one session per lane, at most one steered reply
  per lane per step — the serve path's fixed layout);
* ``issuer_step`` pads all planes with zeros up to a multiple of
  ``block_rows * 128``, except ``rep.kind``, which pads with ``-1``:
  padded lanes are *idle*, so they neither fold tallies nor decide, and
  are sliced off again before returning;
* the quorum parameters may be Python ints (one deployment-wide view) or
  per-lane int32 arrays (the fused cluster engine's per-machine views) —
  either way they travel as data planes, never as static shape;
* with ``shard_lanes`` set, the session-lane axis is treated as
  shard-aligned segments of that length padded independently to the block
  tile (same contract as ``paxos_apply.ops.replica_step``), so compiled
  blocks never straddle a shard boundary of a partitioned plane stack.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.proposer_vector import (
    IssuerReplyBatch, ProposerTable, proposer_core,
)
from repro.kernels.paxos_apply.ops import pad_segments, unpad_segments
from .kernel import LANE, N_PAR, paxos_propose


def _pad(a: jnp.ndarray, n_to: int, fill: int = 0) -> jnp.ndarray:
    return jnp.pad(a, (0, n_to - a.shape[0]), constant_values=fill)


def validate_lanes(t: ProposerTable, rep: IssuerReplyBatch,
                   block_rows: int,
                   shard_lanes: Optional[int] = None) -> None:
    """Enforce the lane contract before any trace/compile happens."""
    if block_rows < 1:
        raise ValueError(f"block_rows must be >= 1, got {block_rows}")
    n = t.phase.shape[0]
    if shard_lanes is not None and (shard_lanes < 1 or n % shard_lanes):
        raise ValueError(
            f"issuer_step: shard_lanes={shard_lanes} does not divide the "
            f"lane axis ({n}) into aligned shard segments")
    for name, plane in list(zip(ProposerTable._fields, t)) \
            + list(zip(IssuerReplyBatch._fields, rep)):
        shape = jnp.shape(plane)
        if len(shape) != 1 or shape[0] != n:
            raise ValueError(
                f"issuer_step: plane {name!r} has shape {shape}; the lane "
                f"contract requires 1-D planes of one shared lane count "
                f"(here {n}), one session per lane, at most one steered "
                f"reply per lane.")


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret",
                                             "use_kernel", "shard_lanes"))
def _issuer_step(t: ProposerTable, rep: IssuerReplyBatch,
                 params: jnp.ndarray, *, block_rows: int,
                 interpret: Optional[bool], use_kernel: bool,
                 shard_lanes: Optional[int] = None):
    n = t.phase.shape[0]
    if use_kernel:
        tile = block_rows * LANE
        # one segment without shard_lanes == the old whole-axis padding
        seg = shard_lanes if shard_lanes else n
        seg_pad = ((seg + tile - 1) // tile) * tile
        t_p = ProposerTable(*[pad_segments(a, seg, seg_pad) for a in t])
        # padded lanes are idle (kind = -1): no fold, no decision
        rep_p = IssuerReplyBatch(
            pad_segments(rep.kind, seg, seg_pad, fill=-1),
            *[pad_segments(a, seg, seg_pad) for a in rep[1:]])
        par_p = jnp.stack([pad_segments(params[i], seg, seg_pad, fill=1)
                           for i in range(N_PAR)])
        new_t, actions = paxos_propose(t_p, rep_p, par_p,
                                       block_rows=block_rows,
                                       interpret=interpret)
        new_t = ProposerTable(
            *[unpad_segments(a, seg, seg_pad) for a in new_t])
        actions = type(actions)(
            *[unpad_segments(a, seg, seg_pad) for a in actions])
    else:
        new_t, actions = proposer_core(t, rep, params[0], params[1],
                                       params[2], params[3])
    return new_t, actions


def issuer_step(t: ProposerTable, rep: IssuerReplyBatch, *,
                n_machines, majority, commit_need, log_too_high_threshold,
                block_rows: int = 32, interpret: Optional[bool] = None,
                use_kernel: bool = True, shard_lanes: Optional[int] = None):
    """One issuer step of a replica over steered-reply session lanes.

    The quorum parameters may each be an int or a length-``n`` int32
    array.  ``shard_lanes`` declares shard-aligned lane segments padded
    per segment (kernel blocks stay shard-local).  Returns
    ``(new_table, actions)`` — identical planes to
    :func:`repro.core.proposer_vector.proposer_step`.
    """
    validate_lanes(t, rep, block_rows, shard_lanes)
    n = t.phase.shape[0]
    params = jnp.stack([
        jnp.broadcast_to(jnp.asarray(p, jnp.int32), (n,))
        for p in (n_machines, majority, commit_need,
                  log_too_high_threshold)])
    return _issuer_step(t, rep, params, block_rows=block_rows,
                        interpret=interpret, use_kernel=use_kernel,
                        shard_lanes=shard_lanes)
