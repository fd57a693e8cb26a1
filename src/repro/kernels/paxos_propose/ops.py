"""The issuer step over session lanes: the paxos_propose kernel or its oracle.

:func:`propose_lanes` is the one issuer step and the only place that pads
for the kernel; ``use_kernel=False`` runs the pure-jnp oracle
(:func:`repro.core.proposer_vector.proposer_core`) on the same planes,
bit-identically.  Two entries call it: :func:`issuer_step` (one replica,
1-D session lanes) and :func:`stacked_issuer_step` (every replica of a
cluster on ``(F, M, S)`` stacks, which the serve engine's fused issuer step
traces).

Padding contract (enforced with a ``ValueError`` inside
:func:`repro.kernels.paxos_propose.kernel.paxos_propose`):

* every ``ProposerTable`` and ``IssuerReplyBatch`` plane has one shared
  shape, ``n`` lanes once flattened (one session per lane, at most one
  steered reply per lane per step — the serve path's fixed layout);
* the kernel path pads all planes with zeros up to a multiple of
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

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.proposer_vector import (
    ActionBatch, IssuerReplyBatch, ProposerTable, proposer_core,
)
from repro.kernels.paxos_apply.ops import (
    pad_segments, segment_layout, unpad_segments,
)
from .kernel import N_PAR, N_REP, N_TAB, paxos_propose


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


def propose_lanes(t: ProposerTable, rep: IssuerReplyBatch,
                  params: jnp.ndarray, *, use_kernel: bool, block_rows: int,
                  shard_lanes: Optional[int], interpret: Optional[bool]):
    """The issuer step over session planes of one shape, 1-D ``(n,)`` or
    stacked ``(M, S)``: ``(new_table, actions)``, through the Pallas kernel
    or the jnp oracle.  ``params`` stacks the four quorum parameters on a
    leading axis, broadcastable to the planes.  The kernel path flattens
    the planes, pads each lane segment to the block tile (padded lanes are
    idle, ``rep.kind = -1``, with quorum parameters 1) and restores the
    shape; the oracle runs on the planes as they are."""
    if use_kernel:
        shape = t.phase.shape
        n = math.prod(shape)
        seg, seg_pad = segment_layout(n, block_rows, shard_lanes)

        def pad(a, fill=0):
            return pad_segments(a.reshape(n), seg, seg_pad, fill=fill)

        def unpad(a):
            return unpad_segments(a, seg, seg_pad).reshape(shape)

        par = jnp.broadcast_to(params, (N_PAR, *shape)).reshape(N_PAR, n)
        new_t, actions = paxos_propose(
            ProposerTable(*map(pad, t)),
            IssuerReplyBatch(pad(rep.kind, fill=-1), *map(pad, rep[1:])),
            jnp.stack([pad(par[i], fill=1) for i in range(N_PAR)]),
            block_rows=block_rows, interpret=interpret)
        return (ProposerTable(*map(unpad, new_t)),
                ActionBatch(*map(unpad, actions)))
    return proposer_core(t, rep, params[0], params[1], params[2], params[3])


_issuer_step = jax.jit(propose_lanes, static_argnames=(
    "block_rows", "interpret", "use_kernel", "shard_lanes"))


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


def stacked_issuer_step(tab_stack, rep_stack, params, *, use_kernel,
                        block_rows, shard_lanes=None, interpret=None):
    """One issuer step for every replica: ``(65, M, S)`` proposer stack,
    ``(13, M, S)`` steered replies and ``(4, M, 1)`` quorum parameters,
    one column per machine (each machine's active view pins its own
    quorum sizes, §8.7) -> ``(65, M, S)``, ``(14, M, S)`` actions.
    ``shard_lanes`` as in
    :func:`repro.kernels.paxos_apply.ops.stacked_replica_step`.

    Not jitted: its callers trace it inline, so it lowers into their own
    program."""
    t = ProposerTable(*[tab_stack[i] for i in range(N_TAB)])
    rep = IssuerReplyBatch(*[rep_stack[i] for i in range(N_REP)])
    new_t, act = propose_lanes(t, rep, params, use_kernel=use_kernel,
                               block_rows=block_rows,
                               shard_lanes=shard_lanes, interpret=interpret)
    return jnp.stack(new_t), jnp.stack(act)
