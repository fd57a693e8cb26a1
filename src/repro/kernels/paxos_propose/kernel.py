"""Pallas TPU kernel: batched proposer/issuer step (the other hot half).

`kernels/paxos_apply` tiles the receiver select network; this kernel tiles
the issuer one (:func:`repro.core.proposer_vector.proposer_core` — tally
folds, quorum arbitration, decision cascade, emission muxes).  The lane
layout is fixed by the serve path: one session per lane, at most one
steered reply per lane per step, so the step is data-parallel across
sessions exactly like the receiver step is across keys.

Lanes live in HBM as struct-of-arrays ``(rows, 128)`` int32 planes; each
grid step streams a ``(block_rows, 128)`` tile of every plane into VMEM and
runs the branch-free select network on the VPU (entirely element-wise — no
MXU work).  The quorum parameters (``n_machines`` / ``majority`` /
``commit_need`` / ``log_too_high_threshold``) arrive as four *input planes*
rather than static arguments: the fused cluster engine stacks many
machines' lanes into one call, and each machine's active view pins its own
quorum sizes (§8.7 view-sized tallies), so they are data, not shape.

The kernel body *is* the oracle (``proposer_core``) applied to VMEM tiles:
the select network is identical by construction, and the tests verify
kernel-vs-oracle over shape sweeps in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.proposer_vector import (
    ActionBatch, IssuerReplyBatch, ProposerTable, proposer_core,
)
from repro.runtime import kernel_interpret

N_TAB = len(ProposerTable._fields)       # 65 session-state planes
N_REP = len(IssuerReplyBatch._fields)    # 13 steered-reply planes
N_ACT = len(ActionBatch._fields)         # 14 decision/emission planes
N_PAR = 4                                # per-lane quorum parameter planes

LANE = 128                               # TPU lane width (minor dim)


def _paxos_propose_kernel(*refs):
    """refs = tab[65], rep[13], par[4], out_tab[65], out_act[14]."""
    tab_refs = refs[:N_TAB]
    rep_refs = refs[N_TAB:N_TAB + N_REP]
    par_refs = refs[N_TAB + N_REP:N_TAB + N_REP + N_PAR]
    out = refs[N_TAB + N_REP + N_PAR:]
    out_tab_refs = out[:N_TAB]
    out_act_refs = out[N_TAB:N_TAB + N_ACT]

    t = ProposerTable(*[r[...] for r in tab_refs])
    rep = IssuerReplyBatch(*[r[...] for r in rep_refs])
    n_machines, majority, commit_need, lth = (r[...] for r in par_refs)

    new_t, actions = proposer_core(t, rep, n_machines, majority,
                                   commit_need, lth)

    for r, v in zip(out_tab_refs, new_t):
        r[...] = v
    for r, v in zip(out_act_refs, actions):
        r[...] = v


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret"))
def paxos_propose(t: ProposerTable, rep: IssuerReplyBatch,
                  params: jnp.ndarray, *, block_rows: int = 32,
                  interpret: Optional[bool] = None):
    """One issuer step over session lanes on TPU via Pallas.

    All lane arrays must be 1-D of one equal length; ``params`` is the
    ``(4, n)`` int32 per-lane quorum-parameter stack.  The wrapper in
    ``ops.py`` handles padding to a multiple of ``block_rows * 128`` and
    un-padding (padded lanes carry ``rep.kind = -1`` — idle — so they
    neither fold nor decide).  A TPU tile is ``(8k, 128)``, so
    ``block_rows`` must be a multiple of 8 unless one block covers every
    row.  ``interpret=None`` runs compiled on a TPU and interpreted
    elsewhere (:func:`repro.runtime.kernel_interpret`).
    """
    if interpret is None:
        interpret = kernel_interpret()
    n = t.phase.shape[0]
    if n % (block_rows * LANE) != 0:
        raise ValueError(
            f"paxos_propose: lane count {n} is not a multiple of "
            f"block_rows * LANE = {block_rows} * {LANE} = "
            f"{block_rows * LANE}. Padding contract: every ProposerTable/"
            f"IssuerReplyBatch plane must be 1-D, all of one equal length, "
            f"padded with idle reply lanes (kind=-1) up to a tile multiple "
            f"— use repro.kernels.paxos_propose.ops.issuer_step, which "
            f"owns the padding/un-padding.")
    if params.shape != (N_PAR, n):
        raise ValueError(
            f"paxos_propose: params must be shape ({N_PAR}, {n}) — one "
            f"int32 lane-plane each for n_machines, majority, commit_need "
            f"and log_too_high_threshold — got {params.shape}.")
    rows = n // LANE
    grid = (rows // block_rows,)

    def plane(a):
        return a.reshape(rows, LANE)

    inputs = ([plane(a) for a in t] + [plane(a) for a in rep]
              + [plane(params[i]) for i in range(N_PAR)])

    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    out_shapes = ([jax.ShapeDtypeStruct((rows, LANE), jnp.int32)]
                  * (N_TAB + N_ACT))

    outs = pl.pallas_call(
        _paxos_propose_kernel,
        grid=grid,
        in_specs=[spec] * len(inputs),
        out_specs=[spec] * len(out_shapes),
        out_shape=out_shapes,
        interpret=interpret,
    )(*inputs)

    new_t = ProposerTable(*[o.reshape(n) for o in outs[:N_TAB]])
    actions = ActionBatch(*[o.reshape(n)
                            for o in outs[N_TAB:N_TAB + N_ACT]])
    return new_t, actions
