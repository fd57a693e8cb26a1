"""Jitted public wrapper for the paxos_apply kernel.

Handles lane padding, the per-session registered-rmw-id gather/scatter (the
only non-lane-parallel piece of the receiver step), and exposes a full
"replica step": table' , replies, registry' = step(table, batch, registry).

Padding contract (validated here, *before* trace, and enforced again with a
``ValueError`` inside :func:`repro.kernels.paxos_apply.kernel.paxos_apply`):

* every ``KVTable`` and ``MsgBatch`` plane is 1-D with one shared lane
  count ``n`` (slot ``i`` targets key ``i`` — conflict-free batches, see
  :mod:`repro.core.vector`);
* ``replica_step`` pads all planes with zeros up to a multiple of
  ``block_rows * 128``; padded message lanes are ``kind = NOOP`` by
  construction, so they neither mutate state nor emit replies, and are
  sliced off again before returning;
* with ``shard_lanes`` set, the lane axis is treated as shard-aligned
  segments of that length and each segment pads *independently* to the
  block tile — compiled blocks then never straddle a shard boundary, so
  a shard-partitioned plane stack keeps every block device-local.  The
  step stays elementwise either way, so segmented padding is
  bit-identical to whole-axis padding (pinned by the sharded replay
  gates);
* ``registered`` is the 1-D per-global-session committed-counter table;
  commit-lane registrations scatter into it *after* the batch.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.vector import KVTable, MsgBatch, apply_batch
from .kernel import LANE, paxos_apply


def _pad(a: jnp.ndarray, n_to: int) -> jnp.ndarray:
    return jnp.pad(a, (0, n_to - a.shape[0]))


def pad_segments(a: jnp.ndarray, seg: int, seg_pad: int,
                 fill: int = 0) -> jnp.ndarray:
    """Pad each length-``seg`` lane segment independently to ``seg_pad``.

    ``a.shape[0]`` must be a multiple of ``seg``; with one segment this is
    exactly whole-axis padding.  Shared with ``paxos_propose.ops`` — both
    fused engines use it to keep kernel blocks shard-local.
    """
    n_seg = a.shape[0] // seg
    return jnp.pad(a.reshape(n_seg, seg), ((0, 0), (0, seg_pad - seg)),
                   constant_values=fill).reshape(n_seg * seg_pad)


def unpad_segments(a: jnp.ndarray, seg: int, seg_pad: int) -> jnp.ndarray:
    """Inverse of :func:`pad_segments` (drop per-segment padding)."""
    n_seg = a.shape[0] // seg_pad
    return a.reshape(n_seg, seg_pad)[:, :seg].reshape(n_seg * seg)


def gather_is_registered(registered: jnp.ndarray,
                         msg: MsgBatch) -> jnp.ndarray:
    """registered[gsess] >= counter, guarding gsess < 0 (fresh lanes)."""
    sess = jnp.clip(msg.rmw_sess, 0, registered.shape[0] - 1)
    got = registered[sess]
    return (msg.rmw_sess >= 0) & (got >= msg.rmw_cnt)


def scatter_register(registered: jnp.ndarray, msg: MsgBatch,
                     mask: jnp.ndarray) -> jnp.ndarray:
    """Segment-max registration of committed rmw-ids (§3.1.1).

    Masked-out lanes must not alias any live global session: they are
    routed to the one-past-the-end *dead slot* and discarded by the
    out-of-bounds scatter (``mode="drop"``).  Routing them to session 0
    with a sentinel counter would silently rely on live counters never
    being smaller than the sentinel.
    """
    dead = registered.shape[0]
    sess = jnp.where(mask, msg.rmw_sess, dead)
    return registered.at[sess].max(msg.rmw_cnt, mode="drop")


def validate_batch(kv: KVTable, msg: MsgBatch, registered: jnp.ndarray,
                   block_rows: int,
                   shard_lanes: Optional[int] = None) -> None:
    """Enforce the padding contract before any trace/compile happens."""
    if block_rows < 1:
        raise ValueError(f"block_rows must be >= 1, got {block_rows}")
    n = kv.state.shape[0]
    if shard_lanes is not None and (shard_lanes < 1 or n % shard_lanes):
        raise ValueError(
            f"replica_step: shard_lanes={shard_lanes} does not divide the "
            f"lane axis ({n}) into aligned shard segments")
    for name, plane in list(zip(KVTable._fields, kv)) \
            + list(zip(MsgBatch._fields, msg)):
        shape = jnp.shape(plane)
        if len(shape) != 1 or shape[0] != n:
            raise ValueError(
                f"replica_step: plane {name!r} has shape {shape}; the "
                f"padding contract requires 1-D planes of one shared lane "
                f"count (here {n}), one lane per key, at most one non-NOOP "
                f"message per key.")
    if len(jnp.shape(registered)) != 1:
        raise ValueError(
            f"replica_step: registered table must be 1-D (one committed "
            f"counter per global session), got shape "
            f"{jnp.shape(registered)}")


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret",
                                             "use_kernel", "shard_lanes"))
def _replica_step(kv: KVTable, msg: MsgBatch, registered: jnp.ndarray,
                  *, block_rows: int, interpret: Optional[bool],
                  use_kernel: bool,
                  shard_lanes: Optional[int] = None):
    n = kv.state.shape[0]
    tile = block_rows * LANE
    # shard-aligned segment padding: with shard_lanes unset there is one
    # segment and this is exactly the old whole-axis padding
    seg = shard_lanes if shard_lanes else n
    seg_pad = ((seg + tile - 1) // tile) * tile

    is_reg = gather_is_registered(registered, msg)
    if use_kernel:
        kv_p = KVTable(*[pad_segments(a, seg, seg_pad) for a in kv])
        # padded lanes become NOOP automatically (kind=0)
        msg_p = MsgBatch(*[pad_segments(a, seg, seg_pad) for a in msg])
        new_kv, replies, reg_mask = paxos_apply(
            kv_p, msg_p, pad_segments(is_reg.astype(jnp.int32), seg, seg_pad),
            block_rows=block_rows, interpret=interpret)
        new_kv = KVTable(*[unpad_segments(a, seg, seg_pad) for a in new_kv])
        replies = type(replies)(
            *[unpad_segments(a, seg, seg_pad) for a in replies])
        reg_mask = unpad_segments(reg_mask, seg, seg_pad) != 0
    else:
        new_kv, replies, reg_mask = apply_batch(kv, msg, is_reg)

    new_registered = scatter_register(registered, msg, reg_mask)
    return new_kv, replies, new_registered


def replica_step(kv: KVTable, msg: MsgBatch, registered: jnp.ndarray,
                 *, block_rows: int = 32, interpret: Optional[bool] = None,
                 use_kernel: bool = True,
                 shard_lanes: Optional[int] = None):
    """One receiver step of a replica over a conflict-free message batch.

    ``registered`` is the bounded per-global-session table of committed
    rmw-id counters.  ``shard_lanes`` (optional) declares the lane axis to
    be shard-aligned segments of that length, padded per segment so kernel
    blocks stay shard-local.  Returns (new_table, replies, new_registered).
    """
    validate_batch(kv, msg, registered, block_rows, shard_lanes)
    return _replica_step(kv, msg, registered, block_rows=block_rows,
                         interpret=interpret, use_kernel=use_kernel,
                         shard_lanes=shard_lanes)
