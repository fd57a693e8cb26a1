"""The receiver step over lanes: the paxos_apply kernel or its jnp oracle.

:func:`apply_lanes` is the one flat-lane receiver step: segment padding,
then :func:`~.kernel.paxos_apply` or :func:`repro.core.vector.apply_batch`
(``use_kernel``), then unpadding.  Two entries call it:

* :func:`replica_step` — one replica, with the per-session registered-rmw-id
  gather/scatter (the only non-lane-parallel piece of the receiver step)
  on the device: table', replies, registry' = step(table, batch, registry);
* :func:`stacked_replica_step` — every replica of a cluster at once on
  ``(F, M, K)`` stacks, the registry gathered on the host and packed with
  the message planes.  The serve engine's fused receiver step and the
  fused differential replay both trace it.

Padding contract (validated here, *before* trace, and enforced again with a
``ValueError`` inside :func:`repro.kernels.paxos_apply.kernel.paxos_apply`):

* every ``KVTable`` and ``MsgBatch`` plane is 1-D with one shared lane
  count ``n`` (slot ``i`` targets key ``i`` — conflict-free batches, see
  :mod:`repro.core.vector`);
* the kernel path pads all planes with zeros up to a multiple of
  ``block_rows * 128``; padded message lanes are ``kind = NOOP`` by
  construction, so they neither mutate state nor emit replies, and are
  sliced off again before returning;
* with ``shard_lanes`` set, the lane axis is treated as shard-aligned
  segments of that length and each segment pads *independently* to the
  block tile — compiled blocks then never straddle a shard boundary, so
  a shard-partitioned plane stack keeps every block device-local.  The
  step stays elementwise either way, so segmented padding is
  bit-identical to whole-axis padding (pinned by the fused replay's
  sharded cases);
* ``registered`` is the 1-D per-global-session committed-counter table;
  commit-lane registrations scatter into it *after* the batch.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.vector import NOOP_LANE, KVTable, MsgBatch, apply_batch
from .kernel import LANE, N_KV, N_MSG, paxos_apply

# The stacked step's packed message operand: the 11 message planes and the
# host-gathered is_registered bit as a 12th, so one transfer stages a wave.
# An unstaged lane holds this column: a NOOP, not registered.
N_MSGREG = N_MSG + 1
NOOP_COLUMN = np.array(NOOP_LANE + (0,), np.int32)


def segment_layout(n: int, block_rows: int,
                   shard_lanes: Optional[int]) -> tuple:
    """``(seg, seg_pad)``: a length-``n`` lane axis is segments of ``seg``
    lanes (``shard_lanes``, or one whole-axis segment), each padded to
    ``seg_pad``, the next multiple of the kernel tile.  Shared with
    ``paxos_propose.ops``."""
    tile = block_rows * LANE
    seg = shard_lanes if shard_lanes else n
    return seg, ((seg + tile - 1) // tile) * tile


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


def apply_lanes(kv: KVTable, msg: MsgBatch, is_reg: jnp.ndarray, *,
                use_kernel: bool, block_rows: int,
                shard_lanes: Optional[int], interpret: Optional[bool]):
    """The receiver step over 1-D lanes: ``(new_kv, replies, reg_mask)``,
    through the Pallas kernel or the jnp oracle.  The kernel path pads each
    lane segment to the block tile (padded message lanes are NOOPs, kind
    0) and drops the padding again; both paths give the same planes."""
    if use_kernel:
        seg, seg_pad = segment_layout(kv.state.shape[0], block_rows,
                                      shard_lanes)
        pad = functools.partial(pad_segments, seg=seg, seg_pad=seg_pad)
        unpad = functools.partial(unpad_segments, seg=seg, seg_pad=seg_pad)
        new_kv, replies, reg_mask = paxos_apply(
            KVTable(*map(pad, kv)), MsgBatch(*map(pad, msg)),
            pad(is_reg.astype(jnp.int32)),
            block_rows=block_rows, interpret=interpret)
        return (KVTable(*map(unpad, new_kv)),
                type(replies)(*map(unpad, replies)), unpad(reg_mask) != 0)
    return apply_batch(kv, msg, is_reg)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret",
                                             "use_kernel", "shard_lanes"))
def _replica_step(kv: KVTable, msg: MsgBatch, registered: jnp.ndarray,
                  *, block_rows: int, interpret: Optional[bool],
                  use_kernel: bool,
                  shard_lanes: Optional[int] = None):
    new_kv, replies, reg_mask = apply_lanes(
        kv, msg, gather_is_registered(registered, msg),
        use_kernel=use_kernel, block_rows=block_rows,
        shard_lanes=shard_lanes, interpret=interpret)
    return new_kv, replies, scatter_register(registered, msg, reg_mask)


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


def stacked_replica_step(kv_stack, msgreg_stack, *, use_kernel, block_rows,
                         shard_lanes=None, interpret=None):
    """One receiver step for every replica: ``(18, M, K)`` KV stack and
    ``(12, M, K)`` packed message operand (:data:`N_MSGREG`) ->
    ``(18, M, K)``, ``(11, M, K)`` replies and the ``(M, K)`` registration
    mask.  The machine axis folds into the lane axis: the step is
    elementwise, so rows stay isolated by construction.  ``shard_lanes``
    declares each row as blocks of that many lanes, so the flat axis is
    ``M·n_shards`` segments, each padded on its own (kernel blocks never
    straddle a shard boundary).

    Not jitted: its callers trace it inline, so it lowers into their own
    program."""
    msg_stack = msgreg_stack[:N_MSG]
    is_reg = msgreg_stack[N_MSG]
    m, k = is_reg.shape
    n = m * k
    kv = KVTable(*[kv_stack[i].reshape(n) for i in range(N_KV)])
    msg = MsgBatch(*[msg_stack[i].reshape(n) for i in range(N_MSG)])
    new_kv, replies, mask = apply_lanes(
        kv, msg, is_reg.reshape(n) != 0, use_kernel=use_kernel,
        block_rows=block_rows, shard_lanes=shard_lanes, interpret=interpret)
    return (jnp.stack([a.reshape(m, k) for a in new_kv]),
            jnp.stack([a.reshape(m, k) for a in replies]),
            mask.reshape(m, k))
