"""Vectorized (SIMD) protocol engine — the TPU-native hot path.

The paper scales Classic Paxos by running thousands of *independent* per-key
state machines across worker threads (§3).  On TPU the analogous resource is
vector lanes, not threads: we recast the receiver-side hot loop — "apply one
message per key to the KV-pair metadata table and emit replies" — as a
branch-free select network over struct-of-arrays state.

This module is the pure-``jnp`` engine.  It is simultaneously

* the reference oracle for the Pallas kernel in
  :mod:`repro.kernels.paxos_apply` (same function, explicit VMEM tiling), and
* semantically equivalent to the scalar handlers in
  :mod:`repro.core.handlers` (property-tested against them, and
  differentially trace-replayed against them by :mod:`repro.core.replay`).

**Message vocabulary.**  The engine speaks the *full* receiver-side wire
vocabulary, one lane-kind per :class:`~repro.core.types.MsgKind` a replica
can receive:

===============  ==========================================================
lane kind        scalar handler / semantics
===============  ==========================================================
``NOOP``         empty lane — state untouched, reply ``opcode = kind = -1``
``PROPOSE``      ``handlers.on_propose``  (§4.2, §8.3, §10.3)
``ACCEPT``       ``handlers.on_accept``   (§4.5, all-aboard epoch guard)
``COMMIT``       ``handlers.on_commit``   (§4.7, §8.6 thin commits)
``WRITE_QUERY``  ``handlers.on_write_query`` — ABD write round 1: reply
                 carries the local base-TS (§10)
``WRITE``        ``handlers.on_write`` — ABD write round 2: carstamp-gated
                 value install at ``(base-TS, 0)`` (§10)
``READ_QUERY``   ``handlers.on_read_query`` — §11 three-way carstamp
                 compare; ``Carstamp-too-low`` ships value + carstamp +
                 last-committed rmw-id/log-no for the read write-back
``READ_COMMIT``  §11 read write-back: commit semantics on the receiver
                 (``handlers.on_commit``) and a ``COMMIT_ACK`` reply
                 (issuer-side routing stays lid-based); the distinct kind
                 keeps write-backs visible in traces/stats and lets the
                 replay bucketer treat them as registering commit lanes
===============  ==========================================================

ABD lanes are the paper's common case: they bypass consensus entirely
(no proposed/accepted state is touched), which is what makes write and
read lanes cheaper *per client op* than RMW lanes — an RMW costs three
receiver messages (propose, accept, commit), an ABD write two, an ABD
read one (see ``benchmarks/bench_vector.py``).

**Conflict-free-batch contract.**  Slot ``i`` of a message batch targets
key ``i`` of the table, and each key carries *at most one* real message
per batch (idle lanes are ``NOOP``) — exactly the paper's per-key
serialization, reshaped for SIMD.  The scheduler (or
``replay.bucket_conflict_free``) must additionally start a new batch
before a PROPOSE/ACCEPT whose rmw-id was registered by a commit lane
earlier in the same batch: registrations scatter *after* the batch, so
in-batch registered-ness would otherwise be invisible to the gather.
Per-key message order must be preserved across batches; cross-key order
is free (lanes are independent).

The per-session registered-rmw-id table needs gather/scatter and therefore
lives *outside* the lane-parallel core: ``is_registered`` is a precomputed
input lane, and commit registrations are returned for a segment-max scatter
done by the jitted wrapper (see ``repro.kernels.paxos_apply.ops``).

**Machine-axis batching.**  Because every lane transition here is
elementwise (no cross-lane reads or writes), the lane axis composes
freely: stacking N machines' tables as ``(M, K)`` planes and flattening
to ``(M*K,)`` lanes runs N replica steps in ONE call, with rows isolated
by construction.  That stacked step
(:func:`repro.kernels.paxos_apply.ops.stacked_replica_step`) is what the
serve engine runs and the fused differential replay checks; keep new
transitions elementwise or they break it.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp

from .types import KVState, MsgKind, Rep

# message kinds in the vector engine: the RMW path ...
NOOP, PROPOSE, ACCEPT, COMMIT = 0, 1, 2, 3
# ... and the ABD path (§10–§11)
WRITE_QUERY, WRITE, READ_QUERY, READ_COMMIT = 4, 5, 6, 7

# wire MsgKind -> vector lane kind, for every receiver-side message
VEC_KIND = {
    MsgKind.PROPOSE: PROPOSE,
    MsgKind.ACCEPT: ACCEPT,
    MsgKind.COMMIT: COMMIT,
    MsgKind.WRITE_QUERY: WRITE_QUERY,
    MsgKind.WRITE: WRITE,
    MsgKind.READ_QUERY: READ_QUERY,
    MsgKind.READ_COMMIT: READ_COMMIT,
}

# vector lane kind -> reply MsgKind emitted on that lane
REPLY_KIND = {
    PROPOSE: MsgKind.PROP_REPLY,
    ACCEPT: MsgKind.ACC_REPLY,
    COMMIT: MsgKind.COMMIT_ACK,
    WRITE_QUERY: MsgKind.WRITE_QUERY_REPLY,
    WRITE: MsgKind.WRITE_ACK,
    READ_QUERY: MsgKind.READ_QUERY_REPLY,
    READ_COMMIT: MsgKind.COMMIT_ACK,
}

I32 = jnp.int32


class KVTable(NamedTuple):
    """Struct-of-arrays KV-pair metadata (§3.1.1), one lane per key."""

    state: jnp.ndarray          # KVState: 0 invalid / 1 proposed / 2 accepted
    log_no: jnp.ndarray
    last_log: jnp.ndarray       # last-committed-log-no
    prop_v: jnp.ndarray         # proposed-TS (version, machine)
    prop_m: jnp.ndarray
    acc_v: jnp.ndarray          # accepted-TS
    acc_m: jnp.ndarray
    acc_val: jnp.ndarray        # accepted-value
    acc_base_v: jnp.ndarray     # acc-base-TS (§10.3)
    acc_base_m: jnp.ndarray
    rmw_cnt: jnp.ndarray        # rmw-id working on log_no
    rmw_sess: jnp.ndarray
    value: jnp.ndarray
    base_v: jnp.ndarray         # carstamp base of `value`
    base_m: jnp.ndarray
    val_log: jnp.ndarray        # carstamp log part of `value`
    last_rmw_cnt: jnp.ndarray   # last-committed rmw-id
    last_rmw_sess: jnp.ndarray

    @staticmethod
    def create(n_keys: int) -> "KVTable":
        z = jnp.zeros((n_keys,), I32)
        return KVTable(*([z] * 18))

    @staticmethod
    def fresh(n_keys: int) -> "KVTable":
        """All-default table matching ``KVPair()`` field defaults exactly
        (TS_ZERO mids and RMW_ID_NONE sessions are ``-1``, not ``0``) — the
        correct t=0 state for differential replay against the scalar side."""
        z = jnp.zeros((n_keys,), I32)
        neg = jnp.full((n_keys,), -1, I32)
        return KVTable(
            state=z, log_no=z, last_log=z,
            prop_v=z, prop_m=neg, acc_v=z, acc_m=neg, acc_val=z,
            acc_base_v=z, acc_base_m=neg,
            rmw_cnt=z, rmw_sess=neg,
            value=z, base_v=z, base_m=neg, val_log=z,
            last_rmw_cnt=z, last_rmw_sess=neg,
        )


class MsgBatch(NamedTuple):
    """One message per key lane (``kind = NOOP`` for idle lanes)."""

    kind: jnp.ndarray
    ts_v: jnp.ndarray
    ts_m: jnp.ndarray
    log_no: jnp.ndarray
    rmw_cnt: jnp.ndarray
    rmw_sess: jnp.ndarray
    value: jnp.ndarray
    base_v: jnp.ndarray
    base_m: jnp.ndarray
    val_log: jnp.ndarray
    has_value: jnp.ndarray      # 0 for §8.6 thin commits

    @staticmethod
    def noop(n_keys: int) -> "MsgBatch":
        return MsgBatch(*[jnp.full((n_keys,), v, I32) for v in NOOP_LANE])


# A NOOP message lane, field by field: kind 0 and has_value 1 (a NOOP is
# no §8.6 thin commit).  Every staging buffer fills unstaged lanes from it.
NOOP_LANE = tuple(int(f == "has_value") for f in MsgBatch._fields)


class ReplyBatch(NamedTuple):
    """Reply lanes (kind + opcode + payloads, presence per opcode)."""

    kind: jnp.ndarray           # reply MsgKind (REPLY_KIND), -1 for NOOP lanes
    opcode: jnp.ndarray         # Rep value, or -1 for NOOP lanes
    ts_v: jnp.ndarray           # Seen-higher-*: blocking proposed-TS
    ts_m: jnp.ndarray
    log_no: jnp.ndarray         # Log-too-low: last committed log-no
    rmw_cnt: jnp.ndarray
    rmw_sess: jnp.ndarray
    value: jnp.ndarray
    base_v: jnp.ndarray
    base_m: jnp.ndarray
    val_log: jnp.ndarray


# -- TS / carstamp lattice helpers (lexicographic int pairs) -----------------

def ts_lt(av, am, bv, bm):
    return (av < bv) | ((av == bv) & (am < bm))


def ts_gt(av, am, bv, bm):
    return ts_lt(bv, bm, av, am)


def ts_ge(av, am, bv, bm):
    return ~ts_lt(av, am, bv, bm)


def cs_gt(abase_v, abase_m, alog, bbase_v, bbase_m, blog):
    """Carstamp (base-TS, log) lexicographic greater-than (§10)."""
    base_eq = (abase_v == bbase_v) & (abase_m == bbase_m)
    return ts_gt(abase_v, abase_m, bbase_v, bbase_m) | (base_eq & (alog > blog))


def popcount8(x):
    """Branch-free population count for small int bitmasks (< 8 bits).

    Quorum arithmetic over per-machine reply bitmaps: the issuer engine
    (:mod:`repro.core.proposer_vector`) tracks repliers/ackers/storers as
    bitmasks (n_machines <= 7, §3) and compares counts against majorities.
    """
    total = x & 1
    for i in range(1, 8):
        total = total + ((x >> i) & 1)
    return total


def _where(c, a, b):
    return jnp.where(c, a, b)


# ---------------------------------------------------------------------------
# The fused receiver step (mirrors handlers.on_propose/on_accept/on_commit)
# ---------------------------------------------------------------------------

def apply_batch(kv: KVTable, msg: MsgBatch,
                is_registered: jnp.ndarray
                ) -> Tuple[KVTable, ReplyBatch, jnp.ndarray]:
    """Apply one conflict-free message batch to the KV table.

    Returns ``(new_table, replies, register_mask)`` where ``register_mask``
    marks lanes whose (rmw_cnt, rmw_sess) must be registered by the caller
    (commit lanes only — the registry is a gather/scatter structure).
    """
    is_prop_msg = msg.kind == PROPOSE
    is_acc_msg = msg.kind == ACCEPT
    # §11 read write-backs are commits on the receiver (handlers.apply_msg)
    is_commit = (msg.kind == COMMIT) | (msg.kind == READ_COMMIT)
    is_wq = msg.kind == WRITE_QUERY
    is_w = msg.kind == WRITE
    is_rq = msg.kind == READ_QUERY
    active = msg.kind != NOOP
    pa = is_prop_msg | is_acc_msg           # propose-or-accept path

    # ---- common prefix: rmw-id + log window checks (§4.2) -----------------
    registered = pa & is_registered
    committed_no_bcast = registered & (kv.last_log >= msg.log_no)
    r_rmw_committed = registered & ~committed_no_bcast
    not_reg = pa & ~registered
    r_log_too_low = not_reg & (msg.log_no <= kv.last_log)
    r_log_too_high = not_reg & ~r_log_too_low & (msg.log_no > kv.last_log + 1)
    in_window = not_reg & ~r_log_too_low & ~r_log_too_high

    st_prop = kv.state == int(KVState.PROPOSED)
    st_acc = kv.state == int(KVState.ACCEPTED)

    # proposed-TS comparison: proposes block on >=, accepts only on > (§4.5)
    prop_blocks_prop = ts_ge(kv.prop_v, kv.prop_m, msg.ts_v, msg.ts_m)
    prop_blocks_acc = ts_gt(kv.prop_v, kv.prop_m, msg.ts_v, msg.ts_m)

    # ---- propose path (§4.2, §8.3, §10.3) ---------------------------------
    p = in_window & is_prop_msg
    p_seen_higher_prop = p & st_prop & prop_blocks_prop
    p_seen_higher_acc = p & st_acc & prop_blocks_prop
    same_rmw = (kv.rmw_cnt == msg.rmw_cnt) & (kv.rmw_sess == msg.rmw_sess)
    # §8.3 fastpath: same rmw accepted with both TSes lower -> plain Ack
    p_fast = (p & st_acc & ~prop_blocks_prop & same_rmw
              & ts_lt(kv.acc_v, kv.acc_m, msg.ts_v, msg.ts_m))
    p_seen_lower_acc = p & st_acc & ~prop_blocks_prop & ~p_fast
    p_ack_fresh = p & ~st_prop & ~st_acc                      # INVALID
    p_ack_prop = p & st_prop & ~prop_blocks_prop              # lower propose
    p_ack = p_ack_fresh | p_ack_prop | p_fast
    # §10.3: ack carrying a stale base-TS ships the fresher local value
    base_stale = cs_gt(kv.base_v, kv.base_m, kv.val_log,
                       msg.base_v, msg.base_m, msg.val_log)
    p_ack_stale = p_ack & base_stale

    # ---- accept path (§4.5) ------------------------------------------------
    a = in_window & is_acc_msg
    a_seen_higher_prop = a & st_prop & prop_blocks_acc
    # All-aboard epoch conflict (first-accept-wins within version 2; see
    # handlers.on_accept and DESIGN.md): a propose-less accept must not
    # displace a different RMW's propose-less acceptance.
    a_aboard_conflict = (a & (msg.ts_v == 2) & st_acc & (kv.acc_v == 2)
                         & ~same_rmw & ~prop_blocks_acc)
    a_seen_higher_acc = (a & st_acc & prop_blocks_acc) | a_aboard_conflict
    a_ack = a & ~(a_seen_higher_prop | a_seen_higher_acc)

    # ---- commit path (§4.7, §8.6 thin commits) -----------------------------
    c = is_commit
    thin = c & (msg.has_value == 0)
    thin_resolvable = (thin & st_acc & same_rmw & (kv.log_no == msg.log_no))
    c_value = _where(thin, kv.acc_val, msg.value)
    c_base_v = _where(thin, kv.acc_base_v, msg.base_v)
    c_base_m = _where(thin, kv.acc_base_m, msg.base_m)
    c_has_value = c & (~thin | thin_resolvable)
    # log bookkeeping always advances; value install is carstamp-gated
    c_log_adv = c & (msg.log_no > kv.last_log)
    c_install = c_has_value & cs_gt(c_base_v, c_base_m, msg.val_log,
                                    kv.base_v, kv.base_m, kv.val_log)
    c_release = c & (kv.state != int(KVState.INVALID)) \
        & (kv.log_no <= msg.log_no)

    # ---- ABD write lane (§10): install iff carstamp (base, 0) is newer ----
    w_install = is_w & cs_gt(msg.base_v, msg.base_m, 0,
                             kv.base_v, kv.base_m, kv.val_log)

    # ---- ABD read-query lane (§11): three-way carstamp comparison ----------
    rq_low = is_rq & cs_gt(kv.base_v, kv.base_m, kv.val_log,
                           msg.base_v, msg.base_m, msg.val_log)
    rq_eq = (is_rq & (msg.base_v == kv.base_v) & (msg.base_m == kv.base_m)
             & (msg.val_log == kv.val_log))
    rq_high = is_rq & ~rq_low & ~rq_eq

    # ---- new KV state -------------------------------------------------------
    # propose acks (non-fast) grab/overwrite the pair as PROPOSED
    grab = p_ack_fresh | p_ack_prop
    adv_prop_ts = grab | p_seen_lower_acc | p_fast | a_ack
    new_state = kv.state
    new_state = _where(grab, int(KVState.PROPOSED), new_state)
    new_state = _where(a_ack, int(KVState.ACCEPTED), new_state)
    new_state = _where(c_release, int(KVState.INVALID), new_state)

    new_log_no = _where(grab | a_ack, msg.log_no, kv.log_no)
    new_prop_v = _where(adv_prop_ts, msg.ts_v, kv.prop_v)
    new_prop_m = _where(adv_prop_ts, msg.ts_m, kv.prop_m)
    new_acc_v = _where(a_ack, msg.ts_v, kv.acc_v)
    new_acc_m = _where(a_ack, msg.ts_m, kv.acc_m)
    # releasing the slot clears the round TSes (mirrors commit_to_kv; the
    # unresolvable-thin-commit branch releases *without* clearing)
    clr = c_release & c_has_value
    new_prop_v = _where(clr, 0, new_prop_v)
    new_prop_m = _where(clr, -1, new_prop_m)
    new_acc_v = _where(clr, 0, new_acc_v)
    new_acc_m = _where(clr, -1, new_acc_m)
    new_acc_val = _where(a_ack, msg.value, kv.acc_val)
    new_acc_base_v = _where(a_ack, msg.base_v, kv.acc_base_v)
    new_acc_base_m = _where(a_ack, msg.base_m, kv.acc_base_m)
    new_rmw_cnt = _where(grab | a_ack, msg.rmw_cnt, kv.rmw_cnt)
    new_rmw_sess = _where(grab | a_ack, msg.rmw_sess, kv.rmw_sess)

    new_value = _where(c_install, c_value, kv.value)
    new_base_v = _where(c_install, c_base_v, kv.base_v)
    new_base_m = _where(c_install, c_base_m, kv.base_m)
    new_val_log = _where(c_install, msg.val_log, kv.val_log)
    # ABD writes land at carstamp (msg base-TS, 0), regardless of msg.val_log
    new_value = _where(w_install, msg.value, new_value)
    new_base_v = _where(w_install, msg.base_v, new_base_v)
    new_base_m = _where(w_install, msg.base_m, new_base_m)
    new_val_log = _where(w_install, 0, new_val_log)
    new_last_log = _where(c_log_adv, msg.log_no, kv.last_log)
    new_last_rmw_cnt = _where(c_log_adv, msg.rmw_cnt, kv.last_rmw_cnt)
    new_last_rmw_sess = _where(c_log_adv, msg.rmw_sess, kv.last_rmw_sess)

    new_kv = KVTable(
        state=new_state, log_no=new_log_no, last_log=new_last_log,
        prop_v=new_prop_v, prop_m=new_prop_m,
        acc_v=new_acc_v, acc_m=new_acc_m, acc_val=new_acc_val,
        acc_base_v=new_acc_base_v, acc_base_m=new_acc_base_m,
        rmw_cnt=new_rmw_cnt, rmw_sess=new_rmw_sess,
        value=new_value, base_v=new_base_v, base_m=new_base_m,
        val_log=new_val_log,
        last_rmw_cnt=new_last_rmw_cnt, last_rmw_sess=new_last_rmw_sess,
    )

    # ---- replies ------------------------------------------------------------
    op = jnp.full_like(msg.kind, -1)
    op = _where(r_rmw_committed, int(Rep.RMW_ID_COMMITTED), op)
    op = _where(committed_no_bcast, int(Rep.RMW_ID_COMMITTED_NO_BCAST), op)
    op = _where(r_log_too_low, int(Rep.LOG_TOO_LOW), op)
    op = _where(r_log_too_high, int(Rep.LOG_TOO_HIGH), op)
    op = _where(p_seen_higher_prop | a_seen_higher_prop,
                int(Rep.SEEN_HIGHER_PROP), op)
    op = _where(p_seen_higher_acc | a_seen_higher_acc,
                int(Rep.SEEN_HIGHER_ACC), op)
    op = _where(p_seen_lower_acc, int(Rep.SEEN_LOWER_ACC), op)
    op = _where(p_ack | a_ack, int(Rep.ACK), op)
    op = _where(p_ack_stale, int(Rep.ACK_BASE_TS_STALE), op)
    op = _where(c | is_wq | is_w, int(Rep.ACK), op)
    op = _where(rq_low, int(Rep.CARSTAMP_TOO_LOW), op)
    op = _where(rq_eq, int(Rep.CARSTAMP_EQUAL), op)
    op = _where(rq_high, int(Rep.CARSTAMP_TOO_HIGH), op)
    op = _where(~active, -1, op)

    rep_kind = jnp.full_like(msg.kind, -1)
    for lane_kind, reply_kind in REPLY_KIND.items():
        rep_kind = _where(msg.kind == lane_kind, int(reply_kind), rep_kind)

    seen_higher = (p_seen_higher_prop | p_seen_higher_acc
                   | a_seen_higher_prop | a_seen_higher_acc)
    rep_ts_v = _where(seen_higher, kv.prop_v,
                      _where(p_seen_lower_acc, kv.acc_v, 0))
    rep_ts_m = _where(seen_higher, kv.prop_m,
                      _where(p_seen_lower_acc, kv.acc_m, 0))
    # Carstamp-too-low (§11) ships the same local-value payload group as
    # Log-too-low / Ack-base-TS-stale, plus the last-committed rmw-id/log-no
    # the reader needs for its write-back commit.
    local_val = r_log_too_low | p_ack_stale | rq_low
    rep_log = _where(r_log_too_low | rq_low, kv.last_log, 0)
    rep_rmw_cnt = _where(r_log_too_low | rq_low, kv.last_rmw_cnt,
                         _where(p_seen_lower_acc, kv.rmw_cnt, 0))
    rep_rmw_sess = _where(r_log_too_low | rq_low, kv.last_rmw_sess,
                          _where(p_seen_lower_acc, kv.rmw_sess, -1))
    rep_value = _where(local_val, kv.value,
                       _where(p_seen_lower_acc, kv.acc_val, 0))
    # Write-query replies (§10 round 1) carry the local base-TS alone.
    rep_base_v = _where(local_val | is_wq, kv.base_v,
                        _where(p_seen_lower_acc, kv.acc_base_v, 0))
    rep_base_m = _where(local_val | is_wq, kv.base_m,
                        _where(p_seen_lower_acc, kv.acc_base_m, 0))
    rep_val_log = _where(local_val, kv.val_log,
                         _where(p_seen_lower_acc, msg.log_no, 0))

    replies = ReplyBatch(
        kind=rep_kind, opcode=op, ts_v=rep_ts_v, ts_m=rep_ts_m,
        log_no=rep_log, rmw_cnt=rep_rmw_cnt, rmw_sess=rep_rmw_sess,
        value=rep_value, base_v=rep_base_v, base_m=rep_base_m,
        val_log=rep_val_log,
    )
    register_mask = c & (msg.rmw_sess >= 0)
    return new_kv, replies, register_mask
