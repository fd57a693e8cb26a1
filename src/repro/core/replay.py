"""Differential trace replay: sim schedules become SIMD-engine tests.

The discrete-event simulator (:mod:`repro.core.sim`) generates adversarial
schedules — drops, duplicates, reordering, heavy tails, crashes — and every
machine can tap BOTH halves of what it processed:

* the **receiver** message stream (``Machine.msg_trace``, enabled by
  ``Cluster.enable_msg_trace``), replayed here through the scalar handlers
  (:func:`repro.core.handlers.apply_msg`) AND the SIMD engine, asserting
  reply- and plane-for-plane state equality after every conflict-free
  batch.  Two drivers share the staging and the checks:
  :func:`replay_cluster_fused` runs every machine's batches through
  :func:`repro.kernels.paxos_apply.ops.stacked_replica_step`, the step the
  serve engine runs, over one to many shard blocks; :func:`replay_cluster`
  runs one machine at a time through
  :func:`repro.kernels.paxos_apply.ops.replica_step`, whose registry
  gather and scatter run on the device.  Either takes the Pallas kernel
  (the default) or the pure-jnp oracle;
* the **issuer** event stream (``Machine.issuer_trace``, enabled by
  ``Cluster.enable_issuer_trace``): round starts, steered replies,
  decisions and pauses (see :mod:`repro.core.proposer`), replayed through
  a scalar shadow built from the same pure transitions the live Machine
  dispatches on AND the batched proposer engine
  (:func:`repro.core.proposer_vector.proposer_step`), asserting decisions,
  emission payloads and every :class:`ProposerTable` plane.

Any schedule the simulator can produce is thereby a correctness test of
both engines.  The ``run_and_replay*`` harnesses share one seeded faulty
schedule.

**Receiver bucketing contract** (see ``core/vector.py``): per batch, at
most one message per key (lane ``i`` == key ``i``); per-key message order
preserved across batches; and a batch is flushed early when a
PROPOSE/ACCEPT's rmw-id was registered by a commit lane earlier in the
*same* batch — registrations scatter after the batch, so the scalar side
(which registers immediately) would otherwise observe a fresher registry
than the gather.

**Issuer bucketing contract**: per batch, at most one reply per session
(lane ``i`` == session ``i``); per-session order preserved; round/pause
events flush any pending reply for their session before applying (they
reload the lane — they are inputs, exactly like messages are inputs to
the receiver replay).
"""

from __future__ import annotations

import dataclasses
from collections import Counter, deque
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import handlers, proposer, proposer_vector, vector
from .handlers import Registry, get_kv
from .proposer import (
    ABD_PAUSED, ACTION_PAYLOAD_KEYS, AbdEntry, AbdPhase, AbdRound,
    BCAST_KINDS, Decision, DecisionEvent, PauseEvent, Phase, ReplyEvent,
    RmwRound,
)
from .sim import Cluster, NetConfig, workload
from .node import ProtocolConfig
from .types import (
    Carstamp, KVPair, Msg, MsgKind, Reply, RmwId, RmwOp, Tally,
)

# The scalar<->lane converters, issuer round-lane loaders and the
# conflict-free bucketer live in repro.core.lanes, shared with the live
# batched serve path (repro.serve.paxos) — single definitions, so the
# replay oracle and the serving machine can never drift apart.
from .lanes import (
    LOG_OPS as _LOG_OPS, RMW_OPS as _RMW_OPS, TS_OPS as _TS_OPS,
    VALUE_OPS as _VALUE_OPS, ShardMap, bucket_conflict_free, kv_to_lanes,
    load_abd_round as _load_abd_round_lanes,
    load_rmw_round as _load_rmw_round_lanes, msg_to_lanes, reply_to_lanes,
)

from repro.kernels.paxos_apply import ops

__all__ = [
    "ReplayMismatch", "bucket_conflict_free", "kv_to_lanes", "msg_to_lanes",
    "reply_to_lanes", "replay_trace", "replay_cluster",
    "replay_cluster_fused", "run_and_replay", "run_and_replay_fused",
    "replay_issuer_trace", "replay_issuer_cluster", "run_and_replay_issuer",
]


class ReplayMismatch(AssertionError):
    """The SIMD engine diverged from the scalar handlers on a trace."""


# ---------------------------------------------------------------------------
# shared pieces of the replays
# ---------------------------------------------------------------------------

_MSG_FIELDS = vector.MsgBatch._fields
_REP_FIELDS = vector.ReplyBatch._fields
_KV_FIELDS = vector.KVTable._fields
_N_MSG = len(_MSG_FIELDS)


def _traces(cluster: Cluster, machines: Optional[Sequence[int]],
            tap: str = "msg_trace") -> List[tuple]:
    """``(mid, trace)`` of every (or each selected) machine's ``tap``
    (``msg_trace`` or ``issuer_trace``)."""
    mids = machines if machines is not None else range(len(cluster.machines))
    traces = []
    for mid in mids:
        trace = getattr(cluster.machines[mid], tap)
        if trace is None:
            raise ValueError(
                f"machine {mid} has no {tap} — call "
                f"cluster.enable_{tap}() before running the workload")
        traces.append((mid, trace))
    return traces


def _bucketed(trace: Sequence[Msg], n_keys: int) -> List[List[Msg]]:
    """A trace's conflict-free batches; every key must have a lane."""
    for msg in trace:
        if msg.key >= n_keys:
            raise ValueError(f"trace touches key {msg.key} >= n_keys "
                             f"{n_keys}")
    return bucket_conflict_free(trace)


def _stage(rows: Sequence[Sequence[Msg]], n_lanes: int,
           registries: Sequence[List[int]]) -> np.ndarray:
    """The packed ``(12, M, K)`` operand of one wave: row ``r`` holds the
    conflict-free batch ``rows[r]`` at its keys and NOOP lanes elsewhere.
    The 12th plane is ``is_registered``, gathered against row ``r``'s
    committed counters (the host mirror of ``ops.gather_is_registered``:
    clip, then compare)."""
    out = np.empty((ops.N_MSGREG, len(rows), n_lanes), np.int32)
    out[:] = ops.NOOP_COLUMN[:, None, None]
    for r, batch in enumerate(rows):
        reg = registries[r]
        for msg in batch:
            lane = msg_to_lanes(msg)
            rid = msg.rmw_id
            out[:, r, msg.key] = [lane[f] for f in _MSG_FIELDS] + [
                rid.gsess >= 0
                and reg[min(rid.gsess, len(reg) - 1)] >= rid.counter]
    return out


def _expected_reply_lanes(rep) -> Dict[str, int]:
    """The ReplyBatch lanes a scalar Reply pins down (others are free);
    fields meaningful per opcode, mirroring the wire format (opcode groups
    shared with repro.serve.paxos.bridge.reply_from_lanes)."""
    want = {"kind": int(rep.kind), "opcode": int(rep.opcode)}
    if rep.opcode in _TS_OPS:
        want["ts_v"], want["ts_m"] = rep.ts.version, rep.ts.mid
    if rep.opcode in _LOG_OPS:
        want["log_no"] = rep.log_no
    if rep.opcode in _RMW_OPS:
        want["rmw_cnt"] = rep.rmw_id.counter
        want["rmw_sess"] = rep.rmw_id.gsess
    if rep.opcode in _VALUE_OPS:
        want["value"] = rep.value
        want["base_v"], want["base_m"] = rep.base_ts.version, rep.base_ts.mid
        want["val_log"] = rep.val_log
    if rep.kind == MsgKind.WRITE_QUERY_REPLY:
        want["base_v"], want["base_m"] = rep.base_ts.version, rep.base_ts.mid
    return want


def _check_reply(rep: Reply, col: np.ndarray, where: str) -> None:
    """The scalar reply against ``col``, the engine's ``(11,)`` reply
    column at the message's lane."""
    want = _expected_reply_lanes(rep)
    got = {f: int(col[_REP_FIELDS.index(f)]) for f in want}
    if got != want:
        raise ReplayMismatch(f"reply diverged at {where}:\n scalar: {want}\n"
                             f" vector: {got}")


def _check_kv(kvs: Dict[int, KVPair], planes: np.ndarray, keys,
              where: str) -> None:
    """Every lane in ``keys`` of one row's ``(18, K)`` KV planes against
    the scalar store, plane for plane."""
    for key in keys:
        want = kv_to_lanes(kvs.get(key) or KVPair(key=key))
        got = {f: int(planes[i, key]) for i, f in enumerate(_KV_FIELDS)}
        if got != want:
            diff = {f: (want[f], got[f]) for f in want if want[f] != got[f]}
            raise ReplayMismatch(
                f"final KV state diverged at {where}, key {key} "
                f"(field: (scalar, vector)): {diff}")


def _run_and_replay(replay, seed: int, trace, *, n_ops: int = 24,
                    keys: int = 3, cfg: Optional[ProtocolConfig] = None,
                    net: Optional[NetConfig] = None, rmw_frac: float = 0.45,
                    write_frac: float = 0.3, all_aboard: bool = False
                    ) -> Dict[str, int]:
    """The harnesses' one schedule: a seeded faulty sim run with the
    ``trace`` tap on (``Cluster.enable_msg_trace`` or
    ``enable_issuer_trace``), run until quiet, then ``replay(cluster,
    keys)``'s stats with the history length.

    Defaults exercise the full vocabulary (mixed RMW/write/read) under an
    adversarial network (drops, dups, heavy tails).  ``all_aboard=True``
    deploys the §9 fast path, putting the all-aboard epoch-conflict lane
    into the replayed schedules.
    """
    if cfg is None:
        cfg = ProtocolConfig(n_machines=5, sessions_per_machine=2,
                             all_aboard=all_aboard)
    elif all_aboard and not cfg.all_aboard:
        # don't silently drop the §9 deployment request on an explicit cfg
        cfg = dataclasses.replace(cfg, all_aboard=True)
    net = net or NetConfig(seed=seed, drop_prob=0.06, dup_prob=0.05,
                           heavy_tail_prob=0.03, heavy_tail_extra=25.0)
    cluster = Cluster(cfg, net)
    trace(cluster)
    workload(cluster, n_ops=n_ops, keys=keys, seed=seed,
             rmw_frac=rmw_frac, write_frac=write_frac, op=RmwOp.FAA)
    if not cluster.run_until_quiet(max_ticks=120_000):
        raise RuntimeError(f"sim (seed {seed}) did not quiesce")
    stats = replay(cluster, keys)
    stats["history"] = len(cluster.history)
    return stats


# ---------------------------------------------------------------------------
# one machine at a time, registry on the device (ops.replica_step)
# ---------------------------------------------------------------------------

def replay_trace(trace: Sequence[Msg], *, n_keys: int, num_gsess: int,
                 use_kernel: bool = True,
                 block_rows: int = 1) -> Dict[str, int]:
    """Replay one machine's message trace through both implementations.

    Returns replay stats; raises :class:`ReplayMismatch` on the first
    divergence (reply stream, final KV planes, or registry).
    """
    kvs: Dict[int, KVPair] = {}
    registry = Registry(num_gsess)
    table = vector.KVTable.fresh(n_keys)
    registered = jnp.zeros((num_gsess,), jnp.int32)

    batches = _bucketed(trace, n_keys)
    kinds: Counter = Counter()
    for step, batch in enumerate(batches):
        # replica_step gathers is_registered on the device: the staged
        # 12th plane is dropped
        staged = _stage([batch], n_keys, [registry.committed])
        msgb = vector.MsgBatch(*map(jnp.asarray, staged[:_N_MSG, 0]))
        table, replies, registered = ops.replica_step(
            table, msgb, registered, block_rows=block_rows,
            use_kernel=use_kernel)
        rep_np = np.stack([np.asarray(p) for p in replies])
        for msg in batch:
            rep = handlers.apply_msg(get_kv(kvs, msg.key), msg, registry)
            kinds[msg.kind.name.lower()] += 1
            _check_reply(rep, rep_np[:, msg.key],
                         f"batch {step}, key {msg.key}, msg {msg}")

    _check_kv(kvs, np.stack([np.asarray(p) for p in table]), range(n_keys),
              "the end of the trace")
    got_reg = [int(x) for x in np.asarray(registered)]
    if got_reg != registry.committed:
        raise ReplayMismatch(
            f"registry diverged:\n scalar: {registry.committed}\n"
            f" vector: {got_reg}")

    stats = {"messages": len(trace), "batches": len(batches)}
    stats.update(kinds)
    return stats


def replay_cluster(cluster: Cluster, *, n_keys: int,
                   use_kernel: bool = True,
                   block_rows: int = 1,
                   machines: Optional[Sequence[int]] = None
                   ) -> Dict[str, int]:
    """Replay every (or selected) machine's trace; aggregate the stats."""
    total: Counter = Counter()
    for _, trace in _traces(cluster, machines):
        total["machines"] += 1
        total.update(replay_trace(trace, n_keys=n_keys,
                                  num_gsess=cluster.cfg.num_gsess,
                                  use_kernel=use_kernel,
                                  block_rows=block_rows))
    return dict(total)


def run_and_replay(seed: int, *, use_kernel: bool = True,
                   block_rows: int = 1, **sim) -> Dict[str, int]:
    """End-to-end harness: seeded faulty sim run (``sim``: the schedule's
    keywords, see :func:`_run_and_replay`) -> differential replay of
    **every** machine's trace, through the Pallas kernel by default."""
    return _run_and_replay(
        lambda cluster, keys: replay_cluster(
            cluster, n_keys=keys, use_kernel=use_kernel,
            block_rows=block_rows),
        seed, Cluster.enable_msg_trace, **sim)


# ===========================================================================
# Fused (stacked-machine) replay: cluster ticks, plane-for-plane
# ===========================================================================
#
# The device-resident ClusterEngine (repro.serve.paxos.cluster_engine)
# stacks all N replicas' KV planes on a leading machine axis and runs ONE
# fused receiver call per wave: ops.stacked_replica_step, the step the
# engine serves, on the packed (12, M, K) operand.  This replay stages that
# operand straight from recorded message traces — machine ``i``'s batch
# ``w`` in row ``i`` of wave ``w`` — runs the same step under its own jit,
# and asserts, against N independent scalar-handler shadows, that rows stay
# isolated: every reply, every KV plane of every row, and every
# per-machine registry mirror are bit-identical after every wave.  The
# registry gather stays host-side exactly as the engine does it (the one
# cross-lane piece of the step): ``is_registered`` is computed per staged
# lane against the machine's own mirror before the wave, and commit-lane
# registrations max-merge back after it (out-of-range gsess dropped,
# mirroring ops.scatter_register's dead-slot drop).  Only the engine's wire
# (dense or compact, tests/test_cluster_engine.py) is not replayed here.
#
# Wave alignment across machines is arbitrary (machines with shorter
# traces simply stop contributing rows) — the step is elementwise, so this
# checks precisely the row-isolation property the fused engine's
# correctness argument rests on, with no serve-layer code imported.

_fused_step = jax.jit(ops.stacked_replica_step, static_argnames=(
    "use_kernel", "block_rows", "shard_lanes", "interpret"))


def replay_cluster_fused(cluster: Cluster, *, n_keys: int, shards: int = 1,
                         use_kernel: bool = True,
                         block_rows: int = 1,
                         machines: Optional[Sequence[int]] = None
                         ) -> Dict[str, int]:
    """Replay every (or selected) machine's trace through fused waves,
    checked shard for shard.

    Unlike :func:`replay_cluster` (N independent single-machine replays),
    all machines share each fused step: one ``(M*K,)`` engine call per
    wave, exactly like the serve-path ClusterEngine.  The lane axis is
    aligned up to ``shards`` contiguous blocks (the
    :class:`~repro.core.lanes.ShardMap` block partition — lane == key, no
    permutation); with ``shards > 1`` the wave runs with shard-local
    kernel segments, as the sharded ClusterEngine does.  Per wave this
    asserts every staged reply, and that each machine's registry
    (gathered pre-wave, commit registrations scattered post-wave) matches
    the scalar one AND that re-merging the per-shard registration journals
    — the cross-shard scatter bookkeeping the serve bridge mirrors —
    reproduces it; finally, every KV plane of every shard block of every
    row.  Raises :class:`ReplayMismatch` on the first divergence.
    """
    traces = _traces(cluster, machines)
    mids = [mid for mid, _ in traces]
    batches = [_bucketed(trace, n_keys) for _, trace in traces]
    num_gsess = cluster.cfg.num_gsess
    m = len(mids)
    k_al = ShardMap(shards, shards).aligned(n_keys)
    sm = ShardMap(shards, k_al)
    # scalar shadows (one per row); fused side: the machine-global registry
    # every shard gathers from, plus one registration journal per shard
    # (the bridge's reg_mirror analogue)
    kvs: List[Dict[int, KVPair]] = [{} for _ in mids]
    regs = [Registry(num_gsess) for _ in mids]
    freg = [[0] * num_gsess for _ in mids]
    journals = [[{} for _ in range(shards)] for _ in mids]
    fresh = vector.KVTable.fresh(k_al)
    kv_stack = jnp.stack([jnp.broadcast_to(p, (m, k_al)) for p in fresh])

    n_waves = max(map(len, batches), default=0)
    shard_lanes = [0] * shards
    kinds: Counter = Counter()
    for wave in range(n_waves):
        rows = [b[wave] if wave < len(b) else [] for b in batches]
        kv_stack, rep_stack, reg_mask = _fused_step(
            kv_stack, jnp.asarray(_stage(rows, k_al, freg)),
            use_kernel=use_kernel, block_rows=block_rows,
            shard_lanes=sm.lanes_per_shard if shards > 1 else None)
        rep_np = np.asarray(rep_stack)
        mask_np = np.asarray(reg_mask)
        for row, batch in enumerate(rows):
            for msg in batch:
                shard = sm.shard_of(msg.key)
                shard_lanes[shard] += 1
                kinds[msg.kind.name.lower()] += 1
                rep = handlers.apply_msg(get_kv(kvs[row], msg.key), msg,
                                         regs[row])
                _check_reply(rep, rep_np[:, row, msg.key],
                             f"wave {wave}, machine {mids[row]}, shard "
                             f"{shard}, key {msg.key}, msg {msg}")
        # cross-shard registry scatter: a commit lane's registration
        # max-merges into the machine-global registry AND journals under
        # its owning shard
        for row, batch in enumerate(rows):
            for msg in batch:
                gs, cnt = msg.rmw_id.gsess, msg.rmw_id.counter
                if mask_np[row, msg.key] and 0 <= gs < num_gsess:
                    freg[row][gs] = max(freg[row][gs], cnt)
                    j = journals[row][sm.shard_of(msg.key)]
                    j[gs] = max(j.get(gs, 0), cnt)
        for row in range(m):
            if freg[row] != regs[row].committed:
                raise ReplayMismatch(
                    f"fused registry diverged at wave {wave}, machine "
                    f"{mids[row]}:\n scalar: {regs[row].committed}\n"
                    f" fused:  {freg[row]}")
            merged = [0] * num_gsess
            for j in journals[row]:
                for gs, cnt in j.items():
                    merged[gs] = max(merged[gs], cnt)
            if merged != freg[row]:
                raise ReplayMismatch(
                    f"per-shard registration journals diverged from the "
                    f"global registry at wave {wave}, machine {mids[row]}:"
                    f"\n merged journals: {merged}\n global: {freg[row]}")

    kv_np = np.asarray(kv_stack)
    for row in range(m):
        for shard in range(shards):
            _check_kv(kvs[row], kv_np[:, row],
                      range(*sm.slice_of(shard).indices(k_al)),
                      f"machine {mids[row]}, shard {shard}")

    stats = {"machines": m, "messages": sum(len(t) for _, t in traces),
             "fused_waves": n_waves, "shards": shards, "lane_axis": k_al}
    for s, c in enumerate(shard_lanes):
        stats[f"shard{s}_lanes"] = c
    stats.update(kinds)
    return stats


def run_and_replay_fused(seed: int, *, shards: int = 1,
                         use_kernel: bool = True, block_rows: int = 1,
                         **sim) -> Dict[str, int]:
    """End-to-end fused harness: seeded faulty sim (``sim`` as in
    :func:`run_and_replay`) -> stacked replay over ``shards`` blocks."""
    return _run_and_replay(
        lambda cluster, keys: replay_cluster_fused(
            cluster, n_keys=keys, shards=shards, use_kernel=use_kernel,
            block_rows=block_rows),
        seed, Cluster.enable_msg_trace, **sim)


# ===========================================================================
# Differential proposer replay: issuer traces vs the batched proposer engine
# ===========================================================================
#
# The issuer is driven by replies *and* by local KV-coupled context, so its
# trace carries both: round-start events (the broadcasts, which reload a
# session's lane — they are inputs, exactly like messages are inputs to the
# receiver replay), steered replies (the engine's work), the decisions the
# live machine took (the oracle for the engine's decision planes), and
# pauses (rounds abandoned from inspection timeouts).  The replay drives a
# scalar shadow — the same Tally/abd_fold/decide_* code the Machine runs —
# and the batched ProposerTable through identical event streams and asserts
# after every reply batch that decisions, emissions and every table plane
# agree.

# ActionBatch planes a decision's payload pins down, and the wire kind of
# engine-owned emissions — canonical tables in repro.core.proposer, shared
# with the live batched dispatch (repro.serve.paxos.machine).
_ACTION_KEYS = ACTION_PAYLOAD_KEYS
_BCAST_KIND = BCAST_KINDS


def _bits(srcs) -> int:
    out = 0
    for s in srcs:
        out |= 1 << s
    return out


class _SessShadow:
    """Scalar shadow of one issuer lane, driven by the SAME pure transition
    functions the live Machine runs (Tally.note, abd_fold, decide_*)."""

    def __init__(self):
        self.phase = Phase.IDLE
        self.lid = 0
        self.aboard = 0
        self.helping = 0
        self.lth_counter = 0
        self.key = 0
        self.ts_v, self.ts_m = 0, -1
        self.log_no = 0
        self.rmw_cnt, self.rmw_sess = 0, -1
        self.value = 0
        self.has_value = 0
        self.base_v, self.base_m = 0, -1
        self.val_log = 0
        self.tally = Tally()
        self.abd = AbdEntry(sess=0)
        self.abd_paused = False

    # -- event application (inputs: identical for shadow and lanes) ---------

    def load_rmw_round(self, ev: RmwRound) -> None:
        self.phase = ev.phase
        self.lid = ev.lid
        self.aboard, self.helping = ev.aboard, ev.helping
        self.lth_counter = ev.lth_counter
        self.key = ev.key
        self.ts_v, self.ts_m = ev.ts.version, ev.ts.mid
        self.log_no = ev.log_no
        self.rmw_cnt, self.rmw_sess = ev.rmw_id.counter, ev.rmw_id.gsess
        self.value, self.has_value = ev.value, ev.has_value
        self.base_v, self.base_m = ev.base_ts.version, ev.base_ts.mid
        self.val_log = ev.val_log
        self.tally = Tally()

    def load_abd_round(self, ev: AbdRound) -> None:
        ab = AbdEntry(sess=ev.sess)
        ab.phase = ev.phase
        ab.lid, ab.key, ab.value = ev.lid, ev.key, ev.value
        ab.repliers = {s for s in range(8) if ev.rep_bits >> s & 1}
        ab.storers = {s for s in range(8) if ev.store_bits >> s & 1}
        if ev.phase in (AbdPhase.W_QUERY, AbdPhase.W_WRITE):
            ab.max_base = ev.base_ts
        else:
            ab.best_cs = Carstamp(ev.base_ts, ev.val_log)
            ab.best_value = ev.value
            ab.best_log_no, ab.best_rmw_id = ev.log_no, ev.rmw_id
            ab.sent_cs = Carstamp(ev.sent_base_ts, ev.sent_val_log)
        self.abd = ab
        self.abd_paused = False

    def pause(self, abd: int) -> None:
        if abd:
            self.abd_paused = True
        else:
            self.phase = Phase.PAUSED

    # -- reply application (the logic under differential test) --------------

    def _abd_apply(self, rep: Reply, cfg: ProtocolConfig):
        if self.abd_paused or not proposer.abd_fold(self.abd, rep):
            return Decision.WAIT, None
        ab = self.abd
        d = proposer.decide_abd(ab, majority=cfg.majority)
        if d == Decision.WAIT:
            return d, None
        self.abd_paused = True
        if d == Decision.ABD_W2:
            return d, {"key": ab.key, "value": ab.value,
                       "base_v": ab.max_base.version,
                       "base_m": ab.max_base.mid}
        if d == Decision.ABD_R_WB:
            return d, {"key": ab.key, "log_no": ab.best_log_no,
                       "rmw_cnt": ab.best_rmw_id.counter,
                       "rmw_sess": ab.best_rmw_id.gsess,
                       "value": ab.best_value,
                       "base_v": ab.best_cs.base.version,
                       "base_m": ab.best_cs.base.mid,
                       "val_log": ab.best_cs.log_no}
        return d, None

    def apply_reply(self, rep: Reply, cfg: ProtocolConfig):
        """Steer + fold + decide; returns (Decision, payload dict | None).

        Mirrors ``proposer_step`` gating exactly (a PAUSED lane tallies
        nothing); the live machine may fold a straggler into a tally no
        check will ever read again — invisible to decisions either way.
        """
        if rep.kind in (MsgKind.WRITE_QUERY_REPLY, MsgKind.WRITE_ACK,
                        MsgKind.READ_QUERY_REPLY):
            return self._abd_apply(rep, cfg)
        if rep.kind == MsgKind.COMMIT_ACK:
            if self.phase == Phase.COMMITTED and self.lid == rep.lid:
                self.tally.note(rep)
                d = proposer.decide_commit(
                    self.tally, majority=cfg.majority,
                    quorum_is_majority=cfg.commit_ack_quorum_is_majority)
                if d != Decision.WAIT:
                    self.phase = Phase.PAUSED
                return d, None
            return self._abd_apply(rep, cfg)
        if (rep.kind == MsgKind.PROP_REPLY and self.phase == Phase.PROPOSED
                and self.lid == rep.lid):
            self.tally.note(rep)
            d, pay = proposer.decide_propose(
                self.tally, majority=cfg.majority,
                own_rmw_id=RmwId(self.rmw_cnt, self.rmw_sess),
                log_too_high_counter=self.lth_counter,
                log_too_high_threshold=cfg.log_too_high_threshold)
            if d == Decision.WAIT:
                return d, None
            self.phase = Phase.PAUSED
            if d == Decision.RETRY:
                return d, proposer.retry_payload(self.tally)
            if d == Decision.LOG_TOO_LOW:
                return d, proposer.log_too_low_payload(pay)
            if d in (Decision.HELP, Decision.HELP_SELF):
                return d, proposer.lower_acc_payload(pay)
            return d, None
        if (rep.kind == MsgKind.ACC_REPLY and self.phase == Phase.ACCEPTED
                and self.lid == rep.lid):
            self.tally.note(rep)
            d, pay = proposer.decide_accept(
                self.tally, n_machines=cfg.n_machines,
                majority=cfg.majority, helping=self.helping == 1,
                all_aboard=self.aboard == 1)
            if d == Decision.WAIT:
                return d, None
            self.phase = Phase.PAUSED
            if d == Decision.RETRY:
                return d, proposer.retry_payload(self.tally)
            if d == Decision.LOG_TOO_LOW:
                return d, proposer.log_too_low_payload(pay)
            if d == Decision.COMMIT_BCAST:
                thin = self.tally.acks >= cfg.n_machines
                return d, {"log_no": self.log_no, "rmw_cnt": self.rmw_cnt,
                           "rmw_sess": self.rmw_sess,
                           "value": 0 if thin else self.value,
                           "has_value": 0 if thin else 1,
                           "base_v": self.base_v, "base_m": self.base_m,
                           "val_log": self.val_log}
            return d, None
        return Decision.WAIT, None

    # -- plane conversion ----------------------------------------------------

    def to_lanes(self) -> Dict[str, int]:
        t = self.tally
        sh, ltl, la = t.seen_higher, t.log_too_low, t.lower_acc
        ab = self.abd
        return dict(
            phase=int(self.phase), lid=self.lid, aboard=self.aboard,
            helping=self.helping, lth_counter=self.lth_counter,
            key=self.key, ts_v=self.ts_v, ts_m=self.ts_m,
            log_no=self.log_no, rmw_cnt=self.rmw_cnt,
            rmw_sess=self.rmw_sess, value=self.value,
            has_value=self.has_value, base_v=self.base_v,
            base_m=self.base_m, val_log=self.val_log,
            rep_bits=_bits(t.repliers), ack_bits=_bits(t.ackers),
            rmw_flag=int(t.rmw_committed),
            rmw_nb_flag=int(t.rmw_committed_no_bcast),
            lth_flag=int(t.log_too_high),
            sh_has=int(sh is not None),
            sh_v=sh.version if sh is not None else 0,
            sh_m=sh.mid if sh is not None else -1,
            ltl_has=int(ltl is not None),
            ltl_log=ltl.log_no if ltl is not None else 0,
            ltl_cnt=ltl.rmw_id.counter if ltl is not None else 0,
            ltl_sess=ltl.rmw_id.gsess if ltl is not None else -1,
            ltl_val=ltl.value if ltl is not None else 0,
            ltl_base_v=ltl.base_ts.version if ltl is not None else 0,
            ltl_base_m=ltl.base_ts.mid if ltl is not None else -1,
            ltl_vlog=ltl.val_log if ltl is not None else 0,
            la_has=int(la is not None),
            la_ts_v=la.ts.version if la is not None else 0,
            la_ts_m=la.ts.mid if la is not None else -1,
            la_cnt=la.rmw_id.counter if la is not None else 0,
            la_sess=la.rmw_id.gsess if la is not None else -1,
            la_val=la.value if la is not None else 0,
            la_base_v=la.base_ts.version if la is not None else 0,
            la_base_m=la.base_ts.mid if la is not None else -1,
            la_vlog=la.val_log if la is not None else 0,
            fr_has=int(t.fresh_value is not None),
            fr_val=t.fresh_value if t.fresh_value is not None else 0,
            fr_base_v=t.fresh_cs.base.version,
            fr_base_m=t.fresh_cs.base.mid,
            fr_log=t.fresh_cs.log_no,
            abd_phase=ABD_PAUSED if self.abd_paused else int(ab.phase),
            abd_lid=ab.lid, abd_key=ab.key, abd_value=ab.value,
            abd_rep_bits=_bits(ab.repliers), abd_ack_bits=_bits(ab.ackers),
            abd_store_bits=_bits(ab.storers),
            abd_maxb_v=ab.max_base.version, abd_maxb_m=ab.max_base.mid,
            abd_sent_base_v=ab.sent_cs.base.version,
            abd_sent_base_m=ab.sent_cs.base.mid,
            abd_sent_vlog=ab.sent_cs.log_no,
            best_base_v=ab.best_cs.base.version,
            best_base_m=ab.best_cs.base.mid,
            best_vlog=ab.best_cs.log_no, best_val=ab.best_value,
            best_log=ab.best_log_no, best_cnt=ab.best_rmw_id.counter,
            best_sess=ab.best_rmw_id.gsess)


def replay_issuer_trace(events: Sequence[object], *, cfg: ProtocolConfig
                        ) -> Dict[str, int]:
    """Replay one machine's issuer trace through the scalar shadow AND the
    batched proposer engine, asserting plane-for-plane equality after every
    reply batch, and decisions/emissions against the live machine's record.

    Raises :class:`ReplayMismatch` on the first divergence.
    """
    n_sess = cfg.sessions_per_machine
    commit_need = (cfg.majority - 1 if cfg.commit_ack_quorum_is_majority
                   else 1)
    lanes = {f: np.full((n_sess,), v, np.int32)
             for f, v in proposer_vector.TABLE_DEFAULTS.items()}
    shadows = [_SessShadow() for _ in range(n_sess)]
    pending: Dict[int, Reply] = {}
    expected: List[deque] = [deque() for _ in range(n_sess)]
    stats = {"events": len(events), "replies": 0, "batches": 0,
             "decisions": 0}

    def compare_planes(where: str) -> None:
        for sess, sh in enumerate(shadows):
            want = sh.to_lanes()
            got = {f: int(lanes[f][sess]) for f in want}
            if got != want:
                diff = {f: (want[f], got[f]) for f in want
                        if want[f] != got[f]}
                raise ReplayMismatch(
                    f"proposer planes diverged ({where}) at session {sess} "
                    f"(plane: (scalar, vector)): {diff}")

    def flush() -> None:
        if not pending:
            return
        stats["batches"] += 1
        repb = {f: np.zeros((n_sess,), np.int32)
                for f in proposer_vector.IssuerReplyBatch._fields}
        repb["kind"] -= 1
        for sess, rep in pending.items():
            for f, v in reply_to_lanes(rep).items():
                repb[f][sess] = v
        table = proposer_vector.ProposerTable(
            *[jnp.asarray(lanes[f])
              for f in proposer_vector.ProposerTable._fields])
        batch = proposer_vector.IssuerReplyBatch(
            *[jnp.asarray(repb[f])
              for f in proposer_vector.IssuerReplyBatch._fields])
        table, actions = proposer_vector.proposer_step(
            table, batch, n_machines=cfg.n_machines, majority=cfg.majority,
            commit_need=commit_need,
            log_too_high_threshold=cfg.log_too_high_threshold)
        for f, plane in zip(proposer_vector.ProposerTable._fields, table):
            lanes[f] = np.asarray(plane).copy()
        act = {f: np.asarray(p) for f, p in
               zip(proposer_vector.ActionBatch._fields, actions)}
        # scalar shadow + three-way decision/emission check
        for sess in range(n_sess):
            got_d = Decision(int(act["decision"][sess]))
            if sess not in pending:
                if got_d != Decision.WAIT:
                    raise ReplayMismatch(
                        f"engine decided {got_d.name} on idle lane {sess}")
                continue
            sh_d, sh_pay = shadows[sess].apply_reply(pending[sess], cfg)
            if got_d != sh_d:
                raise ReplayMismatch(
                    f"decision diverged at session {sess}: scalar "
                    f"{sh_d.name}, vector {got_d.name} "
                    f"(reply {pending[sess]})")
            if sh_d == Decision.WAIT:
                continue
            stats["decisions"] += 1
            stats[f"d_{sh_d.name.lower()}"] = \
                stats.get(f"d_{sh_d.name.lower()}", 0) + 1
            if not expected[sess]:
                raise ReplayMismatch(
                    f"session {sess} decided {sh_d.name} but the live "
                    f"machine recorded no decision here")
            ev = expected[sess].popleft()
            if ev.decision != sh_d:
                raise ReplayMismatch(
                    f"live machine decided {ev.decision.name} at session "
                    f"{sess}, replay decided {sh_d.name}")
            keys = _ACTION_KEYS.get(sh_d)
            if keys is not None:
                got_pay = {k: int(act[k][sess]) for k in keys}
                if ev.payload != got_pay or sh_pay != got_pay:
                    raise ReplayMismatch(
                        f"decision payload diverged at session {sess} "
                        f"({sh_d.name}): machine {ev.payload}, shadow "
                        f"{sh_pay}, vector {got_pay}")
            want_kind = _BCAST_KIND.get(sh_d, -1)
            if int(act["bcast_kind"][sess]) != want_kind:
                raise ReplayMismatch(
                    f"emission kind diverged at session {sess} "
                    f"({sh_d.name}): want {want_kind}, got "
                    f"{int(act['bcast_kind'][sess])}")
        pending.clear()
        compare_planes("after batch")

    for ev in events:
        if isinstance(ev, ReplyEvent):
            if ev.sess in pending:
                flush()
            stats["replies"] += 1
            pending[ev.sess] = ev.reply
        elif isinstance(ev, DecisionEvent):
            expected[ev.sess].append(ev)
        elif isinstance(ev, RmwRound):
            if ev.sess in pending:
                flush()
            shadows[ev.sess].load_rmw_round(ev)
            _load_rmw_round_lanes(lanes, ev)
        elif isinstance(ev, AbdRound):
            if ev.sess in pending:
                flush()
            shadows[ev.sess].load_abd_round(ev)
            _load_abd_round_lanes(lanes, ev)
        elif isinstance(ev, PauseEvent):
            if ev.sess in pending:
                flush()
            shadows[ev.sess].pause(ev.abd)
            if ev.abd:
                lanes["abd_phase"][ev.sess] = ABD_PAUSED
            else:
                lanes["phase"][ev.sess] = int(Phase.PAUSED)
        else:
            raise TypeError(f"unknown issuer trace event {ev!r}")
    flush()
    compare_planes("end of trace")
    leftovers = sum(len(q) for q in expected)
    if leftovers:
        raise ReplayMismatch(
            f"{leftovers} live-machine decisions were never reproduced "
            f"by the replay")
    return stats


def replay_issuer_cluster(cluster: Cluster,
                          machines: Optional[Sequence[int]] = None
                          ) -> Dict[str, int]:
    """Replay every (or selected) machine's issuer trace; aggregate stats."""
    total: Counter = Counter()
    for _, events in _traces(cluster, machines, "issuer_trace"):
        total["machines"] += 1
        total.update(replay_issuer_trace(events, cfg=cluster.cfg))
    return dict(total)


def run_and_replay_issuer(seed: int, **sim) -> Dict[str, int]:
    """End-to-end proposer harness: seeded faulty sim -> issuer replay.

    The mirror image of :func:`run_and_replay`: the same schedule
    (``sim``), but the differential surface is the *issuer* side — every
    machine's recorded reply stream is replayed through the scalar shadow
    and :func:`repro.core.proposer_vector.proposer_step`.
    """
    return _run_and_replay(
        lambda cluster, keys: replay_issuer_cluster(cluster),
        seed, Cluster.enable_issuer_trace, **sim)
