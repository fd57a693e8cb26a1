"""Differential trace replay: sim schedules vs the SIMD engines/kernel.

Every seeded run drives a mixed RMW/write/read workload over an adversarial
network (drops, duplicates, heavy-tail delays) and differentially replays
per-machine traces:

* receiver side — the message stream through the Pallas kernel (interpret
  mode) or the jnp oracle AND the scalar handlers, asserting reply- and
  plane-for-plane state equality, one machine at a time
  (repro.core.replay.run_and_replay) and through the stacked step the
  serve engine runs, over one to four shard blocks (run_and_replay_fused);
* issuer side — the reply/round/decision stream through the batched
  proposer engine (repro.core.proposer_vector) AND the scalar shadow built
  from the same pure transitions the Machine runs, asserting decisions,
  emissions and every ProposerTable plane (run_and_replay_issuer).

Both mixes include all-aboard (§9) deployments.
"""

import pytest

from repro.core import replay
from repro.core.node import ProtocolConfig
from repro.core.sim import Cluster, NetConfig, workload
from repro.core.types import Msg, MsgKind, RmwId, TS

# ≥ 20 seeded adversarial traces in CI (acceptance criterion for PR 3)
SEEDS = range(22)
# all-aboard deployments in the replayed schedule mix (§9 epoch-conflict
# lane on the receiver, full-quorum/fallback arbitration on the issuer)
ABOARD_SEEDS = (0, 3, 7, 11, 15)


@pytest.mark.parametrize("seed", SEEDS)
def test_differential_replay_kernel(seed):
    stats = replay.run_and_replay(seed, n_ops=24, keys=3,
                                  use_kernel=True)
    assert stats["machines"] == 5
    assert stats["messages"] > 0
    assert stats["history"] == 24


@pytest.mark.parametrize("seed", ABOARD_SEEDS)
def test_differential_replay_kernel_all_aboard(seed):
    stats = replay.run_and_replay(seed, n_ops=24, keys=3, all_aboard=True,
                                  use_kernel=True)
    assert stats["machines"] == 5
    assert stats["history"] == 24


def test_replay_covers_full_vocabulary():
    """Across a handful of seeds the traces must exercise every receiver
    kind, including the §11 read write-back."""
    counts = {}
    for seed in (0, 1, 5):
        stats = replay.run_and_replay(seed, n_ops=30, keys=3,
                                      use_kernel=False)
        for k, v in stats.items():
            counts[k] = counts.get(k, 0) + v
    for kind in ("propose", "accept", "commit", "write_query", "write",
                 "read_query", "read_commit"):
        assert counts.get(kind, 0) > 0, f"vocabulary gap: no {kind} lanes"


def test_replay_jnp_path_matches_too():
    """The pure-jnp oracle path through replica_step agrees as well."""
    stats = replay.run_and_replay(3, use_kernel=False)
    assert stats["machines"] == 5


def test_replay_with_crash_and_restart():
    """Traces from crashed/restarted schedules replay cleanly (restart
    keeps the trace; a crashed machine's trace simply ends)."""
    cfg = ProtocolConfig(n_machines=5, sessions_per_machine=2)
    cl = Cluster(cfg, NetConfig(seed=9, drop_prob=0.04))
    cl.enable_msg_trace()
    workload(cl, n_ops=20, keys=2, seed=9, rmw_frac=0.5, write_frac=0.25)
    cl.step(8)
    cl.crash(4)
    cl.step(6)
    cl.restart(4)
    assert cl.run_until_quiet(max_ticks=120_000)
    stats = replay.replay_cluster(cl, n_keys=2)
    assert stats["machines"] == 5


# ---------------------------------------------------------------------------
# fused (stacked-machine) replay: cluster ticks, plane-for-plane
# ---------------------------------------------------------------------------

# seeds apart from test_sharded_replay's, whose shards=1 cases run the
# same driver
@pytest.mark.parametrize("seed", (2, 6, 10, 17))
def test_fused_replay_jnp(seed):
    """All machines share each fused (M*K,) step — the step the
    ClusterEngine serves — yet every row stays bit-identical to its own
    scalar shadow, wave for wave."""
    stats = replay.run_and_replay_fused(seed, n_ops=24, keys=3,
                                        use_kernel=False)
    assert stats["machines"] == 5
    assert stats["shards"] == 1
    assert stats["messages"] > 0
    assert stats["fused_waves"] > 0
    assert stats["history"] == 24


def test_fused_replay_kernel():
    """Same through the Pallas kernel (interpret mode): the machine axis
    folded into the lane axis pads to the block tile and back."""
    stats = replay.run_and_replay_fused(3, use_kernel=True,
                                        block_rows=1)
    assert stats["machines"] == 5
    assert stats["fused_waves"] > 0


def test_fused_replay_with_crash_and_restart():
    """Row isolation under uneven traces: a crashed machine's trace simply
    ends, so its row rides later waves as all-NOOP lanes."""
    cfg = ProtocolConfig(n_machines=5, sessions_per_machine=2)
    cl = Cluster(cfg, NetConfig(seed=9, drop_prob=0.04))
    cl.enable_msg_trace()
    workload(cl, n_ops=20, keys=2, seed=9, rmw_frac=0.5, write_frac=0.25)
    cl.step(8)
    cl.crash(4)
    cl.step(6)
    cl.restart(4)
    assert cl.run_until_quiet(max_ticks=120_000)
    stats = replay.replay_cluster_fused(cl, n_keys=2, use_kernel=False)
    assert stats["machines"] == 5


# ---------------------------------------------------------------------------
# sharded replay (shard-for-shard vs the scalar shadows)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards", (1, 2, 4))
@pytest.mark.parametrize("seed", (1, 4, 8, 13))
def test_sharded_replay(seed, shards):
    """Shard-for-shard replay against the N scalar shadows: replies,
    per-shard registration journals, and every shard block of every KV
    plane bit-identical at every shard count."""
    stats = replay.run_and_replay_fused(seed, shards=shards,
                                        use_kernel=False)
    assert stats["machines"] == 5
    assert stats["shards"] == shards
    assert stats["fused_waves"] > 0
    assert stats["lane_axis"] % shards == 0
    staged = sum(stats[f"shard{s}_lanes"] for s in range(shards))
    assert staged == stats["messages"]


def test_sharded_replay_kernel():
    """Same through the Pallas kernel (interpret mode): each shard's lane
    block pads to its own tile segment, so no compiled block spans a
    shard boundary — and the planes still match the scalar shadows."""
    stats = replay.run_and_replay_fused(3, shards=4, use_kernel=True,
                                        block_rows=1)
    assert stats["machines"] == 5
    assert stats["shards"] == 4
    assert stats["fused_waves"] > 0


def test_sharded_replay_with_crash_and_restart():
    """Uneven traces (a crashed row goes all-NOOP mid-run) stay shard-
    isolated too."""
    cfg = ProtocolConfig(n_machines=5, sessions_per_machine=2)
    cl = Cluster(cfg, NetConfig(seed=9, drop_prob=0.04))
    cl.enable_msg_trace()
    workload(cl, n_ops=20, keys=2, seed=9, rmw_frac=0.5, write_frac=0.25)
    cl.step(8)
    cl.crash(4)
    cl.step(6)
    cl.restart(4)
    assert cl.run_until_quiet(max_ticks=120_000)
    stats = replay.replay_cluster_fused(cl, n_keys=2, shards=2,
                                        use_kernel=False)
    assert stats["machines"] == 5
    assert stats["shards"] == 2


# ---------------------------------------------------------------------------
# differential proposer replay (scalar Machine vs proposer_step)
# ---------------------------------------------------------------------------

# ≥ 20 seeded faulty traces, all-aboard deployments included (acceptance
# criterion for this PR): odd seeds deploy the §9 fast path.
ISSUER_SEEDS = range(22)


@pytest.mark.parametrize("seed", ISSUER_SEEDS)
def test_differential_issuer_replay(seed):
    stats = replay.run_and_replay_issuer(seed, n_ops=24, keys=3,
                                         all_aboard=bool(seed % 2))
    assert stats["machines"] == 5
    assert stats["replies"] > 0
    assert stats["decisions"] > 0
    assert stats["history"] == 24


def test_issuer_replay_covers_decision_vocabulary():
    """Across a handful of seeds the replayed decisions must cover the
    protocol's arbitration outcomes: local accepts, commit rounds, retries,
    helping, and every ABD phase transition."""
    counts = {}
    for seed, aboard in ((0, False), (2, False), (3, True), (7, True)):
        stats = replay.run_and_replay_issuer(seed, n_ops=24, keys=3,
                                             all_aboard=aboard)
        for k, v in stats.items():
            if k.startswith("d_"):
                counts[k] = counts.get(k, 0) + v
    for d in ("d_local_accept", "d_commit_bcast", "d_commit_done", "d_retry",
              "d_help", "d_help_self", "d_stop_help", "d_log_too_low",
              "d_abd_w2", "d_abd_w_done", "d_abd_r_done", "d_abd_r_wb",
              "d_abd_rc_done"):
        assert counts.get(d, 0) > 0, f"decision vocabulary gap: no {d}"


def test_issuer_replay_with_crash_and_restart():
    """Issuer traces spanning a crash/restart replay cleanly: the restart
    parks every lane (volatile tallies died), so stale-round replies are
    dropped on both sides."""
    cfg = ProtocolConfig(n_machines=5, sessions_per_machine=2)
    cl = Cluster(cfg, NetConfig(seed=9, drop_prob=0.04))
    cl.enable_issuer_trace()
    workload(cl, n_ops=20, keys=2, seed=9, rmw_frac=0.5, write_frac=0.25)
    cl.step(8)
    cl.crash(4)
    cl.step(6)
    cl.restart(4)
    assert cl.run_until_quiet(max_ticks=120_000)
    stats = replay.replay_issuer_cluster(cl)
    assert stats["machines"] == 5
    assert stats["decisions"] > 0


def test_issuer_and_receiver_replay_share_a_schedule():
    """Both taps can record the same run: the receiver replay and the
    issuer replay validate the two halves of every machine end to end."""
    cfg = ProtocolConfig(n_machines=5, sessions_per_machine=2)
    cl = Cluster(cfg, NetConfig(seed=4, drop_prob=0.05, dup_prob=0.04))
    cl.enable_msg_trace()
    cl.enable_issuer_trace()
    workload(cl, n_ops=24, keys=3, seed=4, rmw_frac=0.45, write_frac=0.3)
    assert cl.run_until_quiet(max_ticks=120_000)
    recv = replay.replay_cluster(cl, n_keys=3)
    issu = replay.replay_issuer_cluster(cl)
    assert recv["machines"] == issu["machines"] == 5


# ---------------------------------------------------------------------------
# bucketing contract
# ---------------------------------------------------------------------------

def _msg(kind, key, cnt=1, gsess=0):
    return Msg(kind, src=0, key=key, rmw_id=RmwId(cnt, gsess),
               ts=TS(3, 0), log_no=1, value=5)


def test_bucketing_one_message_per_key_order_preserved():
    trace = [_msg(MsgKind.PROPOSE, 0), _msg(MsgKind.PROPOSE, 1),
             _msg(MsgKind.ACCEPT, 0), _msg(MsgKind.COMMIT, 0),
             _msg(MsgKind.WRITE, 1)]
    batches = replay.bucket_conflict_free(trace)
    for batch in batches:
        keys = [m.key for m in batch]
        assert len(keys) == len(set(keys)), "two messages for one key"
    # per-key order is the trace order
    for key in (0, 1):
        flat = [m for b in batches for m in b if m.key == key]
        want = [m for m in trace if m.key == key]
        assert flat == want


def test_bucketing_flushes_on_inbatch_registration():
    """A commit registering (cnt, gsess) followed by a propose with the
    same rmw-id on ANOTHER key must split batches: the vector gather reads
    pre-batch registry state, the scalar handler an up-to-date one."""
    trace = [_msg(MsgKind.COMMIT, 0, cnt=5, gsess=2),
             _msg(MsgKind.PROPOSE, 1, cnt=5, gsess=2)]
    batches = replay.bucket_conflict_free(trace)
    assert len(batches) == 2
    # ... while an unrelated rmw-id shares the batch just fine
    trace2 = [_msg(MsgKind.COMMIT, 0, cnt=5, gsess=2),
              _msg(MsgKind.PROPOSE, 1, cnt=6, gsess=2)]
    assert len(replay.bucket_conflict_free(trace2)) == 1


def test_read_commit_rides_commit_lane():
    """§11 write-backs register their rmw-id and flush like commits."""
    trace = [_msg(MsgKind.READ_COMMIT, 0, cnt=4, gsess=1),
             _msg(MsgKind.ACCEPT, 1, cnt=4, gsess=1)]
    assert len(replay.bucket_conflict_free(trace)) == 2
