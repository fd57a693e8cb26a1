"""The batched serve subsystem (repro.serve.paxos) end to end.

The acceptance bar: ``Cluster(machine_cls=BatchedMachine)`` runs the
existing seeded faulty workloads *completion-for-completion identical* to
the scalar cluster (same tags, values, carstamps, rmw-ids, in the same
order) with every safety checker green — the engines are a drop-in swap,
not a behavioral fork.  scripts/batched_smoke.py runs the full 20-seed
matrix in CI; here a representative slice plus the targeted fault cases
(crash mid-batch, restart with a fresh incarnation issuing new rmw-ids
through the int32 lanes, partitions) and the trace-replayability of a
batched machine's own taps.
"""

import functools

import pytest

from repro.core import checkers, replay
from repro.core.node import Machine, ProtocolConfig, ReqKind
from repro.core.sim import Cluster, NetConfig, completion_tuples, workload
from repro.serve.paxos import BatchedMachine

SEEDS = (0, 1, 2, 3)
ABOARD_SEEDS = (1, 3)


def faulty_cluster(machine_cls, seed, *, all_aboard=False, sessions=2,
                   trace=False):
    cfg = ProtocolConfig(n_machines=5, sessions_per_machine=sessions,
                         all_aboard=all_aboard)
    net = NetConfig(seed=seed, drop_prob=0.06, dup_prob=0.05,
                    heavy_tail_prob=0.03, heavy_tail_extra=25.0)
    cl = Cluster(cfg, net, machine_cls=machine_cls)
    if trace:
        cl.enable_msg_trace()
        cl.enable_issuer_trace()
    return cl


def run_pair(seed, *, all_aboard=False, n_ops=18, keys=3, fault=None):
    out = []
    for mcls in (Machine, BatchedMachine):
        cl = faulty_cluster(mcls, seed, all_aboard=all_aboard)
        workload(cl, n_ops=n_ops, keys=keys, seed=seed,
                 rmw_frac=0.45, write_frac=0.3)
        if fault is not None:
            fault(cl)
        assert cl.run_until_quiet(max_ticks=120_000)
        out.append(cl)
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_batched_cluster_identical_to_scalar(seed):
    scalar, batched = run_pair(seed, all_aboard=seed in ABOARD_SEEDS)
    assert completion_tuples(batched) == completion_tuples(scalar)
    checkers.check_all(batched)
    # the tick really ran through the engines
    agg = {}
    for m in batched.machines:
        for k, v in m.engine_stats.items():
            agg[k] = agg.get(k, 0) + v
    assert agg["receiver_batches"] > 0 and agg["issuer_batches"] > 0
    assert agg["receiver_lanes"] >= agg["receiver_batches"]
    assert agg["issuer_lanes"] >= agg["issuer_batches"]


def test_crash_mid_batch_and_restart_identical():
    """Crash lands while delivered messages sit unprocessed in the inbox
    (mid-batch on the batched machine); restart rejoins with persistent
    acceptor state and a fresh incarnation."""
    def fault(cl):
        cl.step(8)
        cl.network.deliver_due(cl.network.now + 1.0, cl.machines)
        assert any(m.inbox for m in cl.machines)
        cl.crash(4)
        cl.step(6)
        cl.restart(4)
    scalar, batched = run_pair(7, fault=fault)
    assert completion_tuples(batched) == completion_tuples(scalar)
    checkers.check_all(batched)


def test_restarted_machine_issues_new_rmw_ids():
    """Post-restart submissions exercise the incarnation-tagged rmw-id
    counters through the engines' int32 lanes (the 1<<24 stride)."""
    def fault(cl):
        cl.step(8)
        cl.crash(4)
        cl.step(6)
        cl.restart(4)
        cl.step(4)
        for sess in range(cl.cfg.sessions_per_machine):
            cl.rmw(4, sess, key=sess % 2)
    scalar, batched = run_pair(5, fault=fault)
    assert completion_tuples(batched) == completion_tuples(scalar)
    checkers.check_all(batched)
    m4 = batched.machines[4]
    assert m4.incarnation == 1
    assert any(cnt > 1 << 24 for cnt in m4.rmw_counters)
    assert any(mid == 4 and c.kind == ReqKind.RMW
               and c.rmw_id.counter > 1 << 24
               for mid, _s, c in batched.completions)


def test_partition_heal_identical():
    def fault(cl):
        cl.step(5)
        cl.network.partition([0, 1], [3, 4])
        cl.step(60)
        cl.network.heal()
    scalar, batched = run_pair(3, fault=fault)
    assert completion_tuples(batched) == completion_tuples(scalar)
    checkers.check_all(batched)


def test_batched_machine_traces_replay_clean():
    """A batched machine's own msg/issuer taps satisfy the differential
    replay oracle — the live path and the replay harness share one set of
    converters/loaders, and this closes the loop."""
    cl = faulty_cluster(BatchedMachine, 2, trace=True)
    workload(cl, n_ops=14, keys=3, seed=2, rmw_frac=0.5, write_frac=0.25)
    assert cl.run_until_quiet(max_ticks=120_000)
    stats = replay.replay_cluster(cl, n_keys=3, use_kernel=False)
    assert stats["machines"] == 5 and stats["messages"] > 0
    istats = replay.replay_issuer_cluster(cl)
    assert istats["machines"] == 5 and istats["decisions"] > 0


def test_registry_and_steering_surfaces():
    cl = faulty_cluster(BatchedMachine, 4)
    workload(cl, n_ops=10, keys=2, seed=4, rmw_frac=0.6, write_frac=0.2)
    assert cl.run_until_quiet(max_ticks=120_000)
    m = cl.machines[0]
    assert m.steering.stats["steered"] > 0
    # the persistent ingest scheduler carries serve-path observability
    assert m.ingest.stats["batches"] > 0
    assert m.ingest.stats["emitted"] == m.engine_stats["receiver_lanes"]
    assert m.ingest.pending() == 0
    # bridge quacks like the scalar kvs dict
    kv = m.kvs[0]
    assert kv.key == 0 and m.kvs.get(0) is kv
    assert 0 in m.kvs and m.kvs.n_keys >= 2


def test_sticky_routing_via_batched_registry():
    """serve/engine.py route(): one CAS-with-fetch round trip through a
    PaxosRegistry whose replicas are BatchedMachines — sticky-session
    routing exercises the batched serve path end to end."""
    from repro.coord.registry import PaxosRegistry
    from repro.serve.engine import DecodeEngine, ServeConfig

    class _NoModel:                      # route() never touches the model
        def decode_step(self, *args):
            raise AssertionError("routing must not decode")

    reg = PaxosRegistry(n_machines=3, all_aboard=True, sessions=2,
                        machine_cls=BatchedMachine)
    engines = [DecodeEngine(_NoModel(), None, ServeConfig(), registry=reg,
                            replica_id=i) for i in range(2)]
    rmws_before = sum(m.stats.get("rmw_completed", 0)
                      for m in reg.cluster.machines)
    assert engines[0].route(7) == 0      # claims the session
    assert engines[1].route(7) == 0      # sticky: loser learns from the CAS
    assert engines[1].route(9) == 1
    assert engines[0].route(9) == 1
    rmws_after = sum(m.stats.get("rmw_completed", 0)
                     for m in reg.cluster.machines)
    # one consensus op per first sight of a session — the read-then-CAS
    # double round trip is gone
    assert rmws_after - rmws_before == 4
    # repeat lookups hit the write-once local cache: no further consensus
    assert engines[0].route(7) == 0 and engines[1].route(9) == 1
    assert sum(m.stats.get("rmw_completed", 0)
               for m in reg.cluster.machines) == rmws_after
    assert sum(m.engine_stats["receiver_batches"]
               for m in reg.cluster.machines) > 0


@pytest.mark.slow
def test_batched_cluster_kernel_mode():
    """One small seed with the receiver step through the Pallas kernel in
    interpret mode (block_rows=1) instead of the jnp oracle."""
    mcls = functools.partial(BatchedMachine, use_kernel=True,
                             block_rows=1)
    cfg = ProtocolConfig(n_machines=5, sessions_per_machine=2)
    net = NetConfig(seed=6, drop_prob=0.04)
    ref = Cluster(cfg, NetConfig(seed=6, drop_prob=0.04))
    cl = Cluster(cfg, net, machine_cls=mcls)
    for c in (ref, cl):
        workload(c, n_ops=8, keys=2, seed=6, rmw_frac=0.5, write_frac=0.25)
        assert c.run_until_quiet(max_ticks=120_000)
    assert completion_tuples(cl) == completion_tuples(ref)
    checkers.check_all(cl)
