"""Donation-safety regression for the fused ClusterEngine.

The engine jits its fused steps with ``donate_argnums=(0,)``: the stacked
KV / ProposerTable device buffers are *donated* to XLA each wave and may
be reused as the output allocation.  The safety contract
(:class:`repro.serve.paxos.cluster_engine.PlaneStack`) is that the host
mirror only ever syncs from the freshest engine *output*, never from a
donated input buffer.  A violation would show up as nondeterminism: the
same tick, executed from the same state, would read scrambled planes.

These tests pin the contract the way the ISSUE's acceptance describes it:
run the same tick twice from a checked-out snapshot and require bit-equal
planes, identical completions, and identical
``repro.checkpoint.store`` round-trips.
"""

import numpy as np
import pytest

from repro.checkpoint import store
from repro.core.node import ProtocolConfig
from repro.core.sim import Cluster, NetConfig, completion_tuples, workload
from repro.serve.paxos import BatchedMachine

CFG = dict(n_machines=3, sessions_per_machine=2)


def _cluster(seed=11):
    cl = Cluster(ProtocolConfig(**CFG), NetConfig(seed=seed),
                 machine_cls=BatchedMachine)
    workload(cl, n_ops=24, keys=4, seed=seed, rmw_frac=0.5, write_frac=0.3)
    return cl


def _checkout(engine):
    """Copies of both host mirrors (the 'checked-out snapshot'), each
    checked against its device-resident stack: every wave refreshes the
    mirror from its own output, so a clean mirror is the device state."""
    for stack in (engine.kv, engine.tab):
        if not stack.host_dirty:
            np.testing.assert_array_equal(np.asarray(stack.dev), stack.host)
    return engine.kv.host.copy(), engine.tab.host.copy()


def test_same_tick_twice_from_checked_out_snapshot():
    """Two identical clusters advanced in lockstep: every tick is the
    'same tick run twice' from bit-identical checked-out state.  Any
    read-after-donate would desynchronize them."""
    a, b = _cluster(), _cluster()
    for tick in range(60):
        a.step()
        b.step()
        kv_a, tab_a = _checkout(a.engine)
        kv_b, tab_b = _checkout(b.engine)
        np.testing.assert_array_equal(kv_a, kv_b, err_msg=f"tick {tick} kv")
        np.testing.assert_array_equal(tab_a, tab_b,
                                      err_msg=f"tick {tick} tab")
    assert completion_tuples(a) == completion_tuples(b)
    assert a.engine.stats == b.engine.stats
    assert a.engine.stats["fused_receiver_calls"] > 0


def test_checkout_is_stable_across_repeated_pulls():
    """A checked-out snapshot must not change on re-checkout: the mirror
    is only ever refreshed from the freshest output, and checking out
    twice with no engine step in between has nothing new to read.  (Had
    the mirror come from the *donated* buffer, XLA would have been free
    to overwrite it.)"""
    cl = _cluster()
    for _ in range(20):
        cl.step()
    kv1, tab1 = _checkout(cl.engine)
    kv2, tab2 = _checkout(cl.engine)
    np.testing.assert_array_equal(kv1, kv2)
    np.testing.assert_array_equal(tab1, tab2)


def test_checkpoint_roundtrip_of_checked_out_planes(tmp_path):
    """repro.checkpoint.store round-trip of the checked-out stacks is
    identical before and after further donated-engine ticks re-run from
    the same state (the ISSUE's donation acceptance gate)."""
    a, b = _cluster(), _cluster()
    for _ in range(25):
        a.step()
        b.step()
    trees = []
    for name, cl in (("a", a), ("b", b)):
        kv, tab = _checkout(cl.engine)
        tree = {"kv": kv, "tab": tab}
        assert store.save(str(tmp_path), f"run_{name}", 1, tree)
        got, step = store.restore(str(tmp_path), f"run_{name}",
                                  like=tree, step=1)
        assert step == 1
        np.testing.assert_array_equal(np.asarray(got["kv"]), kv)
        np.testing.assert_array_equal(np.asarray(got["tab"]), tab)
        trees.append(tree)
    # the two re-runs checkpointed the same planes, byte for byte
    np.testing.assert_array_equal(trees[0]["kv"], trees[1]["kv"])
    np.testing.assert_array_equal(trees[0]["tab"], trees[1]["tab"])


def test_donated_tick_preserves_scalar_identity():
    """End-to-end: the donated fused path completes the exact op stream
    the scalar cluster does (the standing differential bar, re-pinned
    here so a donation bug cannot hide behind green unit lanes)."""
    from repro.core.node import Machine

    sc = Cluster(ProtocolConfig(**CFG), NetConfig(seed=11),
                 machine_cls=Machine)
    workload(sc, n_ops=24, keys=4, seed=11, rmw_frac=0.5, write_frac=0.3)
    ba = _cluster(seed=11)
    assert sc.run_until_quiet(max_ticks=50_000)
    assert ba.run_until_quiet(max_ticks=50_000)
    assert completion_tuples(sc) == completion_tuples(ba)


# ---------------------------------------------------------------------------
# sharded plane layout: the same contracts, shard block by shard block
# ---------------------------------------------------------------------------

import functools  # noqa: E402

from repro.core.lanes import ShardMap  # noqa: E402
from repro.serve.paxos import SteeringTable  # noqa: E402


def _sharded_cluster(seed=11, shards=2, **kw):
    mcls = functools.partial(BatchedMachine, shards=shards, **kw)
    cl = Cluster(ProtocolConfig(**CFG), NetConfig(seed=seed),
                 machine_cls=mcls)
    workload(cl, n_ops=24, keys=4, seed=seed, rmw_frac=0.5, write_frac=0.3)
    return cl


@pytest.mark.parametrize("shards", (1, 2, 4))
def test_sharded_scalar_identity(shards):
    """The sharded batched cluster completes the scalar cluster's exact
    op stream at every shard count (shards=1 pins that the sharded code
    path degenerates to the classic layout)."""
    from repro.core.node import Machine

    sc = Cluster(ProtocolConfig(**CFG), NetConfig(seed=11),
                 machine_cls=Machine)
    workload(sc, n_ops=24, keys=4, seed=11, rmw_frac=0.5, write_frac=0.3)
    ba = _sharded_cluster(seed=11, shards=shards)
    assert sc.run_until_quiet(max_ticks=50_000)
    assert ba.run_until_quiet(max_ticks=50_000)
    assert completion_tuples(sc) == completion_tuples(ba)
    eng = ba.machines[0]._engine
    assert eng.stats["shards"] == shards
    if shards > 1:
        assert sum(eng.stats["receiver_shard_lanes"]) \
            == eng.stats["fused_receiver_lanes"]


@pytest.mark.parametrize("shards", (2, 4))
@pytest.mark.parametrize("use_kernel", (False, True))
def test_sharded_donation_safety_per_shard(shards, use_kernel):
    """Lockstep twins with a sharded plane: after every tick each shard's
    lane block must match bit for bit (a read-after-donate — or a kernel
    segment bleeding across a shard boundary — desynchronizes them)."""
    kw = dict(use_kernel=True, block_rows=1) if use_kernel else {}
    a = _sharded_cluster(shards=shards, **kw)
    b = _sharded_cluster(shards=shards, **kw)
    ticks = 30 if use_kernel else 60
    for tick in range(ticks):
        a.step()
        b.step()
        kv_a, tab_a = _checkout(a.engine)
        kv_b, tab_b = _checkout(b.engine)
        sm = a.engine.kv.shard_map
        for s in range(shards):
            sl = sm.slice_of(s)
            np.testing.assert_array_equal(
                kv_a[:, :, sl], kv_b[:, :, sl],
                err_msg=f"tick {tick} kv shard {s}")
        np.testing.assert_array_equal(tab_a, tab_b,
                                      err_msg=f"tick {tick} tab")
    assert completion_tuples(a) == completion_tuples(b)


def test_sharded_checkpoint_roundtrip(tmp_path):
    """Per-shard plane serialization round-trips bit for bit, and a
    checkpoint written at shards=4 restores into a scalar layout (and
    vice versa) — the shard split is a storage layout, not a schema."""
    cl = _sharded_cluster(shards=4)
    for _ in range(25):
        cl.step()
    kv, tab = _checkout(cl.engine)
    tree = {"kv": kv, "tab": tab}
    assert store.save(str(tmp_path), "run_s", 1, tree, shards=4)

    # the npz really holds per-shard lane blocks
    import os
    data = np.load(os.path.join(str(tmp_path), "run_s", "step_00000001",
                                "shards.npz"))
    assert "kv@shard0" in data and "kv@shard3" in data and "kv" not in data
    sm = cl.engine.kv.shard_map
    for s in range(4):
        np.testing.assert_array_equal(data[f"kv@shard{s}"],
                                      kv[:, :, sm.slice_of(s)])

    # restore is layout-agnostic: same tree back, bit for bit
    got, step = store.restore(str(tmp_path), "run_s", like=tree, step=1)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(got["kv"]), kv)
    np.testing.assert_array_equal(np.asarray(got["tab"]), tab)

    # and an unsharded save restores identically too
    assert store.save(str(tmp_path), "run_u", 1, tree)
    got_u, _ = store.restore(str(tmp_path), "run_u", like=tree, step=1)
    np.testing.assert_array_equal(np.asarray(got_u["kv"]), kv)


def test_foreign_shard_checkout_raises():
    """A ShardedKVView checkout of a key steered to another shard is a
    loud ValueError, read and write alike."""
    cl = _sharded_cluster(shards=2)
    for _ in range(10):
        cl.step()
    mach = cl.machines[0]
    sm = mach.kvs.shard_map
    foreign = sm.lanes_per_shard          # first key of shard 1
    view = mach.kvs.shard_view(0)
    with pytest.raises(ValueError, match="foreign plane block"):
        view[foreign]
    with pytest.raises(ValueError, match="foreign plane block"):
        view[foreign] = mach.kvs[foreign]
    assert foreign not in view
    assert (foreign - 1) in view
    # the owning shard's view checks out normally
    assert mach.kvs.shard_view(1)[foreign] is not None
    with pytest.raises(ValueError):
        mach.kvs.shard_view(9)


def test_steering_remap_foreign_shard_raises():
    """A view remap whose shard map would move a *live* session lane to a
    foreign shard raises; moving only idle lanes is allowed."""
    table = SteeringTable(4, mid=0, shard_map=ShardMap(2, 4))
    table.register(3, lid=(7 << 16) | 3)
    # same layout: fine (live lane 3 stays in shard 1)
    table.remap(1, shard_map=ShardMap(2, 4))
    assert table.epoch == 1
    # 4-way layout moves lane 3 from shard 1 to shard 3: live -> loud
    with pytest.raises(ValueError, match="live session lane 3"):
        table.remap(2, shard_map=ShardMap(4, 4))
    # an idle lane may move freely
    idle = SteeringTable(4, mid=0, shard_map=ShardMap(2, 4))
    idle.remap(1, shard_map=ShardMap(4, 4))
    assert idle.shard_map.n_shards == 4


def test_steering_table_shard_of():
    table = SteeringTable(4, mid=0, shard_map=ShardMap(2, 4))
    assert table.shard_of((1 << 16) | 0) == 0
    assert table.shard_of((1 << 16) | 3) == 1
    assert table.shard_of((1 << 16) | 9) is None     # unroutable lane
    assert SteeringTable(4).shard_of(2) is None      # unsharded


class _FakeDevice:
    def __init__(self, platform):
        self.platform = platform
        self.device_kind = f"fake {platform}"


@pytest.mark.parametrize("platform", ("tpu", "cpu"))
def test_shard_mesh_needs_devices_on_tpu(monkeypatch, platform):
    """More shards than devices: a TPU run raises rather than put every
    shard on one chip; CPU keeps the layout-only mode the tests above
    use."""
    from repro.serve.paxos import cluster_engine
    monkeypatch.setattr(cluster_engine.jax, "devices",
                        lambda: [_FakeDevice(platform)])
    assert cluster_engine._shard_mesh(1) is None
    if platform == "tpu":
        with pytest.raises(ValueError, match="needs 4 devices"):
            cluster_engine._shard_mesh(4)
    else:
        assert cluster_engine._shard_mesh(4) is None


# ---------------------------------------------------------------------------
# mirror coherence: each wave refreshes the mirror and ships host writes
# ---------------------------------------------------------------------------

def _checked_after_every_call(engine, log):
    """Wrap the engine's waves: after each, every stack a fused call of
    the wave stepped must equal its device array, and so must the other
    stack unless host code has written to it since its last wave.  Logs
    the halves of each wave."""
    stepped_by = {"_receiver_half": engine.kv, "_issuer_half": engine.tab}

    def wave(halves, _run=engine._run_wave):
        names = [half.__name__ for half in halves]
        out = _run(halves)
        for name in names:
            assert not stepped_by[name].host_dirty
        for stack in (engine.kv, engine.tab):
            if not stack.host_dirty:
                np.testing.assert_array_equal(
                    np.asarray(stack.dev), stack.host,
                    err_msg=f"wave {len(log)} {stack.fields[0]}")
        log.append(names)
        return out
    engine._run_wave = wave


MIRROR_CASES = [
    pytest.param(replicas, shards, None, False, id=f"{replicas}-{shards}")
    for replicas in (3, 5) for shards in (1, 4)] + [
    # a plane wider than a wave: the compact receiver wire
    pytest.param(replicas, 1, 1024, use_kernel,
                 id=f"{replicas}-1-k1024-{'kernel' if use_kernel else 'jnp'}")
    for replicas in (3, 5) for use_kernel in (False, True)]


@pytest.mark.parametrize("replicas,shards,lanes,use_kernel", MIRROR_CASES)
def test_mirror_coherent_after_every_fused_call(replicas, shards, lanes,
                                                use_kernel):
    """All-aboard on, ABD reads and writes and RMWs, one crash and
    restart: after every fused call the host mirrors equal the device
    stacks, and the completions are the scalar cluster's.  With ``lanes``
    per machine (keys at the top of the plane) every receiver wave takes
    the compact wire."""
    from repro.core.node import Machine

    cfg = ProtocolConfig(n_machines=replicas, sessions_per_machine=2,
                         all_aboard=True)
    kw = dict(use_kernel=True, block_rows=8) if use_kernel else {}
    mcls = functools.partial(BatchedMachine, shards=shards, **kw)
    pair = [Cluster(cfg, NetConfig(seed=13), machine_cls=cls)
            for cls in (Machine, mcls)]
    key_base = 0
    if lanes:
        pair[1].machines[0].kvs.ensure(lanes - 1)
        key_base = lanes - 24
    for cl in pair:
        workload(cl, n_ops=40, keys=4, seed=13, rmw_frac=0.6,
                 write_frac=0.2, key_base=key_base)
    log = []
    _checked_after_every_call(pair[1].engine, log)
    victim = replicas - 1
    for cl in pair:
        cl.step(8)
        cl.crash(victim)
        cl.step(6)
        cl.restart(victim)
        assert cl.run_until_quiet(max_ticks=50_000)
    assert completion_tuples(pair[1]) == completion_tuples(pair[0])
    tel = pair[1].engine.telemetry()
    assert sum(map(len, log)) == tel["plane_wave_refreshes"] > 0
    assert len(log) == (tel["fused_receiver_calls"]
                        + tel["fused_issuer_calls"] - tel["paired_waves"])
    assert tel["plane_wave_ships"] > 0 and tel["row_reloads"] > 0
    assert tel["compact_receiver_waves"] == (
        tel["fused_receiver_calls"] if lanes else 0)


def test_one_transfer_each_way_per_fused_call(monkeypatch):
    """The 3-replica TPC-C cell's shape (3 replicas, 40 sessions each,
    All-aboard, FAA-heavy on 120 counters), clock on: after warm-up each
    wave, with one fused call or two, makes one upload and one download,
    no whole-stack push runs on its own, and the stacks shipped are
    exactly the calls that found host writes to ship."""
    from repro.obs import FlightRecorder
    from repro.serve.paxos import cluster_engine

    cfg = ProtocolConfig(n_machines=3, sessions_per_machine=40,
                         all_aboard=True)
    cl = Cluster(cfg, NetConfig(seed=17), machine_cls=BatchedMachine)
    cl.attach_obs(FlightRecorder(mode="off"))
    eng = cl.engine
    cl.machines[0].kvs.ensure(119)              # 128 lanes, as the cell
    workload(cl, n_ops=240, keys=120, seed=17, rmw_frac=0.92)
    cl.step(20)                                  # warm-up
    before = eng.telemetry()

    puts = []
    real_put = cluster_engine.jax.device_put
    monkeypatch.setattr(cluster_engine.jax, "device_put",
                        lambda *a, **kw: puts.append(1) or real_put(*a, **kw))
    # a call carries its stack when the step is handed another array
    # than the one resident when the wave began
    resident, carried = [], []

    def wave(halves, _run=eng._run_wave):
        resident.append({"kv": eng.kv.dev, "tab": eng.tab.dev})
        return _run(halves)
    eng._run_wave = wave
    for name, stack in (("_fused_receiver_step", "kv"),
                        ("_fused_issuer_step", "tab")):
        def step(dev, *a, _step=getattr(cluster_engine, name), _stack=stack,
                 **kw):
            carried.append(dev.nbytes if dev is not resident[-1][_stack]
                           else 0)
            return _step(dev, *a, **kw)
        monkeypatch.setattr(cluster_engine, name, step)
    cl.step(60)
    after = eng.telemetry()

    def delta(key):
        return after.get(key, 0) - before.get(key, 0)
    calls = delta("fused_receiver_calls") + delta("fused_issuer_calls")
    waves = calls - delta("paired_waves")
    assert calls == len(carried) > 100
    assert 0 < delta("paired_waves") and waves == len(resident)
    assert delta("span.engine.upload.n") == waves == len(puts)
    assert delta("span.engine.download.n") == waves
    assert delta("span.plane.push.n") == 0
    assert delta("plane_syncs") == 0
    assert delta("plane_wave_refreshes") == calls
    assert delta("plane_wave_ships") == sum(map(bool, carried)) > 0
    # every stack byte that rode a wave is counted
    assert delta("h2d_bytes") == delta("staging_h2d_bytes") + sum(carried)
    assert delta("d2h_bytes") == (
        delta("staging_d2h_bytes")
        + delta("fused_receiver_calls") * eng.kv.host.nbytes
        + delta("fused_issuer_calls") * eng.tab.host.nbytes)


@pytest.mark.parametrize("lanes", (128, 1024), ids=("dense", "compact"))
def test_paired_waves_match_scalar_and_each_call_alone(monkeypatch, lanes):
    """A wave with receiver and issuer work makes one round trip each way
    for both halves.  On both receiver wires (3 replicas, 128 lanes a
    machine: dense; 1024, wider than a wave: compact) the run pairs
    waves and completes the scalar cluster's op stream, and every fused
    call of a paired wave, replayed alone on copies of its operands,
    gives the outputs it gave in the wave."""
    from repro.core.node import Machine
    from repro.serve.paxos import cluster_engine

    cfg = ProtocolConfig(n_machines=3, sessions_per_machine=4,
                         all_aboard=True)
    pair = [Cluster(cfg, NetConfig(seed=29), machine_cls=cls)
            for cls in (Machine, BatchedMachine)]
    eng = pair[1].engine
    pair[1].machines[0].kvs.ensure(lanes - 1)
    for cl in pair:
        workload(cl, n_ops=60, keys=8, seed=29, rmw_frac=0.6,
                 write_frac=0.2, key_base=lanes - 8)

    wave = {"paired": False, "calls": []}
    checked = []
    for name in ("_fused_receiver_step", "_fused_issuer_step"):
        def step(*args, _step=getattr(cluster_engine, name), _name=name,
                 **kw):
            if wave["paired"]:
                alone = _step(*[np.array(a) for a in args], **kw)
                wave["calls"].append(
                    (_name, [np.asarray(a) for a in alone]))
            out = _step(*args, **kw)
            if wave["paired"]:
                wave["calls"][-1] += (out,)
            return out
        monkeypatch.setattr(cluster_engine, name, step)

    def run_wave(halves, _run=eng._run_wave):
        wave.update(paired=len(halves) > 1, calls=[])
        results = _run(halves)
        if wave["paired"]:
            assert [c[0] for c in wave["calls"]] == [
                "_fused_receiver_step", "_fused_issuer_step"]
        for name, alone, out in wave["calls"]:
            for want, got in zip(alone, out):
                np.testing.assert_array_equal(np.asarray(got), want,
                                              err_msg=name)
        checked.append(len(wave["calls"]))
        return results
    eng._run_wave = run_wave

    for cl in pair:
        assert cl.run_until_quiet(max_ticks=50_000)
    assert completion_tuples(pair[1]) == completion_tuples(pair[0])
    tel = eng.telemetry()
    assert tel["paired_waves"] > 0
    assert sum(checked) == 2 * tel["paired_waves"]
    assert len(checked) == (tel["fused_receiver_calls"]
                            + tel["fused_issuer_calls"] - tel["paired_waves"])
    assert tel["compact_receiver_waves"] == (
        tel["fused_receiver_calls"] if lanes > 128 else 0)


# ---------------------------------------------------------------------------
# telemetry: host<->device bytes split into staging and whole stacks
# ---------------------------------------------------------------------------

def _bytes_split_holds(tel):
    for way in ("h2d", "d2h"):
        assert tel[f"{way}_bytes"] == (tel[f"staging_{way}_bytes"]
                                       + tel[f"stack_{way}_bytes"])


def test_bytes_split_into_staging_and_stacks():
    """After a mixed run (reads, writes, RMWs, a crash and a restart)
    ``h2d_bytes``/``d2h_bytes`` are the staging plus the whole-stack
    bytes, and an out-of-wave sync adds one stack to the stack bytes
    alone."""
    cfg = ProtocolConfig(n_machines=5, sessions_per_machine=2,
                         all_aboard=True)
    cl = Cluster(cfg, NetConfig(seed=21), machine_cls=BatchedMachine)
    workload(cl, n_ops=40, keys=6, seed=21, rmw_frac=0.4, write_frac=0.3)
    cl.step(8)
    cl.crash(4)
    cl.step(6)
    cl.restart(4)
    assert cl.run_until_quiet(max_ticks=50_000)
    eng = cl.engine
    before = eng.telemetry()
    _bytes_split_holds(before)
    assert before["stack_h2d_bytes"] > 0 and before["stack_d2h_bytes"] > 0
    assert before["staging_h2d_bytes"] > 0
    eng.kv.write_views(0)["value"][3] = 9         # a host write
    eng.kv.push()                                 # synced out of a wave
    after = eng.telemetry()
    _bytes_split_holds(after)
    assert after["stack_h2d_bytes"] == (before["stack_h2d_bytes"]
                                        + eng.kv.host.nbytes)
    for key in ("staging_h2d_bytes", "staging_d2h_bytes", "stack_d2h_bytes"):
        assert after[key] == before[key]


def _machine_sums(machines):
    """The protocol counters of ``telemetry()``, summed over
    ``machines``' stats by hand."""
    def total(*stats):
        return sum(m.stats.get(s, 0) for m in machines for s in stats)
    return {"abd_read_write_backs": total("read_write_backs"),
            "round_timeouts": total("round_timeouts"),
            "abd_retransmits": total("abd_retransmits"),
            "all_aboard_attempts": total("all_aboard_attempts"),
            "all_aboard_timeouts": total("all_aboard_timeouts"),
            "helps": total("helps", "help_after_wait"),
            "rmw_completed": total("rmw_completed")}


def test_recovery_counters_sum_the_machines_across_faults():
    """After a partition and its heal, and after a crash and a restart,
    the protocol counters in ``telemetry()`` are the machines' stats
    summed, the replaced incarnation included, and none falls."""
    cfg = ProtocolConfig(n_machines=5, sessions_per_machine=2,
                         all_aboard=True)
    cl = Cluster(cfg, NetConfig(seed=23), machine_cls=BatchedMachine)
    workload(cl, n_ops=80, keys=4, seed=23, rmw_frac=0.8, write_frac=0.1)
    eng = cl.engine

    def counters():
        t = eng.telemetry()
        return {k: t[k] for k in _machine_sums([])}

    cl.network.partition([4], [0, 1, 2, 3])
    cl.step(60)
    cl.network.heal()
    cl.step(20)
    healed = counters()
    assert healed == _machine_sums(cl.machines)
    for key in ("round_timeouts", "all_aboard_attempts",
                "all_aboard_timeouts", "helps", "rmw_completed"):
        assert healed[key] > 0, key
    old = cl.machines[3]
    cl.crash(3)
    cl.step(10)
    crashed = counters()
    assert _machine_sums([old])["rmw_completed"] > 0
    cl.restart(3)
    assert counters() == crashed            # the old incarnation stays
    assert cl.run_until_quiet(max_ticks=50_000)
    final = counters()
    assert final == _machine_sums(cl.machines + [old])
    assert all(final[k] >= crashed[k] >= healed[k] for k in final)
    assert final["rmw_completed"] > crashed["rmw_completed"]


# ---------------------------------------------------------------------------
# the compact receiver wire: bytes per wave independent of the plane
# ---------------------------------------------------------------------------

def _served(lanes, seed=19):
    """Three replicas, All-aboard, a mixed load on keys 100-105, the KV
    plane sized to ``lanes`` per machine before the first op."""
    cfg = ProtocolConfig(n_machines=3, sessions_per_machine=2,
                         all_aboard=True)
    cl = Cluster(cfg, NetConfig(seed=seed), machine_cls=BatchedMachine)
    cl.machines[0].kvs.ensure(lanes - 1)
    workload(cl, n_ops=48, keys=6, seed=seed, rmw_frac=0.4, write_frac=0.3,
             key_base=100)
    return cl


def test_receiver_wave_bytes_do_not_grow_with_lanes():
    """The same traffic on planes of 1,024 and 16,384 lanes per machine:
    once the stack is resident, every receiver wave takes the compact
    wire and moves the same staging and KV-stack bytes on both."""
    per_wave = []
    runs = []
    for lanes in (1024, 16384):
        cl = _served(lanes)
        cl.step(4)                     # the first wave made the stack resident
        eng = cl.engine
        before = dict(eng.telemetry(), kv_h2d=eng.kv.h2d_bytes,
                      kv_d2h=eng.kv.d2h_bytes, kv_ships=eng.kv.wave_ships)
        assert cl.run_until_quiet(max_ticks=50_000)
        after = dict(eng.telemetry(), kv_h2d=eng.kv.h2d_bytes,
                     kv_d2h=eng.kv.d2h_bytes, kv_ships=eng.kv.wave_ships)
        delta = {k: after[k] - before[k] for k in (
            "fused_receiver_calls", "compact_receiver_waves",
            "staging_h2d_bytes", "staging_d2h_bytes", "kv_h2d", "kv_d2h",
            "kv_ships", "patched_lanes")}
        calls = delta["fused_receiver_calls"]
        assert calls > 10 and delta["compact_receiver_waves"] == calls
        assert delta["kv_ships"] == 0        # no whole stack rode a wave
        _bytes_split_holds(after)
        per_wave.append({k: v / calls for k, v in delta.items()})
        runs.append(completion_tuples(cl))
    assert runs[0] == runs[1]
    assert per_wave[0] == per_wave[1]
    # down, a wave's KV bytes are the 18 columns of its 3 x 128 entries
    assert per_wave[0]["kv_d2h"] == 18 * 3 * 128 * 4


def test_host_writes_ride_as_lane_patches():
    """On a compact wire, a bridge checkout and flush reaches the device
    as a lane patch, not as the whole stack; a row reload, or more patch
    lanes than one patch array holds, still ships the whole stack."""
    cl = _served(1024)
    assert cl.run_until_quiet(max_ticks=50_000)
    eng = cl.engine
    bridge = cl.machines[1].kvs
    width = eng._wave_width()
    assert width == 3 * 128

    def wave():
        before = eng.telemetry()
        eng._run_wave([eng._receiver_half([])])  # nothing staged
        after = eng.telemetry()
        assert after["compact_receiver_waves"] == (
            before["compact_receiver_waves"] + 1)
        np.testing.assert_array_equal(np.asarray(eng.kv.dev), eng.kv.host)
        return {k: after[k] - before[k]
                for k in ("plane_wave_ships", "patched_lanes")}

    value = eng.kv.fields.index("value")
    bridge[700].value = 4242                 # a checkout, written
    _ = bridge[701]                          # a checkout, read only
    assert wave() == {"plane_wave_ships": 0, "patched_lanes": 2}
    assert np.asarray(eng.kv.dev)[value, 1, 700] == 4242

    for key in range(width + 1):             # one lane too many to patch
        bridge[key]
    assert wave() == {"plane_wave_ships": 1, "patched_lanes": 0}

    eng.kv.load_row(2, eng.kv, 1)            # a row reload: whole rows
    assert wave() == {"plane_wave_ships": 1, "patched_lanes": 0}
    np.testing.assert_array_equal(np.asarray(eng.kv.dev)[:, 2],
                                  np.asarray(eng.kv.dev)[:, 1])


@pytest.mark.parametrize("use_kernel", (False, True))
def test_compact_step_equals_dense_step(use_kernel):
    """On random state, the compact operands (staged entries, lane patches,
    some on the same lanes) step to exactly the outputs of the dense
    operands they stand for, and the gather reads those outputs at the
    entries."""
    from repro.core import vector
    from repro.serve.paxos import cluster_engine as ce

    m, k = 3, 512
    n, w, ne = m * k, m * 128, 300
    rng = np.random.default_rng(5)
    kv = rng.integers(0, 4, (ce.N_KV, m, k)).astype(np.int32)
    idx = rng.choice(n, ne, replace=False)
    vals = rng.integers(0, 4, (ce.N_MSGREG, ne)).astype(np.int32)
    vals[vector.MsgBatch._fields.index("kind")] = rng.integers(0, 9, ne)
    pidx = np.concatenate([idx[:20], rng.choice(
        np.setdiff1d(np.arange(n), idx), 20, replace=False)])
    pvals = rng.integers(0, 4, (ce.N_KV, 40)).astype(np.int32)
    kv_dense = kv.copy()
    kv_dense.reshape(ce.N_KV, n)[:, pidx] = pvals
    msg = np.empty((ce.N_MSGREG, m, k), np.int32)
    msg[:] = ce._NOOP_COL[:, None, None]
    msg.reshape(ce.N_MSGREG, n)[:, idx] = vals
    entries = np.empty((1 + ce.N_MSGREG, w), np.int32)
    entries[0] = n
    entries[1:] = ce._NOOP_COL[:, None]
    entries[0, :ne], entries[1:, :ne] = idx, vals
    patches = np.zeros((1 + ce.N_KV, w), np.int32)
    patches[0] = n
    patches[0, :40], patches[1:, :40] = pidx, pvals
    step = functools.partial(ce._fused_receiver_step, use_kernel=use_kernel,
                             block_rows=8)
    want = [np.asarray(a) for a in step(kv_dense, msg)]
    got = step(kv, entries, patches)
    packed = np.asarray(ce._touched_lanes(*got, entries))
    for a, b in zip(want, got):
        np.testing.assert_array_equal(np.asarray(b), a)
    rows, lanes = idx // k, idx % k
    np.testing.assert_array_equal(packed[:, :ne], np.concatenate([
        want[0][:, rows, lanes], want[1][:, rows, lanes],
        want[2][rows, lanes][None]]))


@pytest.mark.parametrize("shard_lanes", (None, 8))
def test_stacked_issuer_step_kernel_matches_jnp(shard_lanes):
    """The served issuer step, through the Pallas kernel and through the
    jnp oracle, on random ``(M, S)`` planes with a quorum-parameter column
    per machine (each machine's view pins its own quorums) gives the same
    planes bit for bit, with whole-axis or per-shard kernel segments."""
    from repro.core import proposer_vector as pv
    from repro.core.proposer import Decision
    from repro.serve.paxos import cluster_engine as ce

    m, s = 3, 16
    rng = np.random.default_rng(23)
    tab = rng.integers(-1, 4, (len(pv.ProposerTable._fields), m, s))
    rep = rng.integers(-1, 4, (len(pv.IssuerReplyBatch._fields), m, s))
    # live propose and accept rounds with partial tallies, and mostly-ack
    # replies to them, so that quorum sizes decide
    for fields, planes, f, vals in (
            (pv.ProposerTable, tab, "phase", (1, 2)),
            (pv.ProposerTable, tab, "lid", (1,)),
            (pv.ProposerTable, tab, "rep_bits", range(32)),
            (pv.ProposerTable, tab, "ack_bits", range(32)),
            (pv.IssuerReplyBatch, rep, "opcode", (0, 0, 0, 1, 2, 5, 8)),
            (pv.IssuerReplyBatch, rep, "src", range(5)),
            (pv.IssuerReplyBatch, rep, "lid", (1,))):
        planes[fields._fields.index(f)] = rng.choice(vals, (m, s))
    # PROP_REPLY to a propose round, ACC_REPLY to an accept round, or idle
    rep[0] = np.where(rng.random((m, s)) < 0.2, -1, tab[0] + 2)
    params = np.array([[5, 3, 2, 4], [5, 3, 1, 4], [3, 2, 1, 3]]).T[..., None]
    outs = [ce._fused_issuer_step(
        tab.astype(np.int32), rep.astype(np.int32), params.astype(np.int32),
        use_kernel=use_kernel, block_rows=1, shard_lanes=shard_lanes)
        for use_kernel in (False, True)]
    for want, got in zip(*outs):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    decision = np.asarray(outs[0][1])[pv.ActionBatch._fields.index(
        "decision")]
    assert (decision != int(Decision.WAIT)).any()
    # the machines' own quorum columns decide: swapped, they decide apart
    swapped = ce._fused_issuer_step(
        tab.astype(np.int32), rep.astype(np.int32),
        params[:, ::-1].astype(np.int32), use_kernel=False, block_rows=1)
    assert (np.asarray(swapped[1]) != np.asarray(outs[0][1])).any()
