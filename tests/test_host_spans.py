"""Wall-clock host spans (repro.obs.HostClock) and what reads them.

The clock is on exactly while a flight recorder is attached: its span
totals and ingest counters then appear in ``ClusterEngine.telemetry()``,
and never in the recorder's registry or dumps.
"""

import pytest

from repro.core.lanes import ShardMap
from repro.core.node import ProtocolConfig
from repro.core.sim import Cluster, NetConfig, completion_tuples, workload
from repro.core.types import Msg, MsgKind, RmwId, TS
from repro.obs import FlightRecorder, HostClock, dump_jsonl
from repro.serve.paxos import BatchedMachine, IngestScheduler

# the spans the served tick is split into (PERF.md §3 reads their self time)
SPANS = ("tick", "net.deliver", "engine.step_all", "net.send", "machine",
         "engine.stage", "engine.upload", "engine.launch", "engine.wait",
         "engine.download", "engine.unstage")


class FakeNs:
    """A nanosecond clock that moves only when told to."""

    def __init__(self):
        self.t = 0

    def __call__(self):
        return self.t


def batched(seed, *, obs=None, replicas=3, n_ops=40, keys=2):
    cfg = ProtocolConfig(n_machines=replicas, sessions_per_machine=2,
                         all_aboard=True)
    cl = Cluster(cfg, NetConfig(seed=seed), machine_cls=BatchedMachine)
    if obs is not None:
        cl.attach_obs(obs)
    workload(cl, n_ops=n_ops, keys=keys, seed=seed, rmw_frac=1.0)
    return cl


def test_self_time_of_nested_and_sibling_spans():
    ns = FakeNs()
    clock = HostClock(now=ns)
    clock.begin("tick")                  # t=0
    ns.t = 10
    clock.begin("a")                     # a: [10, 40)
    ns.t = 15
    clock.begin("b")                     # b inside a: [15, 25)
    ns.t = 25
    clock.end()
    ns.t = 40
    clock.switch("c")                    # sibling of a: [40, 70)
    ns.t = 70
    clock.end()
    ns.t = 100
    clock.end()
    clock.begin("a")                     # a again, at the root: [100, 105)
    ns.t = 105
    clock.end()
    t = clock.totals()
    assert (t["span.tick.total_ns"], t["span.tick.self_ns"]) == (100, 40)
    assert (t["span.a.total_ns"], t["span.a.self_ns"], t["span.a.n"]) == \
        (35, 25, 2)
    assert (t["span.b.total_ns"], t["span.b.self_ns"]) == (10, 10)
    assert (t["span.c.total_ns"], t["span.c.self_ns"]) == (30, 30)
    # self times partition the root spans' totals
    roots = t["span.tick.total_ns"] + 5
    assert sum(v for k, v in t.items() if k.endswith(".self_ns")) == roots
    clock.count("ingest.emitted", 3)
    clock.count("ingest.emitted", 2)
    assert clock.totals()["ingest.emitted"] == 5


def test_no_recorder_no_clock_same_completions():
    bare = batched(5)
    assert bare.clock is None and bare.engine.clock is None
    assert bare.run_until_quiet(max_ticks=20_000)
    tel = bare.engine.telemetry()
    assert not [k for k in tel if k.startswith(("span.", "ingest."))]
    rec = FlightRecorder(mode="off")
    timed = batched(5, obs=rec)
    assert timed.run_until_quiet(max_ticks=20_000)
    assert completion_tuples(timed) == completion_tuples(bare)
    tel = timed.engine.telemetry()
    for name in SPANS:
        assert tel[f"span.{name}.n"] > 0, name
    assert tel["span.tick.n"] == timed.rounds
    assert tel["ingest.emitted"] == tel["fused_receiver_lanes"]


def test_dumps_hold_no_wall_clock(tmp_path):
    paths = []
    for i in range(2):
        rec = FlightRecorder(mode="sampled", meta={"seed": 9})
        batched(9, obs=rec).run_until_quiet(max_ticks=20_000)
        assert rec.clock.spans["tick"][0] > 0
        counters = rec.snapshot()["counters"]
        assert not [k for k in counters if "span." in k]
        assert counters["ingest.emitted"] > 0
        paths.append(dump_jsonl(rec, str(tmp_path / f"run{i}.jsonl")))
    texts = [open(p).read() for p in paths]
    assert texts[0] == texts[1]


def test_span_totals_never_decrease_across_crash_and_restart():
    rec = FlightRecorder(mode="off")
    cl = batched(3, obs=rec, replicas=5, n_ops=60, keys=3)
    last = {}

    def step(n):
        for _ in range(n):
            cl.step()
            now = cl.engine.telemetry()
            for k, v in last.items():
                assert now[k] >= v, k
            last.update({k: v for k, v in now.items()
                         if k.startswith(("span.", "ingest."))})

    step(8)
    cl.crash(4)
    step(6)
    cl.restart(4)
    assert cl.machines[4].ingest.clock is rec.clock
    step(30)
    assert cl.run_until_quiet(max_ticks=20_000)
    assert last["span.tick.n"] == 44


def test_spans_cover_the_tick():
    """The served tick's time lies in its named parts: what the tick and
    step_all spans keep for themselves is under a tenth of the tick."""
    rec = FlightRecorder(mode="off")
    cl = batched(7, obs=rec, replicas=3, n_ops=90, keys=2)
    assert cl.run_until_quiet(max_ticks=20_000)
    t = cl.engine.telemetry()
    own = t["span.tick.self_ns"] + t["span.engine.step_all.self_ns"]
    assert own < 0.1 * t["span.tick.total_ns"]
    # every self time is inside some tick here
    parts = sum(v for k, v in t.items() if k.endswith(".self_ns"))
    assert parts == t["span.tick.total_ns"]


def _propose(key):
    return Msg(MsgKind.PROPOSE, src=0, key=key, ts=TS(3, 0),
               rmw_id=RmwId(1, 0))


@pytest.mark.parametrize("sharded", [False, True])
def test_ingest_wait_waves_hand_checked(sharded):
    """Three proposes on key 0, then one on key 1, strict order: batches
    [k0], [k0], [k0, k1]; key 1 waited two waves behind key 0."""
    sched = IngestScheduler(strict_order=True)
    sched.clock = HostClock()
    for _ in range(3):
        sched.offer(_propose(0))
    sched.offer(_propose(1))
    if sharded:
        batches = [b for b, _ in sched.drain_sharded(ShardMap(2, 8))]
    else:
        batches = list(sched.drain())
    assert [[m.key for m in b] for b in batches] == [[0], [0], [0, 1]]
    assert sched.clock.counters == {"ingest.wait_waves": 5,
                                    "ingest.emitted": 4}
