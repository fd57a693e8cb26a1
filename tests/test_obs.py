"""Flight-recorder contract tests (repro.obs).

Three pillars:

* **exactness** — path counters reconcile exactly with the cluster
  completion history in every mode (off/sampled/full), scalar and
  batched, faults and crashes included;
* **determinism** — the JSONL dump is a pure function of (seed, spec,
  recorder config): two runs produce byte-identical files;
* **postmortems** — a checker failure inside :func:`repro.obs.flight_guard`
  produces a dump that :mod:`repro.obs.report` can summarize.
"""

import json
import random
from collections import Counter

import pytest

from repro.core import checkers
from repro.core.node import ProtocolConfig, ReqKind, Request
from repro.core.sim import Cluster, NetConfig, workload
from repro.core.types import RmwOp
from repro.obs import (
    FlightRecorder, MetricsRegistry, dump_all, dump_jsonl, flight_guard,
    load_records, summarize, render_summary,
)

KIND_TO_PATHS = {"RMW": ("all_aboard_fast", "cp_slow"),
                 "READ": ("abd_read",), "WRITE": ("abd_write",)}


def faulty_cluster(seed, *, machine_cls=None, all_aboard=False, obs=None,
                   crash=False, n_ops=30):
    cfg = ProtocolConfig(n_machines=5, sessions_per_machine=2,
                         all_aboard=all_aboard)
    net = NetConfig(seed=seed, drop_prob=0.06, dup_prob=0.05,
                    heavy_tail_prob=0.03, heavy_tail_extra=25.0)
    kw = {} if machine_cls is None else {"machine_cls": machine_cls}
    cl = Cluster(cfg, net, **kw)
    if obs is not None:
        cl.attach_obs(obs)
    workload(cl, n_ops=n_ops, keys=3, seed=seed, rmw_frac=0.45,
             write_frac=0.3)
    if crash:
        cl.step(8)
        cl.network.deliver_due(cl.network.now + 1.0, cl.machines)
        cl.crash(4)
        cl.step(6)
        cl.restart(4)
    assert cl.run_until_quiet(max_ticks=160_000)
    return cl


def assert_paths_reconcile(rec, cluster):
    """Exact reconciliation: per-kind completion counts equal the summed
    path counters (fast + slow for RMW), and ops.started covers them."""
    kinds = Counter(h["kind"].name for h in cluster.history)
    paths = rec.path_counts()
    for kind, path_names in KIND_TO_PATHS.items():
        assert sum(paths[p] for p in path_names) == kinds.get(kind, 0), \
            f"{kind} completions do not reconcile with {path_names}"
    assert sum(paths.values()) == len(cluster.history)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.inc("a.b")
    reg.inc("a.b", 4)
    assert reg.counter("a.b") == 5
    assert reg.counter("missing") == 0
    reg.set_gauge("g.pushed", 3.5)
    backing = {"v": 7}
    reg.register_gauge("g.lazy", lambda: backing["v"])
    assert reg.gauge("g.pushed") == 3.5
    assert reg.gauge("g.lazy") == 7
    backing["v"] = 9                       # lazy gauges sample at read time
    assert reg.gauge("g.lazy") == 9
    for v in (2, 4, 8):
        reg.observe("h.lat", v)
    snap = reg.snapshot()
    assert snap["counters"]["a.b"] == 5
    assert snap["gauges"]["g.lazy"] == 9
    assert snap["histograms"]["h.lat"]["count"] == 3
    # snapshots are JSON-ready
    json.dumps(snap)


def test_recorder_mode_validation():
    with pytest.raises(ValueError):
        FlightRecorder(mode="verbose")
    with pytest.raises(ValueError):
        FlightRecorder(sample_every=0)


def test_ring_capacity_bounds_dump():
    rec = FlightRecorder(mode="full", capacity=8)
    for i in range(50):
        sp = rec.op_begin(0, 0, "rmw", key=i, tag=i, t=float(i))
        rec.rmw_end(sp, float(i) + 1.0)
    assert len(rec.ring) == 8
    # counters are exact despite the bounded ring
    assert rec.path_counts()["cp_slow"] == 50


# ---------------------------------------------------------------------------
# path reconciliation (exactness across modes, scalar and batched)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["off", "sampled", "full"])
def test_paths_reconcile_with_history_scalar(mode):
    rec = FlightRecorder(mode=mode, sample_every=4)
    cl = faulty_cluster(3, all_aboard=True, obs=rec)
    assert_paths_reconcile(rec, cl)
    checkers.check_all(cl)


def test_paths_reconcile_with_crash_scalar():
    """Ops killed by a crash abort — recorded, never path-counted — so
    the path counters still equal the completion history exactly."""
    rec = FlightRecorder(mode="full")
    cl = faulty_cluster(5, obs=rec, crash=True)
    assert_paths_reconcile(rec, cl)
    c = rec.registry.counters
    assert c.get("evt.machine_crash", 0) == 1
    started = sum(v for k, v in c.items() if k.startswith("ops.started."))
    finished = sum(rec.path_counts().values()) + c.get("path.aborted", 0)
    assert started >= finished


def test_paths_reconcile_batched_with_engine_telemetry():
    from repro.serve.paxos import BatchedMachine

    rec = FlightRecorder(mode="sampled", sample_every=8)
    cl = faulty_cluster(7, machine_cls=BatchedMachine, all_aboard=True,
                        obs=rec, crash=True, n_ops=18)
    assert_paths_reconcile(rec, cl)
    snap = rec.snapshot()
    c = snap["counters"]
    # engine wave telemetry flows through the recorder
    assert c["engine.fused_receiver_calls"] > 0
    # host writes ride the waves' uploads; every wave refreshes a mirror
    assert c["engine.plane_wave_ships"] > 0
    assert c["engine.plane_wave_refreshes"] == (
        c["engine.fused_receiver_calls"] + c["engine.fused_issuer_calls"])
    assert c["engine.row_reloads"] > 0        # crash/restart reloads rows
    assert snap["gauges"]["engine.receiver_lanes_per_call"] > 0
    # every live machine's ingest scheduler reports on the one surface
    assert c["ingest.m0.offered"] > 0
    assert "ingest.m0.queue_depth" in snap["gauges"]


def test_quorum_wait_and_event_counters_exact():
    rec = FlightRecorder(mode="off")          # counters exact even off
    cl = faulty_cluster(9, all_aboard=True, obs=rec)
    c = rec.registry.counters
    assert c.get("evt.quorum_wait_ticks", 0) > 0
    assert c.get("evt.all_aboard_attempt", 0) > 0
    assert len(rec.ring) == 0                 # off: nothing ring-recorded
    assert_paths_reconcile(rec, cl)


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------

def run_and_dump(tmp_path, name, *, machine_cls=None, seed=13):
    rec = FlightRecorder(mode="full", capacity=1 << 14,
                         meta={"seed": seed, "spec": "determinism"})
    faulty_cluster(seed, machine_cls=machine_cls, all_aboard=True,
                   obs=rec, crash=True, n_ops=20)
    return dump_jsonl(rec, str(tmp_path / name))


def test_dump_byte_identical_scalar(tmp_path):
    a = run_and_dump(tmp_path, "a.jsonl")
    b = run_and_dump(tmp_path, "b.jsonl")
    with open(a, "rb") as fa, open(b, "rb") as fb:
        assert fa.read() == fb.read()


def test_dump_byte_identical_batched(tmp_path):
    from repro.serve.paxos import BatchedMachine

    a = run_and_dump(tmp_path, "a.jsonl", machine_cls=BatchedMachine)
    b = run_and_dump(tmp_path, "b.jsonl", machine_cls=BatchedMachine)
    with open(a, "rb") as fa, open(b, "rb") as fb:
        assert fa.read() == fb.read()


def test_sampling_is_deterministic_by_admission_order():
    recs = []
    for _ in range(2):
        rec = FlightRecorder(mode="sampled", sample_every=3)
        for i in range(30):
            sp = rec.op_begin(0, 0, "rmw", key=i, tag=i, t=float(i))
            rec.rmw_end(sp, float(i) + 2.0)
        recs.append([r["tag"] for r in rec.ring])
    assert recs[0] == recs[1]
    assert len(recs[0]) == 10                 # every 3rd op exactly


# ---------------------------------------------------------------------------
# postmortem dumps
# ---------------------------------------------------------------------------

def tamper_commit_log(cluster):
    """Corrupt one replicated commit record on one machine — the seeded
    log-agreement violation the postmortem path is tested against."""
    seen = {}
    for m in cluster.machines:
        for key, slots in m.commit_log.items():
            for slot, rec in slots.items():
                if (key, slot) in seen and seen[(key, slot)] is not m:
                    rid, value, base = rec
                    slots[slot] = (rid, value + 999, base)
                    return True
                seen[(key, slot)] = m
    return False


def test_checker_failure_dumps_and_reports(tmp_path):
    rec = FlightRecorder(mode="full", meta={"seed": 4, "spec": "postmortem"})
    cl = faulty_cluster(4, all_aboard=True, obs=rec)
    assert tamper_commit_log(cl), "workload produced no replicated record"
    out = tmp_path / "dumps"
    with pytest.raises(checkers.SafetyViolation):
        with flight_guard(rec, str(out), label="checker"):
            checkers.check_all(cl)
    dump = out / "flight.jsonl"
    trace = out / "flight.trace.json"
    assert dump.exists() and trace.exists()
    s = summarize(load_records(str(dump)))
    assert s["dump_reason"].startswith("checker: SafetyViolation")
    assert sum(s["path_mix"].values()) == len(cl.history)
    assert s["ring_spans"] > 0
    text = render_summary(s)
    assert "path mix" in text and "fast-path hit rate" in text
    # the Chrome-trace export is loadable and spans carry the timeline
    with open(trace) as f:
        tr = json.load(f)
    assert any(e["ph"] == "X" for e in tr["traceEvents"])


def test_flight_guard_clean_paths_do_not_dump(tmp_path):
    rec = FlightRecorder()
    out = tmp_path / "dumps"
    with flight_guard(rec, str(out)):
        pass                                   # clean block: no dump
    assert not (out / "flight.jsonl").exists()
    with pytest.raises(SystemExit):
        with flight_guard(rec, str(out)):
            raise SystemExit(0)                # clean exit: no dump
    assert not (out / "flight.jsonl").exists()
    with pytest.raises(SystemExit):
        with flight_guard(rec, str(out)):
            raise SystemExit(2)                # failed exit: dump
    assert (out / "flight.jsonl").exists()


def test_harness_integration_checker_failure_noted(tmp_path):
    """OpenLoopHarness(obs=...) wires the recorder before traffic and
    marks checker failures in the ring."""
    from repro.serve.loadgen.harness import OpenLoopHarness, OpenLoopSpec
    from repro.serve.loadgen.arrivals import ArrivalPhase

    rec = FlightRecorder(mode="sampled", meta={"spec": "open-loop"})
    spec = OpenLoopSpec(seed=2, n_machines=3, sessions=2, n_keys=16,
                        phases=(ArrivalPhase(rate=0.3, ticks=120),))
    h = OpenLoopHarness(spec, obs=rec)
    result = h.run(max_ticks=60_000)
    assert_paths_reconcile(rec, result.cluster)
    assert result.completed == result.offered


def test_machine_restart_keeps_recorder_attached():
    """Crash/restart and add_machine must re-adopt the replacement
    machine: ops issued after the restart still hit the recorder."""
    rec = FlightRecorder(mode="full")
    cfg = ProtocolConfig(n_machines=3, sessions_per_machine=2)
    cl = Cluster(cfg, NetConfig(seed=6))
    cl.attach_obs(rec)
    cl.rmw(0, 0, key=1)
    cl.run_until_quiet()
    cl.crash(2)
    cl.restart(2)
    assert cl.machines[2].obs is rec
    before = rec.path_counts()["cp_slow"]
    cl.rmw(2, 0, key=1)
    cl.run_until_quiet()
    assert rec.path_counts()["cp_slow"] == before + 1


def test_abd_read_write_spans_classify_by_kind():
    rec = FlightRecorder(mode="full")
    cfg = ProtocolConfig(n_machines=3, sessions_per_machine=2)
    cl = Cluster(cfg, NetConfig(seed=8))
    cl.attach_obs(rec)
    rng = random.Random(0)
    for i in range(12):
        mid, sess = rng.randrange(3), rng.randrange(2)
        if i % 3 == 0:
            cl.submit(mid, sess, Request(ReqKind.RMW, i % 2,
                                         op=RmwOp.FAA, arg1=1))
        elif i % 3 == 1:
            cl.submit(mid, sess, Request(ReqKind.WRITE, i % 2, value=i + 1))
        else:
            cl.submit(mid, sess, Request(ReqKind.READ, i % 2))
        cl.run_until_quiet()
    paths = rec.path_counts()
    assert paths["abd_read"] == 4
    assert paths["abd_write"] == 4
    assert paths["all_aboard_fast"] + paths["cp_slow"] == 4
    kinds = {r["kind"]: r["path"] for r in rec.ring if r["type"] == "span"}
    assert kinds["read"] == "abd_read"
    assert kinds["write"] == "abd_write"


# ---------------------------------------------------------------------------
# the served engine's count of §11 write-backs
# ---------------------------------------------------------------------------

def batched_abd_cluster(seed, rec):
    from repro.serve.paxos import BatchedMachine

    cl = Cluster(ProtocolConfig(n_machines=5, sessions_per_machine=2),
                 NetConfig(seed=seed, min_delay=1, max_delay=3),
                 machine_cls=BatchedMachine)
    cl.attach_obs(rec)
    return cl


def write_back_spans(rec):
    return [r for r in rec.ring if r["type"] == "span"
            and r["path"] == "abd_read"
            and any(name == "read_write_back" for _t, name in r["events"])]


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_abd_read_write_backs_reconcile_with_spans(seed):
    """Reads racing writes on two keys: the engine's count equals the
    completed ``abd_read`` spans that carry a ``read_write_back`` event,
    and the recorder's event counter."""
    rec = FlightRecorder(mode="full", capacity=1 << 14)
    cl = batched_abd_cluster(seed, rec)
    workload(cl, n_ops=60, keys=2, seed=seed, rmw_frac=0.0, write_frac=0.4)
    assert cl.run_until_quiet(max_ticks=50_000)
    assert_paths_reconcile(rec, cl)
    n = cl.engine.telemetry()["abd_read_write_backs"]
    assert n == len(write_back_spans(rec)) > 0
    assert n == rec.registry.counters["evt.read_write_back"]
    assert rec.snapshot()["counters"]["engine.abd_read_write_backs"] == n


def test_abd_read_write_backs_survive_a_restart():
    """A restart replaces the machine and its stats: the count keeps
    the old incarnation's write-backs and matches the event counter."""
    rec = FlightRecorder(mode="off")
    cl = batched_abd_cluster(6, rec)
    workload(cl, n_ops=40, keys=2, seed=6, rmw_frac=0.0, write_frac=0.4)
    assert cl.run_until_quiet(max_ticks=50_000)
    before = cl.engine.telemetry()["abd_read_write_backs"]
    assert before == rec.registry.counters["evt.read_write_back"] > 0
    for mid in range(5):
        cl.crash(mid)
        cl.restart(mid)
    assert cl.engine.telemetry()["abd_read_write_backs"] == before
    workload(cl, n_ops=40, keys=2, seed=7, rmw_frac=0.0, write_frac=0.4)
    assert cl.run_until_quiet(max_ticks=50_000)
    assert (cl.engine.telemetry()["abd_read_write_backs"]
            == rec.registry.counters["evt.read_write_back"])


def test_read_racing_an_unacknowledged_write_is_counted():
    """A read issued once the write has entered its phase 2, before any
    other replica acknowledged it, sees the new value at too few replicas
    and writes it back (§11): one write-back, counted, and the read
    returns the written value."""
    rec = FlightRecorder(mode="full")
    cl = batched_abd_cluster(3, rec)
    cl.submit(0, 0, Request(ReqKind.WRITE, 5, value=77))
    while rec.registry.counters.get("evt.write_phase2", 0) == 0:
        cl.step()
    tag = cl.submit(1, 1, Request(ReqKind.READ, 5))
    assert cl.run_until_quiet()
    read = next(h for h in cl.history if h["tag"] == tag)
    assert read["value"] == 77
    assert [r["tag"] for r in write_back_spans(rec)] == [tag]
    assert cl.engine.telemetry()["abd_read_write_backs"] == 1


def test_read_on_a_quiet_key_counts_no_write_back():
    rec = FlightRecorder(mode="full")
    cl = batched_abd_cluster(3, rec)
    cl.submit(0, 0, Request(ReqKind.WRITE, 5, value=77))
    assert cl.run_until_quiet()
    cl.submit(1, 1, Request(ReqKind.READ, 5))
    cl.submit(2, 1, Request(ReqKind.READ, 6))     # a key never written
    assert cl.run_until_quiet()
    assert rec.path_counts()["abd_read"] == 2
    assert cl.engine.telemetry()["abd_read_write_backs"] == 0
    assert write_back_spans(rec) == []


def test_dump_all_names_are_deterministic(tmp_path):
    rec = FlightRecorder()
    sp = rec.op_begin(0, 0, "read", key=0, tag=0, t=1.0)
    rec.abd_end(sp, 2.0)
    paths = dump_all(rec, str(tmp_path), reason="unit", stem="seed003")
    assert paths["jsonl"].endswith("seed003.jsonl")
    assert paths["trace"].endswith("seed003.trace.json")
    header = load_records(paths["jsonl"])[0]
    assert header["meta"]["dump_reason"] == "unit"
