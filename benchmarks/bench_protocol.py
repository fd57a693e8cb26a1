"""Paper-table benchmarks: CP vs All-aboard vs ABD — message/round counts,
fast-path rates, and relative op throughput (§9-§11 claims).

The paper's absolute numbers (5.5 / 7.5 / 12 M ops/s/machine) are
RDMA-cluster wall-clock; the *protocol-level* quantities they derive from
are reproducible exactly in simulation:

  * broadcast rounds per committed op (CP: propose+accept+commit = 3,
    All-aboard: accept+commit = 2, ABD write: 2, ABD read: 1 (+commit)),
  * messages per op,
  * All-aboard fast-path rate (paper: 99.7 % uncontended),
  * rare-reply rates (Log-too-high ~ 1/3k, Rmw-id-committed ~ 1/5k-50k),
  * relative throughput CP < All-aboard < write << read (simulated ticks
    per op under equal concurrency).
"""

from __future__ import annotations

import json

try:                                 # python -m benchmarks.run (package)
    from benchmarks import bench_vector
except ImportError:                  # python benchmarks/bench_protocol.py
    import bench_vector

from repro.core import checkers
from repro.core.node import ProtocolConfig
from repro.core.sim import Cluster, NetConfig, workload
from repro.runtime import use_compile_cache


def run(all_aboard: bool, *, n_ops=600, keys=256, rmw_frac=1.0,
        write_frac=0.0, seed=7):
    cl = Cluster(ProtocolConfig(n_machines=5, sessions_per_machine=8,
                                all_aboard=all_aboard),
                 NetConfig(seed=seed))
    workload(cl, n_ops=n_ops, keys=keys, seed=seed, rmw_frac=rmw_frac,
             write_frac=write_frac)
    assert cl.run_until_quiet(max_ticks=200_000)
    checkers.check_all(cl)
    return cl


def msgs_per_op(cl, kinds, done_stat):
    s = cl.stats()
    done = s.get(done_stat, 0)
    total = sum(s.get(f"sent_{k}", 0) for k in kinds)
    return total / max(done, 1), done


def bench_rmw_modes():
    rows = []
    for mode, aa in (("classic-paxos", False), ("all-aboard", True)):
        cl = run(aa)
        s = cl.stats()
        msgs, done = msgs_per_op(
            cl, ["propose", "accept", "commit"], "rmw_completed")
        ticks = cl.rounds
        rows.append({
            "mode": mode,
            "completed": done,
            "broadcast_msgs_per_rmw": round(msgs, 2),
            "ticks_per_op": round(ticks / done, 3),
            "fast_path_rate": round(
                s.get("all_aboard_successes", 0) / max(done, 1), 4),
            "thin_commit_rate": round(
                s.get("thin_commits", 0) / max(done, 1), 4),
        })
    return rows


def bench_op_classes():
    """Relative cost of RMW / write / read under identical conditions."""
    rows = []
    for name, fr in (("rmw", dict(rmw_frac=1.0, write_frac=0.0)),
                     ("write", dict(rmw_frac=0.0, write_frac=1.0)),
                     ("read", dict(rmw_frac=0.0, write_frac=0.0))):
        cl = run(True, n_ops=600, keys=256, **fr)
        s = cl.stats()
        done_stat = {"rmw": "rmw_completed", "write": "writes_completed",
                     "read": "reads_completed"}[name]
        sent = s.get("net_sent", 0)
        done = s.get(done_stat, 0)
        rows.append({
            "op": name,
            "completed": done,
            "msgs_per_op": round(sent / max(done, 1), 2),
            "ticks_per_op": round(cl.rounds / max(done, 1), 3),
            "read_write_backs": s.get("read_write_backs", 0),
        })
    # the paper's ordering: RMW slowest, reads cheapest
    assert rows[0]["msgs_per_op"] > rows[1]["msgs_per_op"] > \
        rows[2]["msgs_per_op"], rows
    return rows


def bench_rare_replies():
    """Contended run: rare-nack rates per committed RMW."""
    cl = run(False, n_ops=800, keys=4)
    s = cl.stats()
    done = s["rmw_completed"]
    return {
        "completed": done,
        "log_too_high_per_op": round(
            s.get("rep_log_too_high", 0) / done, 4),
        "rmw_id_committed_per_op": round(
            (s.get("rep_rmw_id_committed", 0)
             + s.get("rep_rmw_id_committed_no_bcast", 0)) / done, 4),
        "seen_lower_acc_per_op": round(
            s.get("rep_seen_lower_acc", 0) / done, 4),
        "steals": s.get("steals", 0),
        "helps": s.get("helps", 0),
    }


def bench_availability():
    """Ops complete during a minority crash with no election stall."""
    cl = Cluster(ProtocolConfig(n_machines=5, sessions_per_machine=8,
                                all_aboard=True), NetConfig(seed=3))
    workload(cl, n_ops=300, keys=64, seed=3)
    cl.step(10)
    before = len(cl.history)
    cl.crash(4)
    cl.step(100)                      # no timeout needed: quorum is 3/4
    after_crash = len(cl.history) - before
    assert cl.run_until_quiet(max_ticks=200_000)
    checkers.check_all(cl)
    surviving = [t for t in cl._inflight.values() if t["mid"] != 4]
    return {"completed_during_crash_window": after_crash,
            "stranded_on_survivors": len(surviving),
            "total_completed": len(cl.history)}


def bench_serve_path(n_ops=160, keys=24, seed=11):
    """Scalar vs batched cluster throughput: client ops/s at n=5 replicas,
    mixed op classes, identical seeded schedule — the tracked number for
    the end-to-end serve path (repro.serve.paxos).

    Delegates to :func:`bench_vector.bench_e2e` (one shared
    scalar-vs-batched harness, completions-identical asserted before any
    timing is reported — see its docstring) and reduces to the ratio, so
    the speedup (or, on a host backend where jit dispatch dominates tiny
    lane counts, the slowdown) is a single tracked number.
    """
    rows = bench_vector.bench_e2e(n_ops=n_ops, keys=keys, seed=seed,
                                  sessions=8)
    for row in rows:
        row["ticks_per_op"] = round(row["ticks"]
                                    / max(row["completed"], 1), 2)
    return {"rows": rows,
            "batched_over_scalar": round(rows[1]["client_ops_per_s"]
                                         / max(rows[0]["client_ops_per_s"],
                                               1), 3)}


def bench_host_path(n_items=20_000, reps=5):
    """Per-item host-path microcosts of the serve loop, optimized
    primitive next to the naive one it replaced (µs/item, best of
    ``reps``) — keeps the host-side shave a tracked number:

    * ``broadcast_clone`` — :meth:`Msg.clone` (shallow ``__dict__``
      copy), vs ``dataclasses.replace`` re-running full dataclass
      construction per destination (the old ``Machine._broadcast``).
    * ``scheduler_admit`` — :meth:`IngestScheduler.offer_many` (hoisted
      bookkeeping, one counter update per run), vs per-item
      :meth:`~IngestScheduler.offer`.
    """
    import dataclasses
    import time

    from repro.core.types import Msg, MsgKind, RmwId, TS
    from repro.serve.paxos import IngestScheduler

    def best_us(fn):
        per_item = min(_timed(fn) for _ in range(reps))
        return round(per_item * 1e6, 3)

    def _timed(fn):
        t0 = time.perf_counter()
        fn()
        return (time.perf_counter() - t0) / n_items

    msg = Msg(MsgKind.PROPOSE, src=0, key=1, rmw_id=RmwId(1, 0),
              ts=TS(3, 0), log_no=1, value=5)

    def clone_loop():
        for _ in range(n_items):
            msg.clone()

    def replace_loop():
        for _ in range(n_items):
            dataclasses.replace(msg)

    # spread keys so queue handling, not one hot deque, is what's timed
    msgs = [Msg(MsgKind.PROPOSE, src=0, key=i % 64, rmw_id=RmwId(1, 0),
                ts=TS(3, 0), log_no=1, value=5) for i in range(n_items)]

    def offer_many_loop():
        IngestScheduler(strict_order=True).offer_many(msgs)

    def offer_loop():
        sched = IngestScheduler(strict_order=True)
        for m in msgs:
            sched.offer(m)

    rows = {
        "broadcast_clone_us": best_us(clone_loop),
        "broadcast_replace_us": best_us(replace_loop),
        "scheduler_offer_many_us": best_us(offer_many_loop),
        "scheduler_offer_us": best_us(offer_loop),
    }
    rows["delta_us_per_item"] = round(
        (rows["broadcast_replace_us"] - rows["broadcast_clone_us"])
        + (rows["scheduler_offer_us"] - rows["scheduler_offer_many_us"]), 3)
    return rows


def main():
    use_compile_cache()
    out = {
        "rmw_modes": bench_rmw_modes(),
        "op_classes": bench_op_classes(),
        "rare_replies": bench_rare_replies(),
        "availability": bench_availability(),
        "serve_path": bench_serve_path(),
        "host_path": bench_host_path(),
    }
    print(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    main()
