#!/usr/bin/env python
"""Open-loop harness smoke: 20 seeded faulty workloads (CI gate).

Every seed drives :class:`repro.serve.loadgen.OpenLoopHarness` — Poisson
open-loop arrivals, Zipf key skew, a §2-style op mix — through a fault
plan (crash/restart on some seeds, a partition on others, both on a few)
on the scalar cluster, asserting quiescence and **every safety checker**
in :mod:`repro.core.checkers` green (per-key log agreement, exactly-once,
prefix, registry monotonicity, carstamp linearizability — the fault-window
latencies must come from legal histories or they measure nothing).

A subset of seeds additionally runs the identical spec through
``Cluster(machine_cls=BatchedMachine)`` and asserts the batched run is
completion-for-completion identical to the scalar one — the open-loop
injection path (mid-tick arrivals routed by liveness) is a different
driver than the preloaded-FIFO workloads ``batched_smoke.py`` uses, so it
gets its own differential gate.

Every seed's scalar run carries a :class:`repro.obs.FlightRecorder`:
per-path completion counters are reconciled exactly against the history,
and any failure (quiescence, divergence, checker) auto-dumps the
recorder into ``--dump-dir`` for ``scripts/trace_report.py`` (CI uploads
the directory as an artifact).  ``--dump`` additionally writes the first
seed's dump unconditionally — the CI open_loop job summarizes it with
trace_report as a liveness check on the postmortem tooling itself.

Wired into scripts/check.sh after the reconfig smoke; see
.github/workflows/ci.yml (open_loop job).
"""

from __future__ import annotations

import argparse
import sys
import time
from collections import Counter

from repro.core.sim import completion_tuples
from repro.obs import FlightRecorder, dump_all, flight_guard
from repro.serve.loadgen import (
    ArrivalPhase, FaultPlan, MIXES, OpenLoopHarness, OpenLoopSpec,
)
from repro.serve.paxos import BatchedMachine
from repro.runtime import use_compile_cache

KIND_TO_PATHS = {"RMW": ("all_aboard_fast", "cp_slow"),
                 "READ": ("abd_read",), "WRITE": ("abd_write",)}

SEEDS = range(20)
CRASH_SEEDS = frozenset((1, 4, 7, 10, 13, 16, 19))
PARTITION_SEEDS = frozenset((2, 5, 8, 11, 14, 17))
# both faults overlapping the same run
STORM_SEEDS = frozenset((3, 9, 15))
# differential subset: same spec through the batched serve path,
# completion-identical to the scalar run (kept small — the batched tick
# is host-dispatch-bound at smoke shapes)
BATCHED_SEEDS = frozenset((0, 7, 14))
MIX_ROTATION = tuple(MIXES)


def spec_for(seed: int) -> OpenLoopSpec:
    mix = MIXES[MIX_ROTATION[seed % len(MIX_ROTATION)]]
    return OpenLoopSpec(
        seed=seed, n_machines=5, sessions=2, n_keys=48,
        zipf_s=0.8 + 0.05 * (seed % 5), mix=mix,
        phases=(ArrivalPhase(rate=0.25, ticks=160),),
        drop_prob=0.02, dup_prob=0.02)


def faults_for(seed: int) -> FaultPlan:
    plan = FaultPlan(settle=30.0)
    if seed in CRASH_SEEDS or seed in STORM_SEEDS:
        plan.crash_restart(seed % 5, at=40.0, down_for=25.0)
    if seed in PARTITION_SEEDS or seed in STORM_SEEDS:
        plan.partition(90.0, 120.0, (0, 1, 2), (3, 4))
    return plan


def reconcile_paths(rec: FlightRecorder, cluster, seed: int) -> None:
    """Exact per-path reconciliation against the completion history
    (ops killed by a crash abort — never path-counted — so the counters
    equal the completions even on faulty seeds)."""
    kinds = Counter(h["kind"].name for h in cluster.history)
    paths = rec.path_counts()
    for kind, names in KIND_TO_PATHS.items():
        got = sum(paths[p] for p in names)
        if got != kinds.get(kind, 0):
            raise AssertionError(
                f"seed {seed}: {kind} path counters ({got}) do not "
                f"reconcile with {kinds.get(kind, 0)} completions")
    if sum(paths.values()) != len(cluster.history):
        raise AssertionError(
            f"seed {seed}: total path count {sum(paths.values())} != "
            f"{len(cluster.history)} completions")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dump-dir", default="flight_dumps",
                    help="where failing seeds drop their flight-recorder "
                         "dumps (CI uploads this directory as an artifact)")
    ap.add_argument("--dump", action="store_true",
                    help="also dump the first seed's recorder on success "
                         "(CI runs trace_report.py against it)")
    args = ap.parse_args(argv)
    use_compile_cache()
    t0 = time.time()
    total = fault_total = 0
    for seed in SEEDS:
        spec, faults = spec_for(seed), faults_for(seed)
        rec = FlightRecorder(mode="sampled",
                             meta={"seed": seed, "spec": "open_loop_smoke",
                                   "mix": spec.mix.name})
        with flight_guard(rec, args.dump_dir, label=f"seed {seed}",
                          stem=f"open_loop_seed{seed:03d}"):
            res = OpenLoopHarness(spec, faults=faults,
                                  obs=rec).run()  # check=True:
            # checkers (linearizability included) ran on the final history
            reconcile_paths(rec, res.cluster, seed)
            if seed in BATCHED_SEEDS:
                bat = OpenLoopHarness(spec, machine_cls=BatchedMachine,
                                      faults=faults).run()
                want = completion_tuples(res.cluster)
                got = completion_tuples(bat.cluster)
                if want != got:
                    raise AssertionError(
                        f"seed {seed}: batched open-loop run diverged "
                        f"({len(got)} vs {len(want)} completions)")
        report = res.recorder.report()
        n_fault = sum(s["count"] for s in report["fault"].values() if s)
        total += res.completed
        fault_total += n_fault
        if args.dump and seed == min(SEEDS):
            paths = dump_all(rec, args.dump_dir, reason="smoke sample",
                             stem=f"open_loop_seed{seed:03d}")
            print(f"seed {seed:2d} dump: {paths['jsonl']}")
        mode = ("storm" if seed in STORM_SEEDS
                else "crash" if seed in CRASH_SEEDS
                else "part" if seed in PARTITION_SEEDS else "plain")
        diff = "+batched" if seed in BATCHED_SEEDS else ""
        print(f"seed {seed:2d} [{mode:5s}/{spec.mix.name:12s}]{diff:9s}: "
              f"{res.completed:3d} done ({n_fault:3d} in fault windows), "
              f"{res.lost} lost, checkers green, paths reconcile")
    print(f"open-loop smoke OK: {len(list(SEEDS))} seeds, {total} client "
          f"ops ({fault_total} through fault windows), linearizability "
          f"green, path counters reconcile ({time.time() - t0:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
