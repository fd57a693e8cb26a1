#!/usr/bin/env python
"""Batched-cluster smoke: the serve-path acceptance gate (CI).

Runs >= 20 seeded faulty workloads — drops, duplicates, heavy-tail delays,
all-aboard deployments, crash/restart (including a crash with messages
in-flight mid-batch) — once on the scalar cluster and once on
``Cluster(machine_cls=BatchedMachine)``, asserting

* completions are identical, machine-for-machine, tag-for-tag,
  value-for-value (the batched path is a drop-in engine swap, not a
  behavioral fork),
* every safety checker in :mod:`repro.core.checkers` (per-key log
  agreement, exactly-once, prefix, registry monotonicity, carstamp
  linearizability) is green on the batched cluster, and
* the flight recorder's per-path counters (``repro.obs``) reconcile
  exactly with the batched cluster's completion history on every seed.

On any failure the per-seed flight recorder auto-dumps into
``--dump-dir`` (JSONL + Chrome trace; summarize with
``scripts/trace_report.py``) — CI uploads the directory as an artifact.
``--inject-failure`` corrupts one replicated commit record on the first
seed to demonstrate the postmortem path end to end.

Wired into scripts/check.sh after the SIMD smoke; see
.github/workflows/ci.yml.
"""

from __future__ import annotations

import argparse
import functools
import sys
import time
from collections import Counter

from repro.core import checkers
from repro.core.node import Machine, ProtocolConfig
from repro.core.sim import Cluster, NetConfig, completion_tuples, workload
from repro.obs import FlightRecorder, flight_guard
from repro.serve.paxos import BatchedMachine
from repro.runtime import use_compile_cache

SEEDS = range(20)
ABOARD_SEEDS = frozenset((1, 3, 7, 11, 15, 19))
CRASH_SEEDS = frozenset((2, 5, 9, 13, 17))
# a third of the storm drives the fused engine through the Pallas kernels
# (receiver + issuer paths, interpreted off a TPU) instead of the jnp oracle —
# both use_kernel settings must stay completion-identical to scalar
KERNEL_SEEDS = frozenset((0, 3, 5, 8, 12, 16, 19))

# ReqKind name -> the flight-recorder paths its completions land in
KIND_TO_PATHS = {"RMW": ("all_aboard_fast", "cp_slow"),
                 "READ": ("abd_read",), "WRITE": ("abd_write",)}


def batched_cls(seed: int, shards: int = 1):
    kw = {"shards": shards} if shards > 1 else {}
    if seed in KERNEL_SEEDS:
        return functools.partial(BatchedMachine, use_kernel=True,
                                 block_rows=1, **kw)
    return functools.partial(BatchedMachine, **kw) if kw else BatchedMachine


def run(machine_cls, seed: int, obs=None):
    cfg = ProtocolConfig(n_machines=5, sessions_per_machine=2,
                         all_aboard=seed in ABOARD_SEEDS)
    net = NetConfig(seed=seed, drop_prob=0.06, dup_prob=0.05,
                    heavy_tail_prob=0.03, heavy_tail_extra=25.0)
    cl = Cluster(cfg, net, machine_cls=machine_cls)
    if obs is not None:
        cl.attach_obs(obs)
    workload(cl, n_ops=18, keys=3, seed=seed, rmw_frac=0.45, write_frac=0.3)
    if seed in CRASH_SEEDS:
        cl.step(8)
        # deliver due traffic first so the crash lands with messages
        # in-flight ("crash mid-batch": the inbox dies with the machine)
        cl.network.deliver_due(cl.network.now + 1.0, cl.machines)
        cl.crash(4)
        cl.step(6)
        cl.restart(4)
    if not cl.run_until_quiet(max_ticks=120_000):
        raise RuntimeError(f"seed {seed}: cluster did not quiesce")
    return cl


def reconcile_paths(rec: FlightRecorder, cluster, seed: int) -> None:
    """Exact per-path reconciliation against the completion history."""
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


def inject_log_corruption(cluster) -> bool:
    """Corrupt one replicated commit record (--inject-failure demo)."""
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shards", type=int, default=1,
                    help="state-plane shard count for the batched cluster "
                         "(>1 exercises the sharded lane layout; with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=N the shard rows land on N devices)")
    ap.add_argument("--dump-dir", default="flight_dumps",
                    help="where failing seeds drop their flight-recorder "
                         "dumps (CI uploads this directory as an artifact)")
    ap.add_argument("--inject-failure", action="store_true",
                    help="corrupt one replicated commit record on the "
                         "first seed: demonstrates the checker-failure "
                         "-> dump -> trace_report postmortem path")
    args = ap.parse_args(argv)
    use_compile_cache()
    t0 = time.time()
    total_ops = 0
    for seed in SEEDS:
        rec = FlightRecorder(
            mode="sampled",
            meta={"seed": seed, "spec": "batched_smoke",
                  "shards": args.shards})
        with flight_guard(rec, args.dump_dir, label=f"seed {seed}",
                          stem=f"batched_seed{seed:03d}"):
            scalar = run(Machine, seed)
            batched = run(batched_cls(seed, args.shards), seed, obs=rec)
            want, got = completion_tuples(scalar), completion_tuples(batched)
            if want != got:
                for a, b in zip(want, got):
                    if a != b:
                        print(f"  first diff:\n   scalar  {a}\n"
                              f"   batched {b}", file=sys.stderr)
                        break
                raise AssertionError(
                    f"seed {seed}: batched completions diverged "
                    f"({len(got)} vs {len(want)})")
            if args.inject_failure and seed == min(SEEDS):
                if not inject_log_corruption(batched):
                    raise RuntimeError("--inject-failure found no "
                                       "replicated record to corrupt")
            checkers.check_all(batched)
            reconcile_paths(rec, batched, seed)
        total_ops += len(batched.history)
        mode = ("aboard" if seed in ABOARD_SEEDS
                else "crash" if seed in CRASH_SEEDS else "plain")
        impl = "pallas" if seed in KERNEL_SEEDS else "jnp"
        print(f"seed {seed:2d} [{mode:6s}/{impl:6s}]: {len(got):2d} "
              f"completions identical, checkers green, paths reconcile")
    sharded = f", {args.shards} shards" if args.shards > 1 else ""
    print(f"batched smoke OK: {len(list(SEEDS))} seeds, {total_ops} client "
          f"ops{sharded}, completion-identical to scalar, linearizability "
          f"green, path counters reconcile ({time.time() - t0:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
