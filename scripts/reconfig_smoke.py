#!/usr/bin/env python
"""Reconfiguration smoke: the live-membership acceptance gate (CI).

Runs >= 20 seeded join/leave/rejoin storms — a 3 -> 4 -> 5 -> 4 -> 5 -> 4
membership trajectory driven through the CP-decided config register, with
the client workload still in flight, a crash + restart and a network
partition deliberately overlapping the view changes — once on the scalar
cluster and once on ``Cluster(machine_cls=BatchedMachine)``, asserting

* completions are identical, machine-for-machine, tag-for-tag,
  value-for-value (view installs, epoch fencing and snapshot catch-up are
  engine-invariant: the batched path is still a drop-in swap), and
* every safety checker in :mod:`repro.core.checkers` — including
  :func:`~repro.core.checkers.check_view_transitions` (epoch +1 steps,
  single-member deltas over the decided config history) — is green.

Wired into scripts/check.sh after the batched smoke; see
.github/workflows/ci.yml.
"""

from __future__ import annotations

import argparse
import functools
import sys
import time

from repro.core import checkers
from repro.core.node import Machine, ProtocolConfig
from repro.core.sim import Cluster, NetConfig, completion_tuples, workload
from repro.serve.paxos import BatchedMachine
from repro.runtime import use_compile_cache

SEEDS = range(20)
ABOARD_SEEDS = frozenset((3, 9, 15))
# these storms run the fused engine through the Pallas kernels (receiver
# + issuer, interpreted off a TPU): view changes, crash/restart and catch-up
# must be completion-identical under both use_kernel settings
KERNEL_SEEDS = frozenset((2, 9, 14, 18))


def batched_cls(seed: int, shards: int = 1):
    kw = {"shards": shards} if shards > 1 else {}
    if seed in KERNEL_SEEDS:
        return functools.partial(BatchedMachine, use_kernel=True,
                                 block_rows=1, **kw)
    return functools.partial(BatchedMachine, **kw) if kw else BatchedMachine


def storm(machine_cls, seed: int) -> Cluster:
    """One seeded storm; the script is identical for both machine classes
    so the completion histories are directly comparable."""
    cfg = ProtocolConfig(n_machines=3, sessions_per_machine=2,
                         reconfig=True, all_aboard=seed in ABOARD_SEEDS)
    net = NetConfig(seed=seed, drop_prob=0.06, dup_prob=0.05,
                    heavy_tail_prob=0.03, heavy_tail_extra=25.0)
    cl = Cluster(cfg, net, machine_cls=machine_cls)

    # phase 1: load the register bank, leave the ops genuinely in flight
    workload(cl, n_ops=14, keys=3, seed=seed, rmw_frac=0.5,
             write_frac=0.3, key_base=1)
    cl.step(150)

    # phase 2: grow 3 -> 4 -> 5 with a partition overlapping the changes
    cl.network.partition([2], [0])         # minority link cut, quorums live
    cl.join()                              # epoch 1: members (0,1,2,3)
    cl.join()                              # epoch 2: members (0,1,2,3,4)
    cl.network.heal()

    # phase 3: more load on the grown view, then shrink with a crash
    # overlapping the view change
    workload(cl, n_ops=10, keys=3, seed=seed + 1, rmw_frac=0.5,
             write_frac=0.2, key_base=1, mids=cl.active_view.members)
    cl.crash(2)
    cl.leave(1)                            # epoch 3: members (0,2,3,4)
    cl.restart(2)

    # phase 4: rejoin the leaver, then retire another member
    mid = cl.join(1)                       # epoch 4: members (0,1,2,3,4)
    assert mid == 1
    workload(cl, n_ops=8, keys=3, seed=seed + 2, rmw_frac=0.6,
             write_frac=0.2, key_base=1, mids=cl.active_view.members)
    cl.leave(4)                            # epoch 5: members (0,1,2,3)

    if not cl.run_until_quiet(max_ticks=120_000):
        raise RuntimeError(f"seed {seed}: cluster did not quiesce")
    st = cl.stats()
    if st["view_epoch"] != 5 or st["view_members"] != 4:
        raise RuntimeError(
            f"seed {seed}: storm ended at epoch {st['view_epoch']} with "
            f"{st['view_members']} members (want epoch 5, 4 members)")
    return cl


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shards", type=int, default=1,
                    help="state-plane shard count for the batched cluster "
                         "(>1 drives view installs / snapshot catch-up "
                         "through per-shard plane rows)")
    args = ap.parse_args(argv)
    use_compile_cache()
    t0 = time.time()
    total_ops = 0
    for seed in SEEDS:
        scalar = storm(Machine, seed)
        batched = storm(batched_cls(seed, args.shards), seed)
        want, got = completion_tuples(scalar), completion_tuples(batched)
        if want != got:
            print(f"seed {seed}: batched completions diverged "
                  f"({len(got)} vs {len(want)})", file=sys.stderr)
            for a, b in zip(want, got):
                if a != b:
                    print(f"  first diff:\n   scalar  {a}\n   batched {b}",
                          file=sys.stderr)
                    break
            return 1
        checkers.check_all(scalar)
        checkers.check_all(batched)
        total_ops += len(batched.history)
        st = batched.stats()
        mode = "aboard" if seed in ABOARD_SEEDS else "plain"
        impl = "pallas" if seed in KERNEL_SEEDS else "jnp"
        print(f"seed {seed:2d} [{mode:6s}/{impl:6s}]: {len(got):2d} "
              f"completions identical, epoch {st['view_epoch']}, "
              f"{st['net_removed_dst']} fenced sends, checkers green")
    sharded = f", {args.shards} shards" if args.shards > 1 else ""
    print(f"reconfig smoke OK: {len(list(SEEDS))} seeds, {total_ops} client "
          f"ops through 5 view changes each{sharded}, completion-identical "
          f"to scalar, view-transition + linearizability checkers green "
          f"({time.time() - t0:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
