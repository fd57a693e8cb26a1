"""Batched-machine serve subsystem: the end-to-end SIMD serve path.

PRs 3–4 batched both halves of a simulated machine in isolation — the
receiver (:mod:`repro.core.vector` + the ``paxos_apply`` Pallas kernel) and
the issuer (:mod:`repro.core.proposer_vector`) — but only behind the
differential replay harness.  This package wires them together into a live
replica, :class:`~.machine.BatchedMachine`, that serves real (simulated)
traffic: ``Cluster(machine_cls=BatchedMachine)`` runs every existing
workload — crash/restart, partitions, all-aboard deployments — unchanged
and completion-for-completion identical to the scalar cluster.

Architecture: fused cluster ticks on a device-resident engine
=============================================================

Since the ClusterEngine refactor, the engines are no longer per-machine:
ALL replicas' planes live stacked on a leading machine axis in one
device-resident :class:`~.cluster_engine.ClusterEngine` —
``(18, M, K)`` receiver KV ints and ``(65, M, S)`` issuer proposer ints —
and the cluster tick runs in fused *waves*::

      every machine's inbox ─▶ IngestScheduler ─▶ conflict-free batches
        (per-key FIFOs, strict                        │ (per machine,
         order / aging fairness)                      ▼  per wave)
                               ┌──────────────────────────────────────┐
      wave w, all machines ──▶ │ ONE fused receiver call              │─▶ replies
        msg lanes (M, K)       │ (M·K,) flattened apply_batch /       │  (row views,
        + is_registered bit    │ paxos_apply kernel, donated buffers  │   arrival
                               └──────────────────────────────────────┘   order)
                               ┌──────────────────────────────────────┐
      wave w, all machines ──▶ │ ONE fused issuer call                │─▶ ActionBatch
        steered replies (M, S) │ (M·S,) proposer_core / paxos_propose │  decisions
        (SteeringTable:        │ kernel, per-row quorum params        │  (row views)
         mid, lid → lane)      └──────────────────────────────────────┘
                                                  │
      host dispatch between waves (scalar code,   ▼  bridge row views):
      grab/steal/help (§4.1/§5/§6), accept values (§8.5/§10.1), local
      commits, retries — then inspection timers and FIFO probing, which
      start new rounds and reload the issuer lanes.

:class:`~.machine.BatchedMachine` is the per-replica front end: its tick
is a *generator* yielding ``("recv", batch)`` / ``("issuer", batch)``
requests; ``Cluster`` hands all machines' generators to
:meth:`~.cluster_engine.ClusterEngine.step_all`, which groups
concurrently-pending requests into one fused call per kind per wave and
resumes the generators (in mid order) with views of their row of the
output planes.  A lone machine without a cluster gets a private 1-row
engine — same code path, M = 1.

The host-bridge contract
========================

The engines are pure and lane-parallel; everything needing cross-lane
gather/scatter is a *host* responsibility, mediated by :mod:`.bridge`:

* **KV state** — authoritative in the engine's stacked KV planes; each
  machine's :class:`~.bridge.KVBridge` is a row view.  Host actions check
  out scalar ``KVPair`` views, run the *unchanged* ``Machine`` code paths
  on them, and the bridge scatters them back before the next engine step.
* **Registry** — authoritative host-side (scalar ``Registry``), the one
  cross-lane piece of the receiver step: ``is_registered`` is gathered
  per staged lane on the host and shipped as a 12th message plane, and
  commit-lane registrations are absorbed back after each wave.
* **Issuer lanes** — round starts (every broadcast) reload the session's
  ProposerTable lane via the ``_note_*_round`` hooks; host-initiated round
  abandonment parks the lane (``PAUSED``) exactly where the scalar machine
  stops gathering replies.  Decision *payloads* come back as ActionBatch
  lanes — the same planes the differential replay asserts against the
  scalar oracle.  The fused receiver replay
  (:func:`repro.core.replay.replay_cluster_fused`) runs the very step the
  engine serves (:func:`repro.kernels.paxos_apply.ops.stacked_replica_step`),
  so live dispatch and replay can never drift apart.
* **Residency + donation** — each stack keeps a single device array
  across ticks (``donate_argnums`` updates it in place); crash/restart
  and view installs evict or reload ONE row via
  :meth:`~.cluster_engine.ClusterEngine.adopt` without dropping residency
  for the rest of the cluster.

Why the batched cluster is completion-identical to the scalar one
=================================================================

Messages and replies cross-couple only through the KV store + registry, so
the machine flushes at every message/reply run boundary of the inbox; the
ingest scheduler's strict mode never lets an item overtake another; and
host actions dispatch in arrival order.  Every send therefore happens in
exactly the order the scalar machine would send it, the simulated network
consumes its RNG identically, and the whole cluster evolves the same
schedule — with the per-lane transitions themselves already proven
equivalent, plane-for-plane, by :mod:`repro.core.replay`.
"""

from repro.core.lanes import ShardMap
from .bridge import KVBridge, ShardedKVView, SteeringTable
from .cluster_engine import ClusterEngine
from .machine import BatchedMachine
from .scheduler import DEFAULT_BATCH_TARGET, IngestScheduler, \
    bucket_conflict_free

__all__ = ["BatchedMachine", "ClusterEngine", "DEFAULT_BATCH_TARGET",
           "IngestScheduler", "KVBridge", "ShardMap", "ShardedKVView",
           "SteeringTable", "bucket_conflict_free"]
