"""ClusterEngine: one device-resident fused tick for every replica.

PR 5's :class:`~.machine.BatchedMachine` batched each half of one machine's
tick, but the cluster still paid 2·N engine dispatches per tick and
round-tripped every plane host↔device on each one — dispatch-bound at ~2
lanes/batch (ROADMAP open item #1, BENCH_smoke e2e lane).  This module
restructures the serve stack around *residency*:

* **Stacked planes** — all N replicas' receiver ``KVTable`` planes and
  issuer ``ProposerTable`` lanes live in two :class:`PlaneStack`\\ s with a
  leading machine axis: ``(18, M, K)`` KV ints and ``(65, M, S)`` proposer
  ints.  Per-key/per-session protocol state machines are independent
  (paper §3), and both engines are elementwise across lanes, so a flattened
  ``(M·K,)`` step *is* N machine steps in one dispatch.

* **Device residency + donation** — each stack keeps a single device array
  across ticks; the fused step functions are jitted with
  ``donate_argnums=(0,)`` so the engine updates state in place instead of
  allocating a fresh cluster image per call.  Donation is safe because the
  stack's host mirror is re-synced *only* from the freshest engine output
  (never from a donated input buffer — see :class:`PlaneStack`), which the
  donation-safety regression test (tests/test_cluster_engine.py) pins.

* **One fused tick** — :meth:`ClusterEngine.step_all` advances every
  machine's tick *generator* in waves: each wave executes one fused
  receiver call and/or one fused issuer call for every machine with a
  pending batch, both in one host round trip each way, then resumes the
  generators (in mid order) with views of their row of the output
  planes.  Host code — KV-coupled decisions, registry scatter, wire I/O
  — runs between waves through the unchanged scalar paths.

This module is the code behind ``docs/serve_architecture.md`` — *wave*,
*plane stack*, *residency* and the *donation contract* are used there
exactly as defined above; the tracked numbers this architecture is
measured by (e2e ratio, occupancy, the open-loop tail-latency lane) are
documented in ``docs/benchmarks.md``.

Why fused waves preserve completion-for-completion identity
===========================================================

* Rows are isolated: machine ``i``'s messages/replies land only in row
  ``i``; a NOOP message lane (kind 0) and an idle reply lane (kind -1)
  leave their KV/proposer lane bit-identical (the per-machine path already
  stepped every idle lane of its own row each batch — proven a no-op by
  the PR 5 differential gates), so stepping *all* rows per wave changes
  nothing for non-participants.
* Cross-machine coupling happens only through the network, and messages
  sent in tick T are never delivered before tick T+1 — so interleaving
  machines' within-tick segments is unobservable...
* ...except through the network RNG, which draws per send.
  :meth:`step_all` therefore buffers each machine's sends during the tick
  and flushes them machine-by-machine in mid order afterwards — exactly
  the global send sequence of the sequential loop (all of machine 0's
  sends, then machine 1's, ...), so delays/drops/duplication replicate.
* Registry gather/scatter moved host-side (it is the one cross-lane piece
  of the receiver step): ``is_registered`` is computed per staged message
  against the machine's own scalar registry — the same
  clip-gather predicate as :func:`repro.kernels.paxos_apply.ops.gather_is_registered`
  — and commit-lane registrations scatter back (max-merge, out-of-range
  dropped) before any generator resumes, i.e. before anything can observe
  the registry, exactly where the per-machine path absorbed them.

Crash/restart/join evict or (re)load **one row**: :meth:`ClusterEngine.adopt`
copies the machine's planes into its slice (volatile issuer lanes reset on
restart, durable KV carried by the shared bridge) without dropping
residency for the rest of the cluster — the next fused call simply
re-uploads the patched stack once.
"""

from __future__ import annotations

import functools
import warnings
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import proposer_vector, vector
from repro.core.lanes import (
    ShardMap, kv_to_lanes, msg_to_lanes, reply_to_lanes,
)
from repro.core.types import KVPair
from repro.kernels.paxos_apply import ops as apply_ops
from repro.kernels.paxos_propose import ops as propose_ops
from repro.parallel import sharding as plane_sharding

# CPU backends may decline a donation (the buffer is still consumed
# semantically — we never re-read it); the warning would fire per compile.
warnings.filterwarnings("ignore",
                        message="Some donated buffers were not usable")

I32 = np.int32

N_KV = len(vector.KVTable._fields)                  # 18
N_REP = len(vector.ReplyBatch._fields)              # 11
N_IREP = len(proposer_vector.IssuerReplyBatch._fields)  # 13

KV_DEFAULTS = kv_to_lanes(KVPair(key=0))

_IREP_IDX = {f: i for i, f in enumerate(
    proposer_vector.IssuerReplyBatch._fields)}

# an unstaged message lane is a NOOP, not registered (the message staging
# buffer carries the is_registered gather result as a 12th plane so one
# device transfer ships both); an unstaged reply lane is idle (kind=-1: no
# fold/decision).
_NOOP_COL = apply_ops.NOOP_COLUMN
_IDLE_COL = np.zeros((N_IREP,), I32)
_IDLE_COL[_IREP_IDX["kind"]] = -1

N_MSGREG = apply_ops.N_MSGREG           # 11 message planes + is_registered
N_PAR = propose_ops.N_PAR               # quorum parameters per machine

# telemetry key -> the ``Machine.stats`` counters it sums over the adopted
# machines, incarnations replaced by a restart included
MACHINE_COUNTERS = {
    "abd_read_write_backs": ("read_write_backs",),
    "round_timeouts": ("round_timeouts",),
    "abd_retransmits": ("abd_retransmits",),
    "all_aboard_attempts": ("all_aboard_attempts",),
    "all_aboard_timeouts": ("all_aboard_timeouts",),
    "helps": ("helps", "help_after_wait"),
    "rmw_completed": ("rmw_completed",),
}


def _machine_counts(m) -> Dict[str, int]:
    """``MACHINE_COUNTERS`` of one machine's ``stats``."""
    return {key: sum(m.stats.get(s, 0) for s in stats)
            for key, stats in MACHINE_COUNTERS.items()}


# ---------------------------------------------------------------------------
# PlaneStack: a device-resident (fields, machines, lanes) int32 block
# ---------------------------------------------------------------------------

class PlaneStack:
    """Struct-of-arrays planes for the whole cluster, resident on device.

    One packed ``(F, M, L)`` int32 array holds field ``f`` of machine ``m``
    at lane ``l``.  The device array is the authoritative state; the host
    mirror (:attr:`host`) is what scalar code reads and writes, and every
    fused wave keeps the two coherent in its own transfers.  A wave takes
    one of two wires (:meth:`ClusterEngine._receiver_half` picks by shape):

    * **dense** — the wave downloads the whole new stack with its other
      outputs and :meth:`absorb` copies it into the mirror; host writes
      mark the stack ``host_dirty`` and ride up whole with the wave's
      staging buffer (:meth:`upload_with`, in the wave's one batched
      transfer).
    * **compact** — the wave downloads only the lanes its step touched
      and :meth:`absorb_lanes` scatters them into the mirror (every other
      lane was a NOOP, bit-identical).  Host writes recorded lane by lane
      (:meth:`patch_views`) ride up as a ``(1 + F, width)`` patch array
      with the wave's compact staging (:meth:`upload_compact`); a
      whole-row write (:meth:`write_views`, :meth:`load_row`,
      :meth:`grow`), or more patch lanes than one patch array holds,
      ships the whole stack instead.

    Either way host reads find the mirror fresh, with no transfer of
    their own.  :meth:`push` remains the out-of-wave upload, counted apart
    from the waves' own, for a read-back of the device state.

    The donation contract lives here: the stack handed to a fused step is
    about to be donated, and :meth:`absorb`/:meth:`absorb_lanes`
    immediately replace ``self.dev`` with the engine's *output*.  The
    donated input reference is dropped in the same step, so a donated
    buffer is never re-read — the mirror is only ever refreshed from the
    freshest output.

    Per-machine field->row view dicts are cached (rebuilt only on growth),
    so host bridges hand out lane views without per-access dict builds.

    **Shard axis.**  With ``n_shards > 1`` the lane axis is kept a multiple
    of ``n_shards`` and treated as that many contiguous shard blocks (the
    :class:`~repro.core.lanes.ShardMap` block partition).  :meth:`set_mesh`
    places the device array on a JAX mesh with a ``"shard"`` axis — the
    lane dimension block-partitions over it (``repro.parallel.sharding``
    rule ``"lanes"``), so a shard's lane block and its device are the same
    thing.  Whole-row host dirtiness is tracked per shard block
    (:attr:`shard_dirty`): whole-row host writes mark every block, a
    per-shard flush (:meth:`mark_shard_dirty`) marks one; the upload
    itself ships the stack in one transfer either way (the donated device
    array is one buffer), but the flags record which shard rows actually
    diverged — the sync bookkeeping per-shard checkpointing and the bench
    occupancy lanes read.
    """

    def __init__(self, fields: Tuple[str, ...], defaults: Dict[str, int],
                 n_machines: int, n_lanes: int, n_shards: int = 1):
        self.fields = tuple(fields)
        self.n_shards = max(1, n_shards)
        n_lanes = ShardMap(self.n_shards, self.n_shards).aligned(n_lanes)
        self._defaults = np.array([defaults[f] for f in self.fields], I32)
        self.host = np.empty((len(self.fields), n_machines, n_lanes), I32)
        self.host[:] = self._defaults[:, None, None]
        self.dev: Optional[jnp.ndarray] = None
        self.shard_dirty = np.ones(self.n_shards, dtype=bool)
        # host writes recorded lane by lane: flat lane indices m * L + l
        self._patches: set = set()
        self._no_patches: Optional[jnp.ndarray] = None
        # coherence telemetry: stacks shipped with a wave's staging
        # (wave_ships), lanes shipped as patches instead (patched_lanes)
        # and mirrors refreshed from a wave's download (wave_refreshes);
        # out-of-wave uploads (syncs); the bytes all of them moved, and
        # row evict/reloads — surfaced via ClusterEngine.telemetry()
        self.wave_ships = 0
        self.patched_lanes = 0
        self.wave_refreshes = 0
        self.syncs = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.reloads = 0
        self.clock = None     # repro.obs.HostClock while a recorder is on
        self._mesh: Optional[Mesh] = None
        self._sharding: Optional[NamedSharding] = None
        self._sharding_shape: Optional[Tuple[int, ...]] = None
        self._views: List[Dict[str, np.ndarray]] = []
        self._rebuild_views()

    # -- shape ---------------------------------------------------------------

    @property
    def n_machines(self) -> int:
        return self.host.shape[1]

    @property
    def n_lanes(self) -> int:
        return self.host.shape[2]

    @property
    def shard_map(self) -> ShardMap:
        """The key→shard steering for this stack's current lane axis."""
        return ShardMap(self.n_shards, self.n_lanes)

    # -- host dirtiness (whole rows per shard block, or lane patches) --------

    @property
    def host_dirty(self) -> bool:
        """Whether host code wrote to the mirror since the device array
        last took it in, whole rows or single lanes."""
        return bool(self._patches) or bool(self.shard_dirty.any())

    @host_dirty.setter
    def host_dirty(self, value: bool) -> None:
        self.shard_dirty[:] = value
        if not value:
            self._patches.clear()

    def mark_shard_dirty(self, shard: int) -> None:
        """Record host writes confined to one shard's lane block."""
        self.shard_dirty[shard] = True

    # -- device placement ----------------------------------------------------

    def set_mesh(self, mesh: Optional[Mesh]) -> None:
        """Place the device array on ``mesh``: plane fields and machine
        rows replicate, the lane axis block-partitions over the mesh's
        ``"shard"`` axis.  Resolution is divisibility-aware (a lane axis
        the mesh does not divide falls back to replication), so a stack
        whose shard count exceeds the device count still works — layout
        and steering stay host-side truths either way."""
        self._mesh = mesh
        self._sharding = None
        self._sharding_shape = None
        if self.dev is not None:
            self.dev = None
            self.host_dirty = True

    def device_sharding(self) -> Optional[NamedSharding]:
        if self._mesh is None:
            return None
        if self._sharding_shape != self.host.shape:
            spec = plane_sharding.resolve(
                ("plane_fields", "machines", "lanes"), self._mesh,
                shape=self.host.shape)
            self._sharding = NamedSharding(self._mesh, spec)
            self._sharding_shape = self.host.shape
        return self._sharding

    def _rebuild_views(self) -> None:
        self._views = [
            {f: self.host[i, mi] for i, f in enumerate(self.fields)}
            for mi in range(self.n_machines)]

    def grow(self, n_machines: Optional[int] = None,
             n_lanes: Optional[int] = None) -> None:
        """Grow either axis; new rows/lanes start at field defaults.

        Drops device residency (one re-upload on the next push) — growth
        changes the jit shape anyway, so the compile is the real cost and
        the callers (bridge key growth, membership joins) keep both
        power-of-two / rare.
        """
        new_m = max(self.n_machines, n_machines or 0)
        # lane growth stays shard-aligned: blocks keep their boundaries
        new_l = ShardMap(self.n_shards, self.n_shards).aligned(
            max(self.n_lanes, n_lanes or 0))
        if (new_m, new_l) == (self.n_machines, self.n_lanes):
            return
        grown = np.empty((len(self.fields), new_m, new_l), I32)
        grown[:] = self._defaults[:, None, None]
        grown[:, :self.n_machines, :self.n_lanes] = self.host
        self.host = grown
        self.dev = None
        self.host_dirty = True
        self._patches.clear()            # flat indices of the old shape
        self._no_patches = None
        self._rebuild_views()

    # -- host <-> device coherence -------------------------------------------

    def read_views(self, mi: int) -> Dict[str, np.ndarray]:
        """Field -> row-``mi`` lane views, for host reads."""
        return self._views[mi]

    def write_views(self, mi: int) -> Dict[str, np.ndarray]:
        """Like :meth:`read_views`, but marks the stack for re-upload."""
        self.host_dirty = True
        return self._views[mi]

    def patch_views(self, mi: int, lanes: Iterable[int]
                    ) -> Dict[str, np.ndarray]:
        """Like :meth:`write_views` for a caller that writes only
        ``lanes`` of row ``mi``: records them as lane patches, which a
        compact wave ships instead of the whole stack."""
        base = mi * self.n_lanes
        self._patches.update(base + lane for lane in lanes)
        return self._views[mi]

    def load_row(self, mi: int, src: "PlaneStack", src_mi: int) -> None:
        """Copy machine ``src_mi``'s lanes from ``src`` into row ``mi``
        (growing this stack's lane axis to cover them); lanes past the
        source keep defaults.  Field layouts must match.  With a sharded
        lane axis the reload runs shard block by shard block — the
        evict/reload unit of crash/restart and view installs."""
        assert src.fields == self.fields
        if src.n_lanes > self.n_lanes:
            self.grow(n_lanes=src.n_lanes)
        self.host_dirty = True
        self.reloads += 1
        length = src.n_lanes
        if self.n_shards > 1 and length == self.n_lanes:
            sm = self.shard_map
            for s in range(self.n_shards):
                sl = sm.slice_of(s)
                self.host[:, mi, sl] = src.host[:, src_mi, sl]
            return
        self.host[:, mi, :length] = src.host[:, src_mi, :]
        self.host[:, mi, length:] = self._defaults[:, None]

    def push(self) -> jnp.ndarray:
        """Upload the mirror now if the device array is stale, and return
        the device stack: the out-of-wave upload (a read-back of device
        state; a fused wave ships host writes with :meth:`upload_with`).
        A mesh-placed stack uploads straight into its block-partitioned
        layout (one ``device_put`` distributing the lane blocks).
        """
        if self.host_dirty or self.dev is None:
            clock = self.clock
            if clock is not None:
                clock.begin("plane.push")
            self.dev = jax.device_put(self.host, self.device_sharding(),
                                      may_alias=False)
            self.host_dirty = False
            self.syncs += 1
            self.h2d_bytes += self.host.nbytes
            if clock is not None:
                clock.end()
        return self.dev

    def upload_with(self, staging: np.ndarray):
        """A fused wave's host→device transfer for this stack:
        ``staging``, and the mirror with it when host code wrote to it (or
        nothing is resident yet).  A generator: it yields the host arrays
        and their placements, which the wave ships with everything else it
        uploads in one batched ``device_put``
        (:meth:`ClusterEngine._run_wave`), takes their device copies back,
        and returns the device stack and staging buffer.  The stack is about to be *donated*: the caller
        must :meth:`absorb` the step's output before any further host
        access."""
        if not self.host_dirty and self.dev is not None:
            (staging_dev,) = yield (staging,), (None,)
            return self.dev, staging_dev
        self.dev, staging_dev = yield ((self.host, staging),
                                       (self.device_sharding(), None))
        self.host_dirty = False
        self.wave_ships += 1
        self.h2d_bytes += self.host.nbytes
        return self.dev, staging_dev

    def upload_compact(self, staging: np.ndarray, width: int):
        """A compact wave's host→device transfer, a generator as
        :meth:`upload_with`: ``staging`` and the host writes.  Recorded
        lane patches ride as a ``(1 + F, width)`` array: row 0 the flat
        lane index (padding aims past the stack and is dropped), rows 1..
        the lane's mirror values.  With no patch lanes, or a whole-row
        write, more patch lanes than ``width`` or nothing resident yet,
        :meth:`upload_with` ships instead, with an all-padding patch
        array that stays on the device.  Returns the device stack,
        staging and patches; the same donation rule holds."""
        if (self.dev is None or self.shard_dirty.any()
                or not self._patches or len(self._patches) > width):
            stack, staging_dev = yield from self.upload_with(staging)
            return stack, staging_dev, self._empty_patches(width)
        idx = np.fromiter(self._patches, np.int64, len(self._patches))
        patches = np.zeros((1 + len(self.fields), width), I32)
        patches[0] = self.n_machines * self.n_lanes
        patches[0, :len(idx)] = idx
        patches[1:, :len(idx)] = self.host.reshape(len(self.fields), -1)[
            :, idx]
        staging_dev, patches_dev = yield (staging, patches), (None, None)
        self.patched_lanes += len(idx)
        self.h2d_bytes += patches.nbytes
        self._patches.clear()
        return self.dev, staging_dev, patches_dev

    def _empty_patches(self, width: int) -> jnp.ndarray:
        """An all-padding patch array, uploaded once per shape."""
        if self._no_patches is None or self._no_patches.shape[1] != width:
            empty = np.zeros((1 + len(self.fields), width), I32)
            empty[0] = self.n_machines * self.n_lanes
            self._no_patches = jax.device_put(empty)
            self.h2d_bytes += empty.nbytes
        return self._no_patches

    def absorb(self, dev_out: jnp.ndarray, host_out: np.ndarray) -> None:
        """Adopt a fused step's output as the new resident state, with
        ``host_out``, its host copy from the same wave's download, as the
        new mirror."""
        assert not self.host_dirty, \
            "host writes raced a fused step; upload_with() ships them"
        self.dev = dev_out
        np.copyto(self.host, host_out)
        self.wave_refreshes += 1
        self.d2h_bytes += host_out.nbytes

    def absorb_lanes(self, dev_out: jnp.ndarray, mi: np.ndarray,
                     lanes: np.ndarray, cols: np.ndarray) -> None:
        """:meth:`absorb` for the compact wire: ``cols``, the same wave's
        download of the output's columns at its staged entries, holds
        lane ``lanes[j]`` of row ``mi[j]`` in column ``j`` (the columns
        past ``len(lanes)`` are padding); every other lane of the output
        equals the mirror."""
        assert not self.host_dirty, \
            "host writes raced a fused step; upload_compact() ships them"
        self.dev = dev_out
        self.host[:, mi, lanes] = cols[:, :len(lanes)]
        self.wave_refreshes += 1
        self.d2h_bytes += cols.nbytes


# ---------------------------------------------------------------------------
# fused step functions (module-level: one jit cache across engines)
# ---------------------------------------------------------------------------

def _expand_compact(kv_stack, entries, patches):
    """A compact wave's operands made dense on the device: the host-written
    lanes of ``patches`` scattered into the stack, and the staged
    ``entries`` scattered plane by plane into an all-NOOP ``(12, M, K)``
    message operand.  Row 0 of both arrays is the flat lane index
    ``m·K + l``; padding aims at row ``M``, past the stack, and is
    dropped."""
    k = kv_stack.shape[2]
    kv = kv_stack.at[:, patches[0] // k, patches[0] % k].set(
        patches[1:], mode="drop")
    rows, lanes = entries[0] // k, entries[0] % k
    msgreg = jnp.stack([
        jnp.full(kv_stack.shape[1:], noop, jnp.int32).at[rows, lanes].set(
            entries[1 + i], mode="drop")
        for i, noop in enumerate(_NOOP_COL.tolist())])
    return kv, msgreg


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("use_kernel", "interpret", "block_rows",
                                    "shard_lanes", "out_sharding"))
def _fused_receiver_step(kv_stack, msgreg_stack, patches=None, *,
                         use_kernel, block_rows, shard_lanes=None,
                         out_sharding=None, interpret=None):
    """One receiver step for every machine: (18,M,K),(12,M,K) ->
    (18,M,K),(11,M,K),(M,K), the stacked step of
    :func:`repro.kernels.paxos_apply.ops.stacked_replica_step` (which
    holds the kernel choice and the shard-segment padding).

    With ``patches`` the wave came on the compact wire: ``msgreg_stack``
    is then a ``(13, W)`` batch of staged entries and ``patches`` a
    ``(19, W)`` array of host-written lanes, each with the flat lane index
    as row 0 (:func:`_expand_compact`).  The patches land in the donated
    stack and the entries become the dense message operand on the device,
    so the step is the same elementwise pass over the whole stack.

    ``out_sharding`` (static) is the stack's placement on a device mesh.
    The step then runs under ``shard_map``: each device steps its own lane
    block (a Mosaic kernel cannot be partitioned automatically), and the
    outputs keep the stack's placement across waves.  ``check_vma`` is off
    because ``pallas_call`` outputs carry no varying-axes annotation; a
    stack too narrow to split is replicated, and every device then
    computes the same step.  A sharded stack takes the dense wire only.
    ``interpret`` (static) is left to the kernel, which derives it from
    the platform; only a compile for a described chip passes False."""
    if patches is not None:
        assert out_sharding is None, "a sharded stack takes the dense wire"
        kv_stack, msgreg_stack = _expand_compact(kv_stack, msgreg_stack,
                                                 patches)
    if out_sharding is None:
        return apply_ops.stacked_replica_step(
            kv_stack, msgreg_stack, use_kernel=use_kernel,
            block_rows=block_rows, shard_lanes=shard_lanes,
            interpret=interpret)
    spec = out_sharding.spec
    local = functools.partial(apply_ops.stacked_replica_step,
                              use_kernel=use_kernel,
                              block_rows=block_rows, shard_lanes=None,
                              interpret=interpret)
    return jax.shard_map(local, mesh=out_sharding.mesh,
                         in_specs=(spec, spec),
                         out_specs=(spec, spec, P(*tuple(spec)[1:])),
                         check_vma=False)(kv_stack, msgreg_stack)


@jax.jit
def _touched_lanes(kv_stack, replies, mask, entries):
    """A receiver step's outputs at a compact wave's staged entries,
    packed ``(18 + 11 + 1, W)``: the new KV columns, the reply columns
    and the registration mask, read from the outputs as the step returned
    them.  Padding entries read a clipped lane, which the host drops."""
    k = mask.shape[1]
    rows, lanes = entries[0] // k, entries[0] % k
    return jnp.concatenate([
        kv_stack.at[:, rows, lanes].get(mode="clip"),
        replies.at[:, rows, lanes].get(mode="clip"),
        mask.at[rows, lanes].get(mode="clip")[None].astype(jnp.int32)])


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("use_kernel", "interpret", "block_rows",
                                    "shard_lanes", "out_sharding"))
def _fused_issuer_step(tab_stack, rep_stack, params, *, use_kernel,
                       block_rows, shard_lanes=None, out_sharding=None,
                       interpret=None):
    """One issuer step for every machine: (65,M,S),(13,M,S),(4,M,1) ->
    (65,M,S),(14,M,S), the stacked step of
    :func:`repro.kernels.paxos_propose.ops.stacked_issuer_step`.
    ``out_sharding`` and ``interpret`` as in
    :func:`_fused_receiver_step`."""
    if out_sharding is None:
        return propose_ops.stacked_issuer_step(
            tab_stack, rep_stack, params, use_kernel=use_kernel,
            block_rows=block_rows, shard_lanes=shard_lanes,
            interpret=interpret)
    spec = out_sharding.spec
    local = functools.partial(propose_ops.stacked_issuer_step,
                              use_kernel=use_kernel,
                              block_rows=block_rows, shard_lanes=None,
                              interpret=interpret)
    return jax.shard_map(local, mesh=out_sharding.mesh,
                         in_specs=(spec, spec, P()),
                         out_specs=(spec, spec),
                         check_vma=False)(tab_stack, rep_stack, params)


def _download(outs, clock) -> List[List[np.ndarray]]:
    """Host copies of a wave's outputs, a list per half, in one
    device→host round trip: every copy is started before any is read, so
    they overlap.  With a clock the caller has opened ``engine.wait``: the
    steps' outputs are awaited there (the copies already queued behind the
    computation, so the wait adds no round trip), and ``engine.download``
    times what the copies add."""
    for half in outs:
        for a in half:
            a.copy_to_host_async()
    if clock is not None:
        jax.block_until_ready(outs)
        clock.switch("engine.download")
    return [[np.asarray(a) for a in half] for half in outs]


def _finish(half, host_outs):
    """Resume a wave half with the host copies of its outputs; what it
    returns, the machines' result views."""
    try:
        half.send(host_outs)
    except StopIteration as done:
        return done.value
    raise RuntimeError("a wave half yields twice: its upload, its outputs")


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def _shard_mesh(shards: int) -> Optional[Mesh]:
    """A 1-D ``"shard"`` mesh over the first ``shards`` devices.

    ``None`` when sharding is off.  On a TPU, fewer devices than shards
    raises: a run that asked for sharded state must not silently put every
    shard on one chip.  On CPU the shard *layout* (aligned lane blocks,
    steering, per-shard batches) still applies host-side with no mesh —
    the tests of that layout rely on it; CI forces the devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""
    if shards <= 1:
        return None
    devices = jax.devices()
    if len(devices) < shards:
        if devices[0].platform == "tpu":
            raise ValueError(
                f"shards={shards} needs {shards} devices; this host has "
                f"{len(devices)} {devices[0].device_kind}")
        return None
    return Mesh(np.array(devices[:shards]), ("shard",))


class ClusterEngine:
    """Owns the cluster's stacked planes and drives fused tick waves.

    Machines talk to the engine through a tiny generator protocol: a
    machine's ``_tick_gen()`` yields ``("recv", batch)`` /
    ``("issuer", batch)`` requests and is resumed with row views of the
    fused output planes.  :meth:`drive` groups concurrently-pending
    requests of all machines into one fused call per kind per wave.

    With ``shards > 1`` the state plane is "one resident stack per shard"
    materialized as shard-aligned blocks of the same stacks: the KV lane
    axis (and the session axis, when divisible) splits into contiguous
    blocks placed across a ``"shard"`` device mesh, kernel tiles pad per
    block (``shard_lanes``), staging/occupancy and registry scatter are
    accounted per shard — yet one fused receiver/issuer call per wave
    still *spans every shard* (the partitioned array is a single jit
    argument), so dispatch count is unchanged from the unsharded engine.
    """

    def __init__(self, cfg, n_machines: int = 1, *,
                 use_kernel: bool = False, block_rows: int = 32,
                 n_keys: int = 8, shards: int = 1):
        self.cfg = cfg
        self.use_kernel = use_kernel
        self.block_rows = block_rows
        self.shards = max(1, int(shards))
        # session lanes shard only when the axis divides evenly; the KV
        # lane axis is kept shard-aligned by the stack itself
        sess = cfg.sessions_per_machine
        self.tab_shards = self.shards if sess % self.shards == 0 else 1
        self.kv = PlaneStack(vector.KVTable._fields, KV_DEFAULTS,
                             max(1, n_machines), max(8, n_keys),
                             n_shards=self.shards)
        self.tab = PlaneStack(proposer_vector.ProposerTable._fields,
                              proposer_vector.TABLE_DEFAULTS,
                              max(1, n_machines), sess,
                              n_shards=self.tab_shards)
        self.mesh = _shard_mesh(self.shards)
        if self.mesh is not None:
            self.kv.set_mesh(self.mesh)
            self.tab.set_mesh(self.mesh)
        self._machines: Dict[int, object] = {}    # mi -> BatchedMachine
        # MACHINE_COUNTERS of the incarnations adopt() replaced
        self._replaced = dict.fromkeys(MACHINE_COUNTERS, 0)
        self._bridges: Dict[int, object] = {}     # mi -> its KVBridge
        self._msg_host: Optional[np.ndarray] = None
        self._entries_host: Optional[np.ndarray] = None
        self._replies_host: Optional[np.ndarray] = None
        self._rep_host: Optional[np.ndarray] = None
        self._params_key = None
        self._params_dev: Optional[jnp.ndarray] = None
        self.stats = {"ticks": 0, "shards": self.shards,
                      "staging_h2d_bytes": 0, "staging_d2h_bytes": 0,
                      "fused_receiver_calls": 0, "fused_receiver_lanes": 0,
                      "compact_receiver_waves": 0,
                      "fused_issuer_calls": 0, "fused_issuer_lanes": 0,
                      "paired_waves": 0,
                      "receiver_shard_lanes": [0] * self.shards,
                      "issuer_shard_lanes": [0] * self.tab_shards,
                      "shard_registrations": [0] * self.shards}
        self.clock = None     # repro.obs.HostClock while a recorder is on

    def set_clock(self, clock) -> None:
        """Time this engine's waves and its stacks' copies on ``clock``
        (a :class:`repro.obs.HostClock`; ``None`` turns the spans off)."""
        self.clock = self.kv.clock = self.tab.clock = clock

    # -- telemetry -----------------------------------------------------------

    def telemetry(self) -> Dict[str, object]:
        """``stats`` plus the plane-coherence counters that live on the
        stacks themselves: stacks shipped inside a wave's upload
        (``plane_wave_ships``) and mirrors refreshed from a wave's
        download (``plane_wave_refreshes``), out-of-wave uploads
        (``plane_syncs``, split per stack), row evict/reloads
        (crash/restart + view installs), and every byte moved between host and device
        (``h2d_bytes``/``d2h_bytes``: the whole stacks, in a wave or out
        of one, as ``stack_h2d_bytes``/``stack_d2h_bytes``, plus the
        per-wave staging and reply transfers, ``staging_*``).  It also
        sums the adopted machines' protocol counters, replaced
        incarnations included (``MACHINE_COUNTERS``): the ABD reads that
        took the §11 write-back round (``abd_read_write_backs``), stalled
        rounds retried or retransmitted (``round_timeouts``,
        ``abd_retransmits``), All-aboard attempts and §9.2 time-outs,
        helps (§6, after a wait too) and completed RMWs.  While a
        clock is attached it also carries the clock's span totals and
        counters (:meth:`repro.obs.HostClock.totals`).  The flight
        recorder pulls this at snapshot time."""
        t = dict(self.stats)
        t["plane_wave_ships"] = self.kv.wave_ships + self.tab.wave_ships
        t["patched_lanes"] = self.kv.patched_lanes
        t["plane_wave_refreshes"] = (self.kv.wave_refreshes
                                     + self.tab.wave_refreshes)
        t["kv_plane_syncs"] = self.kv.syncs
        t["tab_plane_syncs"] = self.tab.syncs
        t["plane_syncs"] = self.kv.syncs + self.tab.syncs
        t["row_reloads"] = self.kv.reloads + self.tab.reloads
        t["stack_h2d_bytes"] = self.kv.h2d_bytes + self.tab.h2d_bytes
        t["stack_d2h_bytes"] = self.kv.d2h_bytes + self.tab.d2h_bytes
        t["h2d_bytes"] = (self.stats["staging_h2d_bytes"]
                          + t["stack_h2d_bytes"])
        t["d2h_bytes"] = (self.stats["staging_d2h_bytes"]
                          + t["stack_d2h_bytes"])
        t.update(self._replaced)
        for m in self._machines.values():
            for key, n in _machine_counts(m).items():
                t[key] += n
        if self.clock is not None:
            t.update(self.clock.totals())
        return t

    # -- shard steering ------------------------------------------------------

    def kv_shard_map(self) -> ShardMap:
        """Key→shard steering over the current KV lane axis."""
        return self.kv.shard_map

    def sess_shard_map(self) -> ShardMap:
        """Session→shard steering over the issuer lane axis."""
        return self.tab.shard_map

    # -- membership ----------------------------------------------------------

    def adopt(self, m) -> None:
        """(Re)bind machine ``m`` to row ``m.mid`` of the stacked planes.

        Loads the row from the machine's current planes: a brand-new or
        restarted machine carries default issuer lanes (volatile proposer
        state is lost on crash — the reset *is* the eviction), while its
        KV bridge, if it already shares this engine's stack (restart /
        same-mid rejoin carrying the durable acceptor state), is left in
        place untouched.  Other rows keep their residency."""
        mi = m.mid
        if mi >= self.kv.n_machines:
            self.kv.grow(n_machines=mi + 1)
            self.tab.grow(n_machines=mi + 1)
        if m._engine is not self:
            if m.kvs._stack is not self.kv:
                self.kv.load_row(mi, m.kvs._stack, m.kvs._mi)
                m.kvs._stack = self.kv
                m.kvs._mi = mi
            self.tab.load_row(mi, m._engine.tab, m._mi)
            m._engine = self
            m._mi = mi
        old = self._machines.get(mi)
        if old is not None and old is not m:
            for key, n in _machine_counts(old).items():
                self._replaced[key] += n
        self._machines[mi] = m
        self._bridges[mi] = m.kvs
        self._params_key = None

    def _params(self) -> jnp.ndarray:
        """(4, M, 1) per-machine quorum-parameter stack, cached until any
        adopted machine's view-derived quorums change."""
        m_ax = self.tab.n_machines
        key = (m_ax,) + tuple(
            (mi, mach.view.all_aboard_quorum(), mach.view.quorum(),
             mach._commit_need)
            for mi, mach in sorted(self._machines.items()))
        if key != self._params_key:
            p = np.ones((N_PAR, m_ax, 1), I32)
            p[3] = self.cfg.log_too_high_threshold
            for mi, mach in self._machines.items():
                p[0, mi, 0] = mach.view.all_aboard_quorum()
                p[1, mi, 0] = mach.view.quorum()
                p[2, mi, 0] = mach._commit_need
            self._params_dev = jnp.asarray(p)
            self._params_key = key
            self.stats["staging_h2d_bytes"] += p.nbytes
        return self._params_dev

    # -- staging buffers (persistent, reset lane-by-lane) --------------------

    def _msg_buffers(self) -> np.ndarray:
        shape = (N_MSGREG, self.kv.n_machines, self.kv.n_lanes)
        if self._msg_host is None or self._msg_host.shape != shape:
            self._msg_host = np.empty(shape, I32)
            self._msg_host[:] = _NOOP_COL[:, None, None]
        return self._msg_host

    def _entry_buffers(self, width: int) -> Tuple[np.ndarray, np.ndarray]:
        """The compact wire's staging buffer, ``(1 + 12, width)``, every
        column padding (a NOOP aimed past the stack), and the persistent
        ``(11, M, K)`` host plane its replies are scattered into."""
        shape = (N_REP, self.kv.n_machines, self.kv.n_lanes)
        if (self._replies_host is None or self._replies_host.shape != shape
                or self._entries_host.shape[1] != width):
            self._entries_host = np.empty((1 + N_MSGREG, width), I32)
            self._entries_host[0] = shape[1] * shape[2]
            self._entries_host[1:] = _NOOP_COL[:, None]
            self._replies_host = np.zeros(shape, I32)
        return self._entries_host, self._replies_host

    def _rep_buffers(self) -> np.ndarray:
        shape = (N_IREP, self.tab.n_machines, self.tab.n_lanes)
        if self._rep_host is None or self._rep_host.shape != shape:
            self._rep_host = np.empty(shape, I32)
            self._rep_host[:] = _IDLE_COL[:, None, None]
        return self._rep_host

    def _wave_width(self) -> Optional[int]:
        """Entries one compact receiver wave stages: machines × the
        machines' batch target (one batch per machine per wave, each
        capped by its ``IngestScheduler``).  ``None`` picks the dense
        wire: a machine has no batch target, the stack lies on a device
        mesh, or the plane is no wider than a wave, so a compact batch
        would save nothing."""
        if self.kv.device_sharding() is not None:
            return None
        targets = [m.batch_target for m in self._machines.values()]
        if not targets or None in targets:
            return None
        width = self.kv.n_machines * max(targets)
        if self.kv.n_machines * self.kv.n_lanes <= width:
            return None
        return width

    # -- fused wave execution ------------------------------------------------

    def _receiver_half(self, requests):
        """requests: [(machine, [Msg,...]), ...] — one fused receiver
        call, as a wave half (:meth:`_run_wave`).

        The wave takes the compact wire when :meth:`_wave_width` gives a
        width, else the dense one (:class:`PlaneStack` describes both).
        Dense, the whole ``(12, M, K)`` staging buffer goes up and the
        whole new stack, replies and mask come down.  Compact, only the
        staged entries (and host-written lanes, as patches) go up, and
        after the step one small gather (:func:`_touched_lanes`) brings
        down the outputs at those entries alone."""
        # every bridge sharing the stack scatters its checked-out views
        # first: the fused call steps the *whole* stack
        for br in self._bridges.values():
            br.flush()
        fields = vector.MsgBatch._fields
        lps = self.kv.n_lanes // self.shards    # lanes per shard block
        shard_lanes_stat = self.stats["receiver_shard_lanes"]
        cols: List[List[int]] = []
        s_mi: List[int] = []
        s_key: List[int] = []
        for mach, batch in requests:
            mi = mach._mi
            committed = mach.registry.committed
            last = len(committed) - 1
            for msg in batch:
                vals = msg_to_lanes(msg)
                # host mirror of ops.gather_is_registered (clip + compare):
                # packed as the 12th staging plane
                rid = msg.rmw_id
                gs = rid.gsess
                cols.append([vals[f] for f in fields] + [
                    1 if (gs >= 0 and committed[min(gs, last)] >= rid.counter)
                    else 0])
                s_mi.append(mi)
                s_key.append(msg.key)
                shard_lanes_stat[msg.key // lps] += 1
        # one vectorized scatter for the whole wave (per-item fancy writes
        # were the staging hotspot)
        staged = np.array(cols, I32).T
        width = self._wave_width()
        step = functools.partial(
            _fused_receiver_step,
            use_kernel=self.use_kernel, block_rows=self.block_rows,
            shard_lanes=lps if self.shards > 1 else None)
        if width is None:
            rep_np, mask_e = yield from self._receiver_dense(
                step, staged, s_mi, s_key)
        else:
            rep_np, mask_e = yield from self._receiver_compact(
                step, width, staged, s_mi, s_key)
        for br in self._bridges.values():
            br.drop_views()              # stale against the new stack
        results: Dict[int, Dict[str, np.ndarray]] = {}
        self.stats["fused_receiver_calls"] += 1
        reg_stat = self.stats["shard_registrations"]
        j = 0
        for mach, batch in requests:
            mi = mach._mi
            committed = mach.registry.committed
            for msg in batch:
                # host mirror of ops.scatter_register (max, OOB dropped).
                # This is the cross-shard registry scatter: a registration
                # born in one shard's lane block max-merges into the
                # machine-global registry that every shard's gather reads
                # next wave, with the owning shard journaled in the
                # bridge's per-shard mirror.
                if mask_e[j]:
                    gs = msg.rmw_id.gsess
                    cnt = msg.rmw_id.counter
                    if 0 <= gs < len(committed) and cnt > committed[gs]:
                        committed[gs] = cnt
                    shard = msg.key // lps
                    mach.kvs.note_registration(shard, gs, cnt)
                    reg_stat[shard] += 1
                j += 1
            self.stats["fused_receiver_lanes"] += len(batch)
            results[id(mach)] = {f: rep_np[i, mi] for i, f
                                 in enumerate(vector.ReplyBatch._fields)}
        return results

    def _receiver_dense(self, step, staged, s_mi, s_key):
        """The dense wire from the upload to the unstaging, in the wave
        protocol: returns the ``(11, M, K)`` replies and the mask at each
        staged entry."""
        msg_host = self._msg_buffers()
        msg_host[:, s_mi, s_key] = staged
        kv_dev, msg_dev = yield from self.kv.upload_with(msg_host)
        outs = step(kv_dev, msg_dev, out_sharding=self.kv.device_sharding())
        kv_np, rep_np, mask_np = yield outs
        self.kv.absorb(outs[0], kv_np)
        self.stats["staging_h2d_bytes"] += msg_host.nbytes
        self.stats["staging_d2h_bytes"] += rep_np.nbytes + mask_np.nbytes
        # reset to NOOP for the next wave
        msg_host[:, s_mi, s_key] = _NOOP_COL[:, None]
        return rep_np, mask_np[s_mi, s_key]

    def _receiver_compact(self, step, width, staged, s_mi, s_key):
        """The compact wire, as :meth:`_receiver_dense`: returns the
        persistent reply plane, fresh at the staged lanes, and the mask at
        each staged entry."""
        n = len(s_mi)
        assert n <= width, f"{n} staged messages overflow a {width}-wide wave"
        entries, replies = self._entry_buffers(width)
        mi = np.asarray(s_mi, np.int64)
        key = np.asarray(s_key, np.int64)
        entries[0, :n] = mi * self.kv.n_lanes + key
        entries[1:, :n] = staged
        kv_dev, ent_dev, patch_dev = yield from self.kv.upload_compact(
            entries, width)
        outs = step(kv_dev, ent_dev, patch_dev)
        (packed,) = yield [_touched_lanes(*outs, ent_dev)]
        self.kv.absorb_lanes(outs[0], mi, key, packed[:N_KV])
        replies[:, mi, key] = packed[N_KV:N_KV + N_REP, :n]
        self.stats["compact_receiver_waves"] += 1
        self.stats["staging_h2d_bytes"] += entries.nbytes
        self.stats["staging_d2h_bytes"] += packed[N_KV:].nbytes
        # reset to padding for the next wave
        entries[0, :n] = self.kv.n_machines * self.kv.n_lanes
        entries[1:, :n] = _NOOP_COL[:, None]
        return replies, packed[-1, :n]

    def _issuer_half(self, requests):
        """requests: [(machine, [(lane, Reply),...]), ...] — one fused
        issuer call, as a wave half (:meth:`_run_wave`)."""
        rep_host = self._rep_buffers()
        fields = proposer_vector.IssuerReplyBatch._fields
        lps = self.tab.n_lanes // self.tab_shards
        shard_lanes_stat = self.stats["issuer_shard_lanes"]
        cols: List[List[int]] = []
        s_mi: List[int] = []
        s_lane: List[int] = []
        for mach, batch in requests:
            mi = mach._mi
            for lane, rep in batch:
                vals = reply_to_lanes(rep)
                cols.append([vals[f] for f in fields])
                s_mi.append(mi)
                s_lane.append(lane)
                shard_lanes_stat[lane // lps] += 1
        rep_host[:, s_mi, s_lane] = np.array(cols, I32).T
        tab_dev, rep_dev = yield from self.tab.upload_with(rep_host)
        outs = _fused_issuer_step(
            tab_dev, rep_dev, self._params(),
            use_kernel=self.use_kernel, block_rows=self.block_rows,
            shard_lanes=lps if self.tab_shards > 1 else None,
            out_sharding=self.tab.device_sharding())
        tab_np, act_np = yield outs
        self.tab.absorb(outs[0], tab_np)
        self.stats["staging_h2d_bytes"] += rep_host.nbytes
        self.stats["staging_d2h_bytes"] += act_np.nbytes
        results: Dict[int, Dict[str, np.ndarray]] = {}
        self.stats["fused_issuer_calls"] += 1
        for mach, batch in requests:
            self.stats["fused_issuer_lanes"] += len(batch)
            results[id(mach)] = {
                f: act_np[i, mach._mi] for i, f
                in enumerate(proposer_vector.ActionBatch._fields)}
        # reset to idle for the next wave
        rep_host[:, s_mi, s_lane] = _IDLE_COL[:, None]
        return results

    def _run_wave(self, halves) -> Dict[int, Dict[str, np.ndarray]]:
        """Run a wave's halves, the receiver's and then the issuer's (one
        or both), with one host round trip each way for all of them.

        Each half is a generator of the wave protocol.  It stages its
        batch and yields its upload: a tuple of host arrays and one of
        their placements.  Resumed with their device copies, it launches
        its step and yields the outputs the host reads.  Resumed with
        their host copies, it unstages and returns the machines' result
        views.  The halves step disjoint stacks for different machines
        (each machine yields one request a wave), so nothing one stages
        depends on the other's outputs: every upload goes in one batched
        ``device_put``, both steps are launched, and one wait and one
        download serve both.

        With a clock attached the wave is six sibling spans, each opened
        once: ``stage``, ``upload``, ``launch``, ``wait`` (for the device,
        so the copies after it time the copy alone), ``download`` and
        ``unstage``."""
        clock = self.clock
        if clock is not None:
            clock.begin("engine.stage")
        ups = [next(half) for half in halves]
        if clock is not None:
            clock.switch("engine.upload")
        # may_alias=False: on the CPU a device_put of a numpy array would
        # otherwise share its memory, and a donated stack, and with it the
        # step's output, could live in the mirror
        devs = jax.device_put(tuple(arrays for arrays, _ in ups),
                              tuple(places for _, places in ups),
                              may_alias=False)
        if clock is not None:
            clock.switch("engine.launch")
        outs = [half.send(d) for half, d in zip(halves, devs)]
        if clock is not None:
            clock.switch("engine.wait")
        host_outs = _download(outs, clock)
        if clock is not None:
            clock.switch("engine.unstage")
        results: Dict[int, Dict[str, np.ndarray]] = {}
        for half, host in zip(halves, host_outs):
            results.update(_finish(half, host))
        if len(halves) > 1:
            self.stats["paired_waves"] += 1
        if clock is not None:
            clock.end()
        return results

    def drive(self, pairs: Iterable[Tuple[object, object]]) -> None:
        """Advance (machine, tick-generator) pairs to completion in waves.

        Each wave collects every pending request, executes at most one
        fused receiver call and one fused issuer call, both in one round
        trip each way (:meth:`_run_wave`), and resumes the generators in
        the order given (mid order — matching the sequential loop's
        per-machine ordering of host actions).  Each resumption is
        a ``machine`` span: the machine's scalar host decisions."""
        clock = self.clock
        pending = []
        for mach, gen in pairs:
            if clock is not None:
                clock.begin("machine")
            try:
                req = next(gen)
            except StopIteration:
                continue
            finally:
                if clock is not None:
                    clock.end()
            pending.append((mach, gen, req))
        while pending:
            recv = [(m, r[1]) for m, _g, r in pending if r[0] == "recv"]
            iss = [(m, r[1]) for m, _g, r in pending if r[0] == "issuer"]
            halves = []
            if recv:
                halves.append(self._receiver_half(recv))
            if iss:
                halves.append(self._issuer_half(iss))
            results = self._run_wave(halves)
            nxt = []
            for mach, gen, _req in pending:
                if clock is not None:
                    clock.begin("machine")
                try:
                    req = gen.send(results[id(mach)])
                except StopIteration:
                    continue
                finally:
                    if clock is not None:
                        clock.end()
                nxt.append((mach, gen, req))
            pending = nxt

    # -- the cluster tick ----------------------------------------------------

    def step_all(self, machines, net_send) -> None:
        """One fused tick for the whole cluster.

        Sends are buffered per machine during the waves and flushed in mid
        order afterwards, reproducing the sequential loop's global send
        sequence exactly (the network draws RNG per send)."""
        clock = self.clock
        if clock is not None:
            clock.begin("engine.step_all")
        self.stats["ticks"] += 1
        for mach in machines:
            if mach._engine is not self:
                self.adopt(mach)
        buffers: List[List[Tuple[int, int, object]]] = []
        saved = []
        try:
            for mach in machines:
                buf: List[Tuple[int, int, object]] = []
                buffers.append(buf)
                saved.append(mach._send)
                mach._send = (lambda src, dst, payload, _b=buf:
                              _b.append((src, dst, payload)))
            self.drive([(mach, mach._tick_gen()) for mach in machines])
        finally:
            for mach, fn in zip(machines, saved):
                mach._send = fn
        if clock is not None:
            clock.begin("net.send")
        for buf in buffers:
            for src, dst, payload in buf:
                net_send(src, dst, payload)
        if clock is not None:
            clock.end()                  # net.send
            clock.end()                  # engine.step_all
