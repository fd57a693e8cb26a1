#!/usr/bin/env python3
"""Run the served register store end to end on a TPU, and check it.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded KV plane on four chips

The deployment is the paper's in-datacenter register store: 5 replicas
(the paper runs 3 to 7), a key universe of 2^20 keys drawn Zipfian with
YCSB's constant 0.99, and the ``update_heavy`` mix (30% RMW, 30% write,
40% read) offered open-loop by ``OpenLoopHarness``.  Every replica's KV
plane is sized to the whole universe before the first op, so the device
holds 18 int32 planes x 5 replicas x 2^20 keys = 377 MB of register state
and nothing grows or recompiles mid-run.  Values are single int32.

One chip runs three phases through ``Cluster(machine_cls=BatchedMachine)``
with its fused ``ClusterEngine``:

* ``kernel_cp``          Pallas kernels, All-aboard off (classic Paxos RMWs);
* ``kernel_all_aboard``  Pallas kernels, All-aboard on;
* ``jnp_cp``             the jnp engine, a shorter run.

``--chips 4`` runs only the sharded path: ``BatchedMachine(shards=4)``
with the KV plane split over four chips, and its ``shards=1`` twin.

Each phase is checked against a scalar ``Machine`` cluster on the same
spec and seed: the completions must be identical and the linearizability
checkers green (``OpenLoopHarness.run`` runs them).  Between them the two
kernel phases must reach all four protocol paths of the flight recorder.
Any failure raises, and the script exits non-zero without a result line.
Without a TPU it exits non-zero before any phase.  The last line of a
passing run is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

N_MACHINES = 5
N_KEYS = 1 << 20
SESSIONS = 8
ZIPF_S = 0.99
MIX = "update_heavy"
SEED = 11
# Offered load: Poisson arrivals at RATE ops per virtual tick for TICKS
# ticks, ~100 ops per kernel phase, which keeps the one-chip run to a few
# minutes.
RATE = 0.5
TICKS = 200
JNP_TICKS = 70
FOUR_CHIP_TICKS = 100
SHARDS = 4
PATHS = ("abd_read", "abd_write", "cp_slow", "all_aboard_fast")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and how many
    backend compiles ran, read from ``jax.monitoring`` while installed."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def _listen(self, event, duration, **_):
        if event in self._EVENTS:
            self.seconds += duration
            self.compiles += event == self._EVENTS[-1]

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listen)


def spec_for(*, n_keys: int, ticks: float, all_aboard: bool,
             rate: float = RATE, seed: int = SEED):
    from repro.serve.loadgen import MIXES, ArrivalPhase, OpenLoopSpec
    return OpenLoopSpec(seed=seed, n_machines=N_MACHINES, sessions=SESSIONS,
                        n_keys=n_keys, zipf_s=ZIPF_S, mix=MIXES[MIX],
                        phases=(ArrivalPhase(rate=rate, ticks=ticks),),
                        all_aboard=all_aboard)


def run_phase(name: str, spec, *, use_kernel: bool, shards: int = 1,
              scalar=None) -> dict:
    """One batched run of ``spec`` against the scalar cluster.  Returns a
    row of what the run moved and reached; raises on any divergence."""
    from repro.core.node import Machine
    from repro.core.sim import completion_tuples
    from repro.obs import FlightRecorder
    from repro.serve.loadgen import OpenLoopHarness
    from repro.serve.paxos import BatchedMachine

    if scalar is None:
        scalar = OpenLoopHarness(spec, machine_cls=Machine).run()
    rec = FlightRecorder(mode="off")
    mcls = functools.partial(BatchedMachine, use_kernel=use_kernel,
                             shards=shards)
    with CompileClock() as clock:
        t0 = time.perf_counter()
        res = OpenLoopHarness(spec, machine_cls=mcls, obs=rec).run()
        wall = time.perf_counter() - t0
    want = completion_tuples(scalar.cluster)
    got = completion_tuples(res.cluster)
    if got != want:
        raise AssertionError(
            f"{name}: batched completions diverged from the scalar cluster "
            f"({len(got)} vs {len(want)} completions)")
    engine = res.cluster.engine
    tel = engine.telemetry()
    paths = rec.path_counts()
    return {
        "phase": name, "use_kernel": use_kernel, "shards": shards,
        "all_aboard": spec.all_aboard, "offered": res.offered,
        "completions": res.completed, "lost": res.lost, "ticks": res.ticks,
        "kv_plane": list(engine.kv.host.shape),
        "kv_devices": len(engine.kv.dev.sharding.device_set),
        "compile_s": clock.seconds, "compiles": clock.compiles,
        "wall_s": wall,
        "fused_calls": tel["fused_receiver_calls"] + tel["fused_issuer_calls"],
        "fused_receiver_calls": tel["fused_receiver_calls"],
        "plane_syncs": tel["plane_syncs"],
        "plane_wave_ships": tel["plane_wave_ships"],
        "plane_wave_refreshes": tel["plane_wave_refreshes"],
        "compact_receiver_waves": tel["compact_receiver_waves"],
        "patched_lanes": tel["patched_lanes"],
        "h2d_bytes": tel["h2d_bytes"], "d2h_bytes": tel["d2h_bytes"],
        "paths": {p: paths[p] for p in PATHS},
        "identical_to_scalar": True, "checkers": "green",
    }


def one_chip_phases(n_keys: int = N_KEYS, ticks: float = TICKS,
                    jnp_ticks: float = JNP_TICKS) -> list:
    """The one-chip phases; raises unless every one matches the scalar
    cluster and the kernel phases reach every protocol path."""
    rows = [
        run_phase("kernel_cp", spec_for(n_keys=n_keys, ticks=ticks,
                                        all_aboard=False), use_kernel=True),
        run_phase("kernel_all_aboard",
                  spec_for(n_keys=n_keys, ticks=ticks, all_aboard=True),
                  use_kernel=True),
        run_phase("jnp_cp", spec_for(n_keys=n_keys, ticks=jnp_ticks,
                                     all_aboard=False), use_kernel=False),
    ]
    reached = {p: sum(r["paths"][p] for r in rows[:2]) for p in PATHS}
    missing = [p for p, n in reached.items() if n == 0]
    if missing:
        raise AssertionError(f"kernel phases never took paths {missing}: "
                             f"{reached}")
    return rows


def four_chip_phases(n_keys: int = N_KEYS,
                     ticks: float = FOUR_CHIP_TICKS) -> list:
    """The KV plane sharded over four chips and its unsharded twin, both
    against one scalar run; raises unless the sharded stack spans four
    devices."""
    from repro.core.node import Machine
    from repro.serve.loadgen import OpenLoopHarness

    spec = spec_for(n_keys=n_keys, ticks=ticks, all_aboard=False)
    scalar = OpenLoopHarness(spec, machine_cls=Machine).run()
    rows = [run_phase(f"kernel_shards{SHARDS}", spec, use_kernel=True,
                      shards=SHARDS, scalar=scalar),
            run_phase("kernel_shards1", spec, use_kernel=True, shards=1,
                      scalar=scalar)]
    if rows[0]["kv_devices"] != SHARDS:
        raise AssertionError(f"sharded KV stack lives on "
                             f"{rows[0]['kv_devices']} devices, not {SHARDS}")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the sharded "
                         "four-chip path and its one-shard twin")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r});"
              f" this script runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.runtime import use_compile_cache
    cache = use_compile_cache()
    kv_mb = 18 * 4 * N_MACHINES * N_KEYS / 1e6
    print(f"jax {jax.__version__}; device_kind {devices[0].device_kind}; "
          f"devices {len(devices)}; compile cache {cache}")
    ticks = FOUR_CHIP_TICKS if args.chips == 4 else TICKS
    print(f"deployment: {N_MACHINES} replicas, {N_KEYS} keys (Zipf s="
          f"{ZIPF_S}), {SESSIONS} sessions/replica, mix {MIX}, "
          f"{RATE} ops/tick for {ticks} ticks, seed {SEED}; KV plane "
          f"{kv_mb:.0f} MB on device")
    t0 = time.perf_counter()
    rows = four_chip_phases() if args.chips == 4 else one_chip_phases()
    for row in rows:
        print(json.dumps(row))
    print(f"all phases completion-identical to the scalar cluster, "
          f"checkers green ({time.perf_counter() - t0:.1f} s)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
