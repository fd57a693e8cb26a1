#!/usr/bin/env python3
"""The control and the planted faults that the check must catch.

    python3 benchmarks/chip/control.py \\
        --workload tpcc3_w12.tpcc_next_o_id --plant control \\
        --seeds 11 12 13 --seconds 10

Each plant breaks the served path from outside, for the length of a run,
and the run then goes through the benchmark's own window, drain and
check (``cell.Run``).  The check must come out not correct:

* ``control``: the guarantee the configurations state, "an acknowledged
  op is committed at a quorum", broken: every quorum (the majority and
  All-aboard's) is one replica, and an ABD write is acknowledged once its
  own replica has installed it, before its phase-2 message goes out (at
  three replicas one-replica quorums alone are not observable: every
  phase-2 message reaches every replica within the 3 ticks before a later
  op's first round can ask, so the write must stop short of a quorum);
* ``unchanged_step``: the fused receiver step returns the replicas'
  state as it got it;
* ``half_lanes``: the fused receiver step's state update is kept on even
  lanes only (odd lanes keep their old state; the replies stand);
* ``altered_answer``: every seventh completion hands its client a value
  one above what the store produced.

A four-chip exchange does not exist in these cells (one chip each), so
it has no plant.  The benchmark's own runs never plant anything; this
script and ``tests/test_benchmark.py`` do.  Prints one JSON line per seed
with the numbers compared.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import run_cell


@contextlib.contextmanager
def _patched(obj, name, value):
    saved = obj.__dict__[name]
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, saved)


def _receiver_plant(keep_lanes):
    """Wrap the fused receiver step so that only lanes where
    ``keep_lanes(lane_index)`` holds take its new state."""
    import jax.numpy as jnp
    from repro.serve.paxos import cluster_engine as ce
    step = ce._fused_receiver_step

    def planted(kv_stack, *args, **kw):
        old = jnp.array(kv_stack, copy=True)     # the input is donated
        new_kv, replies, mask = step(kv_stack, *args, **kw)
        lanes = jnp.arange(new_kv.shape[-1])
        return jnp.where(keep_lanes(lanes), new_kv, old), replies, mask
    return _patched(ce, "_fused_receiver_step", planted)


def _local_write(self, ab):
    """ABD write phase 2 applied at the issuing replica alone and
    acknowledged at once (the control's write path)."""
    from repro.core import handlers
    from repro.core.handlers import get_kv
    from repro.core.node import ReqKind
    from repro.core.types import TS, Carstamp, Msg, MsgKind
    self.write_clock = max(self.write_clock + 1, ab.max_base.version + 1)
    ab.max_base = TS(self.write_clock, self.mid)
    handlers.on_write(get_kv(self.kvs, ab.key),
                      Msg(MsgKind.WRITE, self.mid, key=ab.key,
                          value=ab.value, base_ts=ab.max_base,
                          lid=self._new_lid(ab.sess)))
    self._complete_abd(ab, ReqKind.WRITE, ab.value, Carstamp(ab.max_base, 0))


def plant(name: str):
    """A context manager that plants fault ``name``."""
    from repro.core.node import Machine
    from repro.core.sim import Cluster
    from repro.core.types import View
    if name == "control":
        stack = contextlib.ExitStack()
        stack.enter_context(_patched(Machine, "_write_phase2", _local_write))
        stack.enter_context(_patched(View, "quorum_of",
                                     staticmethod(lambda n: 1)))
        stack.enter_context(_patched(View, "all_aboard_quorum",
                                     lambda self: 1))
        return stack
    if name == "unchanged_step":
        return _receiver_plant(lambda lanes: lanes < 0)
    if name == "half_lanes":
        return _receiver_plant(lambda lanes: lanes % 2 == 0)
    if name == "altered_answer":
        complete = Cluster._complete
        seen = [0]

        def altered(self, mid, sess, comp):
            seen[0] += 1
            if seen[0] % 7 == 0:
                comp.value += 1
            return complete(self, mid, sess, comp)
        return _patched(Cluster, "_complete", altered)
    raise ValueError(f"unknown plant {name!r}")


PLANTS = ("control", "unchanged_step", "half_lanes", "altered_answer")


def planted_run(spec: dict, seed: int, seconds: float, name: str) -> dict:
    """One run of the cell with ``name`` planted; the numbers compared."""
    import cell
    import reference
    with plant(name):
        run = cell.Run(spec["config"], spec["traffic"], seed)
        run.warm_up()
        w = run.window(seconds)
        run.drain()
        numbers = run.check()
    return {"plant": name, "seed": seed, "ops": w.ops,
            "correct": reference.failures(numbers) is None and w.ops > 0,
            **numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plant", choices=PLANTS, nargs="+",
                    default=["control"])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench = json.loads((run_cell.ROOT / "BENCHMARK.json").read_text())
    spec = run_cell.load_cell(args.workload, bench)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run_cell.CACHE_DIR)
    if run_cell.devices_or_none(spec["cell"]["chips"]) is None:
        return 1
    import jax
    from repro.runtime import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for name in args.plant:
        for seed in args.seeds:
            t = time.perf_counter()
            row = planted_run(spec, seed, args.seconds, name)
            row["wall_s"] = time.perf_counter() - t
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
