"""Open-loop workload benchmark: tail latency under skew, faults and scale.

The existing bench lanes (``bench_vector.py``) measure *throughput* of the
engines and the closed-loop e2e path.  This lane measures what the paper's
deployment model (§2) actually cares about: **client-visible tail latency**
— p50/p99/p999 per op class (RMW / write / read), under Zipfian key skew,
with arrivals that do not wait for completions (open loop: overload shows
up as queueing delay *in* the latency), reported separately for
steady-state and fault windows (crash/restart + partition injected during
the load).  Built on :mod:`repro.serve.loadgen`; methodology in
``docs/workloads.md``, lane schema in ``docs/benchmarks.md``.

Latency is measured in **virtual ticks** of the simulated network, so
every number here is a deterministic function of the seed and the protocol
code — a change in a reported percentile is a protocol-behavior change,
never host noise.  That is what lets ``scripts/perf_guard.py`` gate the
steady-state p99 with a tight tolerance.

``--smoke`` (the CI gate, wired into ``scripts/check.sh``) runs three
scenarios and *merges* an ``open_loop`` lane into ``BENCH_smoke.json``
(preserving the lanes ``bench_vector --smoke`` already wrote) plus one
``mode: open_loop_smoke`` row appended to ``BENCH_trajectory.jsonl``:

* ``scalar_faults``  — scalar cluster, kv_mixed Zipf traffic, a
  crash/restart and a partition injected mid-load; linearizability
  checkers run on the final history.
* ``batched``        — the same spec driven through
  ``Cluster(machine_cls=BatchedMachine)`` with a completion-for-completion
  identity assertion against a scalar twin, plus ingest-scheduler gauges.
* ``million_keys``   — scalar cluster over a 10^6-key universe (s = 1.1):
  demonstrates the harness's key-universe scale; the batched plane layout
  is deliberately not exercised here (``docs/workloads.md`` explains the
  key-universe / plane-memory trade).
"""

from __future__ import annotations

import argparse
import json
import time

from repro.core.node import Machine
from repro.core.sim import completion_tuples
from repro.serve.loadgen import (
    ArrivalPhase, FaultPlan, MIXES, OpenLoopHarness, OpenLoopSpec,
    merged_class_summary,
)
from repro.serve.paxos import BatchedMachine
from repro.runtime import use_compile_cache

try:
    from benchmarks.bench_vector import _run_metadata
except ImportError:                                  # run as a script
    from bench_vector import _run_metadata


def _smoke_spec(seed: int = 11, n_keys: int = 256) -> OpenLoopSpec:
    """The smoke scenario: kv_mixed Zipf traffic, two-phase rate ramp."""
    return OpenLoopSpec(
        seed=seed, n_machines=5, sessions=4, n_keys=n_keys, zipf_s=0.99,
        mix=MIXES["kv_mixed"],
        phases=(ArrivalPhase(rate=0.3, ticks=150),
                ArrivalPhase(rate=0.6, ticks=150)))


def _smoke_faults() -> FaultPlan:
    """One crash/restart and one (disjoint) partition during the load —
    windows sized so the steady intervals still see every op class."""
    return (FaultPlan(settle=30.0)
            .crash_restart(1, at=50.0, down_for=30.0)
            .partition(170.0, 200.0, (0, 1, 2), (3, 4)))


def run_scenario(spec: OpenLoopSpec, machine_cls=Machine,
                 faults: FaultPlan = None) -> dict:
    t0 = time.time()
    result = OpenLoopHarness(spec, machine_cls=machine_cls,
                             faults=faults).run()
    lane = result.lane()
    lane["seed"] = spec.seed
    lane["n_keys"] = spec.n_keys
    lane["zipf_s"] = spec.zipf_s
    lane["mix"] = spec.mix.name
    lane["impl"] = ("batched" if machine_cls is BatchedMachine
                    else "scalar")
    lane["wall_s"] = round(time.time() - t0, 2)
    return lane, result


def smoke() -> dict:
    spec = _smoke_spec()
    scal, scal_res = run_scenario(spec, Machine, _smoke_faults())

    bat, bat_res = run_scenario(spec, BatchedMachine, _smoke_faults())
    assert (completion_tuples(scal_res.cluster)
            == completion_tuples(bat_res.cluster)), \
        "open-loop batched run diverged from the scalar oracle"
    bat["identical_to_scalar"] = True

    mill, _ = run_scenario(
        OpenLoopSpec(seed=2, n_keys=1_000_000, zipf_s=1.1,
                     phases=(ArrivalPhase(rate=1.0, ticks=120),)))

    # The perf_guard gate: steady-state percentiles in virtual ticks are
    # deterministic per seed, so a shift is a protocol change, not noise.
    steady = scal["windows"]["steady"]
    gate = {
        "steady_p99": {c: s["p99"] for c, s in steady.items() if s},
        "steady_p99_all": merged_class_summary(
            scal_res.recorder, "steady")["p99"],
        "offered": scal["offered"], "completed": scal["completed"],
        "lost": scal["lost"],
    }
    return {"scenarios": {"scalar_faults": scal, "batched": bat,
                          "million_keys": mill},
            "gate": gate}


def sweep(rates=(0.2, 0.5, 1.0, 2.0), seed: int = 5) -> list:
    """Arrival-rate sweep (no faults): watch the steady p99 climb as the
    offered load crosses the serving capacity — the open-loop signature a
    closed-loop bench cannot show."""
    rows = []
    for rate in rates:
        spec = OpenLoopSpec(seed=seed, n_keys=256,
                            phases=(ArrivalPhase(rate=rate, ticks=200),))
        lane, res = run_scenario(spec)
        all_steady = merged_class_summary(res.recorder, "steady")
        rows.append({"rate": rate, "offered": lane["offered"],
                     "p50": all_steady["p50"], "p99": all_steady["p99"],
                     "fifo_max": lane["gauges"]
                     ["client_fifo_depth"]["max"]})
        print(f"rate {rate:5.2f} ops/tick: offered {lane['offered']:5d}  "
              f"p50 {all_steady['p50']:7.2f}  p99 {all_steady['p99']:7.2f}"
              f"  fifo_max {rows[-1]['fifo_max']}")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: three seeded scenarios, checkers green, "
                         "batched==scalar; merges the open_loop lane into "
                         "--json and appends a trajectory row")
    ap.add_argument("--sweep", action="store_true",
                    help="arrival-rate sweep (steady p99 vs offered load)")
    ap.add_argument("--json", default="BENCH_smoke.json", metavar="PATH",
                    help="smoke-results file to merge the open_loop lane "
                         "into (read-modify-write: lanes written by "
                         "bench_vector --smoke are preserved)")
    ap.add_argument("--trajectory",
                    default="benchmarks/BENCH_trajectory.jsonl",
                    metavar="PATH",
                    help="append one open_loop_smoke row to this tracked "
                         "JSONL history; pass '' to disable")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.sweep:
        return sweep()

    if not args.smoke:
        ap.error("choose a mode: --smoke or --sweep")

    lane = smoke()
    try:
        with open(args.json) as fh:
            rows = json.load(fh)
    except (FileNotFoundError, json.JSONDecodeError):
        rows = {"schema": 1, "mode": "smoke"}
    rows["open_loop"] = lane
    with open(args.json, "w") as fh:
        json.dump(rows, fh, indent=1)
    if args.trajectory:
        rec = {"schema": 1, "mode": "open_loop_smoke", "open_loop": lane,
               "when": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
               **_run_metadata()}
        with open(args.trajectory, "a") as fh:
            fh.write(json.dumps(rec, separators=(",", ":")) + "\n")
    print(json.dumps(lane["gate"], indent=1))
    print(f"open-loop smoke OK: checkers green, batched == scalar, "
          f"lanes merged into {args.json}")
    return lane


if __name__ == "__main__":
    main()
