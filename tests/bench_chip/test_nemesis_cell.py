"""The one-replica partition nemesis of ``tpcc5_w12_nemesis`` on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_chip

The cell's schedule (``traffic/tpcc_next_o_id_isolate5s.json``) cuts one
replica off from the other four for 5 s in every 10 of the 51-s window.
Here it runs at the small size of the other CPU runs, with its times
scaled into a window long enough for stalled rounds to time out: the
check must come out correct with nothing lost, the recovery counters must
fire, the control must still fail, and a partition that is never healed
must leave ops unfinished.  The ABD cell keeps its own run under a short
partition, which the closed runs' shared partition case
(``test_chip_benchmark.RUNS``) now gives the last cell, this one.
"""

from __future__ import annotations

import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import cell  # noqa: E402
import control  # noqa: E402
import reference  # noqa: E402
import run_cell  # noqa: E402
import traffic  # noqa: E402
from test_chip_benchmark import (BENCH, RUNS, SEED, _run, _small,  # noqa: E402
                                 _spec as _cell_spec)

CELL = "tpcc5_w12_nemesis.tpcc_next_o_id_isolate5s"
SECONDS = 6.0
SCALE = SECONDS / BENCH["run_seconds"]    # the schedule, into SECONDS


def _spec(heal_last: bool = True) -> dict:
    spec = run_cell.load_cell(CELL, BENCH)
    faults = [dict(ev, at_s=ev["at_s"] * SCALE)
              for ev in spec["traffic"]["faults"]]
    if not heal_last:
        faults = faults[:-1]
    spec["config"] = _small(spec["config"])
    spec["traffic"] = traffic.validate({**spec["traffic"], "faults": faults})
    return spec


def _measure(spec: dict, seed: int):
    """Window, drain and check of one run: the window, its failed ops and
    the numbers compared with the reference."""
    run = cell.Run(spec["config"], spec["traffic"], seed)
    run.warm_up()
    w = run.window(SECONDS)
    run.drain()
    return w, run.failed_in_window(), run.check()


def test_schedule_isolates_each_replica_once_and_heals_in_the_window():
    spec = run_cell.load_cell(CELL, BENCH)
    reps = set(range(spec["config"]["replicas"]))
    faults = spec["traffic"]["faults"]
    assert [ev["action"] for ev in faults] == ["partition", "heal"] * 5
    assert [ev["at_s"] for ev in faults] == list(range(5, 51, 5))
    isolated = []
    for ev in faults[::2]:
        alone, rest = ev["groups"]
        assert len(alone) == 1 and set(alone) | set(rest) == reps
        assert not set(alone) & set(rest)
        isolated += alone
    assert isolated == [4, 1, 3, 0, 2]
    assert faults[-1]["at_s"] < BENCH["run_seconds"]


def test_partitions_heal_and_every_op_completes():
    w, failed, numbers = _measure(_spec(), 2**31 + 29)
    assert reference.failures(numbers) is None, numbers
    assert w.ops > 0 and failed == 0
    t = w.telemetry
    assert t["round_timeouts"] > 0 and t["all_aboard_timeouts"] > 0
    assert run_cell.reader("stalled_rounds_per_op")(w) > 0
    assert run_cell.reader("aa_timeout_share")(w) > 0


def test_control_under_the_partitions_is_not_correct(monkeypatch):
    monkeypatch.setattr(cell, "DRAIN_MAX_S", 3.0)
    with control.plant("control"):
        _w, _failed, numbers = _measure(_spec(), 2**31 + 31)
    assert reference.failures(numbers) is not None
    assert any(numbers[k] > lim for k, lim in reference.LIMITS.items()
               if k != "unfinished"), numbers


def test_a_partition_open_past_the_window_leaves_ops_unfinished(monkeypatch):
    # the isolated replica's sessions never drain while it is cut off
    monkeypatch.setattr(cell, "DRAIN_MAX_S", 3.0)
    _w, _failed, numbers = _measure(_spec(heal_last=False), 2**31 + 37)
    assert numbers["unfinished"] > 0, numbers
    assert reference.failures(numbers) is not None


def test_abd_cell_under_a_short_partition_is_correct():
    _cell, changes = RUNS["partition"]
    out = _run(_cell_spec("tatp5.tatp_vlr_location", **changes), SEED)
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["window"]["compiles"] == 0
