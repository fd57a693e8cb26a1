"""Tests of the chip benchmark (``benchmarks/chip``) that run on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_chip

They check that ``BENCHMARK.json`` keeps its contract and resolves to the
files the harness finds by name, drive whole runs of every cell's client
loop at a small size (closed and open loop, writes, a crash and a
partition) and see them come out correct, plant the control and each
fault and see them come out not correct, and check the trace reduction on
a small recorded trace.  What they expect is read from ``BENCHMARK.json``,
so a cell, mix or metric added there is tested with no edit here.
"""

from __future__ import annotations

import json
import math
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(ROOT / "src"))

import control  # noqa: E402
import reference  # noqa: E402
import run_cell  # noqa: E402
import tracefile  # noqa: E402
import traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
CELLS = [c["name"] for c in BENCH["workloads"]]
SECONDS = 1.5
SEED = 2**31 + 17


# -- the file and what it names ------------------------------------------------

def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(BENCH["command"]) <= 32
    for word in BENCH["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        if (ROOT / word).exists():
            assert any(word.startswith(p + "/") for p in BENCH["paths"])
    assert (ROOT / BENCH["command"][1]).is_file()


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_and_units(section):
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}[section]
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))
    for e in BENCH[section]:
        assert set(e) - {"workloads"} == keys, e
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")
        if section == "end_to_end":
            assert 0.01 <= e["bound"] <= 0.25
            assert e["source"] in ("host_clock", "device_trace")
        if section == "per_layer":
            assert e["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
        if section == "configs":
            assert all(NAME.match(k) for k in e["reduced"])
            assert any(e["file"].startswith(p + "/")
                       for p in BENCH["paths"])
        if section == "workloads":
            assert e["chips"] in (1, 4)
            assert NAME.match(e["config"]) and NAME.match(e["traffic"])


def test_setup_s_is_an_end_to_end_metric():
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_files(cell):
    spec = run_cell.load_cell(cell, BENCH)
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == spec["cell"]["config"])
    config = json.loads((ROOT / entry["file"]).read_text())
    assert config == spec["config"]
    for key in entry["reduced"]:
        assert key in config and key in config["reduced"]
    assert spec["traffic"]["loop"] in ("closed", "open")
    for m in spec["per_layer"]:
        assert callable(run_cell.reader(m["name"]))
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2 and spec["per_layer"]
    traffic.OpStream(spec["traffic"], spec["config"], SEED)   # it fits


def test_every_config_used_and_moves_reported():
    used = {c["config"] for c in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", CELLS)
        assert set(m.get("workloads", CELLS)) <= set(moved)
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_peaks_keyed_by_device_kind():
    import roofline
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("no such chip")


def test_run_cell_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, str(CHIP / "run_cell.py"),
                        "--workload", CELLS[0], "--seed", str(2**31 + 5),
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


# -- the traffic generator ----------------------------------------------------

def _config(**kw):
    return {"replicas": 3, "sessions_per_replica": 10, "recordcount": 30,
            "home_keys": 10, "terminals_per_home": 10, "zipf_s": 0.99,
            **kw}


@pytest.mark.parametrize("rule", traffic.KEY_RULES)
def test_key_rules(rule):
    t = traffic.validate({"loop": "closed", "mix": {"rmw": 0.5,
                                                    "read": 0.5},
                          "keys": {"rmw": rule, "read": rule}})
    config = _config()
    a = traffic.OpStream(t, config, SEED)
    b = traffic.OpStream(t, config, SEED)
    for terminal in range(30):
        for _ in range(20):
            op = a.next(terminal)
            assert op == b.next(terminal)        # a function of the seed
            assert 0 <= op["key"] < 30
            if rule == "home":
                assert op["key"] // 10 == terminal // 10
            if rule == "own":
                assert op["key"] == terminal


def test_home_keys_must_cover_the_terminals():
    t = traffic.validate({"loop": "closed", "mix": {"rmw": 1.0},
                          "keys": {"rmw": "home"}})
    with pytest.raises(ValueError):
        traffic.OpStream(t, _config(recordcount=40), SEED)


def test_open_loop_arrivals_are_poisson_at_the_rate():
    t = traffic.validate({"loop": "open", "rate_ops_per_s": 200.0,
                          "mix": {"read": 1.0}})
    due = traffic.arrival_offsets(t, SEED, 50.0)
    assert due == traffic.arrival_offsets(t, SEED, 50.0)
    assert all(0 < x < 50 for x in due) and due == sorted(due)
    assert abs(len(due) - 10_000) < 4 * math.sqrt(10_000)


# -- whole runs of the client loop at a small size ----------------------------

def _small(config):
    """The configuration with as few terminals as keep its shape: whole
    home groups spread evenly over the replicas."""
    config = dict(config)
    reps = config["replicas"]
    if "home_keys" in config:
        per = config["terminals_per_home"]
        terminals = reps * per // math.gcd(reps, per)
        config.update(sessions_per_replica=terminals // reps,
                      recordcount=terminals // per * config["home_keys"])
    else:
        config.update(sessions_per_replica=4,
                      recordcount=min(config["recordcount"], 256))
    return config


def _spec(cell, **traffic_changes):
    spec = run_cell.load_cell(cell, BENCH)
    spec["config"] = _small(spec["config"])
    spec["traffic"] = traffic.validate({**spec["traffic"],
                                        **traffic_changes})
    return spec


def _run(spec, seed, trace=False):
    import jax
    return run_cell.run(spec, seed, SECONDS, trace, jax.devices())


RUNS = {f"closed:{c}": (c, {}) for c in CELLS}
RUNS.update({
    "open": (CELLS[0], {"loop": "open", "rate_ops_per_s": 150.0}),
    "writes": (CELLS[0], {"mix": {"read": 0.4, "write": 0.3, "rmw": 0.3},
                          "keys": {"read": "home", "write": "home",
                                   "rmw": "home"}}),
    "crash_restart": (CELLS[0], {"faults": [
        {"at_s": 0.3, "action": "crash", "replica": 1},
        {"at_s": 0.8, "action": "restart", "replica": 1}]}),
    "partition": (CELLS[-1], {"faults": [
        {"at_s": 0.3, "action": "partition", "groups": [[0], [1, 2]]},
        {"at_s": 0.8, "action": "heal"}]}),
})


@pytest.mark.parametrize("name", sorted(RUNS))
def test_sound_run_is_correct(name):
    cell, changes = RUNS[name]
    spec = _spec(cell, **changes)
    out = _run(spec, SEED)
    assert out["correct"], out["check"]
    assert out["window"]["compiles"] == 0
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert list(out)[-1] == "check"
    if name == "crash_restart":
        assert out["failed"] >= 1       # the crashed replica's ops
    else:
        assert out["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(cell):
    spec = _spec(cell)
    out = _run(spec, 2**31 + 19, trace=True)
    assert out["correct"]
    # the CPU has no device plane: the metrics read from it stay out
    assert set(out["metrics"]) == {m["name"] for m in spec["per_layer"]
                                   if m["source"] != "device_trace"}


PLANTED = [(p, c) for p in control.PLANTS for c in CELLS]


@pytest.mark.parametrize("plant,cell", PLANTED)
def test_planted_fault_is_not_correct(plant, cell, monkeypatch):
    import cell as cell_mod
    # a broken step may never go quiet: drain briefly, and ask for a
    # failure beyond the ops the drain left unfinished
    monkeypatch.setattr(cell_mod, "DRAIN_MAX_S", 3.0)
    with control.plant(plant):
        out = _run(_spec(cell), 2**31 + 23)
    assert out["correct"] is False, out["check"]
    assert any(v["value"] > v["limit"] for k, v in out["check"].items()
               if k != "unfinished"), out["check"]


# -- the reference on handmade histories -----------------------------------

def _op(key, kind, value, sub, done, got, cs):
    return {"key": key, "kind": kind, "value": value, "submit_step": sub,
            "complete_step": done, "got": got, "cs": cs}


def _planes(states):
    """(replicas, lanes) planes with ``states[m][key] = (cs, value)``."""
    r, k = len(states), 4
    p = {"value": np.zeros((r, k), np.int32),
         "base_v": np.zeros((r, k), np.int32),
         "base_m": np.full((r, k), -1, np.int32),
         "val_log": np.zeros((r, k), np.int32)}
    for m, row in enumerate(states):
        for key, ((v, mid, log), val) in row.items():
            p["value"][m, key] = val
            p["base_v"][m, key], p["base_m"][m, key] = v, mid
            p["val_log"][m, key] = log
    return p


def _counter_history(stale_read=False, dup=False):
    ops = [_op(1, "rmw", 1, 0, 3, 0, (2, 0, 1)),
           _op(1, "rmw", 1, 1, 5, 1, (2, 0, 2)),
           _op(1, "read", 0, 6, 8, 1 if stale_read else 2,
               (2, 0, 1) if stale_read else (2, 0, 2))]
    if dup:
        ops[1].update(got=0)
    final = {1: ((2, 0, 2), 2)}
    return ops, _planes([final, final, {1: ((2, 0, 1), 1)}])


@pytest.mark.parametrize("case,bad", [("sound", None),
                                      ("stale_read", "order_violation"),
                                      ("duplicate_faa", "value_mismatch")])
def test_reference_on_a_counter(case, bad):
    ops, planes = _counter_history(stale_read=case == "stale_read",
                                   dup=case == "duplicate_faa")
    out = reference.check(ops, planes, quorum=2)
    if bad is None:
        assert reference.failures(out) is None, out
    else:
        assert out[bad] > 0, out


def test_reference_holds_replicas_to_a_quorum():
    ops, _ = _counter_history()
    lagging = _planes([{1: ((2, 0, 2), 2)}, {1: ((2, 0, 1), 1)},
                       {1: ((2, 0, 1), 1)}])
    assert reference.check(ops, lagging, quorum=2)["replica_mismatch"] == 1
    ops, planes = _counter_history()
    planes["value"][0, 3] = 9              # a lane no op touched
    assert reference.check(ops, planes, quorum=2)["replica_mismatch"] == 1


def test_reference_explains_a_lost_faa():
    ops = [_op(1, "rmw", 1, 0, 3, 0, (2, 0, 1)),
           {"key": 1, "kind": "rmw", "value": 1, "submit_step": 2,
            "complete_step": None, "lost": True},
           _op(1, "rmw", 1, 4, 7, 2, (2, 0, 3)),
           _op(1, "read", 0, 4, 6, 2, (2, 0, 2))]   # the lost FAA's state
    planes = _planes([{1: ((2, 0, 3), 3)}] * 3)
    assert reference.failures(reference.check(ops, planes, 2)) is None
    del ops[1]["lost"]
    assert reference.check(ops, planes, 2)["unfinished"] == 1


# -- the trace reduction --------------------------------------------------

RECORDED = pathlib.Path(__file__).resolve().parent / "trace_v5e_3x1024.json"


def test_reduction_on_a_synthetic_trace():
    ev = {"host": [["bench.slice", 1000.0, 10000.0],
                   ["bench.step_all", 1000.0, 4000.0],
                   ["bench.fused_receiver_step", 1500.0, 500.0]],
          "device": [["XLA Modules", "jit__fused_receiver_step(7)", 2000.0,
                      2000.0],
                     ["XLA Ops", "fusion.1", 2000.0, 1500.0],
                     ["XLA Ops", "fusion.2", 3000.0, 1000.0],
                     ["XLA Ops", "copy.3", 8000.0, 4000.0]]}
    red = tracefile.reduce(ev)
    assert red.window_s == pytest.approx(1e-5)
    # busy: [2000, 4000) and [8000, 11000) clipped at the slice's end
    assert red.busy_s == pytest.approx(5e-6)
    assert red.module("_fused_receiver_step") == (1, pytest.approx(2e-6))
    assert red.device_ops[0] == ("copy.3", pytest.approx(3e-6))
    # idle: [1000, 2000) under step_all's receiver span, [4000, 8000)
    assert red.idle_gaps == [("outside_spans", pytest.approx(4e-6)),
                             ("fused_receiver_step", pytest.approx(1e-6))]


def test_reduction_refuses_a_device_plane_without_its_ops_line():
    ev = {"host": [["bench.slice", 0.0, 100.0]],
          "device": [["Async XLA Ops", "copy-start.1", 10.0, 20.0]]}
    with pytest.raises(ValueError):
        tracefile.reduce(ev)
    assert tracefile.reduce({"host": ev["host"], "device": []}) is None


def _brute_busy(ev, s0, s1):
    """Busy nanoseconds by marking every nanosecond: the reference the
    interval union is held to."""
    mask = np.zeros(int(s1 - s0), bool)
    for line, _n, a, d in ev["device"]:
        if line == tracefile.OPS_LINE:
            lo, hi = max(int(a - s0), 0), min(int(a + d - s0), len(mask))
            if hi > lo:
                mask[lo:hi] = True
    return int(mask.sum())


def test_reduction_on_the_recorded_trace():
    """A 40 ms excerpt of a traced run on one v5e: 3 replicas over a
    1,024-lane plane."""
    import roofline
    ev = json.loads(RECORDED.read_text())
    red = tracefile.reduce(ev)
    _name, s0, dur = next(h for h in ev["host"] if h[0] == tracefile.SLICE)
    assert red.window_s == pytest.approx(0.04)
    assert red.busy_s * 1e9 == pytest.approx(_brute_busy(ev, s0, s0 + dur),
                                             abs=2)
    assert red.busy_s == pytest.approx(6.934e-05)
    n, device_s = red.module("_fused_receiver_step")
    assert (n, device_s) == (4, pytest.approx(2.1459e-05))
    # operands (18+12 int32 planes) and results (18+11 int32 planes and a
    # bool mask) of the receiver step over 3 replicas x 1,024 lanes
    call = (18 + 12 + 18 + 11) * 4 * 3 * 1024 + 3 * 1024
    share = roofline.share(n, call, device_s, 819e9)
    assert share == pytest.approx(100 * 4 * 728064 / 2.1459e-05 / 819e9)
    assert 16 < share < 17
    assert red.idle_gaps[0][0] == "step_all"
