#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 benchmarks/chip/run_cell.py \\
        --workload tpcc3_w12.tpcc_next_o_id --seed 7 --seconds 51 --trace 0

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json`` at the root of the checkout names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``);
each per-layer metric is read by ``metrics/<metric>.py``.  A new cell,
mix or metric is a new file and a new entry, with no edit here.

With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` they are its per-layer metrics, read partly
from a profiler trace of a short slice of the window.  The last line on
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), and
last ``check``: each number compared with the reference beside its
limit.  The same numbers end standard error.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with status 1 before any work and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

# a fixed directory inside the checkout: the cache key holds the path
CACHE_DIR = ROOT / ".jax_cache"


def load_cell(name: str, bench: dict, root: pathlib.Path = HERE) -> dict:
    """The cell ``name`` with its configuration, mix and metrics."""
    import traffic
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    cell = cells[name]

    def mine(metric):
        return name in metric.get("workloads", [name])

    return {"cell": cell,
            "config": json.loads((root / "configs" /
                                  f"{cell['config']}.json").read_text()),
            "traffic": traffic.load(cell["traffic"], root),
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def reader(metric: str, root: pathlib.Path = HERE):
    """``read(window)`` of ``metrics/<metric>.py``."""
    path = root / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices_or_none(chips: int):
    """JAX's devices when they are TPUs and enough of them, else None."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"run_cell: no TPU (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"run_cell: the cell needs {chips} chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return None
    return devs


def run(spec: dict, seed: int, seconds: float, trace: bool,
        devices, t_start: float = T_START) -> dict:
    """One run of a cell (``load_cell``) on ``devices``; the result."""
    import cell as cell_mod
    import roofline
    import tracefile

    kind = devices[0].device_kind
    peaks = roofline.peaks(kind) if devices[0].platform == "tpu" else {}
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        with cell_mod.CompileClock() as setup_clock:
            run_ = cell_mod.Run(spec["config"], spec["traffic"], seed,
                                trace=trace, trace_dir=trace_dir)
            run_.warm_up()
        setup_s = time.perf_counter() - t_start
        w = run_.window(seconds)
        w.peaks = peaks
        if trace:
            w.trace = tracefile.reduce(tracefile.extract(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    t = time.perf_counter()
    run_.drain()
    drain_s = time.perf_counter() - t
    numbers = run_.check()
    check_s = time.perf_counter() - t - drain_s
    w.failed = run_.failed_in_window()
    device = {"platform": devices[0].platform, "kind": kind,
              "count": spec["cell"]["chips"],
              "memory_peak_bytes": w.memory_peak_bytes}
    if trace:
        values = {m["name"]: reader(m["name"])(w) for m in spec["per_layer"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["per_layer"]
                   if values[m["name"]] is not None}
    else:
        e2e = cell_mod.end_to_end(w) if w.latencies_ms else {}
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"] if m["name"] in e2e}
    out = {"correct": None, "attempted": w.attempted, "failed": w.failed,
           "metrics": metrics, "device": device}
    if trace and w.trace is not None:
        device.update(busy_s=w.trace.busy_s, window_s=w.trace.window_s)
        out["breakdown"] = {"device_ops": [list(x) for x in
                                           w.trace.device_ops],
                            "idle_gaps": [list(x) for x in
                                          w.trace.idle_gaps]}
    import reference
    out["correct"] = (reference.failures(numbers) is None
                      and w.ops > 0)
    out["window"] = {"ops": w.ops, "ticks": w.ticks, "seconds": w.seconds,
                     "compiles": w.compiles, "lateness_ms": w.lateness_ms,
                     "setup_compiles": setup_clock.compiles,
                     "setup_cache_hits": setup_clock.cache_hits,
                     "drain_s": drain_s, "check_s": check_s}
    out["check"] = {k: {"value": numbers[k], "limit": lim}
                    for k, lim in reference.LIMITS.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = load_cell(args.workload, bench)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    devices = devices_or_none(spec["cell"]["chips"])
    if devices is None:
        return 1
    import jax
    from repro.runtime import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = run(spec, args.seed, args.seconds, bool(args.trace), devices)
    for k, v in out["check"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
