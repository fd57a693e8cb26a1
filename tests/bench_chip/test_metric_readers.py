"""The per-layer readers of the whole-stack bytes and the §11 write-backs
on handmade windows (``benchmarks/chip/metrics``).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_chip
"""

from __future__ import annotations

import pathlib
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import cell  # noqa: E402
import run_cell  # noqa: E402

STACK = run_cell.reader("stack_bytes_per_op")
FAST = run_cell.reader("abd_read_fast_share")


def _window(ops, **telemetry):
    return cell.Window(ops=ops, telemetry=dict(telemetry))


def test_stack_bytes_per_op():
    w = _window(4, stack_h2d_bytes=300, stack_d2h_bytes=500,
                staging_h2d_bytes=40, staging_d2h_bytes=60,
                h2d_bytes=340, d2h_bytes=560)
    assert STACK(w) == 200.0
    staging = (w.telemetry["staging_h2d_bytes"]
               + w.telemetry["staging_d2h_bytes"]) / w.ops
    assert STACK(w) + staging == run_cell.reader("hd_bytes_per_op")(w)


@pytest.mark.parametrize("ops,telemetry", [
    (0, {"stack_h2d_bytes": 300, "stack_d2h_bytes": 500}),   # no ops
    (4, {"h2d_bytes": 340, "d2h_bytes": 560}),   # a program without it
])
def test_stack_bytes_per_op_reads_nothing(ops, telemetry):
    assert STACK(_window(ops, **telemetry)) is None


def test_abd_read_fast_share():
    w = _window(10, abd_read_write_backs=1)
    w.paths = {"abd_read": 8, "abd_write": 2}
    assert FAST(w) == pytest.approx(87.5)
    w.telemetry["abd_read_write_backs"] = 0
    assert FAST(w) == 100.0


@pytest.mark.parametrize("paths,telemetry", [
    ({"abd_read": 0, "abd_write": 5}, {"abd_read_write_backs": 0}),  # no read
    ({}, {"abd_read_write_backs": 0}),                # untraced: no paths
    ({"abd_read": 8}, {}),                            # a program without it
])
def test_abd_read_fast_share_reads_nothing(paths, telemetry):
    w = _window(5, **telemetry)
    w.paths = paths
    assert FAST(w) is None
