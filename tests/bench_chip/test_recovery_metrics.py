"""The per-layer readers of the protocol's recovery paths on handmade
windows (``benchmarks/chip/metrics``): stalled rounds, All-aboard
time-outs and helps.

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

STALLED = run_cell.reader("stalled_rounds_per_op")
AA_TIMEOUT = run_cell.reader("aa_timeout_share")
HELPS = run_cell.reader("helps_per_rmw")

# what ClusterEngine.telemetry() carried before these counters
OLDER = {"fused_receiver_calls": 6, "fused_issuer_calls": 4,
         "abd_read_write_backs": 1}
COUNTERS = {"round_timeouts": 0, "abd_retransmits": 0,
            "all_aboard_attempts": 0, "all_aboard_timeouts": 0,
            "helps": 0, "rmw_completed": 0}


def _window(ops, **telemetry):
    return cell.Window(ops=ops, telemetry={**OLDER, **telemetry})


def test_stalled_rounds_per_op():
    w = _window(40, **{**COUNTERS, "round_timeouts": 6, "abd_retransmits": 2})
    assert STALLED(w) == pytest.approx(0.2)


def test_aa_timeout_share():
    w = _window(40, **{**COUNTERS, "all_aboard_attempts": 32,
                       "all_aboard_timeouts": 8})
    assert AA_TIMEOUT(w) == pytest.approx(25.0)


def test_helps_per_rmw():
    w = _window(40, **{**COUNTERS, "helps": 3, "rmw_completed": 36})
    assert HELPS(w) == pytest.approx(1 / 12)


@pytest.mark.parametrize("reader,denominator", [
    (STALLED, None), (AA_TIMEOUT, "all_aboard_attempts"),
    (HELPS, "rmw_completed")])
def test_zero_counts_read_zero(reader, denominator):
    counts = dict(COUNTERS)
    if denominator:
        counts[denominator] = 10
    assert reader(_window(40, **counts)) == 0.0


@pytest.mark.parametrize("reader", [STALLED, AA_TIMEOUT, HELPS])
def test_a_program_without_the_counters_reads_nothing(reader):
    assert reader(_window(40)) is None


@pytest.mark.parametrize("reader,ops", [
    (STALLED, 0), (AA_TIMEOUT, 40), (HELPS, 40)])
def test_a_zero_denominator_reads_nothing(reader, ops):
    # no op completed; no All-aboard attempt; no RMW completed
    w = _window(ops, **{**COUNTERS, "round_timeouts": 3,
                        "all_aboard_timeouts": 0, "helps": 2})
    assert reader(w) is None
