"""The per-layer reader of the paired waves on handmade windows
(``benchmarks/chip/metrics/paired_wave_share.py``).

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

PAIRED = run_cell.reader("paired_wave_share")


def _window(ops, **telemetry):
    return cell.Window(ops=ops, telemetry=dict(telemetry))


def test_paired_wave_share():
    # 6 receiver and 4 issuer calls, 3 of them paired: 7 waves
    w = _window(5, fused_receiver_calls=6, fused_issuer_calls=4,
                paired_waves=3)
    assert PAIRED(w) == pytest.approx(100.0 * 3 / 7)
    w.telemetry["paired_waves"] = 0
    assert PAIRED(w) == 0.0


@pytest.mark.parametrize("telemetry", [
    {"fused_receiver_calls": 6, "fused_issuer_calls": 4},  # without it
    {"fused_receiver_calls": 0, "fused_issuer_calls": 0,
     "paired_waves": 0},                                    # no call
])
def test_paired_wave_share_reads_nothing(telemetry):
    assert PAIRED(_window(5, **telemetry)) is None
