"""``correct`` is a comparison that has been shown to fail.

Each test skips the harness's look for a chip (``cpu=True``: the tiny
rehearsal sizes of ``harness/rehearsal.json``) and drives the rest of a run:
broker child, fleet processes, window, settle, comparison with the plain
trie. Run with ``python -m pytest benchmark/tests`` from the checkout's root.

- a sound broker reads ``correct`` true;
- the control (``drop``: the delivery guarantee broken, every PUBACK still
  sent) reads false through ``missing_pairs``;
- an answer altered where it is produced (``alter``) reads false through
  ``missing_pairs`` and ``unexpected_pairs``.
"""

import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from harness import cell  # noqa: E402

CELLS = ["cfg2_100k_plus.fleet_sat", "cfg2_100k_plus.fleet_sat_q0"]


@pytest.fixture(autouse=True)
def short_run(monkeypatch):
    # a test run need not wait for the hybrid's probes, nor a minute for
    # deliveries that a broken router never sends
    monkeypatch.setattr(cell, "WARMUP_MIN_S", 2.0)
    monkeypatch.setattr(cell, "WARMUP_CAP_S", 6.0)
    monkeypatch.setattr(cell, "SETTLE_LIMIT_S", 5.0)


def run(name, monkeypatch, fault=None):
    launcher = cell.brokermod.LAUNCHER
    if fault:
        monkeypatch.setenv("BENCHMARK_FAULT", fault)
        launcher = HERE / "faulty_broker.py"
    return cell.run_cell(name, 20240930, 3.0, False, time.perf_counter(),
                         cpu=True, launcher=launcher)


@pytest.mark.parametrize("name", CELLS)
def test_sound_broker_is_correct(name, monkeypatch):
    r = run(name, monkeypatch)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 1000
    assert r["device"]["platform"] == "cpu"  # a rehearsal names its platform


@pytest.mark.parametrize("name", CELLS)
def test_control_dropped_delivery_is_not_correct(name, monkeypatch):
    r = run(name, monkeypatch, "drop")
    assert r["correct"] is False
    assert r["checks"]["missing_pairs"]["value"] > 0
    assert r["checks"]["unacked_qos1"]["value"] == 0  # every PUBACK still came


@pytest.mark.parametrize("name", CELLS)
def test_altered_answer_is_not_correct(name, monkeypatch):
    r = run(name, monkeypatch, "alter")
    assert r["correct"] is False
    assert r["checks"]["missing_pairs"]["value"] > 0
    assert r["checks"]["unexpected_pairs"]["value"] > 0
