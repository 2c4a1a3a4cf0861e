"""``cfg3_1m_mixed.fleet_sat``: its ``correct`` has been shown to fail, and the
readers PR 27 added read what they say.

- the ``--cpu`` rehearsal of the cell (tiny sizes of ``harness/rehearsal.json``)
  reads ``correct`` true on a sound broker and false under the ``drop`` control
  (``faulty_broker.py``: the delivery guarantee broken, every PUBACK still sent);
- each new reader gives a number on a fabricated ``run`` and None where the
  broker has no such counter (a program from before PR 27: the parent, with
  these files laid over it).

Run with ``python -m pytest benchmark/tests`` from the checkout's root.
"""

import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from harness import cell, spec  # noqa: E402

CELL = "cfg3_1m_mixed.fleet_sat"


@pytest.fixture
def short_run(monkeypatch):
    monkeypatch.setattr(cell, "WARMUP_MIN_S", 2.0)
    monkeypatch.setattr(cell, "WARMUP_CAP_S", 6.0)
    monkeypatch.setattr(cell, "SETTLE_LIMIT_S", 5.0)


def run(monkeypatch, fault=None):
    launcher = cell.brokermod.LAUNCHER
    if fault:
        monkeypatch.setenv("BENCHMARK_FAULT", fault)
        launcher = HERE / "faulty_broker.py"
    return cell.run_cell(CELL, 20241001, 3.0, False, time.perf_counter(),
                         cpu=True, launcher=launcher)


def test_sound_broker_is_correct(short_run, monkeypatch):
    r = run(monkeypatch)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 100
    assert r["device"]["platform"] == "cpu"  # a rehearsal names its platform
    # the cell leaves deliver_p99_ms out: its spread over six runs of the
    # final tree was 26 % against the 12.5 % a bounded metric may have
    assert set(r["metrics"]) == {"deliveries_per_s", "puback_p99_ms", "setup_s"}


def test_control_dropped_delivery_is_not_correct(short_run, monkeypatch):
    r = run(monkeypatch, "drop")
    assert r["correct"] is False
    assert r["checks"]["missing_pairs"]["value"] > 0
    assert r["checks"]["unacked_qos1"]["value"] == 0  # every PUBACK still came


# ------------------------------------------------- the readers, on made runs
def _run(stats0, stats1, metrics0, metrics1, backend0, backend1) -> dict:
    """Two snapshots ten seconds apart, as ``cell.run_cell`` hands them on."""
    snap = lambda t, s, m, b: {"t": t, "stats": s, "metrics": m,  # noqa: E731
                               "device": {"backend": b}}
    return {"before": snap(100.0, stats0, metrics0, backend0),
            "after": snap(110.0, stats1, metrics1, backend1), "trace": None}


def _hold_buckets(at: dict) -> dict:
    return {f"hist_fanout_hold_b{i:02d}": at.get(i, 0) for i in range(40)}


WITH = _run(
    dict(_hold_buckets({}), stage_matcher_compile_busy_ms_total=5.0),
    # 99 holds in bucket 27 (134-268 ms) and 1 in bucket 30: p99 in bucket 27
    dict(_hold_buckets({27: 99, 30: 1}), stage_matcher_compile_busy_ms_total=5.0),
    {"fanout.held": 0, "publish.received": 1000, "fanout.enqueues": 10000,
     "deliver.queue_over_half": 0, "messages.dropped.queue_full": 2},
    {"fanout.held": 250, "publish.received": 2000, "fanout.enqueues": 22000,
     "deliver.queue_over_half": 9000, "messages.dropped.queue_full": 2,
     "messages.dropped": 0},
    {"hybrid_compiling_side": [1, 500], "hybrid_large_batches": 10},
    {"hybrid_compiling_side": [4, 2000], "hybrid_large_batches": 22})
# a program from before PR 27: no hold buckets, no new counters
WITHOUT = _run({"stage_matcher_compile_busy_ms_total": 0.0},
               {"stage_matcher_compile_busy_ms_total": 1250.0},
               {"publish.received": 1000}, {"publish.received": 2000}, {}, {})


@pytest.mark.parametrize("name,value,without", [
    ("fanout.hold_p99_ms", (1 << 28) / 1e6, None),
    ("fanout.held_share_pct", 25.0, None),
    ("deliver.queue_over_half_share_pct", 75.0, None),
    ("deliver.dropped_per_s", 0.0, 0.0),       # an existing counter family
    ("matcher.compile_onpath_ms", 0.0, 1250.0),  # an existing stage
    ("hybrid.compiling_side_share_pct", 25.0, None),
])
def test_new_reader_reads_its_counter_and_is_silent_without_it(name, value, without):
    reader = spec.load_reader(name)
    assert reader.read(WITH) == pytest.approx(value)
    got = reader.read(WITHOUT)
    assert got is None if without is None else got == pytest.approx(without)
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert {k: entry[k] for k in reader.SPEC} == reader.SPEC
    assert CELL in entry["workloads"] and len(entry["workloads"]) == 3


def test_no_hold_in_the_window_reads_zero_not_silence():
    quiet = _run(_hold_buckets({27: 5}), _hold_buckets({27: 5}), {}, {}, {}, {})
    assert spec.load_reader("fanout.hold_p99_ms").read(quiet) == 0.0
