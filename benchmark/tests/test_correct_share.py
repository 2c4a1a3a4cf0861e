"""``shared_missed`` and ``shared_extra`` have been shown to fail.

A ``--cpu`` rehearsal of ``cfg2_100k_plus.fleet_sat``'s fleet on a table
with ``$share`` groups (``share_table.py``: one row in ten in groups of four
equal rows, and a wildcard group that overlaps them), through a whole run:
broker child, fleet, window, settle, comparison with the reference.

- a sound broker reads ``correct`` true, both new checks 0;
- ``share_all`` (every member of a group sent the publish) reads ``correct``
  false through ``shared_extra`` alone, ``share_none`` (the groups dropped)
  through ``shared_missed`` alone;
- a table on which one subscriber holds two rows of one filter is refused
  by the reference process in words.

Run with ``python -m pytest benchmark/tests`` from the checkout's root.
"""

import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from harness import cell, generators  # noqa: E402
from harness.reference import Reference  # noqa: E402

CELL = "cfg2_100k_plus.fleet_sat"


@pytest.fixture
def share_table(monkeypatch):
    """The cell's fleet on ``share_table``'s rows; the short settle of
    ``test_correct.py``, so a fault's missing deliveries are not waited for."""
    monkeypatch.setenv(generators.TEST_GENERATOR, str(HERE / "share_table.py"))
    for name, seconds in (("WARMUP_MIN_S", 2.0), ("WARMUP_CAP_S", 6.0),
                          ("SETTLE_LIMIT_S", 5.0)):
        monkeypatch.setattr(cell, name, seconds)
    load = cell.spec.load_cell

    def load_cell(name):
        c = load(name)
        c["config"] = dict(c["config"], generator="share_table")
        return c
    monkeypatch.setattr(cell.spec, "load_cell", load_cell)


@pytest.mark.parametrize("fault, check", [(None, None), ("share_all", "shared_extra"),
                                          ("share_none", "shared_missed")],
                         ids=["sound", "share_all", "share_none"])
def test_shared_checks_decide_correct(fault, check, share_table, monkeypatch):
    launcher = cell.brokermod.LAUNCHER
    if fault:
        monkeypatch.setenv("BENCHMARK_FAULT", fault)
        launcher = HERE / "faulty_broker.py"
    r = cell.run_cell(CELL, 20261016, 3.0, False, time.perf_counter(),
                      cpu=True, launcher=launcher)
    values = {k: v["value"] for k, v in r["checks"].items()}
    assert list(values)[-3:] == ["shared_missed", "shared_extra", "expected_pairs"]
    assert values.pop("expected_pairs") > 1000
    if fault is None:
        assert r["correct"] is True and not any(values.values())
    else:
        failing = {k: v for k, v in values.items() if v}
        assert r["correct"] is False and list(failing) == [check], failing


def test_a_subscriber_on_one_filter_twice_is_refused_in_words(monkeypatch):
    monkeypatch.setenv(generators.TEST_GENERATOR, str(HERE / "share_table.py"))
    # one subscriber holds every row, so a group's equal rows are its twice
    ref = Reference("share_table", 5, {"subscriptions": 4000}, 1)
    try:
        with pytest.raises(SystemExit) as e:
            ref.built(120.0)
    finally:
        ref.close()
    words = str(e.value)
    assert "benchmark: the reference refuses the table: subscriber 0 holds" in words
    assert "both on the filter" in words and "No result is printed" in words
