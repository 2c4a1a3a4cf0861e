"""``harness/host_spans.py`` against the second reading of the recorded trace.

``fixtures/trace_spans.xplane.pb`` was recorded on a TPU v5e by
``fixtures/record_spans.py``; ``fixtures/trace_spans.expected.json`` comes
from ``fixtures/handcheck_spans.py``, which walks the protobuf itself, in
picoseconds, and cuts the trace at every edge. ``ProfileData`` gives whole
nanoseconds, so a sum may differ by a nanosecond for each event that
touches it.
"""

import json
import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from harness import host_spans  # noqa: E402

FIXTURES = BENCH / "fixtures"
WANT = json.loads((FIXTURES / "trace_spans.expected.json").read_text())
TOL_S = WANT["events"] * 1e-9


@pytest.fixture(scope="module")
def got():
    return host_spans.read(FIXTURES / "trace_spans.xplane.pb")


@pytest.mark.parametrize("name", sorted(WANT["idle_ps"]))
def test_idle_seconds_by_cause(got, name):
    assert got["idle_s"][name] == pytest.approx(WANT["idle_ps"][name] / 1e12, abs=TOL_S)


def test_idle_names_and_total(got):
    assert set(got["idle_s"]) == set(WANT["idle_ps"])
    assert got["idle_total_s"] == pytest.approx(WANT["idle_total_ps"] / 1e12, abs=TOL_S)
    assert sum(got["idle_s"].values()) == pytest.approx(got["idle_total_s"], abs=1e-12)


@pytest.mark.parametrize("scope", sorted(WANT["scope_ps"]))
def test_device_seconds_by_scope(got, scope):
    assert got["scope_s"][scope] == pytest.approx(WANT["scope_ps"][scope] / 1e12, abs=TOL_S)


def test_scopes_spans_and_runs(got):
    assert set(got["scope_s"]) == set(WANT["scope_ps"])
    assert {k: v[0] for k, v in got["spans"].items()} \
        == {k: v[0] for k, v in WANT["spans_ps"].items()}
    for k, (_n, ps) in WANT["spans_ps"].items():
        assert got["spans"][k][1] == pytest.approx(ps / 1e12, abs=TOL_S)
    assert sorted(got["loop_spans"]) == WANT["loop_spans"]
    assert got["match_runs"] == WANT["match_runs"]
    assert got["match_s"] == pytest.approx(WANT["match_ps"] / 1e12, abs=TOL_S)


def test_longest_gaps_are_named(got):
    assert len(got["gap_names"]) == len(WANT["gaps"])
    for (name, s), (wname, ps) in zip(got["gap_names"], WANT["gaps"]):
        assert name == wname and s == pytest.approx(ps / 1e12, abs=2e-9)
    assert host_spans.gap_names(FIXTURES / "trace_spans.xplane.pb") == got["gap_names"]


def test_a_trace_without_spans_reads_none():
    """The parent's traces hold no ``rmqtt/*`` event: the readers are absent,
    they do not raise."""
    assert host_spans.read(FIXTURES / "trace_small.xplane.pb") is None
    assert host_spans.from_run({"trace": None}) is None


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(match_fused_impl)/jit(main)/scan/while/body/gather:", "scan"),
    ("jit(match_fused_impl)/resolve/jit(_take)/gather:", "resolve"),
    ("jit(match_fused_small)/sort/sort:", "sort"),
    ("reduce_window_sum:", "unscoped"), ("", "unscoped")])
def test_scope_of(tf_op, scope):
    assert host_spans.scope_of(tf_op) == scope
