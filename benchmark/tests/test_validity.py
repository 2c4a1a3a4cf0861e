"""The instrument's validity rule, and the sixth number of ``correct``.

*A run in which the device path was never offered a batch is not a result of
a cell, and the harness says so itself instead of printing it* (PERF.md §4):

- ``cell.warm_up`` driven by a broker that plays scripted ``/api/v1/device``
  and ``/api/v1/stats`` bodies on a clock the test owns: traffic that forms no
  batch over ``hybrid_max`` ends the run in words; every shape the three
  cells' warm-ups take (settled early, give-up, cap) returns what PR 30's
  ``warm_up`` returned on the same script (kept here as literals), field for
  field and to the same second, with as many GETs; and since PR 35 a
  warm-up does not end while a compile worker of the program lives (a warm
  cache, the compile PR 35's check met, a checkout's first run, a shape met
  late, a worker that never ends) nor, where large batches form, before
  the hybrid's first probe;
- ``cell.require_device_time``: a traced span with no operation on the device
  fails in words;
- ``acks_out_of_order``: the publisher child counts a PUBACK that passes an
  older publish's ([MQTT-4.6.0-2]), the harness sums the count and holds it
  to 0;
- the two readers that now print what they know: ``breakdown.idle_gaps`` by
  host span, ``expand.busy_pct`` 0 where the stage is there and made no pass.

Run with ``python -m pytest benchmark/tests`` from the checkout's root.
"""

import asyncio
import math
import os
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from harness import cell, fleet, mqtt, spec  # noqa: E402

FIXTURES = BENCH / "fixtures"
CELL = "cfg2_100k_plus.fleet_sat"


# ------------------------------------------------- a broker on the test's clock
class Clock:
    """``time`` as ``cell.py`` uses it: sleeping is what moves it."""

    def __init__(self) -> None:
        self.now = 1000.0

    def perf_counter(self) -> float:
        return self.now

    def sleep(self, s: float) -> None:
        self.now += s


class ScriptedBroker:
    """``get()`` answers from ``script(t)``, t = seconds since ``go``: →
    {large, dev, traces, choice, dispatches, items, compiling}; ``large`` None
    plays a program from before PR 27 (no ``hybrid_large_batches``);
    ``compiling`` is what the control thread says of a compile worker;
    ``probes`` None plays a program that does not count its probes."""

    def __init__(self, clock: Clock, script) -> None:
        self.clock, self.script, self.t_go = clock, script, None
        self.gets = []

    def go(self) -> None:
        self.t_go = self.clock.now

    def compiling(self) -> bool:
        return self.script(self.clock.now - self.t_go)["compiling"]

    def get(self, path: str):
        self.gets.append(path)
        s = self.script(0.0 if self.t_go is None else self.clock.now - self.t_go)
        if path == "/api/v1/stats":
            return [{"node": 1, "stats": {"routing_dispatches": s["dispatches"],
                                          "routing_dispatched_items": s["items"]}}]
        be = {"hybrid_served": {"side": [0, 0], "device": [s["dev"], s["dev"] * 500]},
              "hybrid_choice": s["choice"], "hybrid_max": 64}
        if s["large"] is not None:
            be["hybrid_large_batches"] = s["large"]
        if s["probes"] is not None:
            be["hybrid_probes"] = {"side": 0, "device": s["probes"]}
        return {"compile": {"traces": s["traces"]}, "backend": be}


def script(large=lambda t: int(20 * t), dev=lambda t: 0, traces=lambda t: 4,
           choice=lambda t: None, dispatches=lambda t: int(100 * t),
           compiling=lambda t: False, probes=lambda t: None):
    return lambda t: {"large": large(t), "dev": dev(t), "traces": traces(t),
                      "choice": choice(t), "dispatches": dispatches(t),
                      "items": dispatches(t) * 150, "compiling": compiling(t),
                      "probes": probes(t)}


def _warm(seconds, cap_hit, large, dev, clean, traces, choice, compiled=0.0,
          probed=True) -> dict:
    return {"seconds": seconds, "cap_hit": cap_hit, "compiling_until_s": compiled,
            "probed": probed, "large_batches": large,
            "device_batches": dev, "clean_device_batches": clean,
            "compile_traces": traces, "hybrid_choice": choice}


# name → (script, what warm_up returns, its GETs of /api/v1/device, of
# /api/v1/stats). The returns and the device GETs (one before go, one a
# poll of 0.5 s) are what PR 30's warm_up gave on the same scripts, plus
# large_batches; /api/v1/stats is read once before go, and once more only
# where the rule has to ask whether traffic flowed: never in the polls.
SHAPES = {
    # cfg2: two device batches in the first seconds, the first one compiles
    "settled_early": (script(dev=lambda t: min(int(t), 3),
                             traces=lambda t: 5 if t >= 1 else 4,
                             choice=lambda t: "side" if t >= 2 else None),
                      _warm(10.0, False, 200, 3, 2, 5, "side"), 21, 1),
    # hundreds of large batches, the device's first batch comes at 16 s and
    # no compile worker was seen: the give-up branch
    "first_device_batch_at_16s": (script(dev=lambda t: int(t >= 16)),
                                  _warm(15.0, False, 300, 0, 0, 4, None), 31, 1),
    # cfg3 as PR 35's check met it: that first batch stands behind an off-path
    # compile of 16 s. The worker is waited for, and the give-up clock counts
    # from its end: the window no longer opens on a compile
    "first_device_batch_behind_a_compile_of_16s": (
        script(compiling=lambda t: t < 16, dev=lambda t: int(t >= 16) + int(t >= 30),
               traces=lambda t: 4 + int(t >= 16),
               choice=lambda t: "side" if t >= 16 else None),
        _warm(45.0, False, 900, 2, 1, 5, "side", compiled=15.5), 91, 1),
    # a warm compile cache: the worker is done in 5 s, inside the least length
    "compile_worker_5s_then_settled": (
        script(compiling=lambda t: t < 5, dev=lambda t: max(0, min(int(t - 5), 3)),
               traces=lambda t: 4 + min(int(t), 5),
               choice=lambda t: "side" if t >= 7 else None),
        _warm(10.0, False, 200, 3, 3, 9, "side", compiled=4.5), 21, 1),
    # a checkout's first run: 110 s of compiles, far past WARMUP_CAP_S, and no
    # cap is hit; 15 s without a device batch after the worker's end
    "cold_checkout_110s": (
        script(compiling=lambda t: t < 110, dev=lambda t: int(t >= 13),
               traces=lambda t: 4 + min(int(t / 13), 8),
               choice=lambda t: "side" if t >= 13 else None),
        _warm(124.5, False, 2490, 1, 0, 12, "side", compiled=109.5), 250, 1),
    # settled at 2 s, and a new shape is met at 8 s: its compile is waited for
    "new_shape_met_late": (
        script(compiling=lambda t: t < 4 or 8 <= t < 14, dev=lambda t: min(int(t), 2),
               choice=lambda t: "side"),
        _warm(14.0, False, 280, 2, 2, 4, "side", compiled=13.5), 29, 1),
    # cfg3 where the mirror wins: settled at 3 s on the start's wide batches,
    # and the hybrid's first probe (its 64th large batch, at 18.3 s) brings the
    # everyday shape to the device, which is compiled then, not in the window
    "first_probe_brings_the_everyday_shape": (
        script(large=lambda t: int(3.5 * t), probes=lambda t: int(3.5 * t) // 64,
               dev=lambda t: min(int(t), 3) + int(t >= 19.5) + int(t >= 25) + int(t >= 31),
               compiling=lambda t: t < 2 or 18.5 <= t < 19.5,
               traces=lambda t: 4 + int(t >= 2) + int(t >= 19.5),
               choice=lambda t: "side" if t >= 2 else None),
        _warm(31.0, False, 108, 6, 2, 6, "side", compiled=19.0), 63, 1),
    # large batches so rare that no probe comes: the cap, and it is printed
    "no_probe_before_the_cap": (
        script(large=lambda t: int(0.5 * t), probes=lambda t: 0,
               dev=lambda t: min(int(t), 3), choice=lambda t: "side"),
        _warm(60.0, True, 30, 3, 3, 4, "side", probed=False), 121, 1),
    # a worker that never ends: the compile's own cap, and it is printed
    "compile_worker_never_ends": (
        script(compiling=lambda t: True),
        _warm(480.0, True, 9600, 0, 0, 4, None, compiled=480.0), 961, 1),
    # device batches that stop early: given up 15 s after the last one
    "give_up_after_the_last": (script(dev=lambda t: min(int(t), 1),
                                      choice=lambda t: "side"),
                               _warm(16.0, False, 320, 1, 1, 4, "side"), 33, 1),
    # a new program with every device batch: never settled, never given up
    "cap": (script(dev=lambda t: int(t // 4), traces=lambda t: 4 + int(t // 4),
                   choice=lambda t: "device"),
            _warm(60.0, True, 1200, 15, 0, 19, "device"), 121, 1),
    # a program from before PR 27 has no hybrid_large_batches: the device's
    # batches alone say that it was offered some
    "no_large_count_device_served": (script(large=lambda t: None,
                                            dev=lambda t: min(int(t / 3), 2),
                                            choice=lambda t: "side" if t >= 6 else None),
                                     _warm(10.0, False, 0, 2, 2, 4, "side"), 21, 1),
    # no traffic at all is not this rule's to judge: the comparison's
    # expected_pairs >= 1 fails such a run
    "nothing_dispatched": (script(large=lambda t: 0, dispatches=lambda t: 7),
                           _warm(15.0, False, 0, 0, 0, 4, None), 31, 2),
}


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(cell, "time", types.SimpleNamespace(
        perf_counter=c.perf_counter, sleep=c.sleep))
    return c


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_warm_up_returns_what_it_returned(shape, clock):
    play, want, device_gets, stats_gets = SHAPES[shape]
    b = ScriptedBroker(clock, play)
    assert cell.warm_up(b, b.go, CELL) == want  # field for field, to the second
    assert b.gets.count("/api/v1/device") == device_gets
    assert b.gets.count("/api/v1/stats") == stats_gets
    assert len(b.gets) == device_gets + stats_gets


@pytest.mark.parametrize("large, probes", [
    (lambda t: 0, lambda t: None), (lambda t: None, lambda t: None),
    (lambda t: 37, lambda t: None), (lambda t: 37, lambda t: 0)],
    ids=["none_formed", "no_such_count", "none_since_go", "none_since_go_probes_counted"])
def test_traffic_that_never_offers_the_device_a_batch_fails_in_words(large, probes, clock):
    b = ScriptedBroker(clock, script(large=large, probes=probes,
                                     dispatches=lambda t: int(150 * t)))
    with pytest.raises(SystemExit) as e:
        cell.warm_up(b, b.go, "cfg2_100k_plus.pub40")
    words = str(e.value)
    assert clock.now - b.t_go == 15.0  # where the give-up branch returns
    for part in ("benchmark: cell 'cfg2_100k_plus.pub40'", "in 15.0 s", "hybrid_max = 64",
                 "routed 337500 publishes in 2250 dispatches (150.0 topics a dispatch)",
                 "--router xla is inert on this program",
                 "A cell has to drive the device path", "no result is printed"):
        assert part in words


# -------------------------------------------------- after a traced window
def _traced(large: int, batches: int) -> dict:
    snap = lambda n, d: {"device": {"backend": {  # noqa: E731
        "hybrid_large_batches": n, "hybrid_served": {"device": [d, d * 70]}}}}
    return {"window_s": 50.9, "before": snap(400, 9), "after": snap(400 + large, 9 + batches)}


def test_a_traced_span_without_device_time_fails_in_words(tmp_path):
    from harness import trace_reduce

    (tmp_path / "empty.xplane.pb").write_bytes(b"")  # no plane at all
    red = trace_reduce.reduce(tmp_path)
    assert red["busy_s"] == 0.0 and red["device_planes"] == 0
    with pytest.raises(SystemExit) as e:
        cell.require_device_time(CELL, _traced(41, 0), red)
    for part in (f"cell {CELL!r}", "trace of 50.9 s", "0 device plane(s)",
                 "no operation on the device", "routed 41 large batches",
                 "served 0 batches of 0 topics", "no result is printed"):
        assert part in str(e.value)
    red = trace_reduce.reduce(FIXTURES / "trace_small.xplane.pb")
    assert red["busy_s"] > 0
    assert cell.require_device_time(CELL, _traced(41, 3), red) is None


# ------------------------------------------------------- acks_out_of_order
class _Conn:
    """As much of ``fleet._Conn`` as a publisher touches."""

    def __init__(self) -> None:
        self.tr, self.wrote = self, []
        self.lost = asyncio.get_running_loop().create_future()

    def write(self, data: bytes) -> None:
        self.wrote.append(data)


def _publishers(inflight: int):
    """A publisher child with one connection and ``inflight`` publishes out,
    made inside a running loop as its process makes it."""
    a = {"procs": 1, "proc": 0, "traffic": {"loop": "closed", "inflight": inflight}}
    p = fleet._Publishers(None, a, types.SimpleNamespace(value=math.inf))
    c = _Conn()
    p.conns, p.pending[c], p.pid[c] = [c], {}, 0
    p.stream = (f"t/{i}" for i in range(100))
    return p, c


def _puback(pid: int):
    return (mqtt.PUBACK, 0, bytes([pid >> 8, pid & 255]))


@pytest.mark.parametrize("acks,want", [([1, 2, 3, 4], 0), ([2, 1, 3, 4], 1),
                                       ([4, 3, 2, 1], 3), ([1, 9, 2], 0)],
                         ids=["in_order", "one_swapped", "reversed", "unknown_id"])
def test_publisher_counts_acks_that_pass_an_older_publish(acks, want):
    async def main():
        p, c = _publishers(4)
        await p.cmd_go(0.0)
        assert list(p.pending[c]) == [1, 2, 3, 4]
        for pid in acks:  # one PUBACK a read, as the wrong order would come
            p.on_packets(c, [_puback(pid)], 5.0)
        return (await p.cmd_drain())["out_of_order"]

    assert asyncio.run(main()) == want


class _DrainedFleet:
    """What ``Records.drain`` asks of a fleet, answered from made replies."""

    subs, pubs = "subs", "pubs"

    def __init__(self, out_of_order) -> None:
        f8 = lambda *v: np.array(v, np.float64).tobytes()  # noqa: E731
        self.replies = {"subs": [], "pubs": [
            {"topics": ["a/b"], "t_send": f8(10.0), "t_due": f8(10.0), "waited": bytes(1),
             "t_ack": f8(10.2), "inflight": 0,
             "out_of_order": n, "cpu": f8(), "lost": 0} for n in out_of_order]}

    def ask(self, procs, _cmd):
        return self.replies[procs]


def test_harness_sums_the_count_and_holds_it_to_zero():
    ref = types.SimpleNamespace(
        expected=lambda topics: (np.zeros(len(topics), np.int64), np.zeros(0, np.int64)))
    for counts in ([0, 0], [1, 2]):
        rec = cell.Records(2)
        rec.drain(_DrainedFleet(counts))
        rec.drain(_DrainedFleet(counts))  # cumulative in the child: not added twice
        checks = cell.compare(rec, ref, 4, 9.0, 11.0)["checks"]
        assert checks["acks_out_of_order"] == sum(counts)
        assert list(checks) == ["missing_pairs", "unexpected_pairs", "unacked_qos1",
                                "acks_out_of_order", "unknown_publish_ids",
                                "connections_lost"]


# ---------------------------------- two readers that print what they know
def test_idle_gaps_carry_the_host_span_that_covered_them():
    from harness import host_spans, trace_reduce

    pb = FIXTURES / "trace_spans.xplane.pb"
    gaps = cell.idle_gaps(pb, trace_reduce.reduce(pb))
    assert gaps == host_spans.read(pb)["gap_names"] and gaps
    assert all(name != "unattributed" for name, _s in gaps)
    # a trace without the program's spans (a program from before PR 25)
    pb = FIXTURES / "trace_small.xplane.pb"
    red = trace_reduce.reduce(pb)
    assert cell.idle_gaps(pb, red) == [["unattributed", g] for g in red["gaps"]]


def _stage_run(stats0: dict, stats1: dict) -> dict:
    snap = lambda t, s: {"t": t, "stats": s}  # noqa: E731
    return {"before": snap(100.0, stats0), "after": snap(110.0, stats1), "trace": None}


@pytest.mark.parametrize("stats0,stats1,want", [
    ({"stage_routing_expand_count": 5, "stage_routing_expand_busy_ms_total": 2.0},
     {"stage_routing_expand_count": 5, "stage_routing_expand_busy_ms_total": 2.0}, 0.0),
    ({"stage_routing_expand_count": 5, "stage_routing_expand_busy_ms_total": 2.0},
     {"stage_routing_expand_count": 9, "stage_routing_expand_busy_ms_total": 252.0}, 2.5),
    ({}, {}, None),
], ids=["no_pass_reads_zero", "passes", "no_such_stage_is_silent"])
def test_expand_busy_pct_reads_zero_where_the_stage_made_no_pass(stats0, stats1, want):
    got = spec.load_reader("expand.busy_pct").read(_stage_run(stats0, stats1))
    assert got is None if want is None else got == pytest.approx(want)
    # the shared helper keeps its silence for every other reader
    from _stages import busy_pct  # load_reader put layer_metrics/ on the path

    if want == 0.0:
        assert busy_pct(_stage_run(stats0, stats1), ("routing.expand",)) is None
