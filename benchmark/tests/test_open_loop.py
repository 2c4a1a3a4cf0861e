"""The open loop: a schedule from the seed, MQTT's window, latency from the
due instant, and the rule that the fleet, not the broker, keeps the schedule.

- ``fleet.schedule``: the due instants of a mix repeat; a burst's publishes
  share one instant, fall on distinct connections and are split evenly over
  the publisher processes, every connection used alike; ``due_between``
  counts what fell due in a window from the schedule itself;
- the publisher child on a clock and a transport of the test's own: with the
  window full a due publish waits for the PUBACK and keeps its due instant;
  nothing leaves after ``stop_at`` and the rest stays queued; a closed mix
  yields the records it yielded, with ``t_due`` equal to ``t_send``;
- ``cell.compare`` takes the latencies from the due instant and window
  membership from the send instant; ``cell.open_loop`` counts what the
  window offered;
- ``spec.load_traffic`` accepts the open mix and refuses each malformed one
  in words, an arrival other than ``burst`` among them;
- ``cell.require_schedule_kept`` fails in words on a publisher process at
  95 % of a core and on a generator whose own lateness is the tail, and is
  silent on a slow broker and on a closed mix;
- the ``--cpu`` rehearsal of a cell under an open mix is ``correct``, and
  under the ``drop`` control is not.

No cell of ``BENCHMARK.json`` runs an open mix yet (PERF.md §7 says what the
first one waits for), so the mix here is ``fleet_sat`` with the open loop's
keys set, and the rehearsal is ``cfg2_100k_plus.fleet_sat`` under it.

Run with ``python -m pytest benchmark/tests`` from the checkout's root.
"""

import asyncio
import itertools
import json
import math
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from harness import cell, fleet, mqtt, spec  # noqa: E402

CELL = "cfg2_100k_plus.fleet_sat"
OPEN = {"loop": "open", "rate_publishes_per_s": 3200, "arrival": "burst",
        "burst_size": 256, "inflight": 16}
BURST = spec.load_traffic("fleet_sat", OPEN)


def head(traffic, proc, procs, conns, n):
    return list(itertools.islice(fleet.schedule(traffic, proc, procs, conns), n))


# ------------------------------------------------------------- the schedule
def test_the_schedule_of_a_mix_repeats():
    for proc in (0, 1):
        assert head(BURST, proc, 2, 256, 5000) == head(BURST, proc, 2, 256, 5000)
    times = [t for t, _c in head(BURST, 0, 2, 256, 5000)]
    assert times == sorted(times) and times[0] >= 0.0


def test_what_fell_due_in_a_window_is_counted_from_the_schedule():
    mix = dict(BURST, burst_size=4, rate_publishes_per_s=40)  # a burst every 100 ms
    # bursts at 100.0, 100.1, ... ; [100.25, 100.55) holds those at .3 .4 .5
    assert fleet.due_between(mix, [3, 3], 100.0, 100.25, 100.55) == 12
    assert fleet.due_between(mix, [3, 3], 100.0, 100.3, 100.5) == 8  # [t0, t1)
    assert fleet.due_between(mix, [3, 3], 100.0, 99.0, 100.0) == 0


def test_a_burst_is_one_instant_on_distinct_connections_split_evenly():
    size, rate, procs, conns = BURST["burst_size"], BURST["rate_publishes_per_s"], 2, 256
    share = size // procs
    per_proc = [head(BURST, k, procs, conns, share * 40) for k in range(procs)]
    used = np.zeros((procs, conns), int)
    for b in range(40):
        instants = set()
        for k in range(procs):
            mine = per_proc[k][b * share:(b + 1) * share]
            instants |= {t for t, _c in mine}
            cs = [c for _t, c in mine]
            assert len(set(cs)) == share  # no connection twice in one burst
            np.add.at(used[k], cs, 1)
        assert instants == {b * size / rate}  # one instant, in every process
    assert used.min() == used.max() == 40 * share // conns  # every connection alike


def test_an_uneven_burst_is_split_to_the_publish():
    mix = dict(BURST, burst_size=7)
    shares = [len([1 for t, _c in head(mix, k, 3, 4, 50) if t == 0.0]) for k in range(3)]
    assert sorted(shares) == [2, 2, 3] and sum(shares) == 7
    with pytest.raises(RuntimeError, match="publishes of a burst on 2 connections"):
        head(mix, 2, 3, 2, 1)


# --------------------------------------- the publisher child, on a test's clock
class Clock:
    def __init__(self) -> None:
        self.now = 100.0

    def perf_counter(self) -> float:
        return self.now


class _Conn:
    """As much of ``fleet._Conn`` as a publisher touches."""

    def __init__(self) -> None:
        self.tr, self.wrote = self, []
        self.lost = asyncio.get_running_loop().create_future()

    def write(self, data: bytes) -> None:
        self.wrote.append(data)


class _Pub(fleet._Publishers):
    """The publisher child with its timer in the test's hands: ``_arm`` only
    notes the instant, ``run_to`` moves the clock from timer to timer."""

    armed = None

    def _arm(self, when: float) -> None:
        self.armed = when

    def run_to(self, clock: Clock, t: float, late: float = 0.0) -> None:
        while self.armed is not None and self.armed + late <= t:
            clock.now, self.armed = self.armed + late, None
            self._on_due()
        clock.now = t


def _puback(pid: int):
    return (mqtt.PUBACK, 0, bytes([pid >> 8, pid & 255]))


def child(traffic: dict, conns: int, stop_at: float = math.inf):
    a = {"procs": 1, "proc": 0, "seed": 5, "traffic": traffic}
    p = _Pub(None, a, types.SimpleNamespace(value=stop_at))
    p.conns = [_Conn() for _ in range(conns)]
    for c in p.conns:
        p.pending[c], p.pid[c], p.queue[c] = {}, 0, fleet.deque()
    p.stream = (f"t/{i}" for i in itertools.count())
    return p


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(fleet, "time", types.SimpleNamespace(perf_counter=c.perf_counter))
    return c


def drained(r: dict) -> dict:
    f8 = lambda k: np.frombuffer(r[k]).tolist()  # noqa: E731
    return dict(r, t_send=f8("t_send"), t_due=f8("t_due"), t_ack=f8("t_ack"),
                waited=np.frombuffer(r["waited"], dtype=np.int8).tolist())


# one connection, a window of 2, one publish due every 10 ms
ONE_A_TICK = dict(BURST, publishers=1, burst_size=1, rate_publishes_per_s=100, inflight=2)


def test_a_due_publish_waits_for_the_puback_and_keeps_its_due_instant(clock):
    async def main():
        p = child(ONE_A_TICK, 1)
        c = p.conns[0]
        await p.cmd_go(clock.now + 0.5)
        assert p.armed == 100.5
        p.run_to(clock, 100.535, late=0.001)  # dues at .50 .51 .52 .53
        assert len(c.wrote) == 2 and list(p.queue[c]) == [100.52, 100.53]
        p.on_packets(c, [_puback(1)], clock.now)  # frees one slot: the OLDEST waiting leaves
        assert len(c.wrote) == 3 and list(p.queue[c]) == [100.53]
        p.run_to(clock, 100.545, late=0.001)  # .54 queues behind .53
        p.on_packets(c, [_puback(2), _puback(3)], clock.now)
        return drained(await p.cmd_drain())

    r = asyncio.run(main())
    assert r["t_due"] == [100.5, 100.51, 100.52, 100.53, 100.54]  # first in, first out
    assert r["t_send"] == pytest.approx([100.501, 100.511, 100.535, 100.545, 100.545])
    assert r["waited"] == [0, 0, 1, 1, 1]
    assert r["inflight"] == 2 and r["out_of_order"] == 0
    assert r["topics"] == [f"t/{i}" for i in range(5)]


def test_nothing_leaves_after_stop_at_and_the_rest_stays_queued(clock):
    async def main():
        p = child(ONE_A_TICK, 1, stop_at=100.555)
        c = p.conns[0]
        await p.cmd_go(100.5)
        p.run_to(clock, 100.549)  # .50 .51 sent; .52 .53 .54 queued
        clock.now = 100.56       # the window has closed
        p.on_packets(c, [_puback(1), _puback(2)], clock.now)
        assert len(c.wrote) == 2  # a freed slot sends nothing now
        p.run_to(clock, 100.7, late=0.01)  # the timer for .55 comes at .56
        assert p.armed is None    # and the schedule has ended
        assert list(p.queue[c]) == [100.52, 100.53, 100.54]  # never sent
        return drained(await p.cmd_drain())

    r = asyncio.run(main())
    assert r["t_due"] == [100.5, 100.51] and r["inflight"] == 0


def test_a_burst_leaves_at_its_instant_on_every_connection(clock):
    async def main():
        mix = dict(BURST, publishers=8, burst_size=4, rate_publishes_per_s=40, inflight=16)
        p = child(mix, 8)
        await p.cmd_go(100.5)
        p.run_to(clock, 100.75, late=0.002)  # bursts at .5 .6 .7
        return [len(c.wrote) for c in p.conns], drained(await p.cmd_drain())

    wrote, r = asyncio.run(main())
    assert wrote == [2, 2, 2, 2, 1, 1, 1, 1]  # rotating over the connections
    assert r["t_due"] == [100.5] * 4 + [100.6] * 4 + [100.7] * 4
    assert r["t_send"] == pytest.approx([100.502] * 4 + [100.602] * 4 + [100.702] * 4)
    assert not any(r["waited"])


def test_a_closed_mix_yields_the_records_it_yielded(clock):
    async def main():
        p = child(spec.load_traffic("pub40", {}), 2)
        a, b = p.conns
        await p.cmd_go(clock.now + 0.5)  # a closed loop starts at once
        assert p.armed is None and len(a.wrote) == len(b.wrote) == 16
        clock.now = 100.25
        p.on_packets(a, [_puback(1), _puback(2)], clock.now)
        p.on_packets(b, [_puback(1)], clock.now)
        return [len(c.wrote) for c in p.conns], drained(await p.cmd_drain())

    wrote, r = asyncio.run(main())
    assert wrote == [18, 17]
    assert r["topics"] == [f"t/{i}" for i in range(35)]  # drawn in send order
    assert r["t_send"] == [100.0] * 32 + [100.25] * 3 == r["t_due"]
    assert not any(r["waited"])
    assert r["t_ack"][:2] == [100.25, 100.25] and r["t_ack"][16] == 100.25
    assert r["inflight"] == 32


# ------------------------------------- the harness's reading of the records
class _DrainedFleet:
    """What ``Records.drain`` asks of a fleet, answered from made replies."""

    subs, pubs = "subs", "pubs"

    def __init__(self, pubs: list, subs: list) -> None:
        f8 = lambda v: np.array(v, np.float64).tobytes()  # noqa: E731
        self.replies = {
            "pubs": [{"topics": ["a/b"] * len(p["t_send"]), "t_send": f8(p["t_send"]),
                      "t_due": f8(p["t_due"]), "t_ack": f8(p["t_ack"]),
                      "waited": np.array(p["waited"], np.int8).tobytes(),
                      "inflight": 0, "out_of_order": 0, "cpu": f8([]), "lost": 0}
                     for p in pubs],
            "subs": [{"ids": np.array(s["ids"], np.int64).tobytes(),
                      "times": f8(s["times"]),
                      "subs": np.array(s["subs"], np.dtype("l")).tobytes(),
                      "cpu": f8([]), "lost": 0} for s in subs]}

    def ask(self, procs, _cmd):
        return self.replies[procs]


def everyone_gets_it(subscribers: int):
    return types.SimpleNamespace(expected=lambda topics: (
        np.full(len(topics), subscribers, np.int64),
        np.tile(np.arange(subscribers), len(topics))))


def test_latency_counts_from_the_due_instant_and_membership_from_the_send():
    # one process; publish 0 was due before the window and sent inside it,
    # publish 2 was due and sent inside, publish 3 due inside and sent after
    pub = {"t_send": [9.5, 10.4, 10.6, 12.1], "t_due": [9.0, 9.9, 10.5, 11.9],
           "t_ack": [9.6, 10.7, 10.65, 12.2], "waited": [1, 1, 0, 1]}
    sub = {"ids": [1, 2], "times": [10.8, 10.7], "subs": [0, 0]}
    rec = cell.Records(1)
    rec.drain(_DrainedFleet([pub], [sub]))
    cmp = cell.compare(rec, everyone_gets_it(1), 1, 10.0, 12.0)
    assert not any(cmp["checks"].values()) and cmp["publishes"] == 2  # sent in [10, 12)
    assert cmp["deliver_ms"] == pytest.approx([900.0, 200.0])        # from 9.9 and 10.5
    assert cmp["puback_ms"] == pytest.approx([800.0, 150.0])
    # the schedule had five due in [10, 12): records 2 and 3, three never sent
    offered = cell.open_loop(rec, cmp, 5, 10.0, 12.0)
    assert (offered["due"], offered["sent"], offered["never_sent"]) == (5, 2, 3)
    assert offered["sent_of_due_pct"] == pytest.approx(40.0)
    assert offered["window_full_share_pct"] == pytest.approx(80.0)  # record 3 + the three
    assert offered["late_p50_ms"] == offered["late_max_ms"] == pytest.approx(100.0)
    json.dumps(offered)  # an earlier line of the result: no nan in it


def test_a_closed_mix_reads_what_it_read():
    pub = {"t_send": [10.1, 10.2], "t_due": [10.1, 10.2], "t_ack": [10.3, 10.5],
           "waited": [0, 0]}
    rec = cell.Records(1)
    rec.drain(_DrainedFleet([pub], [{"ids": [0, 1], "times": [10.2, 10.45], "subs": [0, 0]}]))
    cmp = cell.compare(rec, everyone_gets_it(1), 1, 10.0, 12.0)
    assert cmp["deliver_ms"] == pytest.approx([100.0, 250.0])
    assert cmp["puback_ms"] == pytest.approx([200.0, 300.0])


# ----------------------------------------------------------- the mix format
def test_an_open_mix_loads():
    assert (BURST["loop"], BURST["arrival"], BURST["burst_size"]) == ("open", "burst", 256)
    assert BURST["inflight"] == 16 and BURST["publishers"] == 512 >= BURST["burst_size"]


@pytest.mark.parametrize("mix,overrides,words", [
    ("fleet_sat", dict(OPEN, rate_publishes_per_s=None), "needs rate_publishes_per_s > 0"),
    ("fleet_sat", dict(OPEN, rate_publishes_per_s=0), "needs rate_publishes_per_s > 0"),
    ("fleet_sat", dict(OPEN, arrival="poisson", burst_size=None),
     "arrival must be burst: no cell asks for another yet (it names 'poisson')"),
    ("fleet_sat", dict(OPEN, arrival="uniform"), "arrival must be burst"),
    ("fleet_sat", dict(OPEN, arrival=None), "arrival must be burst"),
    ("fleet_sat", dict(OPEN, burst_size=None), "arrival burst needs burst_size >= 1"),
    ("fleet_sat", dict(OPEN, burst_size=0), "arrival burst needs burst_size >= 1"),
    ("fleet_sat", dict(OPEN, publishers=100), "needs as many publishers"),
    ("fleet_sat", dict(OPEN, inflight=0), "needs inflight >= 1"),
    ("fleet_sat", dict(OPEN, inflight=None), "needs inflight >= 1"),
    ("fleet_sat", dict(OPEN, qos1_share=0.5),
     "qos1_share must be 1.0: a QoS0 publish carries no guarantee"),
    ("fleet_sat", {"qos1_share": 0.0}, "qos1_share must be 1.0"),
    ("fleet_sat", {"rate_publishes_per_s": 100}, "a closed loop offers no rate"),
    ("fleet_sat", {"arrival": "burst"}, "a closed loop offers no rate"),
    ("pub40", {"burst_size": 2}, "a closed loop offers no rate"),
    ("fleet_sat", {"inflight": 0}, "needs inflight >= 1"),
    ("fleet_sat", dict(OPEN, loop="half_open"), "loop must be closed or open"),
    ("fleet_sat", dict(OPEN, think_time_s=1), "unknown keys ['think_time_s']"),
])
def test_a_malformed_mix_is_refused_in_words(mix, overrides, words):
    with pytest.raises(SystemExit) as e:
        spec.load_traffic(mix, overrides)
    assert f"traffic {mix!r}" in str(e.value) and words in str(e.value)


# ------------------------------------------- the fleet keeps the schedule
OFFERED = {"late_p50_ms": 41.2, "late_p99_ms": 310.5, "late_max_ms": 402.0,
           "sent": 120_000, "due": 163_200}


def test_a_generator_that_was_the_wall_fails_in_words():
    shares = {"sub0": 30.0, "pub0": 22.0, "pub1": 95.0}
    with pytest.raises(SystemExit) as e:
        cell.require_schedule_kept(CELL, BURST, shares, OFFERED, 5000.0)
    words = str(e.value)
    for part in (f"benchmark: cell {CELL!r}", "publisher process pub1", "95.0 % of a core",
                 "over 90 %", "41.2 / 310.5 / 402.0 ms (p50 / p99 / max)",
                 "120000 sent of 163200 due", "the generator, not the broker, was the wall",
                 "no result is printed"):
        assert part in words


def test_a_generator_whose_own_lateness_is_the_tail_fails_in_words():
    shares = {"sub0": 30.0, "pub0": 16.0, "pub1": 17.0}
    with pytest.raises(SystemExit) as e:  # 310.5 ms late at p99, the PUBACK's p99 480
        cell.require_schedule_kept(CELL, BURST, shares, OFFERED, 480.0)
    words = str(e.value)
    for part in (f"benchmark: cell {CELL!r}", "41.2 / 310.5 / 402.0 ms (p50 / p99 / max)",
                 "65 % of the PUBACK's (480.0 ms; the line is 50 %)",
                 "the load generator's own lateness", "17.0 % of a core at the most",
                 "no result is printed"):
        assert part in words


SLOW = dict(OFFERED, late_p50_ms=None, late_p99_ms=None, late_max_ms=None)


@pytest.mark.parametrize("traffic,shares,offered,p99", [
    # a slow broker: every window full (no publish had a free slot, so none
    # was late by the generator's doing), the publisher processes all but idle
    (BURST, {"pub0": 11.0, "pub1": 12.5, "sub0": 40.0}, SLOW, 5000.0),
    # the lateness a sound run reads: a burst's publishes leave one after another
    (BURST, {"pub0": 16.0, "pub1": 17.0}, dict(OFFERED, late_p99_ms=8.2), 290.0),
    (BURST, {"pub0": 16.0}, dict(OFFERED, late_p99_ms=144.0), 290.0),  # under the line
    # a subscriber process is not the schedule's keeper
    (BURST, {"pub0": 25.0, "pub1": 24.0, "sub0": 97.0}, OFFERED, 5000.0),
    (BURST, {"pub0": 90.0, "pub1": 24.0}, OFFERED, 5000.0),  # at the line is not over it
    # closed mixes are not held to it, whatever they burn
    (spec.load_traffic("fleet_sat", {}), {"pub0": 95.0, "pub1": 99.0}, None, 300.0),
    (spec.load_traffic("fleet_sat_q0", {}), {"pub0": 95.0}, None, 300.0),
    (spec.load_traffic("pub40", {}), {"pub0": 100.0}, None, 300.0),
], ids=["slow_broker", "sound_lateness", "under_the_line", "busy_subscriber", "at_the_line",
        "fleet_sat", "fleet_sat_q0", "pub40"])
def test_the_rule_is_silent_elsewhere(traffic, shares, offered, p99):
    assert cell.require_schedule_kept("a.cell", traffic, shares, offered, p99) is None


# -------------------------------------------- a whole rehearsal of each cell
@pytest.fixture
def short_run(monkeypatch):
    monkeypatch.setattr(cell, "WARMUP_MIN_S", 2.0)
    monkeypatch.setattr(cell, "WARMUP_CAP_S", 6.0)
    monkeypatch.setattr(cell, "SETTLE_LIMIT_S", 5.0)


def run(monkeypatch, fault=None):
    """A rehearsal of ``CELL`` with its mix swapped for the open one."""
    load = cell.spec.load_cell
    monkeypatch.setattr(cell.spec, "load_cell", lambda name: dict(load(name), traffic=dict(BURST)))
    launcher = cell.brokermod.LAUNCHER
    if fault:
        monkeypatch.setenv("BENCHMARK_FAULT", fault)
        launcher = HERE / "faulty_broker.py"
    return cell.run_cell(CELL, 20261004, 3.0, False, time.perf_counter(),
                         cpu=True, launcher=launcher)


def open_loop_line(capfd) -> dict:
    lines = [json.loads(ln) for ln in capfd.readouterr().out.splitlines() if ln.startswith("{")]
    return next(ln for ln in lines if ln.get("phase") == "open_loop")


def test_a_sound_broker_is_correct_under_an_open_mix(short_run, monkeypatch, capfd):
    r = run(monkeypatch)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 1000
    assert r["device"]["platform"] == "cpu"  # a rehearsal names its platform
    line = open_loop_line(capfd)
    assert line["due"] >= line["sent"] > 0 and line["never_sent"] >= 0
    assert line["window_full_share_pct"] < 1 and line["sent_of_due_pct"] >= 99
    assert line["late_p99_ms"] is not None


def test_control_dropped_delivery_is_not_correct_under_an_open_mix(short_run, monkeypatch):
    r = run(monkeypatch, "drop")
    assert r["correct"] is False
    assert r["checks"]["missing_pairs"]["value"] > 0
    assert r["checks"]["unacked_qos1"]["value"] == 0  # every PUBACK still came
