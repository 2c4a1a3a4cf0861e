"""The collector's policy (``rmqtt_tpu/broker/gcpolicy.py``): a full pass that
was long is the last one over that heap; the heap is thawed on a wall-clock
budget.

- the rule on a scripted clock, a scripted collector and scripted callback
  infos: which pass is followed by a freeze, which by nothing, and when a
  thaw is due;
- the rule on a real heap, each case in a process of its own so that the
  pytest process stays thawed: what a freeze takes off the next pass (by
  counts), that a frozen cycle is reclaimed by the next due thaw and never
  before, that the last disarm leaves the process as it was found;
- an in-process broker under 20,000 subscriptions: the counters on
  ``/api/v1/stats`` and ``/api/v1/host``, and deliveries to exactly the
  oracle's client sets once its heap is frozen.
"""

import asyncio
import gc
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from rmqtt_tpu.broker import gcpolicy
from rmqtt_tpu.broker.gcpolicy import LONG_PASS_S, THAW_BUDGET, GcPolicy

ROOT = Path(__file__).resolve().parent.parent


# ------------------------------------------------------------ scripted parts
class Clock:
    def __init__(self) -> None:
        self.t = 1000.0

    def __call__(self) -> float:
        return self.t


class Collector:
    """What the policy uses of the ``gc`` module, recorded."""

    def __init__(self) -> None:
        self.callbacks = []
        self.calls = []
        self.frozen = 0

    def freeze(self) -> None:
        self.calls.append("freeze")
        self.frozen = 123_456

    def unfreeze(self) -> None:
        self.calls.append("unfreeze")
        self.frozen = 0

    def get_freeze_count(self) -> int:
        self.calls.append("count")
        return self.frozen


@pytest.fixture
def rig():
    clock = Clock()
    coll = Collector()
    pol = GcPolicy(clock=clock, collector=coll)
    pol.arm()
    yield pol, clock, coll
    while pol._arms:
        pol.disarm()


def a_pass(pol: GcPolicy, clock: Clock, generation: int, took: float) -> None:
    pol._on_gc("start", {"generation": generation})
    clock.t += took
    pol._on_gc("stop", {"generation": generation, "collected": 0,
                        "uncollectable": 0})


@pytest.mark.parametrize("generation", [0, 1])
def test_a_young_pass_does_nothing_and_counts_nothing(rig, generation):
    pol, clock, coll = rig
    a_pass(pol, clock, generation, 5.0)  # however long
    assert coll.calls == []
    assert pol.full_pauses == 0 and pol.full_pause_s == 0.0


def test_a_short_full_pass_is_counted_and_nothing_else(rig):
    pol, clock, coll = rig
    a_pass(pol, clock, 2, LONG_PASS_S / 2)
    assert coll.calls == []
    assert pol.full_pauses == 1
    assert pol.full_pause_s == pytest.approx(LONG_PASS_S / 2)
    assert pol.snapshot()["next_thaw_in_s"] is None


def test_a_long_full_pass_is_the_last_one_over_that_heap(rig):
    pol, clock, coll = rig
    a_pass(pol, clock, 2, 0.5)
    assert coll.calls == ["freeze", "count"]  # at once, where the pass ended
    assert pol.freezes == 1 and pol.thaws == 0
    assert pol.frozen_objects == 123_456
    assert pol.stats_block() == {
        "host_gc_freezes": 1, "host_gc_thaws": 0,
        "host_gc_frozen_objects": 123_456, "host_gc_full_pauses": 1,
        "host_gc_full_pause_ms_total": 500.0}
    snap = pol.snapshot()
    assert snap["next_thaw_in_s"] == pytest.approx(0.5 / THAW_BUDGET)
    assert snap["long_pass_ms"] == LONG_PASS_S * 1e3
    assert snap["thaw_budget"] == THAW_BUDGET


def test_the_count_is_paid_from_the_same_budget(rig):
    pol, clock, coll = rig
    real = coll.get_freeze_count

    def slow_count():
        clock.t += 0.1  # a count that takes a fifth of the walk
        return real()
    coll.get_freeze_count = slow_count
    a_pass(pol, clock, 2, 0.5)
    assert pol.full_pause_s == pytest.approx(0.6)
    assert pol.snapshot()["next_thaw_in_s"] == pytest.approx(0.6 / THAW_BUDGET)


def test_a_thaw_is_due_only_after_d_over_B(rig):
    pol, clock, coll = rig
    a_pass(pol, clock, 2, 0.5)
    due = clock.t + 0.5 / THAW_BUDGET  # 100 s on
    # short passes before the time: nothing
    clock.t = due - 10.0
    a_pass(pol, clock, 2, 0.001)
    assert coll.calls == ["freeze", "count"]
    # a long pass over the part that is not frozen: frozen too, not counted,
    # and the budget stays where the walk of the whole heap set it
    a_pass(pol, clock, 2, 0.1)
    assert coll.calls == ["freeze", "count", "freeze"]
    assert pol.freezes == 2 and pol._due == pytest.approx(due)
    clock.t = due - 0.5
    a_pass(pol, clock, 2, 0.001)
    assert pol.thaws == 0
    # the first full pass that ends past the time thaws: the heap is
    # unfrozen, and the collector's next full pass walks all of it
    clock.t = due
    a_pass(pol, clock, 2, 0.001)
    assert coll.calls[3:] == ["unfreeze"] and pol.thaws == 1
    assert pol.snapshot()["next_thaw_in_s"] is None
    a_pass(pol, clock, 2, 0.8)  # that walk: long, so frozen, and counted
    assert coll.calls[4:] == ["freeze", "count"] and pol.freezes == 3
    assert pol.full_pauses == 6
    # and the next thaw is 0.8 / B on
    assert pol._due == pytest.approx(clock.t + 0.8 / THAW_BUDGET)
    clock.t += 0.8 / THAW_BUDGET - 1.0
    a_pass(pol, clock, 2, 0.001)
    assert pol.thaws == 1
    clock.t += 1.0
    a_pass(pol, clock, 2, 0.5)  # due beats long: thaw first, freeze next
    assert coll.calls[6:] == ["unfreeze"] and pol.thaws == 2


def test_a_heap_that_shrank_is_left_thawed_until_a_pass_is_long_again(rig):
    pol, clock, coll = rig
    a_pass(pol, clock, 2, 0.5)
    clock.t += 0.5 / THAW_BUDGET
    a_pass(pol, clock, 2, 0.001)  # the thaw
    a_pass(pol, clock, 2, 0.004)  # the whole heap, and it is short now
    assert coll.calls == ["freeze", "count", "unfreeze"]
    clock.t += 1e6
    a_pass(pol, clock, 2, 0.004)  # nothing is frozen: nothing to thaw
    assert pol.thaws == 1 and coll.calls == ["freeze", "count", "unfreeze"]
    a_pass(pol, clock, 2, 0.3)
    assert coll.calls[3:] == ["freeze", "count"]
    assert pol.snapshot()["next_thaw_in_s"] == pytest.approx(0.3 / THAW_BUDGET)


def test_a_pass_that_ends_while_a_broker_arms_or_stops_is_only_counted(rig):
    pol, clock, coll = rig
    with pol._lock:  # arm / disarm under way on another thread, or on this
        a_pass(pol, clock, 2, 0.5)  # one: a collection inside arm itself
    assert coll.calls == [] and pol.full_pauses == 1
    a_pass(pol, clock, 2, 0.5)
    assert coll.calls == ["freeze", "count"]


def test_arm_twice_disarm_twice_on_the_scripted_collector(rig):
    pol, clock, coll = rig
    pol.arm()
    assert coll.callbacks == [pol._on_gc]  # one entry, however many brokers
    a_pass(pol, clock, 2, 0.5)
    pol.disarm()
    assert coll.callbacks == [pol._on_gc]
    assert coll.calls == ["freeze", "count"]
    pol.disarm()
    assert coll.callbacks == []
    assert coll.calls == ["freeze", "count", "unfreeze"]
    assert pol.snapshot()["next_thaw_in_s"] is None
    pol.disarm()  # one too many is nobody's fault
    assert coll.calls == ["freeze", "count", "unfreeze"]
    # a pass whose callback was fetched before the last disarm does nothing
    a_pass(pol, clock, 2, 0.5)
    assert coll.calls == ["freeze", "count", "unfreeze"]


# --------------------------------------------- a real heap, a process each
PRELUDE = """
import gc, json, weakref
from rmqtt_tpu.broker.gcpolicy import GcPolicy, THAW_BUDGET

class Clock:
    # every reading is 50 ms after the last: whatever a pass really takes on
    # this machine, the policy sees a long one
    def __init__(self): self.t = 0.0
    def __call__(self):
        self.t += 0.05
        return self.t

class Row:
    __slots__ = ("cells", "me", "__weakref__")
    def __init__(self, i):
        self.cells = [i]
        self.me = self      # a cycle: only the collector frees a dropped Row

N = 150_000                 # 300,000 containers: a Row and its list each
# the interpreter keeps a few hundred immortal tuples of its static types in
# the permanent generation (every collection moves them there again): what
# "nothing frozen" reads here
out = {"found": gc.get_freeze_count()}
"""


def _in_a_process_of_its_own(body: str) -> dict:
    code = PRELUDE + textwrap.dedent(body) + "\nprint(json.dumps(out))\n"
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True,
                       capture_output=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_a_freeze_takes_the_heap_off_the_next_pass():
    out = _in_a_process_of_its_own("""
        pol = GcPolicy(clock=Clock())
        heap = [Row(i) for i in range(N)]
        pol.arm()
        out["tracked_before"] = len(gc.get_objects())
        gc.collect()                      # a forced full pass, "long"
        out["frozen"] = gc.get_freeze_count()
        out["counted"] = pol.frozen_objects
        out["freezes"] = pol.freezes
        out["tracked_after"] = len(gc.get_objects())
        new = [Row(i) for i in range(1000)]
        del new
        out["collected_new"] = gc.collect()   # walks what is new
        out["tracked_after_new"] = len(gc.get_objects())
        more = [Row(i) for i in range(N // 3)]    # the table grows: the
        gc.collect()                              # collector's own passes
        out["frozen_grown"] = gc.get_freeze_count()   # and this one freeze it
        out["tracked_grown"] = len(gc.get_objects())
        out["heap_alive"] = len(heap) + len(more)
        pol.disarm()
        out["frozen_after_disarm"] = gc.get_freeze_count()
    """)
    assert out["found"] < 1000 and out["freezes"] >= 1
    assert out["frozen"] >= 2 * 150_000 and out["counted"] >= 2 * 150_000
    # the next pass has only what was allocated since to walk
    assert out["tracked_before"] >= 2 * 150_000
    assert out["tracked_after"] < 10_000
    assert out["collected_new"] >= 2 * 1000  # and still frees new garbage
    assert out["tracked_after_new"] < 10_000
    assert out["frozen_grown"] >= 2 * 200_000 and out["tracked_grown"] < 10_000
    assert out["heap_alive"] == 200_000
    assert out["frozen_after_disarm"] == 0


def test_a_frozen_cycle_is_reclaimed_by_the_next_due_thaw_and_never_before():
    out = _in_a_process_of_its_own("""
        clock = Clock()
        pol = GcPolicy(clock=clock)
        heap = [Row(i) for i in range(N)]
        doomed = Row(-1)
        died = []
        weakref.finalize(doomed, died.append, "finalized")
        pol.arm()
        gc.collect()
        out["frozen"] = gc.get_freeze_count()
        due = pol._due
        out["due_in"] = due - clock.t
        del doomed                        # a frozen cycle, now garbage
        for _ in range(3):                # full passes before the time
            gc.collect()
        out["died_before_due"] = list(died)
        out["thaws_before_due"] = pol.thaws
        clock.t = due                     # the time comes
        gc.collect()                      # the pass that ends past it thaws
        out["thaws"] = pol.thaws
        out["frozen_thawed"] = gc.get_freeze_count()
        out["died_at_the_thaw"] = list(died)
        gc.collect()                      # the next walks the whole heap
        out["died_after_the_walk"] = list(died)
        out["frozen_again"] = gc.get_freeze_count()
        out["next_due_in"] = pol._due - clock.t
        out["heap_alive"] = len(heap)
        pol.disarm()
    """)
    assert out["frozen"] >= 2 * 150_000
    # the pass before the freeze "took" 50 ms, one reading of the clock, and
    # so did the count of what it froze
    assert out["due_in"] == pytest.approx(0.10 / THAW_BUDGET, rel=0.05)
    assert out["died_before_due"] == [] and out["thaws_before_due"] == 0
    assert out["thaws"] == 1 and out["frozen_thawed"] == 0
    assert out["died_at_the_thaw"] == []
    assert out["died_after_the_walk"] == ["finalized"]
    assert out["frozen_again"] >= 2 * 150_000
    assert out["next_due_in"] == pytest.approx(0.10 / THAW_BUDGET, rel=0.05)
    assert out["heap_alive"] == 150_000


def test_arm_twice_disarm_twice_leaves_the_process_as_it_was_found():
    out = _in_a_process_of_its_own("""
        before = list(gc.callbacks)
        pol = GcPolicy(clock=Clock())
        heap = [Row(i) for i in range(N)]
        pol.arm()
        pol.arm()
        out["entries_armed"] = len(gc.callbacks) - len(before)
        gc.collect()
        out["frozen"] = gc.get_freeze_count()
        pol.disarm()
        out["frozen_after_first_disarm"] = gc.get_freeze_count()
        out["entries_after_first_disarm"] = len(gc.callbacks) - len(before)
        pol.disarm()
        out["frozen_after_last_disarm"] = gc.get_freeze_count()
        out["callbacks_as_found"] = list(gc.callbacks) == before
        gc.collect()                      # and a pass freezes nothing now
        out["frozen_after_a_pass"] = gc.get_freeze_count()
    """)
    assert out["found"] < 1000 and out["entries_armed"] == 1
    assert out["frozen"] >= 2 * 150_000
    assert out["frozen_after_first_disarm"] >= 2 * 150_000
    assert out["entries_after_first_disarm"] == 1
    assert out["frozen_after_last_disarm"] == 0
    assert out["callbacks_as_found"] is True
    # (a collection moves the interpreter's immortal tuples back itself)
    assert out["frozen_after_a_pass"] == out["found"]


def test_full_passes_that_end_on_many_threads_keep_the_policy_whole():
    # a collection ends on whichever thread allocated: more threads than cores
    # force full passes under a short switch interval while brokers come and
    # go; whatever interleaves, the heap lives, nothing raises, the policy
    # still answers, and the last disarm leaves nothing frozen
    out = _in_a_process_of_its_own("""
        import os, sys, threading, time
        pol = GcPolicy(clock=Clock())
        pol.arm()
        heap = [Row(i) for i in range(20_000)]
        stop = time.monotonic() + 2.0
        errors = []
        def worker():
            try:
                while time.monotonic() < stop:
                    junk = [Row(i) for i in range(200)]
                    del junk
                    gc.collect()
            except Exception as e:
                errors.append(repr(e))
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=worker)
                       for _ in range(2 * (os.cpu_count() or 4))]
            for t in threads:
                t.start()
            while time.monotonic() < stop:    # a second broker comes and goes
                pol.arm()
                time.sleep(0.001)
                pol.disarm()
            for t in threads:
                t.join(timeout=30)
            out["alive"] = sum(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(old)
        out["errors"] = errors
        out["arms"] = pol._arms
        out["freezes"], out["full_pauses"] = pol.freezes, pol.full_pauses
        out["entries"] = gc.callbacks.count(pol._on_gc)
        before = pol.freezes + pol.thaws
        gc.collect()                          # and the policy still answers
        out["answers"] = pol.freezes + pol.thaws - before
        out["heap_alive"] = sum(r.me is r for r in heap)
        pol.disarm()
        out["frozen_after_disarm"] = gc.get_freeze_count()
        out["entries_after_disarm"] = gc.callbacks.count(pol._on_gc)
    """)
    assert out["alive"] == 0 and out["errors"] == []
    assert out["arms"] == 1 and out["entries"] == 1
    assert out["full_pauses"] >= out["freezes"] >= 2
    assert out["answers"] == 1
    assert out["heap_alive"] == 20_000
    assert out["frozen_after_disarm"] == 0 and out["entries_after_disarm"] == 0


# ------------------------------------------------------- through the broker
@pytest.fixture
def thawed():
    """The pytest process is left as it was found, whatever the case did."""
    callbacks, found = list(gc.callbacks), gc.get_freeze_count()
    yield
    while gcpolicy.GCPOLICY._arms:
        gcpolicy.GCPOLICY.disarm()
    # (the interpreter's own few hundred immortal tuples may have been thawed
    # with the rest, until its next collection: nothing of the broker's stays)
    assert gc.get_freeze_count() <= found < 1000
    assert list(gc.callbacks) == callbacks


def test_a_loaded_broker_freezes_its_heap_and_still_delivers_to_the_oracle(
        thawed, monkeypatch):
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.broker.http_api import HttpApi
    from rmqtt_tpu.broker.server import MqttBroker
    from rmqtt_tpu.core.trie import TopicTree
    from tests.mqtt_client import TestClient
    from tests.test_http_plugins import http_get

    # whatever a full pass over this process's heap takes on this machine, it
    # counts as long: the trigger is the test's, the rest is the broker's
    monkeypatch.setattr(gcpolicy, "LONG_PASS_S", 0.0)
    n_clients, per_client = 20, 1000

    def filters_of(c: int) -> list:
        out = []
        for i in range(per_client):
            k = c * per_client + i
            if i == 2:
                out.append(f"site/{c % 5}/#")  # shared with three others
            elif i % 10 == 0:
                out.append(f"site/+/dev{k}/temp")
            elif i % 10 == 1:
                out.append(f"site/{k % 50}/dev{k}/#")
            else:
                out.append(f"site/{k % 50}/dev{k}/temp")
        return out

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        api = HttpApi(b.ctx, port=0)
        await b.start()
        await api.start()
        clients = []
        try:
            base = dict(gcpolicy.GCPOLICY.stats_block())
            oracle: TopicTree = TopicTree()
            for c in range(n_clients):
                cl = await TestClient.connect(b.port, f"sub{c}")
                clients.append(cl)
                fs = filters_of(c)
                await cl.subscribe(*fs, qos=0)
                for f in fs:
                    oracle.insert(f, c)
            assert b.ctx.router.routes_count() == n_clients * per_client
            gc.collect()            # a forced full pass over the loaded heap
            st, body = await http_get(api.bound_port, "/api/v1/stats")
            stats = json.loads(body)[0]["stats"]
            assert stats["host_gc_freezes"] >= base["host_gc_freezes"] + 1
            assert stats["host_gc_frozen_objects"] > n_clients * per_client
            assert stats["host_gc_full_pauses"] > base["host_gc_full_pauses"]
            assert (stats["host_gc_full_pause_ms_total"]
                    > base["host_gc_full_pause_ms_total"])
            assert stats["host_gc_thaws"] == base["host_gc_thaws"]
            assert gc.get_freeze_count() > n_clients * per_client
            st, body = await http_get(api.bound_port, "/api/v1/host")
            policy = json.loads(body)["gc"]["policy"]
            assert policy["freezes"] == stats["host_gc_freezes"]
            assert policy["frozen_objects"] == stats["host_gc_frozen_objects"]
            assert policy["next_thaw_in_s"] is not None
            assert policy["thaw_budget"] == THAW_BUDGET

            # a sample of topics, each to exactly the oracle's clients
            pub = await TestClient.connect(b.port, "pub")
            clients.append(pub)
            topics = [f"site/{k % 50}/dev{k}/temp"
                      for k in range(0, n_clients * per_client, 997)]
            topics += ["site/3/other/temp", "site/4/dev4/x/y", "nobody/home"]
            want = {c: [] for c in range(n_clients)}
            for i, t in enumerate(topics):
                await pub.publish(t, str(i).encode(), qos=1)
                hit = {c for _f, cs in oracle.matches(t) for c in cs}
                for c in hit:
                    want[c].append(i)
            assert sum(len(v) for v in want.values()) > len(topics)
            for c in range(n_clients):
                got = set()  # a second copy for a second filter is the same
                while got != set(want[c]):
                    p = await asyncio.wait_for(clients[c].publishes.get(), 10)
                    got.add(int(p.payload))
                    assert got <= set(want[c]), (c, got, want[c])
            await asyncio.sleep(0.2)
            for c in range(n_clients):  # and nothing beyond
                extra = []
                while not clients[c].publishes.empty():
                    extra.append(int(clients[c].publishes.get_nowait().payload))
                assert set(extra) <= set(want[c]), (c, extra)
        finally:
            for cl in clients:
                try:
                    await cl.close()
                except Exception:
                    pass
            await api.stop()
            await b.stop()
        # the last stop left the process unfrozen
        assert gc.get_freeze_count() == 0

    asyncio.run(run())
