"""The outbound QoS1 window refilled from the ack path (``broker/session.py``
``SessionState._refill``, ``broker/inflight.py`` ``OutInflight.claim`` /
``release``).

A read chunk's PUBACKs / PUBCOMPs that free credit on a session whose deliver
loop is parked on a full window, with work queued, are spent by the read task
itself before it yields; the loop's credit wait stays claimed meanwhile, so:

- deliveries leave in queue order, the window never holds more than
  ``max_inflight``, and the read task and the deliver loop never deliver at
  the same time, even where a hook suspends;
- the freed credit is spent in the loop iteration its acks were read in;
- ``deliver.ack_refills`` counts those deliveries, and the parked loop
  records no credit wait for a refill it did not serve;
- a hold is released by an ack-path pop; a takeover or a disconnect in the
  middle of a refill leaves every unacked entry in the window once;
- a QoS0 session never refills.
"""

import asyncio
import random
import sys
from pathlib import Path

import pytest

from rmqtt_tpu.broker.codec import MqttCodec, packets as pk
from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.fitter import FitterConfig
from rmqtt_tpu.broker.hooks import HookType
from rmqtt_tpu.broker.inflight import OutEntry, OutInflight
from rmqtt_tpu.broker.server import MqttBroker
from rmqtt_tpu.broker.session import SessionState
from rmqtt_tpu.broker.types import Message

from tests.mqtt_client import TestClient

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))
from harness import spec  # noqa: E402

WINDOW, QUEUED = 4, 40
_ENC = MqttCodec(pk.V311)


class _Iterations:
    """A ``call_soon`` marker: ``n`` is the loop iteration we are in."""

    def __init__(self) -> None:
        self.n = 0
        self._loop = asyncio.get_running_loop()
        self._loop.call_soon(self._tick)

    def _tick(self) -> None:
        self.n += 1
        self._loop.call_soon(self._tick)


async def _broker(max_inflight: int = WINDOW, max_mqueue: int = 1000):
    b = MqttBroker(ServerContext(BrokerConfig(
        port=0, router="trie",
        fitter=FitterConfig(max_inflight=max_inflight, max_mqueue=max_mqueue))))
    await b.start()
    return b


async def _queued(b, n: int = QUEUED, qos: int = 1, client_id: str = "rf-sub",
                  clean_start: bool = True, properties=None):
    """A subscriber that acks nothing yet, and ``n`` publishes acked by the
    broker: the first ``WINDOW`` on the wire, the rest in its deliver queue."""
    sub = await TestClient.connect(b.port, client_id, auto_ack=False,
                                   clean_start=clean_start, version=pk.V5,
                                   properties=properties)
    await sub.subscribe("rf/#", qos=qos)
    pub = await TestClient.connect(b.port, "rf-pub")
    for i in range(n):
        await pub.publish("rf/t", b"%d" % i, qos=1)
    return sub, pub


async def _take(sub: TestClient, k: int, timeout: float = 5.0) -> list:
    return [await asyncio.wait_for(sub.publishes.get(), timeout) for _ in range(k)]


def _ack_chunk(sub: TestClient, packets: list) -> None:
    """The PUBACKs of ``packets`` in one write: one read chunk."""
    sub.writer.write(b"".join(_ENC.encode(pk.Puback(p.packet_id)) for p in packets))


async def _drive(sub: TestClient, chunk: int, n: int = QUEUED) -> list:
    """Take the window, ack it ``chunk`` at a time, take what refills it;
    → every delivery, in arrival order."""
    got = await _take(sub, WINDOW)
    acked = 0
    while acked < n:
        batch = got[acked:acked + chunk]
        _ack_chunk(sub, batch)
        acked += len(batch)
        got += await _take(sub, min(len(batch), n - len(got)))
    return got


@pytest.fixture
def watch(monkeypatch):
    """Per client: the window's size after each push, the most deliveries in
    progress at once, and each ack and delivery with its loop iteration and
    task."""
    seen = {"window": [], "busy": 0, "most": 0, "events": [], "it": None}
    real_push, real_ack, real_deliver = OutInflight.push, OutInflight.ack, SessionState._deliver

    def push(self, entry):
        real_push(self, entry)
        seen["window"].append(len(self))

    def ack(self, packet_id):
        if seen["it"] is not None:
            seen["events"].append(("ack", seen["it"].n, asyncio.current_task()))
        return real_ack(self, packet_id)

    async def _deliver(self, item):
        if seen["it"] is not None:
            seen["events"].append(("deliver", seen["it"].n, asyncio.current_task()))
        seen["busy"] += 1
        seen["most"] = max(seen["most"], seen["busy"])
        try:
            await real_deliver(self, item)
        finally:
            seen["busy"] -= 1

    monkeypatch.setattr(OutInflight, "push", push)
    monkeypatch.setattr(OutInflight, "ack", ack)
    monkeypatch.setattr(SessionState, "_deliver", _deliver)
    return seen


# ------------------------------------------------- order, window, one turn
@pytest.mark.timeout(60)
@pytest.mark.parametrize("chunk", [1, 3, 4])
@pytest.mark.parametrize("hook", ["none", "expiry_check_suspends", "both_sleep_at_random"])
def test_refill_keeps_queue_order_and_the_window(watch, chunk, hook):
    async def main():
        b = await _broker()
        calls = []
        rng = random.Random(chunk)
        if hook != "none":
            async def suspends(_htype, args, _prev):
                calls.append(args[1].payload)
                await asyncio.sleep(0)  # the chain suspends: the loop turns
            b.ctx.hooks.register(HookType.MESSAGE_EXPIRY_CHECK, suspends)
        if hook == "both_sleep_at_random":
            async def sleeps(_htype, _args, _prev):
                await asyncio.sleep(rng.choice((0, 0, 0.0005, 0.002)))
            b.ctx.hooks.register(HookType.MESSAGE_DELIVERED, sleeps)
        try:
            sub, pub = await _queued(b)
            st = b.ctx.registry.get("rf-sub").state
            stage = b.ctx.telemetry.stage_stats
            waits0, refills0 = (stage()["stage_deliver_credit_wait_count"],
                                b.ctx.metrics.get("deliver.ack_refills"))
            watch["it"] = _Iterations()
            got = await _drive(sub, chunk)
            await asyncio.sleep(0.05)  # the loop wakes to the empty queue
            assert [int(p.payload) for p in got] == list(range(QUEUED))
            assert max(watch["window"]) == WINDOW
            assert watch["most"] == 1  # one sender at a time
            assert sub.publishes.empty() and not st.s.deliver_queue
            refills = b.ctx.metrics.get("deliver.ack_refills") - refills0
            events = watch["events"]
            from_read = [e for e in events if e[0] == "deliver"
                         and e[2].get_name().startswith("read:")]
            assert refills == len(from_read) > 0
            if hook == "none":
                # the loop filled the window before the first ack and never
                # sent again: every later delivery is the read task's, in the
                # iteration and the task step of the ack before it
                assert refills == QUEUED - WINDOW
                for i, e in enumerate(events):
                    if e in from_read:
                        last_ack = next(a for a in reversed(events[:i]) if a[0] == "ack")
                        assert (last_ack[1], last_ack[2]) == (e[1], e[2])
                # parked once; woken once, by the last chunk, to an empty queue
                assert stage()["stage_deliver_credit_wait_count"] - waits0 == 1
            else:
                assert sorted(int(x) for x in calls) == list(range(QUEUED))
            await sub.close()
            await pub.close()
        finally:
            await b.stop()

    asyncio.run(asyncio.wait_for(main(), 50))


# --------------------------------------------------------------- the holds
@pytest.mark.timeout(60)
def test_a_hold_is_released_by_an_ack_path_pop():
    """A queue of 4 behind a window of 2: the seventh publish is held (its
    PUBACK withheld) until the refill's pop brings the queue under its
    limit."""
    async def main():
        b = await _broker(max_inflight=2, max_mqueue=4)
        try:
            sub, pub = await _queued(b, n=6)
            held = b.ctx.metrics.get("fanout.held")
            await pub.publish("rf/t", b"6", qos=1, wait_ack=False)
            with pytest.raises(asyncio.TimeoutError):
                await pub._wait(("puback", pub._pid), timeout=0.3)
            assert b.ctx.metrics.get("fanout.held") == held + 1
            window = await _take(sub, 2)
            _ack_chunk(sub, window)
            await pub._wait(("puback", pub._pid), timeout=5.0)
            assert b.ctx.metrics.get("deliver.ack_refills") == 2
            got = window + await _take(sub, 2)
            assert [int(p.payload) for p in got] == [0, 1, 2, 3]
            await sub.close()
            await pub.close()
        finally:
            await b.stop()

    asyncio.run(asyncio.wait_for(main(), 50))


# --------------------------------------------- takeover, disconnect, drain
@pytest.mark.timeout(60)
@pytest.mark.parametrize("how", ["takeover", "disconnect"])
def test_a_refill_cut_short_leaves_every_unacked_entry_once(monkeypatch, how):
    """The refill stops inside a MESSAGE_DELIVERED hook (each of its
    deliveries is in the window and on the wire by then) while the session
    is taken over (the refill is cancelled there) or its socket closes (the
    refill goes on into the closed socket, then the read task sees the
    end): the drain that moves the window to the resumed session's queue
    holds each unacked delivery once, and the resumed session gets every
    message."""
    drained = []
    real_drain = OutInflight.drain

    def drain(self):
        entries = list(real_drain(self))
        drained.append([int(e.msg.payload) for e in entries])
        return iter(entries)

    monkeypatch.setattr(OutInflight, "drain", drain)

    async def main():
        b = await _broker()
        gate, stuck = asyncio.Event(), asyncio.Event()

        async def delivered(_htype, args, _prev):
            if (asyncio.current_task().get_name().startswith("read:")
                    and not gate.is_set()):
                stuck.set()
                await gate.wait()
        b.ctx.hooks.register(HookType.MESSAGE_DELIVERED, delivered)
        expiry = {0x11: 300}  # session expiry interval: the session lives on
        try:
            sub, pub = await _queued(b, n=12, clean_start=False, properties=expiry)
            window = await _take(sub, WINDOW)
            _ack_chunk(sub, window[:2])
            await asyncio.wait_for(stuck.wait(), 5.0)
            # the refill sent one frame and waits in its hook: the window
            # holds the two not acked and that one
            s = b.ctx.registry.get("rf-sub")
            unacked = sorted(int(e.msg.payload) for e in s.out_inflight.entries())
            assert unacked == [2, 3, 4]
            resume = lambda: TestClient.connect(  # noqa: E731
                b.port, "rf-sub", auto_ack=True, clean_start=False,
                version=pk.V5, properties=expiry)
            if how == "takeover":
                again = await resume()
                gate.set()
            else:
                sub.writer.close()
                gate.set()
                for _ in range(200):
                    if not s.connected:
                        break
                    await asyncio.sleep(0.01)
                assert not s.connected
                unacked = sorted(int(e.msg.payload) for e in s.out_inflight.entries())
                assert unacked == [2, 3, 4, 5]  # the refill filled the window
                again = await resume()
            assert drained == [unacked]
            got = set()
            while len(got) < 12 - 2:
                got.add(int((await asyncio.wait_for(again.publishes.get(), 5.0)).payload))
            assert got == set(range(2, 12))
            await again.close()
            await pub.close()
            await sub.close()
        finally:
            await b.stop()

    asyncio.run(asyncio.wait_for(main(), 50))


# --------------------------------------------------------------------- QoS0
@pytest.mark.timeout(60)
def test_a_qos0_session_never_refills(watch):
    """QoS0 deliveries hold no window: the loop never parks on credit, and
    PUBACKs the session sends (stray ones) claim nothing."""
    async def main():
        b = await _broker()
        try:
            sub, pub = await _queued(b, qos=0)
            got = await _take(sub, QUEUED)
            assert [int(p.payload) for p in got] == list(range(QUEUED))
            assert all(p.qos == 0 for p in got)
            for i in range(1, 9):
                sub.writer.write(_ENC.encode(pk.Puback(i)))
                await pub.publish("rf/t", b"x", qos=1)
            await _take(sub, 8)
            assert b.ctx.metrics.get("deliver.ack_refills") == 0
            assert watch["window"] == []
            st = b.ctx.registry.get("rf-sub").state
            assert st._claimed is None
            await sub.close()
            await pub.close()
        finally:
            await b.stop()

    asyncio.run(asyncio.wait_for(main(), 50))


# ------------------------------------------------- claim / release, alone
def _entry(pid: int) -> OutEntry:
    return OutEntry(pid, Message(topic="t", payload=b"", qos=1), 1)


@pytest.mark.parametrize("left", ["credit", "full", "cancelled"])
def test_a_claimed_wait_is_resolved_by_its_release_alone(left):
    async def main():
        fl = OutInflight(max_inflight=2)
        assert fl.claim() is None  # nobody parked
        fl.push(_entry(1))
        fl.push(_entry(2))
        loop = asyncio.create_task(fl.wait_credit())
        await asyncio.sleep(0)
        w = fl.claim()
        assert w is not None and fl.claim() is None
        fl.ack(1)  # a freed slot: the claimed wait sleeps on
        await asyncio.sleep(0)
        assert not loop.done()
        if left == "full":
            fl.push(_entry(3))
        elif left == "cancelled":
            loop.cancel()
            await asyncio.sleep(0)
        fl.release(w)
        await asyncio.sleep(0)
        if left == "credit":
            assert loop.done() and not loop.cancelled()
        elif left == "full":
            assert not loop.done() and fl.claim() is w  # parked again
            fl.release(w)
            fl.ack(2)  # an unclaimed wait: the freed slot wakes it
            await asyncio.sleep(0)
            assert loop.done()
        else:
            assert loop.cancelled() and fl.claim() is None

    asyncio.run(main())


# ------------------------------------------------------------- the reader
def _run(metrics0, metrics1, sends0, sends1):
    def snap(t, m, sends):
        return {"t": t, "metrics": m, "stats": {"stage_deliver_send_count": sends}}
    return {"before": snap(100.0, metrics0, sends0),
            "after": snap(151.0, metrics1, sends1), "trace": None}


@pytest.mark.parametrize("refills,sends,want", [
    ((1_000, 4_000), (10_000, 40_000), 10.0),  # 3,000 of 30,000 deliveries
    ((1_000, 1_000), (10_000, 40_000), 0.0),   # deliveries, none refilled
    ((1_000, 1_000), (10_000, 10_000), None),  # nothing delivered
    ((None, None), (10_000, 40_000), None),    # a program without the counter
])
def test_the_refill_share_reader(refills, sends, want):
    read = spec.load_reader("deliver.ack_refill_share_pct").read
    m0, m1 = ({} if r is None else {"deliver.ack_refills": r} for r in refills)
    got = read(_run(m0, m1, *sends))
    assert got == (None if want is None else pytest.approx(want))


def test_the_refill_share_is_held_to_its_per_layer_entry():
    reader = spec.load_reader("deliver.ack_refill_share_pct")
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "deliver.ack_refill_share_pct")
    assert {k: entry[k] for k in reader.SPEC} == reader.SPEC
    assert "workloads" not in entry  # as inflight.credit_waits_per_s
    waits = next(m for m in bench["per_layer"]
                 if m["name"] == "inflight.credit_waits_per_s")
    assert entry["layer"] == waits["layer"]
