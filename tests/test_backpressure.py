"""Backpressure from the deliver queues to the publisher's PUBACK
(``broker/session.py`` ``Session._enqueue_crowded``, ``broker/queue.py``).

- a saturating QoS1 fleet at fan-out > 8 against small queues and windows:
  every PUBACKed publish reaches every subscriber the plain reference
  (``benchmark/harness/trie.py``) names, and nothing is dropped;
- a client subscribed to what it publishes, and two clients that feed each
  other, keep making progress with full queues (the ack is held, never the
  read loop);
- a subscriber that stops acking holds its publishers for the outbound
  window's retry interval and no longer: then upstream's drop policy, counted
  as ``queue_full``;
- QoS0 deliveries and offline sessions keep ``DROP_CURRENT`` / ``DROP_EARLY``.
"""

import asyncio
import random
import sys
import time
from pathlib import Path

import pytest

from rmqtt_tpu.broker.codec import packets as pk
from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.fitter import FitterConfig
from rmqtt_tpu.broker.server import MqttBroker

from tests.mqtt_client import TestClient

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "benchmark"))
from harness.generators import VOCAB6, MixedTree  # noqa: E402
from harness.trie import Trie  # noqa: E402


async def _broker(max_mqueue: int, max_inflight: int, router: str = "xla"):
    b = MqttBroker(ServerContext(BrokerConfig(
        port=0, router=router,
        fitter=FitterConfig(max_mqueue=max_mqueue, max_inflight=max_inflight))))
    await b.start()
    return b


async def _publish_acked(c: TestClient, topic: str, payload: bytes,
                         timeout: float = 30.0) -> float:
    """One QoS1 publish; → seconds until its PUBACK."""
    t0 = time.perf_counter()
    await c.publish(topic, payload, qos=1, wait_ack=False)
    await c._wait(("puback", c._pid), timeout=timeout)
    return time.perf_counter() - t0


async def _slow_acker(c: TestClient, got: set, key, delay: float) -> None:
    """A QoS1 consumer that PUBACKs each delivery ``delay`` after it came."""
    while True:
        p = await c.publishes.get()
        got.add((key, int(p.payload)))
        await asyncio.sleep(delay)
        await c._send(pk.Puback(p.packet_id))


def _dropped(b, reason: str = "queue_full") -> int:
    return b.ctx.metrics.get("messages.dropped." + reason)


# ----------------------------------------------- (a) a fleet at fan-out > 8
SUBS, PUBS, EACH = 12, 16, 25


async def _fleet(seed: int):
    rng = random.Random(seed)
    table = MixedTree(seed, {"subscriptions": 2400}).filters()
    owned = [[f for i, f in enumerate(table) if i % SUBS == k]
             # every consumer also holds four catch-all subtrees, so a topic
             # under one of them reaches all twelve (fan-out >= 12)
             + [f"v0_{j}/#" for j in range(4)] for k in range(SUBS)]
    ref = Trie()
    for k, filters in enumerate(owned):
        for f in filters:
            ref.insert(f, k)
    b = await _broker(max_mqueue=16, max_inflight=2)
    tasks, clients = [], []
    try:
        got: set = set()
        for k, filters in enumerate(owned):
            c = await TestClient.connect(b.port, f"bp-sub{k}", auto_ack=False)
            clients.append(c)
            await c.subscribe(*filters, qos=1)
            tasks.append(asyncio.create_task(_slow_acker(c, got, k, 0.001)))
        topics = ["/".join([f"v0_{rng.randrange(4)}"] + [
            f"v{d}_{rng.randrange(VOCAB6[d])}" for d in range(1, 6)])
            for _ in range(PUBS * EACH)]

        async def publisher(k: int) -> None:
            c = await TestClient.connect(b.port, f"bp-pub{k}")
            clients.append(c)
            for i in range(k, len(topics), PUBS):  # closed loop, one in flight
                await _publish_acked(c, topics[i], b"%d" % i)

        await asyncio.gather(*(publisher(k) for k in range(PUBS)))
        want = {(k, i) for i, t in enumerate(topics) for k in ref.match(t)}
        for _ in range(600):  # every PUBACK is in: the deliveries follow
            if len(got) >= len(want):
                break
            await asyncio.sleep(0.05)
        return b.ctx.metrics.to_json(), want, got
    finally:
        for t in tasks:
            t.cancel()
        for c in clients:
            await c.close()
        await b.stop()


@pytest.mark.timeout(120)
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_every_acked_publish_reaches_every_matching_subscriber(seed):
    m, want, got = asyncio.run(asyncio.wait_for(_fleet(seed), 100))
    assert len(want) >= 8 * PUBS * EACH  # fan-out above eight
    assert want - got == set() and got - want == set()
    assert m.get("messages.dropped.queue_full", 0) == 0
    assert m.get("messages.dropped", 0) == 0
    # the mechanism did the work: queues filled and PUBACKs were held
    assert m.get("fanout.held", 0) > 0
    assert 0 < m["deliver.queue_over_half"] <= m["fanout.enqueues"]


def test_acked_fanout_through_the_native_egress_thread(monkeypatch):
    """The same fleet with every eligible flush written by the native egress
    thread (``_MIN_JOB`` 1): held PUBACKs, deliveries and the consumers'
    windows cross the hand-off, and still no acked publish is lost."""
    from rmqtt_tpu.broker import egress

    if not egress.EgressHub().native:
        pytest.skip("native runtime (egress.cc) unavailable")
    monkeypatch.setattr(egress, "_MIN_JOB", 1)
    m, want, got = asyncio.run(asyncio.wait_for(_fleet(14), 100))
    assert want - got == set() and got - want == set()
    assert m.get("messages.dropped", 0) == 0
    assert m.get("fanout.held", 0) > 0
    assert m["net.egress_offloop_flushes"] > 0.9 * m["net.egress_flushes"]


# ------------------------------------------------ (b) progress, no deadlock
async def _feeders(pairs, n: int):
    """``pairs``: [(client id, the topic it subscribes to, the topic it
    publishes to)]. Every client keeps two QoS1 publishes in flight, never
    more, and acks what it receives slowly; → (metrics, received counts)."""
    b = await _broker(max_mqueue=4, max_inflight=2)
    tasks, clients = [], []
    try:
        got: set = set()
        for cid, sub_topic, _pub_topic in pairs:
            c = await TestClient.connect(b.port, cid, auto_ack=False)
            clients.append(c)
            await c.subscribe(sub_topic, qos=1)
            tasks.append(asyncio.create_task(_slow_acker(c, got, cid, 0.002)))

        async def feed(c: TestClient, topic: str) -> None:
            gate = asyncio.Semaphore(2)

            async def one(pid: int) -> None:
                try:
                    await c._wait(("puback", pid), timeout=30.0)
                finally:
                    gate.release()

            waits = []
            for i in range(n):
                await gate.acquire()
                # the waiter is registered before the PUBLISH leaves
                waits.append(asyncio.create_task(one(c._pid % 65535 + 1)))
                await asyncio.sleep(0)
                await c.publish(topic, b"%d" % i, qos=1, wait_ack=False)
            await asyncio.gather(*waits)

        await asyncio.gather(*(feed(c, p[2]) for c, p in zip(clients, pairs)))
        for _ in range(400):
            if len(got) >= n * len(pairs):
                break
            await asyncio.sleep(0.05)
        return b.ctx.metrics.to_json(), got
    finally:
        for t in tasks:
            t.cancel()
        for c in clients:
            await c.close()
        await b.stop()


@pytest.mark.timeout(60)
@pytest.mark.parametrize("shape", ["self", "pair"])
def test_full_queues_never_deadlock(shape):
    pairs = ([("bp-self", "loop/me", "loop/me")] if shape == "self" else
             [("bp-a", "loop/to-a", "loop/to-b"), ("bp-b", "loop/to-b", "loop/to-a")])
    n = 120
    m, got = asyncio.run(asyncio.wait_for(_feeders(pairs, n), 50))
    assert got == {(cid, i) for cid, _s, _p in pairs for i in range(n)}
    assert m.get("messages.dropped", 0) == 0
    assert m.get("fanout.held", 0) > 0  # the queues did fill


# ------------------------------------- (c) a consumer that stops acking
async def _dead_consumer():
    b = await _broker(max_mqueue=4, max_inflight=2)
    try:
        sub = await TestClient.connect(b.port, "bp-dead", auto_ack=False)
        await sub.subscribe("dead/#", qos=1)
        b.ctx.registry.get("bp-dead").out_inflight.retry_interval = 0.4
        pub = await TestClient.connect(b.port, "bp-live")
        # two fill the window, four the queue: none of them waits
        quick = [await _publish_acked(pub, "dead/t", b"%d" % i) for i in range(6)]
        held = await _publish_acked(pub, "dead/t", b"6")
        dropped_then = _dropped(b)
        after = [await _publish_acked(pub, "dead/t", b"%d" % i) for i in range(7, 12)]
        tele = b.ctx.telemetry
        tele.flush()
        out = (quick, held, after, dropped_then, _dropped(b),
               b.ctx.metrics.get("fanout.held"), tele.hist("fanout.hold").count,
               len(b.ctx.registry.get("bp-dead").deliver_queue))
        await sub.close()
        await pub.close()
        return out
    finally:
        await b.stop()


@pytest.mark.timeout(60)
def test_dead_consumer_holds_for_the_retry_interval_then_drops():
    quick, held, after, dropped_then, dropped, n_held, n_hist, qlen = asyncio.run(
        asyncio.wait_for(_dead_consumer(), 50))
    assert max(quick) < 0.3
    # held one retry interval (the timer was armed with the hold), not two
    assert 0.35 <= held < 0.8
    # the overfull queue was cut back oldest first, under upstream's reason
    assert dropped_then == 1
    # and until the consumer takes something, a full queue drops as before
    assert max(after) < 0.3 and dropped == 1 + len(after)
    assert n_held == 1 and n_hist == 1 and qlen == 4


# ------------------------- (d) QoS0 and offline sessions keep their policies
async def _policy(kind: str):
    b = await _broker(max_mqueue=4, max_inflight=2)
    try:
        pub = await TestClient.connect(b.port, "bp-pol-pub")
        if kind == "qos0":
            # a QoS1 consumer that acks nothing: window and queue fill; then
            # QoS0 publishes arrive for the same (connected) session
            sub = await TestClient.connect(b.port, "bp-pol", auto_ack=False)
            await sub.subscribe("pol/#", qos=1)
            for i in range(6):
                await _publish_acked(pub, "pol/t", b"%d" % i)
            for i in range(6, 9):
                await pub.publish("pol/t", b"%d" % i, qos=0)
            await pub.ping()  # the QoS0 publishes are through the broker
        else:
            sub = await TestClient.connect(b.port, "bp-pol", clean_start=False)
            await sub.subscribe("pol/#", qos=1)
            await sub.close()
            for _ in range(100):
                if not b.ctx.registry.get("bp-pol").connected:
                    break
                await asyncio.sleep(0.01)
            waits = [await _publish_acked(pub, "pol/t", b"%d" % i) for i in range(7)]
            assert max(waits) < 0.3  # nothing holds the ack of an offline target
        s = b.ctx.registry.get("bp-pol")
        queued = [int(it.msg.payload) for it in s.deliver_queue._q]
        out = queued, b.ctx.metrics.to_json()
        await pub.close()
        return out
    finally:
        await b.stop()


@pytest.mark.timeout(60)
@pytest.mark.parametrize("kind,queued", [
    ("qos0", [2, 3, 4, 5]),     # DROP_CURRENT: the newcomers were dropped
    ("offline", [3, 4, 5, 6]),  # DROP_EARLY: the oldest were
])
def test_qos0_and_offline_sessions_keep_the_drop_policies(kind, queued):
    got, m = asyncio.run(asyncio.wait_for(_policy(kind), 50))
    assert got == queued
    assert m["messages.dropped.queue_full"] == 3
    assert m.get("fanout.held", 0) == 0
