"""Runs of pipelined publishes (``broker/session.py`` ``_publish_run``).

The consecutive PUBLISH packets a connection has sent by the time its read
chunk is served enter the routing service together. What must hold:

- PUBACK / PUBREC leave in the order the PUBLISHes came ([MQTT-4.6.0-2]),
  and where nothing is held EVERY answer of the connection leaves in the
  order of its packets: nothing overtakes a run, a run overtakes nothing;
- two publishes of one connection to one topic reach each subscriber in
  that order;
- ``publish.received`` / ``fanout.enqueues`` / ``ingress.runs`` /
  ``ingress.run_publishes`` count what a plain sequential oracle counts;
- a chunk with one publish takes the lone path: no run, no new task;
- a full deliver queue's hold and the durability barrier gate the acks of
  the right publishes inside a run.

The property test writes a seeded mix of QoS0/1/2 PUBLISH, SUBSCRIBE,
UNSUBSCRIBE, PUBREL and PINGREQ for one connection in ONE TCP write and
compares with an oracle that serves the same packets one by one on
``core/trie.py``.
"""

import asyncio
import random
import socket

import pytest

from rmqtt_tpu.broker.codec import MqttCodec, packets as pk
from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.fitter import FitterConfig
from rmqtt_tpu.broker.routing import RoutingService
from rmqtt_tpu.broker.server import MqttBroker
from rmqtt_tpu.broker.session import DeliverItem, SessionState
from rmqtt_tpu.broker.types import Message
from rmqtt_tpu.core.trie import TopicTree

from tests.mqtt_client import TestClient

VERSIONS = [pk.V311, pk.V5]
RECEIVE_MAX = 16  # limits.max_inflight at the defaults: a run's bound
RC_NO_SUBSCRIBERS, RC_TOPIC_INVALID, RC_RECEIVE_MAX = 0x10, 0x90, 0x93
REFUSED = "bad/+/name"  # a wildcard in a topic name: refused by _publish_admit
TOPICS = [f"run/{a}/{b}" for a in "abc" for b in "xyz"] + [REFUSED]
# the standing subscribers: (client id, QoS, filters)
SUBSCRIBERS = [("run-s0", 0, ["run/a/#", "run/+/x"]),
               ("run-s1", 1, ["run/#"]),
               ("run-s2", 2, ["run/b/y", "run/c/+"])]
SELF_FILTERS = ["run/a/x", "run/+/z", "run/c/#"]  # the publisher's own, at QoS0


async def _raw(port: int, client_id: str, version: int):
    """A connected raw socket → (reader, writer, codec). A session's first
    chunk after CONNACK stays on the transport (broker/ingress.py), so one
    PINGREQ goes first: what follows is read the way a live connection's
    traffic is."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.transport.get_extra_info("socket").setsockopt(
        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    codec = MqttCodec(version)
    writer.write(codec.encode(pk.Connect(client_id=client_id, protocol=version)))
    assert isinstance((await _read_packets(reader, codec, 1))[0], pk.Connack)
    writer.write(codec.encode(pk.Pingreq()))
    assert isinstance((await _read_packets(reader, codec, 1))[0], pk.Pingresp)
    return reader, writer, codec


async def _read_packets(reader, codec, n: int, timeout: float = 10.0) -> list:
    """The next ``n`` packets that are not deliveries to this connection
    (those are appended to ``codec.deliveries`` where the list exists)."""
    out = []
    while len(out) < n:
        data = await asyncio.wait_for(reader.read(65536), timeout)
        assert data, "the broker closed the connection"
        for p in codec.feed(data):
            if isinstance(p, pk.Publish) and hasattr(codec, "deliveries"):
                codec.deliveries.append(p)
            else:
                out.append(p)
    return out


@pytest.fixture
def chunks(monkeypatch):
    """The packets of every read chunk, per client id, as the session
    decoded them: the oracle counts runs over the chunks that really came."""
    seen = {}
    real = SessionState._decode_chunk

    def _decode_chunk(self, *a, **k):
        packets = real(self, *a, **k)
        seen.setdefault(self.s.client_id, []).append(list(packets))
        return packets

    monkeypatch.setattr(SessionState, "_decode_chunk", _decode_chunk)
    return seen


# ------------------------------------------------------------------ the mix
def _mix(rng: random.Random, codec) -> list:
    """Packets for one write: runs of 1 to 40 PUBLISHes (repeated topics, a
    refused one, QoS0/1/2, a QoS2 packet id sent twice) between SUBSCRIBE /
    UNSUBSCRIBE / PUBREL / PINGREQ packets."""
    packets, pid, open_qos2 = [], 0, []
    for _ in range(rng.randint(3, 6)):
        for _ in range(rng.choice([1, 1, 2, 3, 8, 17, 25, 40])):
            qos = rng.choice([0, 1, 1, 1, 2])
            if qos == 2 and open_qos2 and rng.random() < 0.15:
                again = rng.choice(open_qos2)  # a resend of an accepted one
                packets.append(pk.Publish(topic="run/a/x", payload=b"dup", qos=2,
                                          packet_id=again, dup=True))
                continue
            pid += 1
            topic = rng.choice(TOPICS)
            packets.append(pk.Publish(topic=topic, payload=b"%d" % len(packets),
                                      qos=qos, packet_id=pid if qos else None))
            if qos == 2 and topic != REFUSED:
                open_qos2.append(pid)
        for _ in range(rng.randint(1, 3)):
            kind = rng.choice(["sub", "unsub", "pubrel", "ping"])
            pid += 1
            if kind == "sub":
                packets.append(pk.Subscribe(pid, [(rng.choice(SELF_FILTERS),
                                                   pk.SubOpts(qos=0))]))
            elif kind == "unsub":
                packets.append(pk.Unsubscribe(pid, [rng.choice(SELF_FILTERS)]))
            elif kind == "pubrel" and open_qos2:
                packets.append(pk.Pubrel(open_qos2.pop(0)))
            else:
                packets.append(pk.Pingreq())
    return packets


class Oracle:
    """The same packets served one by one, in order, on a plain trie."""

    def __init__(self, version: int, me: str) -> None:
        self.v5, self.me = version == pk.V5, me
        self.trie: TopicTree = TopicTree()
        self.qos = {}          # (client, filter) → subscribed QoS
        self.in_qos2 = set()
        self.answers = []      # what the connection is sent back, in order
        self.delivered = {}    # client → [(payload, qos)] in order
        self.received = self.enqueues = self.routed = 0
        for cid, qos, filters in SUBSCRIBERS:
            for f in filters:
                self._subscribe(cid, f, qos)

    def _subscribe(self, cid: str, f: str, qos: int) -> None:
        if (cid, f) not in self.qos:
            self.trie.insert(f, cid)
        self.qos[(cid, f)] = qos

    def _ack(self, p, rc: int = 0):
        return (pk.Puback if p.qos == 1 else pk.Pubrec)(p.packet_id,
                                                         rc if self.v5 else 0)

    def serve(self, p) -> bool:
        """→ whether ``p`` was a PUBLISH that went on to the routing service."""
        if isinstance(p, pk.Publish):
            self.received += 1
            if p.qos == 2 and p.packet_id in self.in_qos2:
                self.answers.append(pk.Pubrec(p.packet_id))
                return False
            if p.qos == 2 and len(self.in_qos2) >= RECEIVE_MAX:
                self.answers.append(self._ack(p, RC_RECEIVE_MAX))
                return False
            if p.topic == REFUSED:
                if p.qos:
                    self.answers.append(self._ack(p, RC_TOPIC_INVALID))
                return False
            if p.qos == 2:
                self.in_qos2.add(p.packet_id)
            self.routed += 1
            # one delivery a matching (client, filter), at the lower QoS
            n = 0
            for levels, clients in self.trie.matches(p.topic):
                for cid in clients:
                    n += 1
                    qos = min(p.qos, self.qos[(cid, "/".join(levels))])
                    self.delivered.setdefault(cid, []).append((p.payload, qos))
            self.enqueues += n
            if p.qos:
                self.answers.append(self._ack(p, 0 if n else RC_NO_SUBSCRIBERS))
            return True
        if isinstance(p, pk.Subscribe):
            for f, opts in p.filters:
                self._subscribe(self.me, f, opts.qos)
            self.answers.append(pk.Suback(p.packet_id, [0] * len(p.filters)))
        elif isinstance(p, pk.Unsubscribe):
            codes = []
            for f in p.filters:
                had = self.qos.pop((self.me, f), None) is not None
                if had:
                    self.trie.remove(f, self.me)
                codes.append(0 if had else 0x11)
            self.answers.append(pk.Unsuback(p.packet_id,
                                            codes if self.v5 else []))
        elif isinstance(p, pk.Pubrel):
            self.in_qos2.discard(p.packet_id)
            self.answers.append(pk.Pubcomp(p.packet_id))
        elif isinstance(p, pk.Pingreq):
            self.answers.append(pk.Pingresp())
        return False


def _runs_of(chunks: list, routed: set) -> int:
    """Runs handed to the routing service, counted over the chunks as they
    came: PUBLISHes in a row in one chunk, ``RECEIVE_MAX`` at most, are one
    run; a publish answered without a fan-out (``id(p)`` not in ``routed``)
    ends its run and is in none; a lone publish is a run of one."""
    runs = 0
    for packets in chunks:
        i, n = 0, len(packets)
        while i < n:
            p = packets[i]
            i += 1
            if not isinstance(p, pk.Publish):
                continue
            if not (i < n and isinstance(packets[i], pk.Publish)):
                runs += id(p) in routed
                continue
            i -= 1
            end, took = min(n, i + RECEIVE_MAX), 0
            while i < end and isinstance(packets[i], pk.Publish):
                i += 1
                if id(packets[i - 1]) not in routed:
                    break
                took += 1
            runs += took > 0
    return runs


def _same(a, b) -> bool:
    return type(a) is type(b) and a == b


async def _drain(client: TestClient, n: int) -> list:
    got = []
    while len(got) < n:
        p = await client.recv(10.0)
        if not p.dup:
            got.append((p.payload, p.qos))
    await client.expect_nothing(0.05)
    return got


# --------------------------------------------------------- the property test
@pytest.mark.timeout(120)
@pytest.mark.parametrize("seed", [31, 32, 33, 34])
@pytest.mark.parametrize("version", VERSIONS, ids=["v311", "v5"])
def test_one_write_is_served_as_the_sequential_oracle_serves_it(
        chunks, version, seed):
    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0, router="xla")))
        await b.start()
        subs = {}
        for cid, qos, filters in SUBSCRIBERS:
            subs[cid] = await TestClient.connect(b.port, cid, version=version)
            await subs[cid].subscribe(*filters, qos=qos)
        me = f"run-pub-{seed}"
        reader, writer, codec = await _raw(b.port, me, version)
        codec.deliveries = []
        rng = random.Random(seed)
        packets = _mix(rng, codec)
        oracle = Oracle(version, me)
        m0 = b.ctx.metrics.to_json()
        chunks.pop(me, None)
        writer.write(b"".join(codec.encode(p) for p in packets))  # ONE write
        for p in packets:
            oracle.serve(p)
        answers = await _read_packets(reader, codec, len(oracle.answers))
        # every answer, in the order of the packets: nothing overtook
        assert len(answers) == len(oracle.answers)
        for got, want in zip(answers, oracle.answers):
            assert _same(got, want), (got, want)
        acks = [a for a in answers if isinstance(a, (pk.Puback, pk.Pubrec))]
        assert [a.packet_id for a in acks] == [
            a.packet_id for a in oracle.answers
            if isinstance(a, (pk.Puback, pk.Pubrec))]
        # each subscriber's sequence, the publisher's own included
        for cid, client in subs.items():
            want = oracle.delivered.get(cid, [])
            assert await _drain(client, len(want)) == want, cid
        own = oracle.delivered.get(me, [])
        while len(codec.deliveries) < len(own):
            data = await asyncio.wait_for(reader.read(65536), 10.0)
            codec.deliveries += [p for p in codec.feed(data)
                                 if isinstance(p, pk.Publish)]
        assert [(p.payload, p.qos) for p in codec.deliveries] == own
        # the counters
        m1 = b.ctx.metrics.to_json()
        d = lambda k: m1.get(k, 0) - m0.get(k, 0)  # noqa: E731
        assert d("publish.received") == oracle.received
        assert d("fanout.enqueues") == oracle.enqueues
        assert d("ingress.run_publishes") == oracle.routed
        mine = chunks[me]
        served = [p for c in mine for p in c]
        assert len(served) == len(packets)
        o2 = Oracle(version, me)
        routed = {id(p) for p in served if o2.serve(p)}
        assert d("ingress.runs") == _runs_of(mine, routed)
        if max(len(c) for c in mine) == len(packets):  # it came as one chunk
            assert d("ingress.runs") < oracle.routed
        assert 0 < d("deliver.cold_enqueues") <= d("fanout.enqueues")
        writer.close()
        for c in subs.values():
            await c.close()
        await b.stop()

    asyncio.run(asyncio.wait_for(run(), 100))


# ------------------------------------------------- the lone path stays lone
@pytest.mark.parametrize("version", VERSIONS, ids=["v311", "v5"])
def test_a_one_publish_chunk_makes_no_run_and_no_task(monkeypatch, version):
    calls = []
    for cls, name in ((SessionState, "_publish_run"), (SessionState, "_run_forward"),
                      (RoutingService, "matches_run")):
        def spy(*a, _name=name, **k):
            calls.append(_name)
            raise AssertionError(f"{_name} on the lone path")
        monkeypatch.setattr(cls, name, spy)
    handled_all = []
    real_all = SessionState._handle_all

    async def _handle_all(self, packets):
        handled_all.append((self.s.client_id, len(packets)))
        await real_all(self, packets)

    monkeypatch.setattr(SessionState, "_handle_all", _handle_all)

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0, router="xla")))
        await b.start()
        sub = await TestClient.connect(b.port, "lone-sub", version=version)
        await sub.subscribe("lone/#", qos=1)
        reader, writer, codec = await _raw(b.port, "lone-pub", version)
        m0 = b.ctx.metrics.to_json()
        tasks = None
        for i in range(1, 9):
            writer.write(codec.encode(pk.Publish(
                topic=f"lone/{i % 3}", payload=b"%d" % i, qos=1, packet_id=i)))
            (ack,) = await _read_packets(reader, codec, 1)
            assert isinstance(ack, pk.Puback) and ack.packet_id == i
            assert (await sub.recv()).payload == b"%d" % i
            # once the subscriber's PUBACK has landed the tasks are those
            # there were after the first publish: no task a publish
            await asyncio.sleep(0.1 if tasks is None else 0.02)
            for _ in range(100):
                now = len(asyncio.all_tasks())
                if tasks is None or now == tasks:
                    break
                await asyncio.sleep(0.02)  # a loaded machine: the ack is late
            assert tasks is None or now == tasks
            tasks = now
        assert not [n for cid, n in handled_all if cid == "lone-pub"]
        # two packets, one of them a PUBLISH: _handle_all, and still no run
        writer.write(codec.encode(pk.Pingreq()) + codec.encode(pk.Publish(
            topic="lone/9", payload=b"9", qos=1, packet_id=9)))
        got = await _read_packets(reader, codec, 2)
        assert [type(p) for p in got] == [pk.Pingresp, pk.Puback]
        m1 = b.ctx.metrics.to_json()
        assert m1["ingress.runs"] - m0["ingress.runs"] == 9
        assert m1["ingress.run_publishes"] - m0["ingress.run_publishes"] == 9
        assert calls == []
        writer.close()
        await sub.close()
        await b.stop()

    asyncio.run(asyncio.wait_for(run(), 60))


@pytest.mark.parametrize("version", VERSIONS, ids=["v311", "v5"])
def test_chunks_that_queued_behind_a_busy_session_are_one_run(monkeypatch, version):
    """The ingress thread reads a burst segment by segment. The chunks that
    came while the session was busy with an earlier one are served together,
    as ``reader.read()`` would have given them: the PUBLISHes a client has
    pipelined are a run again, and the PUBACKs keep their order."""
    from rmqtt_tpu.broker.ingress import IngressHub
    from rmqtt_tpu.broker.metrics import Metrics

    if not IngressHub(Metrics()).native:
        pytest.skip("native runtime (ingress.cc) unavailable")
    gate = asyncio.Event()
    real = SessionState._handle

    async def _handle(self, p):
        if self.s.client_id == "gather-pub" and isinstance(p, pk.Publish):
            await gate.wait()  # the first PUBLISH keeps the session busy
        await real(self, p)

    monkeypatch.setattr(SessionState, "_handle", _handle)

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0, router="xla")))
        await b.start()
        sub = await TestClient.connect(b.port, "gather-sub", version=version)
        await sub.subscribe("gather/#", qos=1)
        reader, writer, codec = await _raw(b.port, "gather-pub", version)
        m0 = b.ctx.metrics.to_json()
        reads = "net.ingress_offloop_reads"

        def chunks() -> int:
            return b.ctx.metrics.to_json().get(reads, 0) - m0.get(reads, 0)

        for i in range(1, 7):  # six segments, six chunks
            writer.write(codec.encode(pk.Publish(
                topic="gather/t", payload=b"%d" % i, qos=1, packet_id=i)))
            await writer.drain()
            for _ in range(200):  # the thread has read it and the hub posted it
                if chunks() >= i:
                    break
                await asyncio.sleep(0.01)
        assert chunks() == 6
        gate.set()
        acks = await _read_packets(reader, codec, 6)
        assert [a.packet_id for a in acks] == [1, 2, 3, 4, 5, 6]
        assert [(await sub.recv()).payload for _ in range(6)] == [
            b"%d" % i for i in range(1, 7)]
        m2 = b.ctx.metrics.to_json()
        d = lambda k: m2[k] - m0[k]  # noqa: E731
        # the first alone (a lone publish), the five behind it as one run
        assert (d("ingress.runs"), d("ingress.run_publishes")) == (2, 6)
        writer.close()
        await sub.close()
        await b.stop()

    asyncio.run(asyncio.wait_for(run(), 30))


def test_a_registry_that_overrides_forwards_serves_no_runs():
    """Cluster modes and the fabric override ``forwards`` wholesale: their
    sessions keep one publish at a time."""
    from rmqtt_tpu.broker.shared import SessionRegistry

    class Other(SessionRegistry):
        async def forwards(self, msg):
            return 0

    ctx = ServerContext(BrokerConfig(port=0))
    assert ctx.registry.run_forwards is True
    assert Other(ctx).run_forwards is False


# ------------------------------------------ many runs, one consumer's queue
@pytest.mark.timeout(120)
@pytest.mark.parametrize("path", ["runs", "lone"])
@pytest.mark.parametrize("qos", [0, 1])
@pytest.mark.parametrize("version", VERSIONS, ids=["v311", "v5"])
def test_a_flood_of_runs_overfills_no_queue_that_lone_publishes_would_not(
        monkeypatch, version, qos, path):
    """100 connections pipeline runs of 16 to ONE QoS0 subscriber. Their
    matches resolve with one dispatch, so without a yield between a run's
    fan-outs 1,600 deliveries meet a queue of 1,000 before its deliver loop
    has had a turn (6,000 of 16,000 were dropped, at either publish QoS:
    the delivery is QoS0, so nothing holds it), where one publish at a time
    offers 100 a turn. ``_run_forward`` yields once a fan-out has met a
    queue over half full; the lone path (``_runs`` off) is the control."""
    conns, per, run_len = 100, 48, 16

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        if path == "lone":
            real = SessionState.__init__

            def init(self, *a, **k):
                real(self, *a, **k)
                self._runs = False

            monkeypatch.setattr(SessionState, "__init__", init)
        reader, writer, codec = await _raw(b.port, "flood-sub", version)
        writer.write(codec.encode(pk.Subscribe(1, [("flood/#", pk.SubOpts(qos=0))])))
        assert isinstance((await _read_packets(reader, codec, 1))[0], pk.Suback)
        got = []

        async def consume():
            while True:
                data = await reader.read(65536)
                if not data:
                    return
                got.extend(p.payload for p in codec.feed(data)
                           if isinstance(p, pk.Publish))

        consumer = asyncio.ensure_future(consume())
        pubs = [await _raw(b.port, f"flood-pub-{k}", version) for k in range(conns)]
        m = b.ctx.metrics
        m0 = m.to_json()
        for j in range(0, per, run_len):
            for k, (_, w, c) in enumerate(pubs):  # one run a connection a round
                w.write(b"".join(c.encode(pk.Publish(
                    topic=f"flood/{k}", payload=b"%d.%d" % (k, i), qos=qos,
                    packet_id=i + 1 if qos else None)) for i in range(j, j + run_len)))
            await asyncio.sleep(0)
        for _, w, c in pubs:
            w.write(c.encode(pk.Pingreq()))
        for r, _, c in pubs:  # every publish served: its acks, then PINGRESP
            answers = await _read_packets(r, c, per * bool(qos) + 1)
            assert isinstance(answers[-1], pk.Pingresp)
            assert [a.packet_id for a in answers[:-1]] == list(range(1, per + 1))[:per * bool(qos)]
        total = conns * per
        for _ in range(200):
            if len(got) + m.get("messages.dropped") >= total:
                break
            await asyncio.sleep(0.05)
        d = lambda k: m.get(k) - m0.get(k, 0)  # noqa: E731
        assert d("publish.received") == total
        assert d("messages.dropped") == 0
        assert len(got) == total
        # each connection's publishes in its order at the subscriber
        for k in range(conns):
            mine = [p for p in got if p.startswith(b"%d." % k)]
            assert mine == [b"%d.%d" % (k, i) for i in range(per)]
        if path == "runs":
            assert d("ingress.runs") < total / 4  # they did come as runs
            assert d("deliver.queue_over_half") > 0  # and the queue was met crowded
        else:
            assert d("ingress.runs") == total
        consumer.cancel()
        writer.close()
        for _, w, _ in pubs:
            w.close()
        await b.stop()

    asyncio.run(asyncio.wait_for(run(), 100))


def test_cold_enqueues_count_the_enqueues_that_found_an_empty_queue():
    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        sub = await TestClient.connect(b.port, "cold-sub", auto_ack=False)
        await sub.subscribe("cold/#", qos=1)
        s = b.ctx.registry.get("cold-sub")
        m = b.ctx.metrics
        before = m.get("deliver.cold_enqueues")
        for i in range(3):  # no turn in between: the first alone is cold
            s.enqueue(DeliverItem(msg=Message(topic="cold/x", payload=b"%d" % i,
                                              qos=1), qos=1, retain=False,
                                  topic_filter="cold/#"))
        assert m.get("deliver.cold_enqueues") - before == 1
        for i in range(3):
            assert (await sub.recv()).payload == b"%d" % i
        s.enqueue(DeliverItem(msg=Message(topic="cold/x", payload=b"3", qos=1),
                              qos=1, retain=False, topic_filter="cold/#"))
        assert m.get("deliver.cold_enqueues") - before == 2
        await sub.close()
        await b.stop()

    asyncio.run(asyncio.wait_for(run(), 30))


# ------------------------------------------------ the rate limit, inside a run
@pytest.mark.parametrize("version", VERSIONS, ids=["v311", "v5"])
def test_a_rate_limit_refusal_ends_its_run_and_overtakes_nothing(version):
    """A bucket of four and one write of eight QoS1 publishes. v5: the four
    admitted ones are routed, fanned out and acknowledged, in order, before
    the fifth's Quota Exceeded goes; every later one is refused in its turn,
    a run of one refusal at a time. v3.1.1 has no per-publish reason code:
    the four are served and acknowledged, then the connection is closed."""
    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, router="xla", overload_enable=True,
            overload_sample_interval=30.0, overload_publish_rate_limit=0.001,
            overload_publish_burst=4.0)))
        await b.start()
        sub = await TestClient.connect(b.port, "quota-sub", version=version)
        await sub.subscribe("quota/#", qos=1)
        reader, writer, codec = await _raw(b.port, "quota-pub", version)
        m0 = b.ctx.metrics.to_json()
        writer.write(b"".join(codec.encode(pk.Publish(
            topic=f"quota/{i % 2}", payload=b"%d" % i, qos=1, packet_id=i))
            for i in range(1, 9)))
        acks = await _read_packets(reader, codec, 8 if version == pk.V5 else 4)
        assert [a.packet_id for a in acks] == list(range(1, len(acks) + 1))
        assert all(isinstance(a, pk.Puback) for a in acks)
        assert [(await sub.recv()).payload for _ in range(4)] == [
            b"1", b"2", b"3", b"4"]
        await sub.expect_nothing(0.1)
        m1 = b.ctx.metrics.to_json()
        d = lambda k: m1.get(k, 0) - m0.get(k, 0)  # noqa: E731
        # the four admitted ones were one run; no refused publish is in any
        assert (d("ingress.runs"), d("ingress.run_publishes")) == (1, 4)
        # the chunk's later publishes are refused too, as one by one they were
        assert d("messages.dropped.rate_limited") == 4
        if version == pk.V5:
            assert [a.reason_code for a in acks] == [0] * 4 + [0x97] * 4
            writer.close()
        else:
            assert await asyncio.wait_for(reader.read(64), 5.0) == b""  # closed
        await sub.close()
        await b.stop()

    asyncio.run(asyncio.wait_for(run(), 30))


# ------------------------------------------- the hold, and who waits behind it
@pytest.mark.timeout(60)
@pytest.mark.parametrize("version", VERSIONS, ids=["v311", "v5"])
def test_a_full_queue_holds_its_publish_and_every_later_ack_of_the_run(version):
    """A consumer that takes one delivery and acks nothing, a queue of four:
    of a run of ten QoS1 publishes the first ``k`` are acknowledged at once,
    the next meets the full queue and is held, and those behind it wait
    their turn — the eighth too, which goes to nobody. ``k`` is five: the
    fourth enqueue finds the queue over half, so the run yields and the
    consumer takes its one delivery before the fifth comes (four where the
    deliver loop has no turn inside the run). Acked deliveries make room;
    the PUBACKs then come in publish order, and nothing was dropped."""
    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, router="xla", fitter=FitterConfig(max_mqueue=4))))
        await b.start()
        slow = await TestClient.connect(b.port, "hold-slow", version=version,
                                        auto_ack=False)
        await slow.subscribe("hold/t", qos=1)
        b.ctx.registry.get("hold-slow").out_inflight.max_inflight = 1
        reader, writer, codec = await _raw(b.port, "hold-pub", version)
        topics = ["hold/t"] * 7 + ["hold/nobody"] + ["hold/t"] * 2
        writer.write(b"".join(codec.encode(pk.Publish(
            topic=t, payload=b"%d" % i, qos=1, packet_id=i + 1))
            for i, t in enumerate(topics)))
        early = await _read_packets(reader, codec, 4)
        try:  # the rest is held
            early += await _read_packets(reader, codec, 1, timeout=0.4)
            with pytest.raises(asyncio.TimeoutError):
                await _read_packets(reader, codec, 1, timeout=0.4)
        except asyncio.TimeoutError:
            pass
        k = len(early)
        assert k in (4, 5) and [a.packet_id for a in early] == list(range(1, k + 1))
        # held: every later publish to hold/t (the eighth goes to nobody)
        assert b.ctx.metrics.get("fanout.held") == 9 - k
        assert b.ctx.metrics.get("ingress.runs") == 1
        got = []
        for _ in range(9):  # ack what comes: each ack lets one more out
            p = await slow.recv(10.0)
            got.append(p.payload)
            await slow._send(pk.Puback(p.packet_id))
        late = await _read_packets(reader, codec, 10 - k)
        assert [a.packet_id for a in late] == list(range(k + 1, 11))
        assert all(isinstance(a, pk.Puback) for a in early + late)
        if version == pk.V5:
            assert [a.reason_code for a in late] == [
                RC_NO_SUBSCRIBERS if a.packet_id == 8 else 0 for a in late]
        assert got == [b"%d" % i for i in (0, 1, 2, 3, 4, 5, 6, 8, 9)]
        assert b.ctx.metrics.get("messages.dropped") == 0
        writer.close()
        await slow.close()
        await b.stop()

    asyncio.run(asyncio.wait_for(run(), 50))


# ------------------------------------------------------ the durability barrier
@pytest.mark.timeout(60)
@pytest.mark.parametrize("version", VERSIONS, ids=["v311", "v5"])
def test_the_durability_barrier_gates_each_ack_of_a_run(tmp_path, version):
    """A persistent QoS1 subscriber makes every fan-out of the run journal a
    pending record: no PUBACK leaves before the group commit that holds its
    record, the acks come in publish order, and a publish further down the
    run is not fanned out past a barrier an earlier one still waits at."""
    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, router="xla", durability_enable=True,
            durability_path=str(tmp_path / "durability.db"),
            durability_flush_interval_ms=3.0)))
        await b.start()
        props = ({0x11: 3600} if version == pk.V5 else None)  # session expiry
        sub = await TestClient.connect(b.port, "dur-sub", version=version,
                                       clean_start=False, properties=props)
        await sub.subscribe("dur/#", qos=1)
        assert b.ctx.registry.get("dur-sub").limits.session_expiry > 0
        dur = b.ctx.durability
        gate, calls, real = asyncio.Event(), [], dur.barrier

        async def barrier():
            calls.append(b.ctx.metrics.get("fanout.enqueues"))
            await gate.wait()
            await real()

        dur.barrier = barrier
        reader, writer, codec = await _raw(b.port, "dur-pub", version)
        e0 = b.ctx.metrics.get("fanout.enqueues")
        writer.write(b"".join(codec.encode(pk.Publish(
            topic=f"dur/{i}", payload=b"%d" % i, qos=1, packet_id=i))
            for i in range(1, 6)))
        with pytest.raises(asyncio.TimeoutError):
            await _read_packets(reader, codec, 1, timeout=0.4)
        # the first publish waits at its barrier; the second is not fanned out
        assert calls == [e0 + 1]
        assert b.ctx.metrics.get("ingress.run_publishes") == 5
        gate.set()
        acks = await _read_packets(reader, codec, 5)
        assert [type(a) for a in acks] == [pk.Puback] * 5
        assert [a.packet_id for a in acks] == [1, 2, 3, 4, 5]
        assert [(await sub.recv()).payload for _ in range(5)] == [
            b"%d" % i for i in range(1, 6)]
        assert calls == [e0 + k for k in range(1, len(calls) + 1)]  # one a publish, in order
        writer.close()
        await sub.close()
        await b.stop()

    asyncio.run(asyncio.wait_for(run(), 50))
