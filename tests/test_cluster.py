"""Broadcast-cluster tests: multiple real brokers on localhost.

The reference tests multi-node with real processes (SURVEY.md §4: the
cluster example deployments + chaos restart). Here each node is a full
broker + cluster server in one event loop on distinct ports — real TCP
between nodes, real MQTT clients at the edges.
"""

import asyncio

import pytest

from rmqtt_tpu.broker.codec import packets as pk
from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.server import MqttBroker
from rmqtt_tpu.cluster import wire
from rmqtt_tpu.cluster.broadcast import BroadcastCluster

from tests.mqtt_client import TestClient


def test_wire_roundtrip():
    cases = [
        None, True, False, 0, 1, -5, 2**40, 3.5, "héllo", b"\x00\xff" * 10,
        [1, "a", None], {"k": [1, {"n": b"b"}], "e": {}},
    ]
    for obj in cases:
        assert wire.loads(wire.dumps(obj)) == obj
    with pytest.raises(ValueError):
        wire.loads(b"\xff")
    with pytest.raises(ValueError):
        wire.loads(wire.dumps([1]) + b"x")


async def make_node(node_id: int):
    ctx = ServerContext(BrokerConfig(port=0, node_id=node_id, cluster=True))
    broker = MqttBroker(ctx)
    await broker.start()
    return broker


async def link(brokers):
    """Start cluster servers and fully mesh the nodes."""
    clusters = []
    for b in brokers:
        c = BroadcastCluster(b.ctx, ("127.0.0.1", 0), [])
        await c.start()
        clusters.append(c)
    for i, c in enumerate(clusters):
        for j, other in enumerate(clusters):
            if i == j:
                continue
            from rmqtt_tpu.cluster.transport import PeerClient

            nid = brokers[j].ctx.node_id
            c.peers[nid] = PeerClient(nid, "127.0.0.1", other.bound_port)
        c.bcast.peers = list(c.peers.values())
    return clusters


def cluster_test(n_nodes):
    def deco(fn):
        def wrapper():
            async def run():
                brokers = [await make_node(i + 1) for i in range(n_nodes)]
                clusters = await link(brokers)
                try:
                    await asyncio.wait_for(fn(brokers, clusters), timeout=30.0)
                finally:
                    for c in clusters:
                        await c.stop()
                    for b in brokers:
                        await b.stop()

            asyncio.run(run())

        wrapper.__name__ = fn.__name__
        return wrapper

    return deco


@cluster_test(2)
async def test_cross_node_pubsub(brokers, clusters):
    b1, b2 = brokers
    sub = await TestClient.connect(b1.port, "sub-on-1")
    await sub.subscribe("cross/#", qos=1)
    pub = await TestClient.connect(b2.port, "pub-on-2")
    await pub.publish("cross/topic", b"over-the-wire", qos=1)
    p = await sub.recv()
    assert p.topic == "cross/topic" and p.payload == b"over-the-wire"


@cluster_test(2)
async def test_cross_node_kick(brokers, clusters):
    b1, b2 = brokers
    c1 = await TestClient.connect(b1.port, "roamer", version=pk.V5)
    await c1.subscribe("r/t")
    c2 = await TestClient.connect(b2.port, "roamer", version=pk.V5)
    await asyncio.wait_for(c1.closed.wait(), 5.0)
    await c2.ping()  # new session on node 2 fully works


@cluster_test(2)
async def test_retain_sync_on_set_and_startup(brokers, clusters):
    b1, b2 = brokers
    pub = await TestClient.connect(b1.port, "pub-ret")
    await pub.publish("synced/t", b"keepme", retain=True, qos=1)
    # broadcast propagation: poll, a fixed 0.2 s was short on a loaded box
    for _ in range(250):
        if b2.ctx.retain.get("synced/t") is not None:
            break
        await asyncio.sleep(0.02)
    # node 2 has the retained copy locally
    assert b2.ctx.retain.get("synced/t") is not None
    late = await TestClient.connect(b2.port, "late")
    await late.subscribe("synced/#")
    p = await late.recv()
    assert p.payload == b"keepme" and p.retain
    # startup sync: a fresh node pulls existing retains
    b3 = await make_node(3)
    c3 = BroadcastCluster(b3.ctx, ("127.0.0.1", 0), [])
    await c3.start()
    from rmqtt_tpu.cluster.transport import PeerClient

    c3.peers[1] = PeerClient(1, "127.0.0.1", clusters[0].bound_port)
    c3.bcast.peers = list(c3.peers.values())
    await c3.start_sync()
    assert b3.ctx.retain.get("synced/t") is not None
    await c3.stop()
    await b3.stop()


@cluster_test(3)
async def test_shared_subscription_global_exactly_once(brokers, clusters):
    b1, b2, b3 = brokers
    w1 = await TestClient.connect(b1.port, "w1", version=pk.V5)
    w2 = await TestClient.connect(b2.port, "w2", version=pk.V5)
    await w1.subscribe("$share/g/work/#", qos=1)
    await w2.subscribe("$share/g/work/#", qos=1)
    pub = await TestClient.connect(b3.port, "pub3")
    n = 10
    for i in range(n):
        await pub.publish("work/item", str(i).encode(), qos=1)
    await asyncio.sleep(0.5)
    total = w1.publishes.qsize() + w2.publishes.qsize()
    assert total == n  # exactly one delivery per message across the cluster
    assert w1.publishes.qsize() > 0 and w2.publishes.qsize() > 0


@cluster_test(2)
async def test_node_counters(brokers, clusters):
    b1, b2 = brokers
    await TestClient.connect(b1.port, "c1")
    await TestClient.connect(b2.port, "c2a")
    await TestClient.connect(b2.port, "c2b")
    from rmqtt_tpu.cluster import messages as M

    replies = await clusters[0].bcast.join_all_call(M.NUMBER_OF_CLIENTS)
    counts = {nid: r["count"] for nid, r in replies if not isinstance(r, Exception)}
    assert counts == {2: 2}


@cluster_test(2)
async def test_peer_down_does_not_break_local(brokers, clusters):
    b1, b2 = brokers
    await clusters[1].stop()
    await brokers[1].stop()
    sub = await TestClient.connect(b1.port, "local-sub")
    await sub.subscribe("l/t", qos=1)
    pub = await TestClient.connect(b1.port, "local-pub")
    await pub.publish("l/t", b"still-works", qos=1)
    p = await sub.recv()
    assert p.payload == b"still-works"


@cluster_test(2)
async def test_session_state_transfer_across_nodes(brokers, clusters):
    """Roaming client: persistent session moves node 1 → node 2 with
    subscriptions AND queued messages (the reference's SessionStateTransfer)."""
    from rmqtt_tpu.broker.codec import props as P

    b1, b2 = brokers
    c1 = await TestClient.connect(
        b1.port, "roam-p", version=pk.V5,
        properties={P.SESSION_EXPIRY_INTERVAL: 300},
    )
    await c1.subscribe("roam/t", qos=1)
    await c1.disconnect_clean()
    await asyncio.sleep(0.05)
    # publish while the client is away: queues on node 1's offline session
    pub = await TestClient.connect(b2.port, "roam-pub")
    await pub.publish("roam/t", b"catch-me", qos=1)
    await asyncio.sleep(0.1)
    # the client reconnects on NODE 2 with clean_start=False
    c2 = await TestClient.connect(
        b2.port, "roam-p", version=pk.V5, clean_start=False,
        properties={P.SESSION_EXPIRY_INTERVAL: 300},
    )
    assert c2.connack.session_present
    p = await c2.recv()
    assert p.payload == b"catch-me"
    # subscription moved with the session: new publishes reach node 2
    await pub.publish("roam/t", b"after-move", qos=1)
    p = await c2.recv()
    assert p.payload == b"after-move"
    # node 1 no longer holds a copy
    assert b1.ctx.registry.get("roam-p") is None


@cluster_test(2)
async def test_offline_inflight_and_grpc_hooks_fire(brokers, clusters):
    """hook.rs OfflineInflightMessages + GrpcMessageReceived: both events
    must actually fire — on offline transition with an unacked window, and
    on every cluster RPC arrival."""
    from rmqtt_tpu.broker.codec import props as P
    from rmqtt_tpu.broker.hooks import HookType

    b1, b2 = brokers
    seen = {"grpc": [], "offline_inflight": []}

    async def on_grpc(_ht, args, prev):
        seen["grpc"].append(args[0])
        return prev

    async def on_offline_inflight(_ht, args, prev):
        seen["offline_inflight"].append([m.topic for m in args[1]])
        return prev

    b2.ctx.hooks.register(HookType.GRPC_MESSAGE_RECEIVED, on_grpc)
    b1.ctx.hooks.register(HookType.OFFLINE_INFLIGHT_MESSAGES, on_offline_inflight)
    # cross-node traffic makes RPCs arrive at node 2
    sub = await TestClient.connect(b2.port, "hooks-sub", version=pk.V5,
                                   clean_start=False,
                                   properties={P.SESSION_EXPIRY_INTERVAL: 300})
    await sub.subscribe("hk/t", qos=1)
    pub = await TestClient.connect(b1.port, "hooks-pub")
    await pub.publish("hk/t", b"x", qos=1)
    await asyncio.sleep(0.3)
    assert seen["grpc"], "no GrpcMessageReceived events"

    # offline with an unacked QoS1 window on node 1
    s1 = await TestClient.connect(b1.port, "hooks-off", version=pk.V5,
                                  clean_start=False,
                                  properties={P.SESSION_EXPIRY_INTERVAL: 300})
    await s1.subscribe("hk/off", qos=1)
    s1.auto_ack = False
    await pub.publish("hk/off", b"pending", qos=1)
    await s1.recv()  # delivered but never acked
    s1.abort()
    await asyncio.sleep(0.3)
    assert seen["offline_inflight"] == [["hk/off"]], seen["offline_inflight"]
    await sub.disconnect_clean()
    await pub.disconnect_clean()


async def _with_storage(brokers, **cfg):
    """Install a message-storage plugin on every node (returns for cleanup)."""
    from rmqtt_tpu.plugins.message_storage import MessageStoragePlugin

    plugins = []
    for b in brokers:
        p = MessageStoragePlugin(b.ctx, {"expiry": 60, **cfg})
        await p.init()
        plugins.append(p)
    return plugins


@cluster_test(2)
async def test_merge_on_read_cross_node_replay(brokers, clusters):
    """A message stored on node A reaches a subscriber that connects to
    node B (merge_on_read, reference message.rs:73 +
    cluster-raft/src/shared.rs:665-699 MessageGet broadcast)."""
    b1, b2 = brokers
    plugins = await _with_storage(brokers)
    try:
        pub = await TestClient.connect(b1.port, "mpub")
        await pub.publish("store/t", b"offline-payload", qos=1)
        await asyncio.sleep(0.1)
        assert plugins[0].count() == 1  # stored on node 1 only
        assert plugins[1].count() == 0
        # subscriber appears on node 2: replay must merge from node 1
        sub = await TestClient.connect(b2.port, "msub")
        await sub.subscribe("store/#", qos=1)
        p = await sub.recv()
        assert p.topic == "store/t" and p.payload == b"offline-payload"
        # re-subscribe: no double replay (marked forwarded on node 1)
        await sub.subscribe("store/#", qos=1)
        await asyncio.sleep(0.3)
        assert sub.publishes.qsize() == 0
    finally:
        for p in plugins:
            await p.stop()


@cluster_test(2)
async def test_forwards_to_ack_marks_forwarded(brokers, clusters):
    """Cross-node live delivery acks back (ForwardsToAck,
    cluster-raft/src/shared.rs:596-613): the publishing node's store marks
    the recipient so a later subscribe-time replay can't repeat."""
    b1, b2 = brokers
    plugins = await _with_storage(brokers)
    try:
        sub = await TestClient.connect(b2.port, "acksub")
        await sub.subscribe("ack/t", qos=1)
        pub = await TestClient.connect(b1.port, "ackpub")
        await pub.publish("ack/t", b"live", qos=1)
        p = await sub.recv()
        assert p.payload == b"live"
        await asyncio.sleep(0.3)  # fire-and-forget ack lands on node 1
        # node 1's store knows the delivery happened
        assert plugins[0].load_unforwarded("ack/t", "acksub") == []
        # re-subscribing on node 2 triggers MessageGet to node 1: no replay
        await sub.subscribe("ack/t", qos=1)
        await asyncio.sleep(0.3)
        assert sub.publishes.qsize() == 0
    finally:
        for p in plugins:
            await p.stop()


@cluster_test(2)
async def test_subscriptions_search_and_routes_get_by(brokers, clusters):
    """SubscriptionsSearch + RoutesGetBy RPCs (grpc.rs:506-535) fan out and
    filter across nodes."""
    from rmqtt_tpu.cluster import messages as M

    b1, b2 = brokers
    c1 = await TestClient.connect(b1.port, "search-1")
    await c1.subscribe("s/one", qos=1)
    c2 = await TestClient.connect(b2.port, "search-2")
    await c2.subscribe("s/+", qos=2)
    # search by client id across the mesh (node 1 asks node 2)
    reply = await clusters[0].peers[2].call(
        M.SUBSCRIPTIONS_SEARCH, {"clientid": "search-2"}
    )
    rows = reply["subscriptions"]
    assert rows == [{"client_id": "search-2", "node_id": 2,
                     "topic_filter": "s/+", "qos": 2, "share": None}]
    # qos filter excludes
    reply = await clusters[0].peers[2].call(
        M.SUBSCRIPTIONS_SEARCH, {"clientid": "search-2", "qos": 1}
    )
    assert reply["subscriptions"] == []
    # RoutesGetBy: which filters on node 2 a publish to s/one would ride
    reply = await clusters[0].peers[2].call(M.ROUTES_GET_BY, {"topic": "s/one"})
    assert reply["routes"] == [{"topic": "s/+", "node_id": 2}]
    # ROUTES_GET lists node-local route edges
    reply = await clusters[0].peers[2].call(M.ROUTES_GET, {"limit": 10})
    assert any(r.get("topic_filter", r.get("topic")) == "s/+" for r in reply["routes"])


def test_topic_only_retain_sync():
    """retain_sync_mode=topic_only (reference retain.rs:162,178): retains are
    NOT replicated; a subscriber's node fetches matches for exactly its
    filter from peers at subscribe time, newest create_time winning the
    per-topic dedup (shared.rs:1109-1127)."""

    async def run():
        brokers = [await make_node(i + 1) for i in range(2)]
        clusters = []
        for b in brokers:
            c = BroadcastCluster(b.ctx, ("127.0.0.1", 0), [],
                                 retain_sync_mode="topic_only")
            await c.start()
            clusters.append(c)
        from rmqtt_tpu.cluster.transport import PeerClient

        for i, c in enumerate(clusters):
            for j, other in enumerate(clusters):
                if i != j:
                    nid = brokers[j].ctx.node_id
                    c.peers[nid] = PeerClient(nid, "127.0.0.1", other.bound_port)
            c.bcast.peers = list(c.peers.values())
        b1, b2 = brokers
        try:
            pub = await TestClient.connect(b1.port, "topub")
            await pub.publish("lazy/t", b"v-old", retain=True, qos=1)
            await asyncio.sleep(0.3)
            # NOT replicated: node 2's store is empty
            assert b2.ctx.retain.get("lazy/t") is None
            # but a subscriber on node 2 still gets it (lazy per-filter fetch)
            sub = await TestClient.connect(b2.port, "topicsub")
            await sub.subscribe("lazy/#", qos=1)
            p = await asyncio.wait_for(sub.recv(), 5.0)
            assert p.payload == b"v-old" and p.retain
            # newest-wins dedup: node 2 now retains a NEWER copy locally;
            # a fresh subscriber must see exactly one message, the newer one
            await asyncio.sleep(0.05)
            pub2 = await TestClient.connect(b2.port, "topub2")
            await pub2.publish("lazy/t", b"v-new", retain=True, qos=1)
            sub2 = await TestClient.connect(b2.port, "topicsub2")
            await sub2.subscribe("lazy/#", qos=1)
            p2 = await asyncio.wait_for(sub2.recv(), 5.0)
            assert p2.payload == b"v-new"
            await asyncio.sleep(0.3)
            assert sub2.publishes.qsize() == 0  # deduped: one delivery only
        finally:
            for c in clusters:
                await c.stop()
            for b in brokers:
                await b.stop()

    asyncio.run(run())
