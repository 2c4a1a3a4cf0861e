"""Membership failure detector, session fencing and anti-entropy tests
(cluster/membership.py): state-machine units with a driven clock, the fence
clock's Lamport merge, the retain reconciliation plan, and in-process
two-node integration — a blackholed peer goes SUSPECT→DEAD and CONNECTs
stop paying the RPC timeout (the fast-fail-kick pin), retain-sync loss is
counted, and a healed partition reconverges stores and fences the
duplicate session."""

from __future__ import annotations

import asyncio
import time

import pytest

from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.server import MqttBroker
from rmqtt_tpu.cluster.broadcast import BroadcastCluster
from rmqtt_tpu.cluster.membership import (
    Membership,
    PeerState,
    retain_delta,
    retain_digest,
)
from rmqtt_tpu.cluster.transport import PeerClient
from rmqtt_tpu.utils.failpoints import FAILPOINTS

from tests.mqtt_client import TestClient

FAST = dict(heartbeat_interval=0.1, suspect_timeout=0.3, dead_timeout=0.6,
            alive_hold=1)


# ------------------------------------------------------------- fence clock
def test_fence_clock_monotonic_and_merging():
    ctx = ServerContext(BrokerConfig(port=0, node_id=3))
    reg = ctx.registry
    assert reg.fence_epoch == 0
    assert reg.next_fence() == (1, 3)
    assert reg.next_fence() == (2, 3)
    # merging a remote epoch fast-forwards the clock; lower values don't
    reg.observe_fence(10)
    assert reg.next_fence() == (11, 3)
    reg.observe_fence(5)
    assert reg.next_fence() == (12, 3)
    # fences order by (epoch, node_id): epoch first, node id tie-break
    assert (2, 1) > (1, 9)
    assert (2, 9) > (2, 1)


def test_take_or_create_stamps_fresh_fence():
    async def run():
        ctx = ServerContext(BrokerConfig(port=0, node_id=1))
        from rmqtt_tpu.broker.fitter import Limits
        from rmqtt_tpu.broker.types import ConnectInfo
        from rmqtt_tpu.router.base import Id

        ci = ConnectInfo(id=Id(1, "f"), protocol=5, keepalive=60,
                         clean_start=False)
        limits = Limits(keepalive=60, server_keepalive=False, max_inflight=8,
                        max_mqueue=16, session_expiry=60.0,
                        max_message_expiry=0, max_topic_aliases_in=0,
                        max_topic_aliases_out=0, max_packet_size=1 << 20)
        s1, present = await ctx.registry.take_or_create(
            ctx, Id(1, "f"), ci, limits, clean_start=False)
        assert not present and s1.fence == (1, 1)
        # a resume-takeover re-fences (new ownership, higher epoch)
        s2, present = await ctx.registry.take_or_create(
            ctx, Id(1, "f"), ci, limits, clean_start=False)
        assert present and s2 is s1 and s1.fence == (2, 1)

    asyncio.run(run())


def test_session_snapshot_roundtrips_fence():
    from rmqtt_tpu.broker.session import (
        Session, restore_session, session_snapshot,
    )
    from rmqtt_tpu.router.base import Id

    async def run():
        ctx = ServerContext(BrokerConfig(port=0, node_id=2))
        from rmqtt_tpu.broker.fitter import Limits
        from rmqtt_tpu.broker.types import ConnectInfo

        ci = ConnectInfo(id=Id(2, "snap"), protocol=5, keepalive=60,
                         clean_start=False)
        limits = Limits(keepalive=60, server_keepalive=False, max_inflight=8,
                        max_mqueue=16, session_expiry=120.0,
                        max_message_expiry=0, max_topic_aliases_in=0,
                        max_topic_aliases_out=0, max_packet_size=1 << 20)
        s = Session(ctx, Id(2, "snap"), ci, limits, clean_start=False)
        s.fence = (7, 2)
        snap = session_snapshot(s)
        assert snap["fence"] == [7, 2]
        restored = await restore_session(ctx, snap)
        assert restored.fence == (7, 2)
        # the restored epoch advanced the local clock: the next takeover
        # must out-fence the state it resumes
        assert ctx.registry.next_fence()[0] > 7

    asyncio.run(run())


# -------------------------------------------------------- delta planning
def test_retain_delta_newest_wins_plan():
    mine = {"a": [10, "h1"], "b": [5, "h2"], "c": [3, "h3"], "e": [4, "hx"]}
    theirs = {"a": [12, "h9"], "b": [5, "h2"], "d": [8, "h4"], "e": [4, "hy"]}
    pull, push = retain_delta(mine, theirs)
    # a: theirs newer → pull; d: missing here → pull
    # c: missing there → push; b: identical → neither
    assert set(pull) >= {"a", "d"} and "b" not in pull
    assert "c" in push and "b" not in push
    # e: equal create_time, differing hash — exactly ONE side moves (the
    # higher hash wins on both nodes, so the exchange converges)
    assert ("e" in pull) != ("e" in push)


def test_retain_digest_tracks_content(tmp_path):
    from rmqtt_tpu.broker.retain import RetainStore
    from rmqtt_tpu.broker.types import Message

    a, b = RetainStore(), RetainStore()
    msg = Message(topic="t/1", payload=b"v", qos=0, retain=True,
                  create_time=123.0)
    a.set_local("t/1", msg)
    assert retain_digest(a) != retain_digest(b)
    b.set_local("t/1", msg)
    assert retain_digest(a) == retain_digest(b)
    assert retain_digest(a)["count"] == 1
    # summaries expose what the delta plan needs
    assert list(a.summary()) == ["t/1"]


# --------------------------------------------------------- state machine
class _StubCluster:
    def __init__(self):
        self.peers = {}
        self.spawned = []

    def spawn(self, coro):
        self.spawned.append(coro)
        coro.close()  # units never run the repair


def _detector(**kw):
    ctx = ServerContext(BrokerConfig(port=0, node_id=1))
    cluster = _StubCluster()
    opts = dict(FAST)
    opts.update(kw)
    ms = Membership(cluster, ctx, **opts)
    cluster.peers[2] = object()  # state_counts iterates the peer table
    return ms


def test_detector_transitions_on_silence():
    ms = _detector(alive_hold=2)
    h = ms._health(2)
    assert ms.state_of(2) == PeerState.ALIVE
    # failures inside the suspect window: still ALIVE (no flapping on one
    # lost heartbeat)
    ms._note_failure(h)
    assert h.state == PeerState.ALIVE
    # silence past suspect_timeout → SUSPECT; past dead_timeout → DEAD
    h.last_seen = time.monotonic() - 0.4
    ms._note_failure(h)
    assert h.state == PeerState.SUSPECT
    h.last_seen = time.monotonic() - 0.7
    ms._note_failure(h)
    assert h.state == PeerState.DEAD
    assert ms.state_counts() == {"alive": 0, "suspect": 0, "dead": 1}
    # recovery hysteresis: alive_hold=2 needs TWO successes
    ms._note_success(h, {"inc": 5, "fence": 0})
    assert h.state == PeerState.DEAD
    ms._note_success(h, {"inc": 5, "fence": 0})
    assert h.state == PeerState.ALIVE
    # DEAD→ALIVE scheduled an anti-entropy repair
    assert 2 in ms.repairs_running or ms.cluster.spawned


def test_detector_restart_incarnation_triggers_repair():
    ms = _detector()
    h = ms._health(2)
    ms._note_success(h, {"inc": 100, "fence": 0})
    assert not ms.cluster.spawned  # steady state: no repair
    # same incarnation again: still nothing
    ms._note_success(h, {"inc": 100, "fence": 0})
    assert not ms.cluster.spawned
    # changed incarnation while ALIVE = unobserved restart → repair
    ms._note_success(h, {"inc": 101, "fence": 0})
    assert ms.cluster.spawned


def test_detector_heartbeat_merges_fence_clock():
    ms = _detector()
    reply = ms.on_heartbeat({"node": 2, "inc": 1, "fence": 42})
    assert ms.ctx.registry.fence_epoch == 42
    assert reply["fence"] == 42 and reply["inc"] == ms.incarnation


# ------------------------------------------------------------------ conf
def test_cluster_conf_tuning_keys(tmp_path):
    from rmqtt_tpu import conf

    p = tmp_path / "c.toml"
    p.write_text("""
[cluster]
listen = "127.0.0.1:0"
mode = "broadcast"
heartbeat_interval = 0.5
suspect_timeout = 1.5
dead_timeout = 3.0
alive_hold = 3
anti_entropy = false
""")
    s = conf.load(str(p))
    assert s.cluster_tuning == {
        "heartbeat_interval": 0.5, "suspect_timeout": 1.5,
        "dead_timeout": 3.0, "alive_hold": 3, "anti_entropy": False,
    }
    p.write_text("[cluster]\nlisten = \"127.0.0.1:0\"\nheartbeats = 1\n")
    with pytest.raises(ValueError, match="unknown \\[cluster\\] keys"):
        conf.load(str(p))


# ------------------------------------------------------------- transport
def test_peer_client_close_awaits_reader():
    """PeerClient.close() must reap its cancelled reader task — no 'Task
    was destroyed but it is pending' at loop teardown."""
    from rmqtt_tpu.cluster import messages as M
    from rmqtt_tpu.cluster.transport import ClusterServer

    async def run():
        async def handler(mtype, body, node):
            return {"pong": True}

        srv = ClusterServer("127.0.0.1", 0, handler)
        await srv.start()
        peer = PeerClient(9, "127.0.0.1", srv.bound_port)
        await peer.call(M.PING, {})
        task = peer._reader_task
        assert task is not None and not task.done()
        await peer.close()
        assert task.done()
        assert peer._reader_task is None
        await srv.stop()

    asyncio.run(run())


# --------------------------------------------------------- two-node e2e
async def _mesh(n, **ms_opts):
    opts = dict(FAST)
    opts.update(ms_opts)
    brokers, clusters = [], []
    for nid in range(1, n + 1):
        ctx = ServerContext(BrokerConfig(port=0, node_id=nid, cluster=True))
        b = MqttBroker(ctx)
        await b.start()
        brokers.append(b)
    for b in brokers:
        c = BroadcastCluster(b.ctx, ("127.0.0.1", 0), [], **opts)
        await c.start()
        clusters.append(c)
    for i, c in enumerate(clusters):
        for j, other in enumerate(clusters):
            if i != j:
                nid = brokers[j].ctx.node_id
                c.peers[nid] = PeerClient(nid, "127.0.0.1", other.bound_port)
        c.bcast.peers = list(c.peers.values())
    return brokers, clusters


async def _teardown(brokers, clusters):
    for c in clusters:
        await c.stop()
    for b in brokers:
        await b.stop()


async def _wait_state(cluster, nid, state, timeout=10.0):
    deadline = time.monotonic() + timeout
    while cluster.membership.state_of(nid) != state:
        assert time.monotonic() < deadline, (
            f"node {nid} never became {state.name}")
        await asyncio.sleep(0.05)


def test_fast_fail_kick_with_dead_peer():
    """Satellite pin: a 2-node cluster with one node blackholed (accepts,
    never answers — the worst case for timeouts) still completes CONNECT
    within the heartbeat detection window, NOT the 5s RPC timeout."""

    async def run():
        brokers, clusters = await _mesh(1)
        # a blackhole "peer": accepts connections, never replies
        async def swallow(reader, writer):
            try:
                while await reader.read(65536):
                    pass
            except (ConnectionError, OSError):
                pass
            finally:
                writer.close()  # else the server side stays half-open

        hole = await asyncio.start_server(swallow, "127.0.0.1", 0)
        hole_port = hole.sockets[0].getsockname()[1]
        c1 = clusters[0]
        c1.peers[2] = PeerClient(2, "127.0.0.1", hole_port)
        c1.bcast.peers = list(c1.peers.values())
        try:
            # detection: heartbeat calls time out against the blackhole
            await _wait_state(c1, 2, PeerState.DEAD, timeout=10.0)
            base_skip = brokers[0].ctx.metrics.get("cluster.kick_skipped")
            t0 = time.monotonic()
            client = await TestClient.connect(brokers[0].port, "ff-kick")
            elapsed = time.monotonic() - t0
            # the kick skipped the DEAD peer instead of paying the 5s call
            # timeout; generous bound for slow CI, still far under 5s
            assert elapsed < 2.0, f"CONNECT stalled {elapsed:.2f}s on dead peer"
            assert brokers[0].ctx.metrics.get("cluster.kick_skipped") > base_skip
            await client.close()
        finally:
            # Python 3.12's Server.wait_closed() waits for every accepted
            # connection to be closed, and the blackhole's only ends when
            # the cluster's PeerClient hangs up: tear the cluster down first
            # (the old order waited forever), and bound the wait anyway
            hole.close()
            await _teardown(brokers, clusters)
            await asyncio.wait_for(hole.wait_closed(), 5.0)

    asyncio.run(run())


def test_retain_sync_loss_counted_and_gauged():
    """Satellite pin: retain pushes dropped on an unreachable peer bump
    messages.dropped.retain_sync and the cluster_retain_sync_dropped
    stats gauge, so divergence is visible until anti-entropy heals it."""

    async def run():
        brokers, clusters = await _mesh(2)
        try:
            from rmqtt_tpu.broker.types import Message
            from rmqtt_tpu.router.base import Id

            ctx1 = brokers[0].ctx
            # sever node 2 and let the detector notice
            await clusters[1].server.stop()
            await _wait_state(clusters[0], 2, PeerState.DEAD, timeout=10.0)
            base = ctx1.metrics.get("messages.dropped.retain_sync")
            ctx1.retain.set("rl/t", Message(
                topic="rl/t", payload=b"v", qos=0, retain=True,
                from_id=Id(1, "x")))
            await asyncio.sleep(0.2)  # the push task runs + counts
            assert ctx1.metrics.get("messages.dropped.retain_sync") > base
            assert ctx1.stats().to_json()["cluster_retain_sync_dropped"] > 0
        finally:
            await _teardown(brokers, clusters)

    asyncio.run(run())


def test_partition_heal_converges_and_fences():
    """The in-process partition cycle: cluster.rpc failpoint cuts the mesh,
    duplicate sessions arise on both sides, heal triggers anti-entropy —
    retained stores reconverge byte-equal and exactly one duplicate
    survives (the higher fence)."""

    async def run():
        brokers, clusters = await _mesh(2)
        try:
            sub = await TestClient.connect(brokers[1].port, "ph-dup")
            await sub.subscribe("ph/#", qos=1)
            pub = await TestClient.connect(brokers[0].port, "ph-pub")
            await pub.publish("ph/warm", b"w", qos=1)
            assert (await sub.recv(timeout=5.0)).payload == b"w"
            FAILPOINTS.set("cluster.rpc", "error")
            await _wait_state(clusters[0], 2, PeerState.DEAD)
            await _wait_state(clusters[1], 1, PeerState.DEAD)
            # divergence during the partition, both directions
            await pub.publish("ph/keep1", b"v1", qos=1, retain=True)
            pub2 = await TestClient.connect(brokers[1].port, "ph-pub2")
            await pub2.publish("ph/keep2", b"v2", qos=1, retain=True)
            # duplicate session: same id lives on both sides
            dup = await TestClient.connect(brokers[0].port, "ph-dup")
            await dup.subscribe("ph/#", qos=1)
            FAILPOINTS.set("cluster.rpc", "off")
            await _wait_state(clusters[0], 2, PeerState.ALIVE)
            await _wait_state(clusters[1], 1, PeerState.ALIVE)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                d = [retain_digest(b.ctx.retain)["digest"] for b in brokers]
                live = [s for s in (b.ctx.registry.get("ph-dup")
                                    for b in brokers)
                        if s is not None and s.connected]
                if d[0] == d[1] and len(live) == 1:
                    break
                await asyncio.sleep(0.1)
            assert d[0] == d[1], "retained stores never reconverged"
            assert len(live) == 1, f"{len(live)} ph-dup sessions alive"
            # both partition-era retains survived on both sides
            for b in brokers:
                assert b.ctx.retain.get("ph/keep1") is not None
                assert b.ctx.retain.get("ph/keep2") is not None
            # the survivor is the NEWER takeover (higher fence epoch)
            assert live[0].fence[0] >= 2
            kicks = sum(b.ctx.metrics.get("cluster.fence_kicks")
                        for b in brokers)
            assert kicks == 1
            # zero loss for the surviving session after the heal (drain
            # past the retained deliveries its subscribe already queued)
            await pub.publish("ph/after", b"post-heal", qos=1)
            survivor_client = dup if live[0].id.node_id == 1 else sub
            deadline = time.monotonic() + 5.0
            while True:
                p = await survivor_client.recv(timeout=5.0)
                if p.payload == b"post-heal":
                    break
                assert time.monotonic() < deadline
        finally:
            FAILPOINTS.clear_all()
            await _teardown(brokers, clusters)

    asyncio.run(run())


def test_cluster_api_shape_single_node():
    """/api/v1/cluster stays shape-stable on single-node brokers."""

    async def run():
        from rmqtt_tpu.broker.http_api import HttpApi

        ctx = ServerContext(BrokerConfig(port=0))
        api = HttpApi(ctx, "127.0.0.1", 0)
        status, body, _ = await api._route("GET", "/api/v1/cluster", b"")
        assert status == 200
        assert body["enabled"] is False
        assert body["fence_epoch"] == 0
        assert "membership" not in body

    asyncio.run(run())


def test_cluster_api_reports_membership_and_digests():
    async def run():
        from rmqtt_tpu.broker.http_api import HttpApi

        brokers, clusters = await _mesh(2)
        try:
            await asyncio.sleep(0.3)  # a heartbeat round
            api = HttpApi(brokers[0].ctx, "127.0.0.1", 0)
            status, body, _ = await api._route("GET", "/api/v1/cluster", b"")
            assert status == 200 and body["enabled"]
            assert body["mode"] == "broadcast"
            peers = {r["node"]: r for r in body["membership"]["peers"]}
            assert peers[2]["state"] == "ALIVE"
            assert set(body["digests"]) == {"retain", "subs"}
            assert "anti_entropy" in body["membership"]
        finally:
            await _teardown(brokers, clusters)

    asyncio.run(run())
