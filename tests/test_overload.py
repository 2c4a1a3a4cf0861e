"""Overload-control subsystem tests (broker/overload.py).

Covers the acceptance list: watermark state machine units (hysteresis — no
flapping at the boundary), the admission token bucket vs a float oracle,
circuit-breaker transitions, the slow-consumer E2E (QoS0 shed with reason
code, QoS1 flow-controlled, session survives), the two-node dead-peer E2E
(open circuit fails fast + bounded, half-open → closed on recovery), the
DeliverQueue.throttle burst-then-sustain timing (satellite), and the pin
that ``[overload] enable = false`` changes no behavior.
"""

import asyncio
import random
import time

import pytest

from rmqtt_tpu.broker.codec import MqttCodec, packets as pk
from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.fitter import FitterConfig
from rmqtt_tpu.broker.overload import (
    CircuitBreaker,
    OverloadState,
    TokenBucket,
    Watermark,
    WatermarkMachine,
)
from rmqtt_tpu.broker.queue import DeliverQueue
from rmqtt_tpu.broker.server import MqttBroker

from tests.mqtt_client import TestClient

RC_QUOTA_EXCEEDED = 0x97


# ------------------------------------------------------------ token bucket
def test_token_bucket_property_vs_oracle():
    """10k random (advance, take) ops: the bucket must agree with an exact
    continuous-accounting float oracle on every decision."""
    rng = random.Random(7)
    t = [100.0]
    rate, burst = 5.0, 12.0
    b = TokenBucket(rate, burst, clock=lambda: t[0])
    tokens, last = burst, t[0]
    for i in range(10_000):
        t[0] += rng.random() * rng.choice([0.0, 0.01, 0.1, 1.0])
        n = rng.choice([1, 1, 1, 2, 5])
        tokens = min(burst, tokens + (t[0] - last) * rate)
        last = t[0]
        want = tokens >= n
        if want:
            tokens -= n
        assert b.allow(n) == want, f"op {i}: oracle {want}, tokens {tokens}"


def test_token_bucket_burst_then_refill():
    t = [0.0]
    b = TokenBucket(10.0, 3.0, clock=lambda: t[0])
    assert [b.allow() for _ in range(4)] == [True, True, True, False]
    t[0] += 0.1  # one token refilled
    assert b.allow() and not b.allow()
    t[0] += 100.0  # cap at burst, never beyond
    assert [b.allow() for _ in range(4)] == [True, True, True, False]


def test_token_bucket_fractional_rate_still_admits():
    """A sub-1/s rate with the default burst must floor the bucket at one
    whole token — burst = rate would cap below allow()'s 1.0 cost and
    refuse everything forever."""
    t = [0.0]
    b = TokenBucket(0.5, clock=lambda: t[0])  # one op per 2 s, burst unset
    assert b.allow()
    assert not b.allow()
    t[0] += 1.0  # half a token: still short
    assert not b.allow()
    t[0] += 1.0  # a full token accrued
    assert b.allow()


# ------------------------------------------------------- watermark machine
def _machine(**kw):
    return WatermarkMachine([Watermark("q", 0.5, 0.9)], **kw)


def test_watermark_escalates_immediately_and_deescalates_with_hold():
    m = _machine(clear_ratio=0.8, hold=2)
    assert m.update({"q": 0.1}) == OverloadState.NORMAL
    assert m.update({"q": 0.5}) == OverloadState.ELEVATED  # at the mark
    assert m.update({"q": 0.95}) == OverloadState.CRITICAL  # jump is immediate
    assert m.trigger == "q"
    # below critical-clear (0.72) but above elevated-clear (0.4): must step
    # down one tier only, and only after `hold` consecutive clear samples
    assert m.update({"q": 0.5}) == OverloadState.CRITICAL
    assert m.update({"q": 0.5}) == OverloadState.ELEVATED
    # fully clear: two samples below 0.4 → NORMAL
    assert m.update({"q": 0.3}) == OverloadState.ELEVATED
    assert m.update({"q": 0.3}) == OverloadState.NORMAL
    assert m.trigger is None


def test_watermark_no_flap_at_boundary():
    """A signal oscillating exactly around the watermark pins the state:
    the clear band (clear_ratio * mark) keeps it ELEVATED, so the state
    changes ONCE, not per oscillation."""
    m = _machine(clear_ratio=0.85, hold=2)
    changes = 0
    prev = m.state
    for i in range(100):
        v = 0.51 if i % 2 == 0 else 0.49  # above/below the 0.5 mark
        st = m.update({"q": v})
        if st != prev:
            changes += 1
            prev = st
    assert prev == OverloadState.ELEVATED
    assert changes == 1, f"state flapped {changes} times"


def test_watermark_hold_requires_consecutive_clears():
    m = _machine(clear_ratio=0.8, hold=3)
    m.update({"q": 0.6})
    assert m.state == OverloadState.ELEVATED
    # clear, clear, spike, clear, clear, clear: the spike resets the run
    for v, want in [(0.1, 1), (0.1, 1), (0.45, 1), (0.1, 1), (0.1, 1), (0.1, 0)]:
        assert m.update({"q": v}) == OverloadState(want), v


def test_watermark_disabled_signal_and_missing_values():
    m = WatermarkMachine([Watermark("off", 0.0, 0.0), Watermark("on", 1.0, 2.0)])
    assert m.update({"off": 99.0}) == OverloadState.NORMAL  # 0 disables
    assert m.update({"on": 1.5}) == OverloadState.ELEVATED
    assert m.update({}) == OverloadState.ELEVATED  # missing value: no change


# --------------------------------------------------------- circuit breaker
def test_breaker_transitions_closed_open_halfopen_closed():
    t = [0.0]
    b = CircuitBreaker(threshold=3, cooldown=1.0, max_cooldown=8.0,
                       backoff=2.0, jitter=0.0, clock=lambda: t[0])
    assert b.state == b.CLOSED
    b.fail(); b.fail()
    assert b.state == b.CLOSED and b.allow()
    b.fail()  # third consecutive failure opens
    assert b.state == b.OPEN and not b.allow() and b.opens == 1
    t[0] += 0.5
    assert not b.allow() and 0.4 < b.remaining() <= 0.5
    t[0] += 0.6  # past cooldown: next allow() is the half-open probe
    assert b.allow() and b.state == b.HALF_OPEN
    b.ok()
    assert b.state == b.CLOSED and b.allow()


def test_breaker_halfopen_failure_backs_off_exponentially_with_cap():
    t = [0.0]
    b = CircuitBreaker(threshold=1, cooldown=1.0, max_cooldown=4.0,
                       backoff=2.0, jitter=0.0, clock=lambda: t[0])
    b.fail()
    assert b.state == b.OPEN
    expect = [2.0, 4.0, 4.0, 4.0]  # doubles, then pinned at max_cooldown
    for want in expect:
        t[0] += b.remaining() + 0.01
        assert b.allow() and b.state == b.HALF_OPEN
        b.fail()  # probe failed → reopen, backed off
        assert b.state == b.OPEN
        assert b.remaining() == pytest.approx(want, abs=0.02)
    # a successful probe resets the backoff to the base cooldown
    t[0] += b.remaining() + 0.01
    assert b.allow()
    b.ok()
    b.fail()
    assert b.remaining() == pytest.approx(1.0, abs=0.02)


def test_breaker_rejections_never_rearm_and_jitter_bounded():
    t = [0.0]
    b = CircuitBreaker(threshold=1, cooldown=1.0, jitter=0.0, clock=lambda: t[0])
    b.fail()
    for _ in range(50):  # a hot retry loop hammering an open breaker
        t[0] += 0.01
        b.allow()
        b.fail()  # failures observed while open must not re-arm
    t[0] += 0.6
    assert b.allow(), "rejected/failed-while-open attempts re-armed the cooldown"
    # jitter stays within its fraction
    rng = random.Random(3)
    for _ in range(100):
        c = CircuitBreaker(threshold=1, cooldown=1.0, jitter=0.25,
                           clock=lambda: 0.0, rng=rng)
        c.fail()
        assert 1.0 <= c._cooldown_cur <= 1.25


def test_breaker_wait_ready_does_not_inflate_rejected():
    """The drain-pump gate sleeps on remaining() instead of polling
    allow(), so `rejected` keeps counting real refused calls only."""

    async def run():
        b = CircuitBreaker(threshold=1, cooldown=0.15, jitter=0.0)
        assert b.allow()  # closed: immediate, no counting
        b.fail()
        assert b.state == b.OPEN
        t0 = time.monotonic()
        await b.wait_ready()  # parks through the cooldown, then probes
        assert time.monotonic() - t0 >= 0.1
        assert b.state == b.HALF_OPEN
        assert b.rejected == 0, b.rejected

    asyncio.run(asyncio.wait_for(run(), 30))


# --------------------------------------------- DeliverQueue throttle timing
def test_throttle_burst_then_sustain_timing():
    """Burst passes instantly; past it the consumer is paced at rate.
    Pre-fix, the un-anchored accrual clock double-counted each sleep and
    sustained at ~2x the configured rate — this pins the fix."""

    async def run():
        rate = 50.0
        q = DeliverQueue(maxlen=10_000, rate_limit=rate)
        for i in range(200):
            q.push(i)
        t0 = time.monotonic()
        for _ in range(int(rate)):  # the full burst allowance
            await q.throttle()
            q.pop()
        burst_elapsed = time.monotonic() - t0
        assert burst_elapsed < 0.5, f"burst throttled: {burst_elapsed:.3f}s"
        n_sustain = 25
        t1 = time.monotonic()
        for _ in range(n_sustain):
            await q.throttle()
            q.pop()
        sustained = time.monotonic() - t1
        # 25 tokens at 50/s is >= 0.5s; the drift bug finished in ~0.25s
        assert sustained >= n_sustain / rate * 0.8, (
            f"sustained rate drifted fast: {n_sustain} in {sustained:.3f}s")
        assert sustained < n_sustain / rate * 4.0, (
            f"sustained rate too slow: {n_sustain} in {sustained:.3f}s")

    asyncio.run(asyncio.wait_for(run(), 30))


def test_throttle_long_run_rate_accuracy():
    async def run():
        rate = 200.0
        q = DeliverQueue(maxlen=10_000, rate_limit=rate)
        for i in range(1000):
            q.push(i)
        # drain the burst so the window below measures pure sustain
        for _ in range(int(rate)):
            await q.throttle()
            q.pop()
        n = 100
        t0 = time.monotonic()
        for _ in range(n):
            await q.throttle()
            q.pop()
        elapsed = time.monotonic() - t0
        eff = n / elapsed
        assert eff <= rate * 1.3, f"effective rate {eff:.0f}/s vs limit {rate}"

    asyncio.run(asyncio.wait_for(run(), 30))


# ------------------------------------------------------------- E2E helpers
async def _raw_connect(port, cid, version=pk.V311, keepalive=600,
                       rcvbuf=None):
    if rcvbuf:
        import socket as _s

        # shrink the client's receive window BEFORE connect (the kernel
        # scales the window from the buffer at handshake): the flood's
        # backlog must land in the broker's deliver queue — the thing the
        # overload controller manages — not in kernel socket buffering
        sk = _s.socket()
        sk.setsockopt(_s.SOL_SOCKET, _s.SO_RCVBUF, rcvbuf)
        sk.setblocking(False)
        await asyncio.get_running_loop().sock_connect(
            sk, ("127.0.0.1", port))
        reader, writer = await asyncio.open_connection(sock=sk)
    else:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
    codec = MqttCodec(version)
    writer.write(codec.encode(pk.Connect(client_id=cid, protocol=version,
                                         keepalive=keepalive)))
    await writer.drain()
    while True:
        data = await reader.read(4096)
        assert data, "peer closed before CONNACK"
        pkts = codec.feed(data)
        if pkts:
            assert isinstance(pkts[0], pk.Connack)
            return reader, writer, codec


def _overload_cfg(**kw):
    base = dict(
        port=0,
        overload_enable=True,
        overload_sample_interval=0.02,
        overload_mqueue_elevated=0.3,
        overload_mqueue_critical=0.95,
        overload_shed_slow_fraction=0.5,
        overload_hold=2,
        fitter=FitterConfig(max_mqueue=50, max_inflight=8),
    )
    base.update(kw)
    return BrokerConfig(**base)


async def _flood_slow_consumer(broker, payload=b"x" * 2048):
    """Subscriber that never reads + a QoS0 flood; returns the publisher
    client (still connected). The subscriber's socket backpressure stalls
    its deliver loop, so its bounded deliver queue fills.

    Deterministic on any host: explicit SO_RCVBUF/SO_SNDBUF on BOTH ends
    of the subscriber connection, and the blast sized from the values the
    kernel actually granted (getsockopt — Linux doubles the request) plus
    the deliver-queue capacity and the asyncio write-buffer high-water
    slack, so queue overflow cannot depend on host socket-buffer defaults
    (the PR 12-era flake: default-autotuned buffers absorbed the whole
    flood and the queue never filled)."""
    import socket as _socket

    req_buf = 32 * 1024
    sr, sw, scodec = await _raw_connect(broker.port, "ov-sub",
                                        rcvbuf=req_buf)
    sw.write(scodec.encode(pk.Subscribe(1, [("ov/#", pk.SubOpts(qos=1))])))
    await sw.drain()
    # deliberately NOT reading from sr anymore: slow consumer.
    # Wait for the broker-side session, then shrink ITS send buffer too.
    deadline = time.monotonic() + 10.0
    srv = None
    while time.monotonic() < deadline:
        srv = broker.ctx.registry.get("ov-sub")
        if srv is not None and "ov/#" in srv.subscriptions:
            break
        await asyncio.sleep(0.01)
    assert srv is not None and "ov/#" in srv.subscriptions
    srv_sock = srv.state.writer.get_extra_info("socket")
    assert srv_sock is not None
    srv_sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_SNDBUF, req_buf)
    sndbuf = srv_sock.getsockopt(_socket.SOL_SOCKET, _socket.SO_SNDBUF)
    rcvbuf = sw.get_extra_info("socket").getsockopt(
        _socket.SOL_SOCKET, _socket.SO_RCVBUF)
    # size the blast from the CONFIGURED values: kernel buffers both ends
    # + the broker's bounded deliver queue + asyncio transport high-water
    # slack, 3x over so overflow is unconditional
    queue_bytes = broker.ctx.cfg.fitter.max_mqueue * len(payload)
    absorb = sndbuf + rcvbuf + queue_bytes + 256 * 1024
    n_msgs = max(800, 3 * absorb // len(payload))
    pub = await TestClient.connect(broker.port, "ov-pub")
    for i in range(n_msgs):
        await pub.publish("ov/t", payload, qos=0, wait_ack=False)
        if i % 64 == 0:
            await asyncio.sleep(0.005)  # let the sampler run mid-flood
    # wait until the broker's ingress has actually processed the flood (its
    # read loop lags the client's writes under backpressure)
    deadline = time.monotonic() + 20.0
    while (broker.ctx.metrics.get("publish.received") < n_msgs
           and time.monotonic() < deadline):
        await asyncio.sleep(0.05)
    await asyncio.sleep(0.2)  # a couple more sampler periods
    return pub, (sr, sw)


def test_e2e_slow_consumer_sheds_qos0_flow_controls_qos1():
    """ELEVATED under a 10:1-style flood: QoS0 to the slow consumer is shed
    with the reason label, QoS1 stays inside the flow-control window, and
    the subscriber session survives."""

    async def run():
        broker = MqttBroker(ServerContext(_overload_cfg()))
        await broker.start()
        try:
            pub, (sr, sw) = await _flood_slow_consumer(broker)
            ctx = broker.ctx
            assert ctx.overload.state >= OverloadState.ELEVATED, (
                ctx.overload.last_signals)
            m = ctx.metrics.to_json()
            assert m.get("messages.dropped.shed_qos0", 0) > 0, m
            # aggregate keeps counting every labeled drop
            labeled = sum(v for k, v in m.items()
                          if k.startswith("messages.dropped."))
            assert m["messages.dropped"] == labeled
            # QoS1 to the same slow consumer: accepted, flow-controlled. Its
            # queue is full and it takes nothing, so the first PUBACK is
            # held for the outbound window's retry interval (shortened
            # here) before the drop policy takes over again
            ctx.registry.get("ov-sub").out_inflight.retry_interval = 0.2
            for _ in range(30):
                await pub.publish("ov/t", b"q1", qos=1)
            sub = ctx.registry.get("ov-sub")
            assert sub is not None and sub.connected, "session did not survive"
            assert len(sub.out_inflight) <= sub.limits.max_inflight
            assert len(sub.deliver_queue) <= sub.limits.max_mqueue
            # the publisher's session never shed (it has no backlog)
            assert ctx.registry.get("ov-pub").connected
            snap = ctx.overload.snapshot()
            assert snap["state"] in ("ELEVATED", "CRITICAL")
            assert snap["shed"]["qos0"] == m["messages.dropped.shed_qos0"]
            await pub.disconnect_clean()
            sw.close()
        finally:
            await broker.stop()

    asyncio.run(asyncio.wait_for(run(), 60))


def test_e2e_disabled_is_zero_behavior_change():
    """The enable=false pin: the same flood produces ONLY the seed-era
    queue-full drops — no shed, no admission refusals, no transitions, no
    sampling task — while the observability shape stays present."""

    async def run():
        broker = MqttBroker(ServerContext(BrokerConfig(
            port=0, fitter=FitterConfig(max_mqueue=50, max_inflight=8))))
        await broker.start()
        try:
            ctx = broker.ctx
            assert not ctx.overload.enabled
            assert ctx.overload._task is None, "sampler ran while disabled"
            pub, (sr, sw) = await _flood_slow_consumer(broker)
            m = ctx.metrics.to_json()
            assert m.get("messages.dropped", 0) > 0  # the old drop behavior
            assert m.get("messages.dropped.queue_full", 0) == m["messages.dropped"]
            assert "messages.dropped.shed_qos0" not in m
            assert "messages.dropped.rate_limited" not in m
            assert m.get("overload.transitions", 0) == 0
            assert ctx.overload.state == OverloadState.NORMAL
            # admission is wide open
            assert ctx.overload.admit_connect(1883)
            assert ctx.overload.admit_publish("anyone")
            assert ctx.overload.allow_retained_scan()
            assert ctx.overload.allow_sys()
            assert ctx.overload.allow_noncritical()
            # shape-stable surfaces
            snap = ctx.overload.snapshot()
            assert snap["enabled"] is False and snap["state"] == "NORMAL"
            st = ctx.stats()
            assert st.overload_state == 0 and st.overload_transitions == 0
            await pub.disconnect_clean()
            sw.close()
        finally:
            await broker.stop()

    asyncio.run(asyncio.wait_for(run(), 60))


def test_e2e_publish_rate_limit_reason_codes():
    """v5 gets Quota Exceeded (0x97) on PUBACK past the bucket; v3 (no
    per-publish reason code) is disconnected."""

    async def run():
        broker = MqttBroker(ServerContext(BrokerConfig(
            port=0, overload_enable=True, overload_sample_interval=30.0,
            overload_publish_rate_limit=2.0, overload_publish_burst=2.0)))
        await broker.start()
        try:
            c5 = await TestClient.connect(broker.port, "rl-v5", version=pk.V5)
            acks = [await c5.publish(f"r/{i}", b"p", qos=1) for i in range(3)]
            assert acks[0].reason_code != RC_QUOTA_EXCEEDED
            assert acks[2].reason_code == RC_QUOTA_EXCEEDED
            m = broker.ctx.metrics.to_json()
            assert m.get("messages.dropped.rate_limited", 0) >= 1
            await c5.disconnect_clean()
            # fresh client id, v3: third publish closes the connection
            c3 = await TestClient.connect(broker.port, "rl-v3")
            await c3.publish("r/a", b"p", qos=0, wait_ack=False)
            await c3.publish("r/b", b"p", qos=0, wait_ack=False)
            await c3.publish("r/c", b"p", qos=0, wait_ack=False)
            await asyncio.wait_for(c3.closed.wait(), 5.0)
            await c3.close()
        finally:
            await broker.stop()

    asyncio.run(asyncio.wait_for(run(), 60))


def test_e2e_critical_refuses_connects_with_reason_code():
    async def run():
        broker = MqttBroker(ServerContext(BrokerConfig(
            port=0, overload_enable=True, overload_sample_interval=30.0)))
        await broker.start()
        try:
            ctx = broker.ctx
            ctx.overload.machine.state = OverloadState.CRITICAL
            c5 = await TestClient.connect(broker.port, "crit-v5", version=pk.V5)
            assert c5.connack.reason_code == RC_QUOTA_EXCEEDED
            await c5.close()
            c3 = await TestClient.connect(broker.port, "crit-v3")
            assert c3.connack.reason_code == 3  # v3 Server Unavailable
            await c3.close()
            assert ctx.metrics.get("handshake.refused_overload") == 2
            # back to NORMAL: connects flow again
            ctx.overload.machine.state = OverloadState.NORMAL
            ok = await TestClient.connect(broker.port, "crit-ok", version=pk.V5)
            assert ok.connack.reason_code == 0
            await ok.disconnect_clean()
        finally:
            await broker.stop()

    asyncio.run(asyncio.wait_for(run(), 60))


def test_e2e_connect_token_bucket_per_listener():
    async def run():
        broker = MqttBroker(ServerContext(BrokerConfig(
            port=0, overload_enable=True, overload_sample_interval=30.0,
            overload_connect_rate_limit=3.0, overload_connect_burst=3.0)))
        await broker.start()
        try:
            codes = []
            for i in range(5):
                c = await TestClient.connect(broker.port, f"cb-{i}", version=pk.V5)
                codes.append(c.connack.reason_code)
                await (c.disconnect_clean() if c.connack.reason_code == 0 else c.close())
            assert codes[:3] == [0, 0, 0]
            assert RC_QUOTA_EXCEEDED in codes[3:], codes
        finally:
            await broker.stop()

    asyncio.run(asyncio.wait_for(run(), 60))


# ------------------------------------------------------ two-node circuit E2E
def test_e2e_two_node_dead_peer_circuit_opens_and_recovers():
    """Broadcast cluster: a dead peer opens the circuit (publishes keep
    completing fast — the forward path is bounded, not hung); when the peer
    returns, the half-open probe closes the breaker and cross-node delivery
    resumes."""
    from rmqtt_tpu.cluster.broadcast import BroadcastCluster
    from rmqtt_tpu.cluster.transport import ClusterServer, PeerClient

    async def run():
        b1 = MqttBroker(ServerContext(BrokerConfig(port=0, node_id=1, cluster=True)))
        b2 = MqttBroker(ServerContext(BrokerConfig(port=0, node_id=2, cluster=True)))
        await b1.start()
        await b2.start()
        c1 = BroadcastCluster(b1.ctx, ("127.0.0.1", 0), [])
        c2 = BroadcastCluster(b2.ctx, ("127.0.0.1", 0), [])
        await c1.start()
        await c2.start()
        try:
            c2_port = c2.bound_port
            p12 = PeerClient(2, "127.0.0.1", c2_port, timeout=2.0)
            p12.breaker = CircuitBreaker(threshold=2, cooldown=0.4,
                                         max_cooldown=2.0, jitter=0.0)
            b1.ctx.overload.register_breaker("cluster.peer.2", p12.breaker)
            c1.peers[2] = p12
            c1.bcast.peers = [p12]
            p21 = PeerClient(1, "127.0.0.1", c1.bound_port)
            c2.peers[1] = p21
            c2.bcast.peers = [p21]

            sub = await TestClient.connect(b2.port, "n2-sub")
            await sub.subscribe("x/#", qos=1)
            pub = await TestClient.connect(b1.port, "n1-pub")
            await pub.publish("x/alive", b"before", qos=1)
            assert (await sub.recv(timeout=10)).payload == b"before"
            assert p12.breaker.state == p12.breaker.CLOSED

            # kill node 2's cluster RPC server: the peer is now dead
            await c2.server.stop()
            for i in range(4):
                t0 = time.monotonic()
                await pub.publish(f"x/dead{i}", b"lost", qos=1)
                assert time.monotonic() - t0 < 3.0, "publish hung on dead peer"
            assert p12.breaker.state == p12.breaker.OPEN
            rejected_before = p12.breaker.rejected
            # while open: forwards fail FAST (no connect timeout per publish)
            t0 = time.monotonic()
            for i in range(10):
                await pub.publish(f"x/fast{i}", b"lost", qos=1)
            assert time.monotonic() - t0 < 1.5, "open circuit still paying timeouts"
            assert p12.breaker.rejected > rejected_before
            assert b1.ctx.stats().overload_open_breakers >= 1

            # the peer comes back on the same port
            c2.server = ClusterServer("127.0.0.1", c2_port, c2._on_message)
            await c2.server.start()
            await asyncio.sleep(p12.breaker.remaining() + 0.1)
            delivered = None
            for i in range(6):  # half-open probe → closed, delivery resumes
                await pub.publish("x/back", b"after", qos=1)
                try:
                    delivered = await sub.recv(timeout=2.0)
                    break
                except asyncio.TimeoutError:
                    await asyncio.sleep(p12.breaker.remaining() + 0.1)
            assert delivered is not None and delivered.payload == b"after"
            assert p12.breaker.state == p12.breaker.CLOSED
            assert p12.breaker.opens >= 1
            await sub.disconnect_clean()
            await pub.disconnect_clean()
        finally:
            for c in (c1, c2):
                await c.stop()
            for b in (b1, b2):
                await b.stop()

    asyncio.run(asyncio.wait_for(run(), 90))


def test_e2e_qos2_dup_resend_bypasses_admission():
    """A DUP retransmit of an ALREADY-ACCEPTED QoS2 publish answers with
    the dedup PUBREC (success) even when the client's bucket is empty —
    refusing it would strand the in_qos2 entry forever."""

    async def run():
        broker = MqttBroker(ServerContext(BrokerConfig(
            port=0, overload_enable=True, overload_sample_interval=30.0,
            overload_publish_rate_limit=2.0, overload_publish_burst=2.0)))
        await broker.start()
        try:
            c = await TestClient.connect(broker.port, "q2", version=pk.V5)
            c.auto_pubrel = False  # hold the flow open at PUBREC
            await c._send(pk.Publish(topic="q/1", payload=b"a", qos=2, packet_id=1))
            rec1 = await c._wait(("pubrec", 1))
            assert rec1.reason_code != RC_QUOTA_EXCEEDED
            # drain the bucket; the NEXT new publish would be refused
            await c.publish("q/x", b"", qos=0, wait_ack=False)
            await c.publish("q/y", b"", qos=0, wait_ack=False)
            await asyncio.sleep(0.1)
            # DUP retransmit of the accepted pid: dedup PUBREC, no charge
            await c._send(pk.Publish(topic="q/1", payload=b"a", qos=2,
                                     packet_id=1, dup=True))
            rec2 = await c._wait(("pubrec", 1))
            assert rec2.reason_code != RC_QUOTA_EXCEEDED, hex(rec2.reason_code)
            await c.close()
        finally:
            await broker.stop()

    asyncio.run(asyncio.wait_for(run(), 60))


def test_publish_bucket_prune_drops_refilled_buckets():
    """The tick()-time prune must actually shrink the dict: buckets whose
    projected refill is full carry no state and are dropped (an id churn
    otherwise grows it unboundedly)."""

    async def run():
        ctx = ServerContext(BrokerConfig(
            port=0, overload_enable=True,
            overload_publish_rate_limit=100.0, overload_publish_burst=100.0))
        try:
            ov = ctx.overload
            for i in range(10_050):
                ov.admit_publish(f"churn-{i}")
            assert len(ov._publish_buckets) > 10_000
            # everyone idle long enough to refill: projected-full → pruned
            for b in ov._publish_buckets.values():
                b._last -= 10.0
            ov.tick()
            assert len(ov._publish_buckets) == 0, len(ov._publish_buckets)
            # an actively-limited client is KEPT across the prune
            for i in range(10_050):
                ov.admit_publish(f"churn2-{i}")
            hot = ov._publish_buckets["churn2-0"]
            hot.tokens = 0.0
            hot._last = time.monotonic() + 100.0  # no projected refill
            for cid, b in ov._publish_buckets.items():
                if cid != "churn2-0":
                    b._last -= 10.0
            ov.tick()
            assert list(ov._publish_buckets) == ["churn2-0"]
        finally:
            await ctx.stop()

    asyncio.run(asyncio.wait_for(run(), 30))


def test_cluster_peer_breakers_use_overload_config():
    """[overload] breaker_* knobs must reach the cluster transport: peers'
    breakers come from the controller registry, not hard-coded defaults."""
    from rmqtt_tpu.cluster.broadcast import BroadcastCluster

    async def run():
        ctx = ServerContext(BrokerConfig(
            port=0, cluster=True, overload_breaker_threshold=2,
            overload_breaker_cooldown=7.5))
        c = BroadcastCluster(ctx, ("127.0.0.1", 0), [(2, "127.0.0.1", 1)])
        p = c.peers[2]
        assert p.breaker.threshold == 2
        assert p.breaker.cooldown == 7.5
        assert ctx.overload.breakers["cluster.peer.2"] is p.breaker

    asyncio.run(asyncio.wait_for(run(), 30))


# ----------------------------------------------------------- config + misc
def test_conf_overload_section(tmp_path):
    from rmqtt_tpu import conf

    p = tmp_path / "rmqtt.toml"
    p.write_text(
        """
[overload]
enable = true
sample_interval = 0.5
queue_elevated = 0.4
mqueue_critical = 0.8
publish_rate_limit = 100.0
breaker_cooldown = 1.5
"""
    )
    s = conf.load(str(p))
    b = s.broker
    assert b.overload_enable is True
    assert b.overload_sample_interval == 0.5
    assert b.overload_queue_elevated == 0.4
    assert b.overload_mqueue_critical == 0.8
    assert b.overload_publish_rate_limit == 100.0
    assert b.overload_breaker_cooldown == 1.5
    # unknown keys in the section fail loud
    p.write_text("[overload]\nenabel = true\n")
    with pytest.raises(ValueError):
        conf.load(str(p))


def test_controller_tick_transitions_and_batch_shrink():
    """Driving tick() synchronously: a forced mqueue spike escalates,
    shrinks the routing batch window, then restores it on recovery."""

    async def run():
        ctx = ServerContext(_overload_cfg(overload_batch_shrink=4))
        ctx.start()
        try:
            ov = ctx.overload
            orig_batch = ctx.routing.max_batch
            from rmqtt_tpu.broker.types import ConnectInfo
            from rmqtt_tpu.router.base import Id

            sid = Id(1, "tick-c")
            sess, _ = await ctx.registry.take_or_create(
                ctx, sid, ConnectInfo(id=sid, protocol=pk.V311, keepalive=60,
                                      clean_start=True),
                ctx.fitter.fit(ConnectInfo(id=sid, protocol=pk.V311,
                                           keepalive=60, clean_start=True)),
                True,
            )
            sess.connected = True
            from rmqtt_tpu.broker.session import DeliverItem
            from rmqtt_tpu.broker.types import Message

            for i in range(sess.limits.max_mqueue):
                sess.deliver_queue.push(DeliverItem(
                    msg=Message(topic="t", payload=b"", qos=1, from_id=sid),
                    qos=1, retain=False, topic_filter="t"))
            assert ov.tick() >= OverloadState.ELEVATED
            assert ctx.routing.max_batch == max(1, orig_batch // 4)
            assert ctx.metrics.get("overload.transitions") >= 1
            sess.deliver_queue.drain()
            for _ in range(ov.machine.hold + 1):  # hysteresis hold
                ov.tick()
            assert ov.state == OverloadState.NORMAL
            assert ctx.routing.max_batch == orig_batch
        finally:
            await ctx.stop()

    asyncio.run(asyncio.wait_for(run(), 30))
