"""Syscall-batched data plane (broker/egress.py): coalesced egress,
keepalive timer wheel, native PUBLISH encode.

The coalescer is default-ON and claims zero behavior change at the
protocol level, so the load-bearing pins here are the *identity* ones:
byte-identical frames in enqueue order (acks can never reorder ahead of
the PUBLISH they follow — one FIFO vector serves the connection), the
`RMQTT_EGRESS_COALESCE=0` / `[network]` kill-switch restoring the exact
legacy byte stream, the slow-consumer drain gate still engaging, and
`buffers_until_drain` writers (WsWriter) bypassing the coalescer so
their flush-on-drain contract holds. Since PR 28 a coalesced flush takes
one of two paths, chosen per connection from what the hub observes: the
asyncio transport ("loop"), or the native egress thread ("native": a plain
stream socket, its transport idle, the runtime library loaded). The
identity pins run over both, and the native path's own hazards (a partial
write, a close or an error with a write in flight, an fd the kernel may
reuse) have cases of their own. The timer wheel must preserve
keepalive *semantics* (idle eviction, traffic re-arms, v5
server-keep-alive override) while collapsing task count to O(1) per
worker."""

import asyncio
import os
import socket
import struct

import pytest

from rmqtt_tpu.broker import egress as egress_mod
from rmqtt_tpu.broker.codec import MqttCodec, packets as pk, props as P
from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.egress import EgressBuf, EgressHub, KeepaliveWheel
from rmqtt_tpu.broker.metrics import Metrics
from rmqtt_tpu.broker.server import MqttBroker

from tests.mqtt_client import TestClient


def run_async(fn, timeout=30.0):
    asyncio.run(asyncio.wait_for(fn(), timeout=timeout))


def _need_native():
    if not EgressHub().native:
        pytest.skip("native runtime (egress.cc) unavailable")


@pytest.fixture(params=["loop", "native"])
def path(request, monkeypatch):
    """Which coalesced path a broker's plain-TCP sessions take. "loop": no
    connection is eligible for the native thread, so every flush is the
    hub's write through the asyncio transport (what TLS, a busy transport
    or an absent library get). "native": every eligible flush goes to the
    thread — ``_MIN_JOB`` 1, so a turn of a single connection too."""
    if request.param == "native":
        _need_native()
        monkeypatch.setattr(egress_mod, "_MIN_JOB", 1)
    else:
        monkeypatch.setattr(egress_mod, "_offloop_fd", lambda writer: -1)
    return request.param


def _offloop(b) -> int:
    return b.ctx.metrics.get("net.egress_offloop_flushes")


def _check_path(b, path) -> None:
    """The broker's flushes went where the case says they go."""
    if path == "native":
        assert _offloop(b) > 0, "no flush took the native thread"
        assert _offloop(b) <= b.ctx.metrics.get("net.egress_flushes")
    else:
        assert _offloop(b) == 0
        assert b.ctx.egress_hub.thread_stats() == (0.0, 0, 0)


# ------------------------------------------------------------ EgressBuf


class _RecWriter:
    """Transport-shaped recorder: every write/writelines call logged."""

    def __init__(self):
        self.calls = []  # ("write"|"writelines", bytes)
        self.closed = False

    def write(self, data):
        self.calls.append(("write", bytes(data)))

    def writelines(self, vec):
        self.calls.append(("writelines", b"".join(vec)))

    def close(self):
        self.closed = True


def test_egress_ordering_oracle_across_ticks():
    """Frames come out byte-identical and in enqueue order, however the
    tick boundaries fall — including ack frames queued behind their
    PUBLISH (the no-reorder guarantee is FIFO of one shared vector)."""

    async def run():
        w = _RecWriter()
        m = Metrics()
        eb = EgressBuf(w, m)
        frames = [b"PUB|%d|" % i + bytes([i]) * i for i in range(1, 40)]
        frames.append(b"PUBACK|1")  # ack behind its publish
        for i, f in enumerate(frames):
            eb.feed(f)
            if i % 7 == 6:  # let the scheduled tick flush run mid-stream
                await asyncio.sleep(0)
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        got = b"".join(data for _, data in w.calls)
        assert got == b"".join(frames), "bytes or order changed"
        # multi-frame ticks went through ONE vectored call each
        assert any(kind == "writelines" for kind, _ in w.calls)
        assert m.get("net.egress_frames") == len(frames)
        assert m.get("net.egress_flushes") == len(w.calls)
        assert m.get("net.egress_bytes") == len(got)
        assert m.get("net.egress_coalesced") == len(frames) - len(w.calls)

    run_async(run)


def test_egress_flush_failure_closes_writer():
    """A failed vectored write may have left a partial frame on the wire:
    the buf must close the writer (read loop reaps the session), never
    retry — a retried tail would desync the stream."""

    async def run():
        class _Boom(_RecWriter):
            def writelines(self, vec):
                raise ConnectionResetError

        w = _Boom()
        eb = EgressBuf(w, Metrics())
        eb.feed(b"a")
        eb.feed(b"b")
        eb.flush()
        assert w.closed, "flush failure must close the writer"
        eb.feed(b"c")
        eb.flush()
        assert all(kind != "write" for kind, _ in w.calls), \
            "no write may follow a failed flush"

    run_async(run)


async def _read_frame(reader) -> bytes:
    """One whole MQTT frame, raw: fixed header byte + varint + body."""
    raw = await reader.readexactly(1)
    length, shift = 0, 0
    while True:
        b = await reader.readexactly(1)
        raw += b
        length |= (b[0] & 0x7F) << shift
        shift += 7
        if not b[0] & 0x80:
            break
    return raw + (await reader.readexactly(length) if length else b"")


async def _raw_sub_stream(port, cid, topic, n_expect):
    """Raw-socket subscriber: returns the exact broker→client byte
    stream after SUBACK, once ``n_expect`` PUBLISH frames arrived."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    codec = MqttCodec(pk.V311)
    writer.write(codec.encode(pk.Connect(client_id=cid)))
    writer.write(codec.encode(pk.Subscribe(1, [(topic, pk.SubOpts(qos=0))])))
    await writer.drain()
    await _read_frame(reader)  # CONNACK
    await _read_frame(reader)  # SUBACK
    stream = b""
    decode = MqttCodec(pk.V311)
    seen = 0
    while seen < n_expect:
        chunk = await reader.read(65536)
        assert chunk, "subscriber stream closed early"
        stream += chunk
        seen += len(decode.feed(chunk))
    writer.close()
    return stream


async def _stream_leg(coalesce, n_subs=1, check=None):
    """→ the raw broker→client streams of ``n_subs`` subscribers of one
    topic over 20 publishes."""
    b = MqttBroker(ServerContext(BrokerConfig(
        port=0, egress_coalesce=coalesce)))
    await b.start()
    try:
        tasks = [asyncio.create_task(
            _raw_sub_stream(b.port, "ks-sub%d" % i, "ks/t", 20))
            for i in range(n_subs)]
        await asyncio.sleep(0.3)  # SUBSCRIBEs land before publishes
        c = await TestClient.connect(b.port, "ks-pub")
        for i in range(20):
            await c.publish("ks/t", b"payload-%03d" % i, qos=0,
                            wait_ack=False)
        streams = [await asyncio.wait_for(t, 10.0) for t in tasks]
        await c.disconnect_clean()
        if check is not None:
            _check_path(b, check)
        return streams
    finally:
        await b.stop()


def test_coalesce_kill_switch_byte_identical(path):
    """The same publish sequence produces the byte-identical subscriber
    stream with the coalescer on (default; either path) and off
    (`egress_coalesce` false — the `RMQTT_EGRESS_COALESCE=0` path resolves
    into the same ctx flag, pinned in test_kill_switch_env_overrides_conf
    below)."""

    async def run():
        on = await _stream_leg(True, check=path)
        off = await _stream_leg(False)
        assert on == off, "coalescer changed the wire bytes"

    run_async(run)


def test_native_streams_identical_across_64_connections():
    """A fan-out of 64: every turn hands the thread one job of many
    connections (the default ``_MIN_JOB``). Each connection's stream is
    byte for byte, frame after frame, what the legacy per-frame writer
    puts on the wire."""
    _need_native()

    async def run():
        native = await _stream_leg(True, 64, check="native")
        legacy = await _stream_leg(False, 64)
        assert len(native) == 64
        for i, (a, b) in enumerate(zip(native, legacy)):
            assert a == b, f"connection {i}: the stream changed"

    run_async(run, timeout=60.0)


def test_kill_switch_env_overrides_conf(monkeypatch):
    """RMQTT_EGRESS_COALESCE=0 / RMQTT_KEEPALIVE_WHEEL=0 AND with the
    TOML knobs: a config file can never re-enable a path the operator
    killed via env."""
    monkeypatch.setenv("RMQTT_EGRESS_COALESCE", "0")
    monkeypatch.setenv("RMQTT_KEEPALIVE_WHEEL", "0")
    ctx = ServerContext(BrokerConfig(egress_coalesce=True,
                                     keepalive_wheel=True))
    assert ctx.egress_coalesce is False
    assert ctx.keepalive_wheel is None
    monkeypatch.delenv("RMQTT_EGRESS_COALESCE")
    monkeypatch.delenv("RMQTT_KEEPALIVE_WHEEL")
    ctx = ServerContext(BrokerConfig())
    assert ctx.egress_coalesce is True
    assert ctx.keepalive_wheel is not None


def test_qos12_ack_flow_ordered_under_coalescer(path):
    """QoS1/2 control frames share the subscriber's coalesced vector with
    its PUBLISH deliveries: the full exactly-once flow must complete and
    payload order must hold across flush ticks (and across the frames that
    wait in the vector behind a native write in flight)."""

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        try:
            sub = await TestClient.connect(b.port, "ord-sub")
            await sub.subscribe("ord/t", qos=2)
            pub = await TestClient.connect(b.port, "ord-pub")
            n = 30
            for i in range(n):
                await pub.publish("ord/t", b"s%04d" % i, qos=2)
            got = [await sub.recv(timeout=10.0) for _ in range(n)]
            assert [p.payload for p in got] == [b"s%04d" % i
                                               for i in range(n)]
            assert all(p.qos == 2 for p in got)
            await sub.expect_nothing()  # exactly once
            await sub.disconnect_clean()
            await pub.disconnect_clean()
            _check_path(b, path)
        finally:
            await b.stop()

    run_async(run)


def test_slow_consumer_still_drains(path):
    """Regression for the send_raw high-water gate: the coalescer counts
    its own pending bytes plus the transport buffer, so a subscriber
    that stops reading still pushes the deliver loop into flush+drain()
    (slow-consumer backpressure) instead of buffering without bound."""

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, egress_high_water=2048)))
        await b.start()
        try:
            codec = MqttCodec(pk.V311)
            reader, writer = await asyncio.open_connection("127.0.0.1", b.port)
            writer.write(codec.encode(pk.Connect(client_id="slow-sub")))
            writer.write(codec.encode(
                pk.Subscribe(1, [("slow/t", pk.SubOpts(qos=0))])))
            await writer.drain()
            await reader.read(16)  # CONNACK+SUBACK; then stop reading
            pub = await TestClient.connect(b.port, "slow-pub")
            for i in range(128):
                await pub.publish("slow/t", bytes(4096), qos=0,
                                  wait_ack=False)
                if b.ctx.metrics.get("net.egress_drains"):
                    break
                await asyncio.sleep(0)
            await asyncio.sleep(0.3)
            assert b.ctx.metrics.get("net.egress_drains") > 0, \
                "slow consumer never hit the drain gate"
            writer.close()
            await pub.disconnect_clean()
            _check_path(b, path)
        finally:
            await b.stop()

    run_async(run)


def test_ws_writer_bypasses_coalescer():
    """WsWriter only flushes its frame buffer on drain(); the coalescer's
    tick flush never drains, so WS sessions must stay on the legacy
    per-frame path (and still roundtrip)."""
    from tests.test_transports import WsTestClient

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0, ws_port=0)))
        await b.start()
        try:
            ws = await WsTestClient.connect(b.ws_port, "ws-bypass")
            state = b.ctx.registry._sessions["ws-bypass"].state
            assert state._egress is None, \
                "buffers_until_drain writer got a coalescer"
            tcp = await TestClient.connect(b.port, "ws-peer")
            assert (b.ctx.registry._sessions["ws-peer"].state._egress
                    is not None), "plain TCP session should coalesce"
            await ws.send_packet(
                pk.Subscribe(1, [("wsb/t", pk.SubOpts(qos=0))]))
            assert isinstance(await ws.recv_packet(), pk.Suback)
            await tcp.publish("wsb/t", b"over-ws", qos=0, wait_ack=False)
            p = await asyncio.wait_for(ws.recv_packet(), 5.0)
            assert isinstance(p, pk.Publish) and p.payload == b"over-ws"
            await tcp.disconnect_clean()
            assert _offloop(b) == 0  # a turn of one TCP connection: inline
        finally:
            await b.stop()

    run_async(run)


# ------------------------------------------------- the native egress path


def test_tls_and_no_library_sessions_stay_on_the_loop(tmp_path, monkeypatch):
    """Who takes the native path is observed: a TLS session never does
    (the transport encrypts: the bytes on the socket are not ours), nor
    does any session of a context whose runtime library lacks egress.cc.
    Both still coalesce and deliver."""
    import ssl
    import subprocess

    from rmqtt_tpu import runtime

    _need_native()
    monkeypatch.setattr(egress_mod, "_MIN_JOB", 1)
    cert, key = str(tmp_path / "c.pem"), str(tmp_path / "k.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", cert, "-days", "1", "-subj", "/CN=localhost"],
        check=True, capture_output=True)

    async def drive(b, port, sslctx, cids):
        clients = []
        for cid in cids:
            r, w = await asyncio.open_connection("127.0.0.1", port, ssl=sslctx)
            codec = MqttCodec(pk.V311)
            w.write(codec.encode(pk.Connect(client_id=cid)))
            w.write(codec.encode(
                pk.Subscribe(1, [("tls/t", pk.SubOpts(qos=0))])))
            await w.drain()
            await _read_frame(r)
            await _read_frame(r)
            clients.append((r, w))
        for i in range(10):
            w.write(codec.encode(pk.Publish(topic="tls/t", payload=b"%d" % i)))
        await w.drain()
        for r, _ in clients:
            got = [MqttCodec(pk.V311).feed(await _read_frame(r))[0].payload
                   for _ in range(10)]
            assert got == [b"%d" % i for i in range(10)]
        assert b.ctx.metrics.get("net.egress_flushes") > 0
        assert _offloop(b) == 0
        assert b.ctx.egress_hub.thread_stats() == (0.0, 0, 0)
        for _, w in clients:
            w.close()

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, tls_port=0, tls_cert=cert, tls_key=key)))
        await b.start()
        try:
            cctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
            cctx.check_hostname = False
            cctx.verify_mode = ssl.CERT_NONE
            await drive(b, b.tls_port, cctx, ["tls-a", "tls-b"])
        finally:
            await b.stop()
        # a library from before egress.cc (the stale-.so rule)
        ctx = ServerContext(BrokerConfig(port=0))
        real = runtime.load()

        class _Stale:
            def __getattr__(self, name):
                if name.startswith("rt_egress"):
                    raise AttributeError(name)
                return getattr(real, name)

        with monkeypatch.context() as mp:
            mp.setattr(runtime, "load", lambda: _Stale())
            ctx.egress_hub = EgressHub(ctx.telemetry)
        assert ctx.egress_hub.native is False
        b = MqttBroker(ctx)
        await b.start()
        try:
            await drive(b, b.port, None, ["nolib-a", "nolib-b"])
        finally:
            await b.stop()

    run_async(run)


def _small_rcvbuf_socket(port) -> socket.socket:
    sock = socket.socket()
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    sock.connect(("127.0.0.1", port))
    sock.setblocking(False)
    return sock


def test_native_partial_write_goes_back_to_the_transport(monkeypatch):
    """A peer that stops reading, behind small socket buffers: the native
    thread's send comes back short (or EAGAIN) and is NOT retried there —
    the remainder goes to the asyncio transport, later frames follow it
    through the same buffer in order, and the high-water gate drains at
    ``egress_high_water`` as it does on the loop path."""
    _need_native()
    monkeypatch.setattr(egress_mod, "_MIN_JOB", 1)

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, egress_high_water=32 * 1024)))
        await b.start()
        try:
            codec = MqttCodec(pk.V311)
            reader, writer = await asyncio.open_connection(
                sock=_small_rcvbuf_socket(b.port), limit=1 << 12)
            writer.write(codec.encode(pk.Connect(client_id="part-sub")))
            writer.write(codec.encode(
                pk.Subscribe(1, [("part/t", pk.SubOpts(qos=0))])))
            await writer.drain()
            await _read_frame(reader)  # CONNACK
            await _read_frame(reader)  # SUBACK; then stop reading
            state = b.ctx.registry._sessions["part-sub"].state
            state.writer.transport.get_extra_info("socket").setsockopt(
                socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
            m = b.ctx.metrics
            pub = await TestClient.connect(b.port, "part-pub")
            sent = 0
            while sent < 200 and not (m.get("net.egress_offloop_partial")
                                      and m.get("net.egress_drains")):
                await pub.publish("part/t", struct.pack(">I", sent) * 2048,
                                  qos=0, wait_ack=False)
                sent += 1
                await asyncio.sleep(0.005)
            assert m.get("net.egress_offloop_partial") > 0, \
                "the native write never came back short"
            assert m.get("net.egress_drains") > 0, \
                "the high-water gate never engaged"
            # the gate holds at the mark: what waits for the socket is the
            # mark plus at most the frame that crossed it, not the backlog
            eb = state._egress
            assert (eb.pending_bytes
                    + state.writer.transport.get_write_buffer_size()
                    <= 32 * 1024 + 2 * 8200)
            # the consumer wakes up: every frame, whole, in publish order
            decode, got = MqttCodec(pk.V311), []
            while len(got) < sent:
                chunk = await asyncio.wait_for(reader.read(65536), 10.0)
                assert chunk, "stream closed early"
                got += decode.feed(chunk)
            assert [p.payload for p in got] == [
                struct.pack(">I", i) * 2048 for i in range(sent)]
            assert _offloop(b) > 0
            writer.close()
            await pub.disconnect_clean()
        finally:
            await b.stop()

    run_async(run, timeout=60.0)


def test_pending_bytes_count_the_write_in_flight():
    """The high-water gate in ``Session._write`` reads ``pending_bytes``:
    bytes the native thread has not reported yet are still pending."""
    eb = EgressBuf(_RecWriter(), Metrics())
    eb._vec, eb._bytes = [b"abc"], 3
    assert eb.pending_bytes == 3
    eb._inflight = b"x" * 100
    assert eb.pending_bytes == 103


async def _server_pairs(n):
    """→ (server, [(server-side reader, writer, client socket)] * n)."""
    accepted: asyncio.Queue = asyncio.Queue()

    async def on_conn(r, w):
        await accepted.put((r, w))

    srv = await asyncio.start_server(on_conn, "127.0.0.1", 0)
    port = srv.sockets[0].getsockname()[1]
    pairs = []
    for _ in range(n):
        c = socket.create_connection(("127.0.0.1", port))
        r, w = await accepted.get()
        pairs.append((r, w, c))
    return srv, pairs


def _n_fds() -> int:
    return len(os.listdir("/proc/self/fd"))


def test_native_close_and_error_with_a_write_in_flight(monkeypatch):
    """The connection's last flush before ``writer.close()`` waits for the
    write in flight and follows it (order kept, nothing lost); a transport
    that aborts under a write in flight takes nothing with it: the thread
    writes the buf's own dup, so the kernel may hand the transport's fd
    number to a new connection at once and that one never sees a byte; a
    hard error from the thread closes the writer; the ``net.egress``
    failpoint fires on this path too; no fd leaks."""
    from rmqtt_tpu.utils.failpoints import FAILPOINTS

    _need_native()
    monkeypatch.setattr(egress_mod, "_MIN_JOB", 1)

    async def run():
        base = _n_fds()
        m = Metrics()
        hub = EgressHub()
        srv, pairs = await _server_pairs(4)
        try:
            bufs = [EgressBuf(w, m, hub=hub) for _, w, _ in pairs]
            assert all(eb._sock_fd >= 0 for eb in bufs)
            # (1) close with a write in flight, many times over: frames fed
            # this turn, the hand-off, then at once the closing flush
            (_, w0, c0), eb0 = pairs[0], bufs[0]
            want = b""
            for i in range(200):
                eb0.feed(b"<%d>" % i)
                await asyncio.sleep(0)  # the hub's turn: handed off
                eb0.feed(b"[bye %d]" % i)  # waits behind the write in flight
                eb0.flush()  # what run()'s finally does before close()
                assert eb0._inflight is None and not eb0._vec
                want += b"<%d>[bye %d]" % (i, i)
            await w0.drain()
            c0.settimeout(5)
            got = b""
            while len(got) < len(want):
                got += c0.recv(65536)
            assert got == want
            assert m.get("net.egress_offloop_flushes") >= 100
            # (2) the transport aborts under a write in flight; its fd number
            # is reused at once by new connections
            (_, w1, c1), eb1 = pairs[1], bufs[1]
            eb1.feed(b"last words")
            await asyncio.sleep(0)
            w1.transport.abort()
            await asyncio.sleep(0)  # connection_lost: the transport's fd closed
            srv2, fresh = await _server_pairs(8)
            eb1.flush()
            eb1.close()
            await asyncio.sleep(0.05)
            hub._collect()
            assert eb1._fd == -1, "the dup outlived its buf"
            c1.settimeout(5)
            assert c1.recv(100) in (b"last words", b"")  # sent, or cut by the abort
            for _, w, c in fresh:
                c.setblocking(False)
                with pytest.raises(BlockingIOError):
                    c.recv(100)  # a stranger's bytes
                w.close()
                c.close()
            srv2.close()
            # (3) a peer that reset: the thread's send fails hard, the buf
            # closes the writer (reading is paused, so only the write path
            # can notice)
            (_, w2, c2), eb2 = pairs[2], bufs[2]
            w2.transport.pause_reading()
            c2.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                          struct.pack("ii", 1, 0))
            c2.close()  # RST
            await asyncio.sleep(0.05)
            for _ in range(50):
                if eb2._closed:
                    break
                eb2.feed(b"anyone there")
                await asyncio.sleep(0.02)
            assert eb2._closed and w2.transport.is_closing()
            eb2.feed(b"ignored")
            await asyncio.sleep(0.02)
            assert not eb2._vec or eb2._closed
            # (4) the failpoint: fires at the hand-off, closes the writer
            (_, w3, c3), eb3 = pairs[3], bufs[3]
            FAILPOINTS.set("net.egress", "times(1, error)")
            try:
                eb3.feed(b"never sent")
                await asyncio.sleep(0)
                await asyncio.sleep(0.02)
            finally:
                FAILPOINTS.clear_all()
            assert eb3._closed and w3.transport.is_closing()
            c3.settimeout(5)
            assert c3.recv(100) == b""
        finally:
            for eb in bufs:
                eb.close()
            for _, w, c in pairs:
                w.close()
                c.close()
            srv.close()
            await srv.wait_closed()
            hub.close()
        await asyncio.sleep(0.05)
        assert _n_fds() <= base, "an fd leaked"

    run_async(run, timeout=60.0)


def test_native_kicked_disconnect_follows_its_publishes(monkeypatch):
    """A v5 session taken over while deliveries are on their way: the old
    connection gets its PUBLISHes in order and then the DISCONNECT (0x8E),
    whether or not a native write was in flight when ``run()`` closed."""
    from rmqtt_tpu.broker.types import RC_SESSION_TAKEN_OVER

    _need_native()
    monkeypatch.setattr(egress_mod, "_MIN_JOB", 1)

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        try:
            pub = await TestClient.connect(b.port, "kick-pub")
            for rnd in range(10):
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", b.port)
                codec = MqttCodec(pk.V5)
                writer.write(codec.encode(
                    pk.Connect(client_id="kick-me", protocol=pk.V5)))
                writer.write(codec.encode(
                    pk.Subscribe(1, [("kick/t", pk.SubOpts(qos=0))])))
                await writer.drain()
                await _read_frame(reader)
                await _read_frame(reader)
                for i in range(30):
                    await pub.publish("kick/t", b"r%d-%02d" % (rnd, i),
                                      qos=0, wait_ack=False)
                await asyncio.sleep(0.002 * rnd)
                usurper = await TestClient.connect(
                    b.port, "kick-me", version=pk.V5)
                raw = await asyncio.wait_for(reader.read(-1), 5.0)  # to EOF
                frames = MqttCodec(pk.V5).feed(raw)
                assert isinstance(frames[-1], pk.Disconnect), frames[-3:]
                assert frames[-1].reason_code == RC_SESSION_TAKEN_OVER
                pubs = frames[:-1]
                assert all(isinstance(p, pk.Publish) for p in pubs)
                assert [p.payload for p in pubs] == [
                    b"r%d-%02d" % (rnd, i) for i in range(len(pubs))]
                writer.close()
                await usurper.disconnect_clean()
            assert _offloop(b) > 0
            await pub.disconnect_clean()
        finally:
            await b.stop()

    run_async(run, timeout=60.0)


# ------------------------------------------------------- native encode


def test_native_encode_matches_python():
    """Property test: rt_codec_encode_publish (runtime/codec.cc) must be
    byte-equal to the Python encoder over v3/v5 × qos × retain × dup ×
    payload sizes straddling the crossover × v5 properties."""
    import random

    from rmqtt_tpu.broker.codec import codec as codec_mod

    if codec_mod._native_lib() is None:
        pytest.skip("native runtime unavailable")
    rng = random.Random(7)
    sizes = [0, 1, 511, 512, 513, 900, 4096, 70000]
    for version in (pk.V311, pk.V5):
        enc = MqttCodec(version)
        for trial in range(120):
            qos = rng.randrange(3)
            props = {}
            if version == pk.V5 and rng.random() < 0.5:
                props = {P.CONTENT_TYPE: "x/y",
                         P.USER_PROPERTY: [("k", "v" * rng.randrange(40))],
                         P.MESSAGE_EXPIRY_INTERVAL: rng.randrange(1 << 16)}
            p = pk.Publish(
                topic="/".join("seg%d" % rng.randrange(9)
                               for _ in range(rng.randint(1, 6))),
                payload=bytes(rng.randrange(256)
                              for _ in range(rng.choice(sizes))),
                qos=qos, retain=rng.random() < 0.5,
                dup=qos > 0 and rng.random() < 0.3,
                packet_id=rng.randrange(1, 65535) if qos else None,
                properties=props)
            native = enc.encode(p)
            saved = codec_mod._native
            codec_mod._native = False  # force the pure-Python arm
            try:
                python = enc.encode(p)
            finally:
                codec_mod._native = saved
            assert native == python, (version, trial, qos, len(p.payload))


def test_encode_stale_so_falls_back_to_python():
    """A prebuilt .so that predates rt_codec_encode_publish must degrade
    to the Python encoder, not crash (the PR 5 stale-binary rule: every
    new native symbol is optional at load time)."""
    from rmqtt_tpu.broker.codec import codec as codec_mod
    from rmqtt_tpu.runtime import codec_encode_publish

    class _StaleLib:  # no rt_codec_encode_publish attribute
        pass

    assert codec_encode_publish(_StaleLib(), b"t", b"x" * 600, b"",
                                0, False, False, None) is None
    p = pk.Publish(topic="stale/t", payload=b"z" * 1024, qos=1,
                   packet_id=7, retain=True)
    enc = MqttCodec(pk.V311)
    saved = codec_mod._native
    codec_mod._native = _StaleLib()  # truthy → taken as a loaded lib
    try:
        stale = enc.encode(p)
        codec_mod._native = False
        python = enc.encode(p)
    finally:
        codec_mod._native = saved
    assert stale == python


# ------------------------------------------------------ keepalive wheel


class _FakeState:
    def __init__(self, last_packet):
        self._last_packet = last_packet
        self._closing = asyncio.Event()
        self.s = type("S", (), {"id": None})()


class _Hooks:
    def __init__(self, proceed=True):
        self.proceed = proceed
        self.fired = 0

    async def fire(self, *a, **kw):
        self.fired += 1
        return self.proceed


def test_wheel_fires_idle_refiles_active_rearms_veto():
    """Wheel unit semantics at fast tick: an idle entry fires the hook
    and closes; an entry whose ``_last_packet`` advanced re-files at its
    true deadline without firing; a hook veto re-arms a full timeout."""
    import time as _time

    async def run():
        hooks = _Hooks()
        m = Metrics()
        wheel = KeepaliveWheel(m, hooks, tick=0.05)
        wheel.start()
        try:
            idle = _FakeState(_time.monotonic())
            active = _FakeState(_time.monotonic())
            # a 1 s timeout against a 0.06 s refresh: on a loaded box one
            # sleep(0.06) stretched past the former 0.2 s and the ACTIVE
            # entry fired. Wider waits, same assertions.
            wheel.arm(idle, 1.0)
            wheel.arm(active, 1.0)
            assert wheel.sessions == 2
            deadline = _time.monotonic() + 10.0
            while not idle._closing.is_set() and _time.monotonic() < deadline:
                await asyncio.sleep(0.06)
                active._last_packet = _time.monotonic()
            assert idle._closing.is_set(), \
                f"idle entry never fired (ticks={wheel.ticks})"
            assert not active._closing.is_set(), "active entry fired"
            assert wheel.sessions == 1
            assert wheel.timeouts == 1
            assert m.get("keepalive.timeouts") == 1
            # veto: the hook says keep it → entry re-arms, nothing closes
            vhooks = _Hooks(proceed=False)
            vwheel = KeepaliveWheel(Metrics(), vhooks, tick=0.05)
            vwheel.start()
            try:
                vetoed = _FakeState(_time.monotonic())
                vwheel.arm(vetoed, 0.15)
                deadline = _time.monotonic() + 5.0
                while not vhooks.fired and _time.monotonic() < deadline:
                    await asyncio.sleep(0.05)
                assert vhooks.fired >= 1, \
                    f"veto hook never consulted (ticks={vwheel.ticks})"
                assert not vetoed._closing.is_set()
                assert vwheel.sessions == 1, "veto must re-arm the entry"
                assert vwheel.timeouts == 0
            finally:
                await vwheel.stop()
        finally:
            await wheel.stop()

    run_async(run)


def test_wheel_evicts_idle_keeps_active_o1_tasks():
    """End-to-end wheel parity with the per-connection timer it replaced:
    a silent client is evicted at the fitter deadline, a pinging client
    survives — with ONE wheel task total and zero per-connection
    keepalive tasks (the O(1) claim)."""

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        try:
            assert b.ctx.keepalive_wheel is not None  # default ON
            idle = await TestClient.connect(b.port, "wheel-idle",
                                            keepalive=1)
            live = await TestClient.connect(b.port, "wheel-live",
                                            keepalive=1)
            assert b.ctx.keepalive_wheel.sessions == 2
            names = [t.get_name() for t in asyncio.all_tasks()]
            assert names.count("keepalive-wheel") == 1
            assert not any("_keepalive_loop" in repr(t.get_coro())
                           for t in asyncio.all_tasks()), \
                "per-connection keepalive task exists despite the wheel"

            async def ping_forever():
                while True:
                    await live.ping()
                    await asyncio.sleep(0.5)

            pinger = asyncio.create_task(ping_forever())
            # keepalive=1 → fitter timeout 4s (small-value slack)
            await asyncio.wait_for(idle.closed.wait(), timeout=10.0)
            pinger.cancel()
            assert not live.closed.is_set(), "active client was evicted"
            assert b.ctx.keepalive_wheel.timeouts >= 1
            assert b.ctx.keepalive_wheel.sessions == 1
            await live.disconnect_clean()
        finally:
            await b.stop()

    run_async(run)


def test_wheel_off_legacy_timer_parity():
    """`[network] keepalive_wheel=false` restores the per-connection
    timer path — identical eviction semantics, no wheel constructed."""

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, keepalive_wheel=False)))
        await b.start()
        try:
            assert b.ctx.keepalive_wheel is None
            c = await TestClient.connect(b.port, "legacy-idle", keepalive=1)
            await asyncio.wait_for(c.closed.wait(), timeout=10.0)
        finally:
            await b.stop()

    run_async(run)


def test_wheel_v5_server_keepalive_override():
    """The v5 server-keep-alive clamp must govern the WHEEL deadline too:
    the armed timeout follows the announced server value, not the
    client's requested keepalive (paho test_server_keep_alive, under the
    wheel)."""

    async def run():
        from rmqtt_tpu.broker.fitter import FitterConfig

        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, fitter=FitterConfig(max_keepalive=60))))
        await b.start()
        try:
            c = await TestClient.connect(b.port, "wheel-ska",
                                         version=pk.V5, keepalive=3600)
            assert c.connack.properties.get(P.SERVER_KEEP_ALIVE) == 60
            wheel = b.ctx.keepalive_wheel
            assert wheel is not None and wheel.sessions == 1
            entry = next(e for slot in wheel.slots for e in slot)
            assert entry.timeout == b.ctx.fitter.keepalive_timeout(60)
            await c.disconnect_clean()
        finally:
            await b.stop()

    run_async(run)
