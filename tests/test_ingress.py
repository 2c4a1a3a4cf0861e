"""Off-loop socket reads (broker/ingress.py, runtime/ingress.cc).

Since PR 30 the reads of a plain-TCP session are done by the runtime
library's ingress thread, which also frames what it read; the session
builds its packets from the scan records. That claims zero change at the
protocol level, so the pins here are identity ones: the same bytes, cut
into the same segments, give ``_handle`` the same packets in the same order
on the thread's path ("offloop") as on the asyncio transport's
("transport": what TLS, WebSocket, QUIC and a host without the library
keep). The thread's own hazards have cases of their own: bytes the
``StreamReader`` held at take-over, a frame the scan refuses, EOF in the
middle of a frame, a reset, a close with a read in flight, and the 64 KB
bound under a publisher that floods a session whose ``_handle`` is stuck."""

import asyncio
import socket
import ssl
import struct
import subprocess

import pytest

from rmqtt_tpu.broker.codec import MqttCodec, packets as pk
from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.ingress import IngressHub
from rmqtt_tpu.broker.metrics import Metrics
from rmqtt_tpu.broker.server import MqttBroker
from rmqtt_tpu.broker.session import SessionState

from tests.mqtt_client import TestClient
from tests.test_transports import WsTestClient


def run_async(fn, timeout=40.0):
    asyncio.run(asyncio.wait_for(fn(), timeout=timeout))


def _need_native():
    if not IngressHub(Metrics()).native:
        pytest.skip("native runtime (ingress.cc) unavailable")


@pytest.fixture(params=["transport", "offloop"])
def path(request, monkeypatch):
    """Who reads a broker's plain-TCP sessions. "transport": no session is
    eligible, so every chunk comes through the asyncio transport and its
    StreamReader. "offloop": the native thread reads every session that is
    eligible (all of these tests', unless a case says otherwise)."""
    if request.param == "offloop":
        _need_native()
    else:
        monkeypatch.setattr(IngressHub, "eligible", lambda self, state: False)
    return request.param


def _m(b, name: str) -> int:
    return b.ctx.metrics.get(name)


def _check_path(b, path, least: int = 1) -> None:
    """The broker's reads went where the case says they go."""
    assert _m(b, "net.ingress_reads") >= least
    if path == "offloop":
        assert _m(b, "net.ingress_offloop_reads") >= least
        assert _m(b, "net.ingress_offloop_reads") <= _m(b, "net.ingress_reads")
        assert b.ctx.ingress_hub.thread_stats()[1] >= least
    else:
        assert _m(b, "net.ingress_offloop_reads") == 0
        assert b.ctx.ingress_hub.thread_stats() == (0.0, 0, 0)


@pytest.fixture
def handled(monkeypatch):
    """Every packet a session serves, per client id, in order: what
    ``_handle`` is given, and the PUBLISHes ``_publish_run`` takes when a
    chunk holds several in a row."""
    seen = {}
    real = SessionState._handle
    real_run = SessionState._publish_run

    async def _handle(self, p):
        seen.setdefault(self.s.client_id, []).append(p)
        await real(self, p)

    async def _publish_run(self, packets, i):
        j = await real_run(self, packets, i)
        seen.setdefault(self.s.client_id, []).extend(packets[i:j])
        return j

    monkeypatch.setattr(SessionState, "_handle", _handle)
    monkeypatch.setattr(SessionState, "_publish_run", _publish_run)
    return seen


async def _raw(port: int, client_id: str, version: int = pk.V311):
    """A connected raw socket: → (reader, writer, codec) after CONNACK."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.transport.get_extra_info("socket").setsockopt(
        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    codec = MqttCodec(version)
    writer.write(codec.encode(pk.Connect(client_id=client_id, protocol=version)))
    ack = await _next_packet(reader, codec)
    assert isinstance(ack, pk.Connack) and ack.reason_code == 0
    # a session's first chunk stays on the transport; the thread has the
    # socket (where it is eligible) before the answer to it is written
    writer.write(codec.encode(pk.Pingreq()))
    assert isinstance(await _next_packet(reader, codec), pk.Pingresp)
    return reader, writer, codec


async def _next_packet(reader, codec, timeout: float = 5.0):
    while True:
        data = await asyncio.wait_for(reader.read(65536), timeout)
        if not data:
            return None
        got = codec.feed(data)
        if got:
            return got[0]


async def _until(cond, timeout: float = 10.0) -> None:
    deadline = asyncio.get_running_loop().time() + timeout
    while not cond():
        assert asyncio.get_running_loop().time() < deadline, "timed out"
        await asyncio.sleep(0.01)


def _pub(codec, i: int, qos: int = 0, size: int = 0) -> bytes:
    return codec.encode(pk.Publish(
        topic=f"in/{i % 7}", payload=b"%d" % i + b"." * size, qos=qos,
        packet_id=(i % 65535 + 1) if qos else None))


def _payloads(packets) -> list:
    return [p.payload for p in packets if isinstance(p, pk.Publish)]


# ------------------------------------------------- identity of the packets
def _segments(kind: str, codec) -> list:
    """The byte segments a case sends, each in a write of its own."""
    if kind == "bytewise":  # three frames, one byte a segment
        data = _pub(codec, 1, 1) + codec.encode(pk.Pingreq()) + _pub(codec, 2)
        return [data[i:i + 1] for i in range(len(data))]
    if kind == "many_frames":  # 300 frames in one segment
        return [b"".join(_pub(codec, i, qos=i % 2) for i in range(300))]
    if kind == "big_subscribe":  # a 30 KB SUBSCRIBE over four reads
        sub = codec.encode(pk.Subscribe(
            9, [(f"big/{i}/+/{'x' * 20}", pk.SubOpts(qos=1)) for i in range(1000)]))
        assert len(sub) > 30000
        cut = len(sub) // 4
        return [sub[:cut], sub[cut:2 * cut], sub[2 * cut:3 * cut],
                sub[3 * cut:] + _pub(codec, 5)]
    if kind == "split_header":  # a frame cut inside its length varint
        big = _pub(codec, 3, qos=1, size=300)
        return [_pub(codec, 1) + big[:2], big[2:10], big[10:] + _pub(codec, 4)]
    raise AssertionError(kind)


@pytest.mark.parametrize("version", [pk.V311, pk.V5])
@pytest.mark.parametrize(
    "kind", ["bytewise", "many_frames", "big_subscribe", "split_header"])
def test_same_packets_in_the_same_order(path, handled, kind, version):
    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        reader, writer, codec = await _raw(b.port, "ident", version)
        want = []
        for seg in _segments(kind, codec):
            writer.write(seg)
            await writer.drain()
            await asyncio.sleep(0.002 if kind == "bytewise" else 0.05)
        want = MqttCodec(version).feed(b"".join(_segments(kind, codec)))
        await _until(lambda: len(handled.get("ident", [])) > len(want))
        assert handled["ident"] == [pk.Pingreq()] + want  # _raw's first chunk
        _check_path(b, path)
        writer.close()
        await b.stop()

    run_async(run)


def test_codec_build_matches_feed():
    """``MqttCodec.build`` over the scan's records gives what ``feed``
    gives for the same bytes (both versions, every packet kind a session
    receives), and reports a frame that does not decode the same way."""
    from rmqtt_tpu import runtime as rt

    lib = rt.load()
    if lib is None:
        pytest.skip("native runtime unavailable")
    for version in (pk.V311, pk.V5):
        enc = MqttCodec(version)
        packets = [
            pk.Publish("a/b", b"x", 1, False, False, 7, {}),
            pk.Publish("a/b", b"", 0, True, False, None, {}),
            pk.Puback(7), pk.Pubrec(8), pk.Pubrel(9), pk.Pubcomp(10),
            pk.Subscribe(3, [("a/+", pk.SubOpts(qos=1))]),
            pk.Unsubscribe(4, ["a/+"]), pk.Pingreq(), pk.Disconnect(),
        ]
        if version == pk.V5:
            packets.append(pk.Puback(11, 0x10, {}))
        data = b"".join(enc.encode(p) for p in packets)
        meta, n, consumed, err, _ = rt.codec_scan(lib, data, version == pk.V5, 1 << 20)
        assert consumed == len(data) and not err and n == len(packets)
        assert MqttCodec(version).build(data, meta, 0, n) == MqttCodec(version).feed(data)
        assert MqttCodec(version).build(data, meta, 2, 3) == packets[2:5]
    # invalid UTF-8 in a well-framed PUBLISH: the packets before it are
    # returned, the error is left pending; alone, it raises
    bad = b"\x30\x05\x00\x02\xff\xfeX"
    good = MqttCodec().encode(pk.Pingreq())
    meta, n, _, err, _ = rt.codec_scan(lib, good + bad, False, 1 << 20)
    assert not err and n == 2
    c = MqttCodec()
    assert c.build(good + bad, meta, 0, 2) == [pk.Pingreq()]
    assert c.pending_error is not None
    with pytest.raises(type(c.pending_error)):
        c.build(good, meta, 0, 1)
    c2 = MqttCodec()
    with pytest.raises(type(c.pending_error)):
        c2.build(good + bad, meta, 1, 1)


# ------------------------------------- what the StreamReader held at take-over
def test_bytes_already_read_at_take_over(path, handled, monkeypatch):
    """Segments that reach the StreamReader while the handshake is still
    under way — a whole PUBLISH and the head of a second — are consumed
    first, the head goes to the thread, and the rest of the stream follows
    it: three publishes, in order, none twice."""
    real = MqttBroker._handshake

    async def slow_handshake(self, *a, **kw):
        state = await real(self, *a, **kw)
        await asyncio.sleep(0.3)  # the client's next segments land meanwhile
        return state

    monkeypatch.setattr(MqttBroker, "_handshake", slow_handshake)

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        reader, writer = await asyncio.open_connection("127.0.0.1", b.port)
        codec = MqttCodec()
        second = _pub(codec, 2, qos=1, size=50)
        writer.write(codec.encode(pk.Connect(client_id="early"))
                     + codec.encode(pk.Pingreq()))  # pipelined: early_packets
        await writer.drain()
        await asyncio.sleep(0.1)
        writer.write(_pub(codec, 1) + second[:20])  # into the StreamReader
        await writer.drain()
        await asyncio.sleep(0.5)  # past the take-over
        writer.write(second[20:] + _pub(codec, 3))
        await writer.drain()
        await _until(lambda: len(_payloads(handled.get("early", []))) >= 3)
        got = handled["early"]
        assert isinstance(got[0], pk.Pingreq)
        assert _payloads(got) == [b"1", b"2" + b"." * 50, b"3"]
        if path == "offloop":
            # the drained chunk came through the transport, the rest did not
            assert _m(b, "net.ingress_reads") > _m(b, "net.ingress_offloop_reads") >= 1
        writer.close()
        await b.stop()

    run_async(run)


# --------------------------------------------- frames the scan must refuse
@pytest.mark.parametrize("bad, reason", [
    (b"\x30\xff\xff\xff\xff\x01", 0x81),          # malformed remaining length
    (b"\x36\x05\x00\x01a\x00\x01", 0x81),          # PUBLISH with QoS 3
    (b"\x30" + b"\x80\x80\x08" + b"x" * 40, 0x95),  # 131072 bytes > max_packet_size
])
def test_refused_frame_closes_with_the_same_reason(path, handled, bad, reason):
    """A valid packet before the bad frame is still handled; the v5 client
    is told the same reason code on either path."""
    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0, max_packet_size=65536)))
        await b.start()
        sub = await TestClient.connect(b.port, "watcher")
        await sub.subscribe("in/#", qos=0)
        reader, writer, codec = await _raw(b.port, "rude", pk.V5)
        writer.write(_pub(codec, 4) + bad)
        await writer.drain()
        assert (await sub.recv()).payload == b"4"
        bye = await _next_packet(reader, codec)
        assert isinstance(bye, pk.Disconnect) and bye.reason_code == reason
        assert await _next_packet(reader, codec) is None  # and closed
        assert _payloads(handled["rude"]) == [b"4"]
        assert _m(b, "protocol.errors") == 1
        _check_path(b, path)
        await sub.disconnect_clean()
        await b.stop()

    run_async(run)


def test_second_connect_is_judged_by_the_codec(path, handled):
    """The scan stops before a CONNECT (it may switch the version): the
    thread posts it raw and the session sees the packet, as on the
    transport path."""
    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        reader, writer, codec = await _raw(b.port, "twice")
        writer.write(_pub(codec, 1) + codec.encode(pk.Connect(client_id="twice")))
        await writer.drain()
        await _until(lambda: len(handled.get("twice", [])) >= 3)
        assert isinstance(handled["twice"][-1], pk.Connect)
        writer.close()
        await b.stop()

    run_async(run)


# ------------------------------------------------------ the connection ends
@pytest.mark.parametrize("how", ["eof_mid_frame", "reset", "disconnect"])
def test_end_of_connection_is_the_teardown_it_was(path, handled, how):
    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        reader, writer, codec = await _raw(b.port, "leaver")
        sock = writer.transport.get_extra_info("socket")
        writer.write(_pub(codec, 1))
        if how == "eof_mid_frame":
            writer.write(_pub(codec, 2, qos=1, size=100)[:40])
            await writer.drain()
            writer.close()
        elif how == "reset":
            await writer.drain()
            await _until(lambda: len(handled.get("leaver", [])) >= 1)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                            struct.pack("ii", 1, 0))
            writer.transport.abort()
        else:
            writer.write(codec.encode(pk.Disconnect()))
            await writer.drain()
        await _until(lambda: _m(b, "connections.closed") == 1)
        assert _payloads(handled["leaver"]) == [b"1"]
        assert b.ctx.registry.get("leaver") is None or \
            not b.ctx.registry.get("leaver").connected
        assert _m(b, "session.loop_errors") == 0
        assert not b.ctx.ingress_hub._conns  # nothing left with the thread
        _check_path(b, path)
        await b.stop()

    run_async(run)


def test_kick_with_reads_in_flight_never_touches_a_reused_fd(path, handled):
    """Sessions are taken over (kicked) again and again while their old
    connections are still sending; the fds the kernel hands out next are
    those just closed. Every new session works, and the thread is left
    with exactly the live connections."""
    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        live = []
        for gen in range(6):
            conns = []
            for k in range(8):
                reader, writer, codec = await _raw(b.port, f"dev{k}")
                # keeps sending while the next generation kicks it
                writer.write(b"".join(_pub(codec, 1000 * gen + i) for i in range(50)))
                conns.append((reader, writer))
            live = conns
        await _until(lambda: _m(b, "connections.closed") == 40)
        for reader, writer in live:
            writer.write(MqttCodec().encode(pk.Pingreq()))
            assert isinstance(await _next_packet(reader, MqttCodec()), pk.Pingresp)
        if path == "offloop":
            assert len(b.ctx.ingress_hub._conns) == 8
        for k in range(8):
            got = _payloads(handled[f"dev{k}"])
            # per connection in order; a kicked connection may lose its tail
            by_gen = {}
            for p in got:
                by_gen.setdefault(int(p) // 1000, []).append(int(p) % 1000)
            for seq in by_gen.values():
                assert seq == list(range(len(seq)))
            assert by_gen[5] == list(range(50))
        for _, writer in live:
            writer.close()
        await b.stop()
        assert not b.ctx.ingress_hub._conns

    run_async(run)


def test_broker_stop_with_connections_attached(path):
    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        clients = [await TestClient.connect(b.port, f"c{i}") for i in range(5)]
        for c in clients:
            await c.publish("x/y", b"first", qos=1)  # on the transport
            await c.publish("x/y", b"z", qos=1)
        _check_path(b, path, least=5)
        await b.stop()
        assert not b.ctx.ingress_hub._conns
        assert b.ctx.ingress_hub._thread is None

    run_async(run)


# -------------------------------------------------------------- the bound
def test_flood_into_a_stuck_session_is_bounded(monkeypatch):
    """A publisher floods a session that does not get past its first
    PUBLISH (a lone one's ``_handle``, a run's ``_publish_run``): the
    thread stops reading its socket near 64 KB, the kernel's buffers fill
    and the sender blocks (TCP backpressure); released, every frame is
    handled, in order."""
    _need_native()
    gate = asyncio.Event()
    seen = []
    real = SessionState._handle
    real_run = SessionState._publish_run

    async def _handle(self, p):
        if self.s.client_id == "flooder" and isinstance(p, pk.Publish):
            await gate.wait()
            seen.append(p.payload)
        await real(self, p)

    async def _publish_run(self, packets, i):
        await gate.wait()
        j = await real_run(self, packets, i)
        seen.extend(p.payload for p in packets[i:j])
        return j

    in_hand = []  # bytes of the chunks the stuck session has decoded
    real_decode = SessionState._decode_chunk

    def _decode_chunk(self, data, size, *a, **k):
        if self.s.client_id == "flooder":
            in_hand.append(size)
        return real_decode(self, data, size, *a, **k)

    monkeypatch.setattr(SessionState, "_handle", _handle)
    monkeypatch.setattr(SessionState, "_publish_run", _publish_run)
    monkeypatch.setattr(SessionState, "_decode_chunk", _decode_chunk)

    async def run():
        loop = asyncio.get_running_loop()
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        sock = socket.socket()
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 64 * 1024)
        sock.setblocking(False)
        await loop.sock_connect(sock, ("127.0.0.1", b.port))
        await loop.sock_sendall(sock, MqttCodec().encode(pk.Connect(client_id="flooder")))
        assert (await loop.sock_recv(sock, 64))[0] == 0x20  # CONNACK
        await loop.sock_sendall(sock, b"\xc0\x00")  # the first chunk: a PINGREQ
        assert (await loop.sock_recv(sock, 64))[0] == 0xD0
        # 20,000 QoS0 frames of 1,020 bytes: more than the kernel will buffer
        n_frames, size = 20000, 1020
        blob = b"".join(b"\x30\xf9\x07\x00\x04in/0" + b"%07d" % i + b"." * 1004
                        for i in range(n_frames))
        assert len(blob) == n_frames * size
        view = memoryview(blob)
        off = stalled = 0
        while off < len(blob) and stalled < 30:
            try:
                off += sock.send(view[off:off + 65536])
                stalled = 0
            except BlockingIOError:
                stalled += 1
                await asyncio.sleep(0.01)
        assert stalled == 30 and off < len(blob), "the sender never blocked"
        conn = next(iter(b.ctx.ingress_hub._conns.values()))
        # what the thread posted and the session has not handled: the chunks
        # in its hands (those that queued behind one are served with it) and
        # the inbox
        held = sum(in_hand[1:]) + sum(
            b[0][i + 4] for b, i in zip(*[iter(conn.inbox)] * 2))
        assert 0 < held < 3 * 64 * 1024, held  # 64 KB, one read, the chunk in hand
        assert _m(b, "net.ingress_paused") >= 1
        assert b.ctx.ingress_hub._thread.stats()[3] >= 1
        gate.set()
        total = off // size + 200  # whole frames: those sent and 200 more
        await loop.sock_sendall(sock, view[off:total * size])
        await _until(lambda: len(seen) >= total, timeout=30.0)
        assert [int(p[:7]) for p in seen] == list(range(total))
        sock.close()
        await b.stop()

    run_async(run, timeout=90.0)


def test_acks_are_told_promptly_for_large_chunks(handled):
    """Consumed bytes ride the next collection, but from ``_ACK_NOW`` on
    they are told at once: a connection that sends 30 KB frames one after
    another is never stopped by bytes the session has long handled."""
    _need_native()

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        reader, writer, codec = await _raw(b.port, "bulk")
        for i in range(12):
            writer.write(_pub(codec, i, qos=1, size=30000))
            await writer.drain()
            ack = await _next_packet(reader, codec)
            assert isinstance(ack, pk.Puback)
        assert _m(b, "net.ingress_paused") == 0
        assert len(_payloads(handled["bulk"])) == 12
        writer.close()
        await b.stop()

    run_async(run)


def test_first_chunk_stays_on_the_transport(handled):
    """A connection's first chunk after the handshake is served by the
    transport, and the thread takes the socket over behind it: the connect
    phase never waits for a second thread, and a client that says one thing
    and goes is never registered."""
    _need_native()

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        hub = b.ctx.ingress_hub
        c = await TestClient.connect(b.port, "once")
        assert not hub._conns  # connected, nothing said yet
        await c.subscribe("a/#", qos=1)
        assert _m(b, "net.ingress_reads") == 1
        assert _m(b, "net.ingress_offloop_reads") == 0
        assert len(hub._conns) == 1  # taken over behind the first chunk
        await c.publish("a/b", b"x", qos=1)
        assert (await c.recv()).payload == b"x"
        assert _m(b, "net.ingress_offloop_reads") >= 1
        assert [type(p) for p in handled["once"]][:2] == [pk.Subscribe, pk.Publish]
        await c.disconnect_clean()
        await b.stop()

    run_async(run)


# ------------------------------------------ who stays on the StreamReader path
@pytest.fixture(scope="module")
def certs(tmp_path_factory):
    d = tmp_path_factory.mktemp("certs")
    cert, key = d / "cert.pem", d / "key.pem"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(cert), "-days", "1",
         "-subj", "/CN=localhost"],
        check=True, capture_output=True)
    return str(cert), str(key)


@pytest.mark.parametrize("kind", ["tls", "websocket", "no_library"])
def test_other_transports_keep_the_stream_reader(kind, certs, monkeypatch):
    _need_native()
    if kind == "no_library":
        from rmqtt_tpu import runtime

        monkeypatch.setattr(runtime, "load", lambda: None)

    async def run():
        cert, key = certs
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, ws_port=0, tls_port=0, tls_cert=cert, tls_key=key)))
        await b.start()
        assert b.ctx.ingress_hub.native == (kind != "no_library")
        if kind == "tls":
            cctx = ssl.create_default_context()
            cctx.check_hostname = False
            cctx.verify_mode = ssl.CERT_NONE
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", b.tls_port, ssl=cctx)
            codec = MqttCodec()
            writer.write(codec.encode(pk.Connect(client_id="tls")))
            assert isinstance(await _next_packet(reader, codec), pk.Connack)
            writer.write(_pub(codec, 1, qos=1))
            assert isinstance(await _next_packet(reader, codec), pk.Puback)
            writer.close()
        elif kind == "websocket":
            ws = await WsTestClient.connect(b.ws_port, "ws")
            await ws.send_packet(pk.Publish(topic="a/b", payload=b"x", qos=1, packet_id=1))
            assert isinstance(await ws.recv_packet(), pk.Puback)
            ws.writer.close()
        else:
            c = await TestClient.connect(b.port, "plain")
            await c.publish("a/b", b"x", qos=1)
            await c.disconnect_clean()
        assert _m(b, "net.ingress_reads") >= 1
        assert _m(b, "net.ingress_offloop_reads") == 0
        assert not b.ctx.ingress_hub._conns
        assert b.ctx.ingress_hub.thread_stats() == (0.0, 0, 0)
        await b.stop()

    run_async(run)


def test_stats_surface_has_the_ingress_counters():
    _need_native()

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        await b.start()
        c = await TestClient.connect(b.port, "s")
        await c.publish("a/b", b"first", qos=1)  # on the transport
        await c.publish("a/b", b"x", qos=1)
        s = b.ctx.stats()
        assert s.net_ingress_reads >= s.net_ingress_offloop_reads >= 1
        assert s.net_ingress_paused == 0
        assert s.ingress_thread_recvs >= 1 and s.ingress_thread_jobs >= 1
        assert s.ingress_thread_busy_ms_total > 0
        flat = b.ctx.telemetry.stage_stats()
        assert flat["stage_ingress_collect_count"] >= 1
        assert flat["stage_ingress_decode_count"] >= 1
        await c.disconnect_clean()
        await b.stop()

    run_async(run)
