"""Off-loop socket reads: the hand-off between the native ingress thread
and the sessions' read loops.

The write side's twin (broker/egress.py ``EgressHub``). One ``IngressHub``
per ``ServerContext``. After its handshake and its first read chunk (the
connect phase, and a client that says one thing and goes, keep the
transport's latency and pay no registration) a session whose connection is a
plain TCP stream gives the socket's reads away (``SessionState._read_offloop``):
the asyncio transport is paused, whatever its ``StreamReader`` still holds
is consumed the old way, and a dup of the socket is registered with the
library's ingress thread (``runtime.IngressThread``, runtime/ingress.cc: one
epoll over every such socket, a non-blocking ``recv`` and the codec's frame
scan on readable, never the GIL). The thread posts, per connection, the
bytes of the frames that are whole with their scan records, and signals an
eventfd once per round. ``_on_ready`` — the eventfd's reader — takes every
connection's new chunks in ONE ctypes call and puts each in its
connection's inbox; the session's read loop awaits that inbox in place of
``reader.read(65536)`` and builds its packets from the records
(``MqttCodec.build``). ``_handle`` and everything behind it do not know.

Which connections: observed per connection, never configured — a plain
``asyncio.StreamReader`` over a socket transport without TLS
(``egress._offloop_fd``), where the runtime library has ``ingress.cc``. TLS,
WebSocket, QUIC, a PROXY-protocol preamble (read before the handshake) and a
host without the library keep the ``StreamReader`` path.

What holds as before: a connection's packets come in the order its bytes
did; a frame the scan refuses (malformed, oversize) reaches ``codec.feed``
as raw bytes, which judges it and closes with the reason code it always
gave; ``_last_packet`` is stamped when the bytes are collected; EOF, a
reset and any ``recv`` error are only reported by the thread — the
session's read loop ends on them as it ends on ``reader.read()`` giving
``b""`` or raising, and ``run()``'s teardown is the one it always was. Memory a connection is bounded
as behind a ``StreamReader``: the thread stops reading a socket that has
64 KB posted and not yet consumed (``IngressConn.ack`` says what was), so a
flooding publisher meets TCP backpressure. A connection is deregistered
(``detach``) before its writer closes, and only out of flight: the thread
reads a dup the hub owns and closes after ``rt_ingress_remove`` returned.

What the hand-off makes per chunk is two entries in a deque, an index and
a reference to the turn's collection: containers that live until their
session's task runs are what the cyclic collector counts, and on a broker
that holds a large table every collection it is driven to is dear
(PERF.md §6, PR 30).

Counters: ``net.ingress_reads`` (chunks handed to a session, both paths),
``net.ingress_offloop_reads`` (those the thread read), ``net.ingress_paused``
(times the bound stopped a connection); the thread's own
``ingress_thread_busy_ms_total`` / ``_recvs`` / ``_jobs`` on ``/api/v1/stats``.
Loop time of the hub's pass is the stage ``ingress.collect``.
"""

from __future__ import annotations

import asyncio
import itertools
import os
import time
from collections import deque
from typing import Dict, Optional, Tuple

from rmqtt_tpu.broker.codec import packets as pk
from rmqtt_tpu.broker.egress import _offloop_fd
from rmqtt_tpu.broker.telemetry import NULL_TELEMETRY
from rmqtt_tpu.runtime import (
    INGRESS_CHUNK,
    INGRESS_DATA,
    INGRESS_PAUSED,
)

#: consumed bytes of one connection the thread has not been told of yet:
#: from here on they are told at once, not with the next collection
_ACK_NOW = 16 * 1024


class IngressConn:
    """One connection whose reads the native thread does: the session's
    end of the hand-off."""

    __slots__ = ("hub", "state", "id", "fd", "inbox", "waiter", "paused",
                 "watch", "lost")

    def __init__(self, hub: "IngressHub", state, conn_id: int, fd: int) -> None:
        self.hub = hub
        self.state = state
        self.id = conn_id
        self.fd = fd  # our dup of the socket, the thread's to read
        # two entries a chunk, oldest first: the collection it came with
        # (``IngressThread.collect``'s chunks, records, bytes) and the
        # index of its row in those chunks
        self.inbox: deque = deque()
        self.waiter: Optional[asyncio.Future] = None
        self.paused = False  # the bound has stopped the thread's reads
        # the transport is paused, so a read of its StreamReader ends only
        # when asyncio loses the connection on its side (a failed write, a
        # writer closed under the session): our dup would keep the socket
        # open and silent, so that read is watched
        self.lost = False
        self.watch = asyncio.ensure_future(state.reader.read(1))
        self.watch.add_done_callback(self._on_lost)

    def _on_lost(self, watch: asyncio.Future) -> None:
        if watch.cancelled():
            return  # detached
        watch.exception()  # retrieved: the close is all it has to tell
        self.lost = True
        waiter, self.waiter = self.waiter, None
        if waiter is not None and not waiter.done():
            waiter.set_result(None)

    def wait(self) -> asyncio.Future:
        """Awaitable that ends when the inbox has a chunk or the transport
        is lost."""
        self.waiter = fut = self.hub._loop.create_future()
        return fut

    def ack(self, n: int) -> None:
        """``n`` bytes of a chunk have been through ``_handle``."""
        hub = self.hub
        acks = hub._acks
        acks[self.id] = owed = acks.get(self.id, 0) + n
        if self.paused or owed >= _ACK_NOW:
            self.paused = False  # told with the pass scheduled here
            if not hub._ack_scheduled:
                hub._ack_scheduled = True
                hub._loop.call_soon(hub._on_ready)

    def detach(self) -> None:
        self.hub.detach(self)


class IngressHub:
    """One per ServerContext: the one collection a loop turn of every
    offloop connection's new bytes, and the thread's lifecycle."""

    def __init__(self, metrics, telemetry=None, native: bool = True) -> None:
        self.metrics = metrics
        self._tele = telemetry if telemetry is not None else NULL_TELEMETRY
        self._st_collect = self._tele.stage("ingress.collect")
        if native:
            from rmqtt_tpu import runtime

            lib = runtime.load()
            native = lib is not None and hasattr(lib, "rt_ingress_new")
        self.native = native
        self._thread = None  # runtime.IngressThread (``start``)
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._conns: Dict[int, IngressConn] = {}
        self._ids = itertools.count(1)
        self._acks: Dict[int, int] = {}  # consumed, not yet told, by id
        self._ack_scheduled = False

    # ------------------------------------------------------------ take-over
    def eligible(self, state) -> bool:
        """May the thread read this session's socket? A plain StreamReader
        (not a WsReader, not QUIC's) over a stream socket transport that
        carries our bytes as they are (no TLS), still open."""
        if not self.native or type(state.reader) is not asyncio.StreamReader:
            return False
        if _offloop_fd(state.writer) < 0:
            return False
        return not state.writer.transport.is_closing()

    def attach(self, state, head: bytes) -> Optional[IngressConn]:
        """Register ``state``'s socket (its transport is paused, its
        StreamReader empty; ``head`` is what the codec holds of a frame not
        whole yet). → the connection, or None where it cannot be done (no
        fd left for the dup, the thread did not start): the caller stays on
        the transport."""
        if not self.start():
            return None
        try:
            fd = os.dup(_offloop_fd(state.writer))
        except OSError:
            return None
        conn_id = next(self._ids)
        codec = state.codec
        try:
            self._thread.add(conn_id, fd, codec.version == pk.V5,
                             codec.max_inbound_size, head)
        except OSError:
            os.close(fd)
            return None
        self._conns[conn_id] = conn = IngressConn(self, state, conn_id, fd)
        return conn

    def detach(self, conn: IngressConn) -> None:
        """Take the connection from the thread (waits for a read in
        flight: microseconds) and close the dup. Chunks it still has are
        dropped with it: the caller is closing, or has seen EOF."""
        if self._conns.pop(conn.id, None) is None:
            return
        conn.watch.cancel()
        self._acks.pop(conn.id, None)
        self._thread.remove(conn.id)
        os.close(conn.fd)
        conn.fd = -1

    # ------------------------------------------------------------- the turn
    def _on_ready(self) -> None:
        """The eventfd's reader (and the prompt acknowledgement's pass):
        one call hands in what the sessions consumed and takes every
        connection's new chunks: two inbox entries a chunk, the
        collection it came with and its index in it, and no container of
        its own (the module's docstring says why)."""
        self._ack_scheduled = False
        if self._thread is None:
            return  # closed with an acknowledgement's pass still scheduled
        tok = self._st_collect.begin() if self._tele.enabled else 0
        acks, self._acks = self._acks, {}
        batch = self._thread.collect(acks)
        chunks = batch[0]
        now = time.monotonic()
        get = self._conns.get
        reads = 0
        for i in range(0, len(chunks), INGRESS_CHUNK):
            conn = get(chunks[i])
            if conn is None:
                continue  # detached since the thread posted it
            conn.state._last_packet = now
            flags = chunks[i + 1]
            if flags & INGRESS_DATA:
                reads += 1
            if flags & INGRESS_PAUSED:
                conn.paused = True
                self.metrics.inc("net.ingress_paused")
            inbox = conn.inbox
            inbox.append(batch)
            inbox.append(i)
            waiter = conn.waiter
            if waiter is not None:
                conn.waiter = None
                if not waiter.done():
                    waiter.set_result(None)
        if reads:
            self.metrics.inc("net.ingress_reads", reads)
            self.metrics.inc("net.ingress_offloop_reads", reads)
        if tok:
            self._st_collect.end(tok)

    # ------------------------------------------------------------ lifecycle
    def start(self) -> bool:
        """Have the thread running and its eventfd read by the running
        loop; → whether it is. Called with the broker's start, not only by
        the first session: loading the library's second handle and
        spawning the thread takes a millisecond that a first CONNECT
        should not wait for."""
        loop = asyncio.get_running_loop()
        if self._thread is None and self.native:
            from rmqtt_tpu import runtime

            try:
                self._thread = runtime.IngressThread()
            except (RuntimeError, OSError):
                self.native = False
                return False
            self._loop = None
        if self._thread is not None and loop is not self._loop:
            # the eventfd's reader follows the loop that runs the hub
            if self._loop is not None and not self._loop.is_closed():
                self._loop.remove_reader(self._thread.eventfd)
            loop.add_reader(self._thread.eventfd, self._on_ready)
            self._loop = loop
        return self._thread is not None

    def thread_stats(self) -> Tuple[float, int, int]:
        """→ (busy ms, recvs, jobs) of the native thread; zeros without."""
        if self._thread is None:
            return 0.0, 0, 0
        busy_ns, recvs, jobs, _ = self._thread.stats()
        return busy_ns / 1e6, recvs, jobs

    def close(self) -> None:
        """Stop the native thread. Sessions are closed by now; a straggler
        is taken from the thread first, so its dup closes out of flight."""
        if self._thread is None:
            return
        for conn in list(self._conns.values()):
            self.detach(conn)
        thread, self._thread = self._thread, None
        if self._loop is not None and not self._loop.is_closed():
            self._loop.remove_reader(thread.eventfd)
        thread.close()
