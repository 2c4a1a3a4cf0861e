"""Syscall-batched data plane: per-connection egress coalescing, flushed
once per loop turn and written off the loop thread where possible + the
keepalive timer wheel.

The IoT broker benchmarking study (PAPERS.md, arxiv 2603.21600) shows
per-connection syscall and timer overhead — not topic matching — dominates
broker cost at high fan-out and high connection counts. Two structures
attack exactly those costs:

``EgressBuf`` and ``EgressHub``
    One buf per plain-socket connection, one hub per ``ServerContext``.
    Every frame ``send_raw`` would have written individually is appended
    to the connection's vector instead, and ONE ``call_soon``-scheduled
    hub pass per loop turn flushes every dirty connection's whole vector
    as a single vectored send — the per-peer flush-loop shape the
    intra-node fabric already proved (broker/fabric.py
    ``_deliver_flush_loop``). The deliver loop drains a connection's whole
    queue without yielding to the event loop, so a 64-subscriber fan-out
    burst that used to cost one write syscall per frame collapses into one
    per connection per tick. Frames stay the exact bytes the codec
    produced (the QoS0 ``wire_cache`` bytes land in the vector uncopied),
    so coalescing is pinned zero-behavior-change at the protocol level:
    byte-identical frames, enqueue order preserved — acks can never
    reorder ahead of the PUBLISH they follow because one FIFO vector
    serves the whole connection. High-water backpressure is kept: past
    ``egress_high_water`` pending bytes the caller flushes inline and
    awaits ``drain()``, feeding asyncio flow control (and through queue
    growth, the overload plane) exactly like the legacy gate.
    Kill-switch: ``RMQTT_EGRESS_COALESCE=0`` or ``[network]
    egress_coalesce=false`` restores byte-identical legacy per-frame
    writes; ``buffers_until_drain`` writers (WsWriter) always take the
    legacy path so their flush-on-drain contract holds.

    Where the write happens is the hub's choice, per connection and turn,
    from what it observes — there is no option for it. A connection whose
    writer is a plain stream socket (no TLS), whose transport is open with
    an empty write buffer, in a turn with at least ``_MIN_JOB`` such
    connections, where the runtime library has ``egress.cc``: its joined
    frames join the turn's ONE job for the native egress thread
    (``runtime.EgressThread``: a pthread that never takes the GIL), handed
    over in one ctypes call. The thread does one non-blocking ``send`` per
    connection in job order and posts (written, errno); the loop collects
    through the thread's eventfd, or at its next turn. Every other
    connection is written through its asyncio transport on the loop
    thread, as before. The loop path's guarantees hold on the native one:
    a connection with a write in flight keeps later frames in its vector
    (one writer at a time, FIFO); a partial write or EAGAIN is never
    retried natively — the remainder goes to the transport, which owns
    slow consumers from there, and ``pending_bytes`` counts the bytes in
    flight so the high-water gate engages at the same counts; ``flush()``
    — the gate's inline one and the one before ``writer.close()`` — waits
    for the write in flight first; a hard error closes the writer so the
    read loop reaps the session; the ``net.egress`` failpoint fires at the
    hand-off. Counters: ``net.egress_flushes`` counts both paths,
    ``net.egress_offloop_flushes`` those the thread wrote,
    ``net.egress_offloop_partial`` those it handed back in part;
    ``egress_thread_busy_ms_total`` / ``_sends`` / ``_jobs`` on
    ``/api/v1/stats`` are the thread's own.

``KeepaliveWheel``
    One hashed timer wheel per worker replacing one asyncio timer handle
    per connection. Entries are lazy: arming/re-arming on packet arrival
    costs nothing (``_read_loop`` already stamps ``_last_packet``); the
    wheel's single ticking task inspects only the slot whose deadline
    cohort is due, compares against the live ``_last_packet`` stamp, and
    either re-files the entry at its true deadline or fires the same
    CLIENT_KEEPALIVE hook → ``keepalive.timeouts`` → close sequence the
    per-connection ``_keepalive_loop`` ran. A million connections cost
    one task and one callback per tick instead of a million heap-queued
    timers.
"""

from __future__ import annotations

import asyncio
import errno
import os
import time
from typing import Dict, List, Optional, Set, Tuple

from rmqtt_tpu.broker.hooks import HookType
from rmqtt_tpu.broker.telemetry import NULL_TELEMETRY
from rmqtt_tpu.utils.failpoints import FAILPOINTS

#: default high-water mark, matching the legacy send_raw drain gate
DEFAULT_HIGH_WATER = 64 * 1024

_FP_EGRESS = FAILPOINTS.register("net.egress")


# errnos of a native write that mean "not now", not "the connection is
# done" (what asyncio's transport takes as BlockingIOError): nothing was
# written, and the transport takes over
_SOFT_ERRNOS = (errno.EAGAIN, errno.EWOULDBLOCK)

#: a turn's job goes to the native thread from this many connections on
_MIN_JOB = 2


def _offloop_fd(writer) -> int:
    """The socket the native thread may write for ``writer``, or -1: that
    of a stream transport which carries our bytes as they are. TLS (the
    transport encrypts) and a writer without an asyncio socket transport
    (tests, pipes) stay on the loop."""
    transport = getattr(writer, "transport", None)
    info = getattr(transport, "get_extra_info", None)
    if info is None or info("sslcontext") is not None:
        return -1
    sock = info("socket")
    return sock.fileno() if sock is not None else -1


class EgressHub:
    """One per ServerContext: the single per-turn flush of every dirty
    connection, and the hand-off to the native egress thread.

    The first ``feed`` of a loop turn schedules ONE ``call_soon(_turn)``;
    every ``EgressBuf`` fed in the turn registers here. ``_turn`` sorts
    them: a connection the native thread may write (``_offloop_fd``, its
    transport idle with an empty write buffer) joins the turn's job; every
    other one is flushed through its transport as before
    (``EgressBuf.flush``). The job — each connection's fd and joined
    frames — crosses in one ctypes call; the thread's completions come
    back through its eventfd (``_collect``), or at the next turn, or when
    a connection waits for its own (``settle``).

    A connection is in flight from hand-off to completion: frames fed
    meanwhile wait in its vector, so it is never written by two paths at
    once and its order holds. The thread writes a dup of the socket that
    the ``EgressBuf`` owns and closes only out of flight, so the transport
    may close (and the kernel reuse) its own fd at any time. A turn with
    fewer than ``_MIN_JOB`` offloop connections is written on the loop: a
    lone write is the trickle regime, where the hand-off spares the loop
    little (waking the sleeping thread costs it 38 us, a send 15-87) and
    adds the thread's wake-up to the delivery's latency (PERF.md §5, PR 28)."""

    def __init__(self, telemetry=None, native: bool = True) -> None:
        self._tele = telemetry if telemetry is not None else NULL_TELEMETRY
        # busy-clock stage ``egress.flush`` (broker/telemetry.py Stage): one
        # pass per transport write, per hand-off and per collection
        self._st_flush = self._tele.stage("egress.flush")
        self._dirty: List["EgressBuf"] = []
        self._scheduled = False
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        if native:
            from rmqtt_tpu import runtime

            lib = runtime.load()
            native = lib is not None and hasattr(lib, "rt_egress_new")
        self.native = native
        self._thread = None  # runtime.EgressThread, started by the first job
        self._inflight: Dict[int, "EgressBuf"] = {}  # by the thread's fd

    # ----------------------------------------------------------- the turn
    def register(self, eb: "EgressBuf") -> None:
        self._dirty.append(eb)
        if not self._scheduled:
            self._scheduled = True
            loop = asyncio.get_running_loop()
            if loop is not self._loop:
                self._bind(loop)
            loop.call_soon(self._turn)

    def _turn(self) -> None:
        if self._inflight:
            self._collect()  # may re-register connections: for this turn
        self._scheduled = False
        dirty, self._dirty = self._dirty, []
        job: List["EgressBuf"] = []
        for eb in dirty:
            eb._dirty = False
            if not eb._vec or eb._inflight is not None:
                continue  # flushed inline meanwhile / waits behind its write
            if eb._sock_fd >= 0 and eb.transport_idle():
                job.append(eb)
            else:
                eb.flush()
        if len(job) >= _MIN_JOB and self._start():
            self._hand_off(job)
        else:
            for eb in job:
                eb.flush()

    def _hand_off(self, job: List["EgressBuf"]) -> None:
        tok = self._st_flush.begin(len(job)) if self._tele.enabled else 0
        taken = [eb for eb in job if eb._take()]
        if taken:
            last = self._thread.submit([eb._fd for eb in taken],
                                       [eb._inflight for eb in taken])
            for ticket, eb in enumerate(taken, last - len(taken) + 1):
                eb._ticket = ticket
                self._inflight[eb._fd] = eb
        if tok:
            self._st_flush.end(tok)

    # -------------------------------------------------------- completions
    def _collect(self) -> None:
        """Apply what the thread has posted (the eventfd's reader). Loop
        time of the same stage as the hand-off."""
        tok = self._st_flush.begin() if self._tele.enabled else 0
        for fd, written, err in self._thread.collect():
            eb = self._inflight.pop(fd, None)
            if eb is not None:
                eb._done(written, err)
        if tok:
            self._st_flush.end(tok)

    def settle(self, eb: "EgressBuf") -> None:
        """Block (the loop thread: milliseconds at most, the sends never
        block) until ``eb``'s write in flight is posted, and apply it: for
        the flushes that must leave everything in the transport — the
        high-water gate's, and the one before ``writer.close()``."""
        self._thread.wait(eb._ticket, 2000)
        self._collect()
        if eb._inflight is not None:  # not posted in 2 s: give it up
            eb._fail()

    # ---------------------------------------------------------- lifecycle
    def _start(self) -> bool:
        if self._thread is None and self.native:
            from rmqtt_tpu import runtime

            try:
                self._thread = runtime.EgressThread()
            except (RuntimeError, OSError):
                self.native = False
                return False
            self._loop.add_reader(self._thread.eventfd, self._collect)
        return self._thread is not None

    def _bind(self, loop) -> None:
        """The eventfd's reader follows the loop that runs the hub."""
        if self._thread is not None:
            if self._loop is not None and not self._loop.is_closed():
                self._loop.remove_reader(self._thread.eventfd)
            loop.add_reader(self._thread.eventfd, self._collect)
        self._loop = loop

    def thread_stats(self) -> Tuple[float, int, int]:
        """→ (busy ms, sends, jobs) of the native thread; zeros without."""
        if self._thread is None:
            return 0.0, 0, 0
        busy_ns, sends, jobs = self._thread.stats()
        return busy_ns / 1e6, sends, jobs

    def close(self) -> None:
        """Stop the native thread (it sends what is queued first)."""
        if self._thread is None:
            return
        if self._inflight:  # sessions are closed by now: stragglers only
            self._thread.wait(
                max(eb._ticket for eb in self._inflight.values()), 2000)
            self._collect()
        thread, self._thread = self._thread, None
        if self._loop is not None and not self._loop.is_closed():
            self._loop.remove_reader(thread.eventfd)
        thread.close()


class EgressBuf:
    """Per-connection frame vector, flushed once per loop turn by its hub."""

    __slots__ = ("writer", "metrics", "high_water", "_vec", "_bytes",
                 "_dirty", "_closed", "_hub", "_sock_fd", "_fd", "_inflight",
                 "_inflight_frames", "_ticket")

    def __init__(self, writer, metrics, high_water: int = DEFAULT_HIGH_WATER,
                 telemetry=None, hub: Optional[EgressHub] = None) -> None:
        self._fd = -1  # our dup of the socket, for the native thread
        self.writer = writer
        self.metrics = metrics
        self.high_water = high_water
        # a buf built by hand (tests) gets a hub of its own: the same one
        # flush per turn, and nothing leaves the loop
        self._hub = hub if hub is not None else EgressHub(
            telemetry, native=False)
        # the transport's socket if the native thread may write it (the
        # first hand-off dups it into _fd)
        self._sock_fd = _offloop_fd(writer) if self._hub.native else -1
        self._vec: List[bytes] = []
        self._bytes = 0
        self._dirty = False
        self._closed = False
        # the joined frames the native thread is writing (None: none)
        self._inflight: Optional[bytes] = None
        self._inflight_frames = 0
        self._ticket = 0  # of the write in flight (EgressHub.settle)

    def __del__(self) -> None:
        self._close_fd()

    @property
    def pending_bytes(self) -> int:
        """Bytes not yet given to the transport or the socket: the vector's
        and those of the write in flight (the high-water gate's count)."""
        if self._inflight is not None:
            return self._bytes + len(self._inflight)
        return self._bytes

    def feed(self, data: bytes) -> None:
        """Append one wire frame; register for the turn's flush if not yet.
        Must run on the event loop (send_raw holds _wlock)."""
        self._vec.append(data)
        self._bytes += len(data)
        self.metrics.inc("net.egress_frames")
        if not self._dirty:
            self._dirty = True
            self._hub.register(self)

    def transport_idle(self) -> bool:
        transport = self.writer.transport
        return (not transport.is_closing()
                and transport.get_write_buffer_size() == 0)

    def flush(self) -> None:
        """Hand the whole vector to the transport as ONE vectored write.
        Synchronous on purpose: run() calls it before ``writer.close()``
        so a closing connection's last frames (DISCONNECT included) still
        reach the transport buffer, which close() flushes — after what the
        native thread is still writing, which it waits for."""
        if self._inflight is not None:
            self._hub.settle(self)
        if not self._vec:
            return
        vec, self._vec = self._vec, []
        n_bytes, self._bytes = self._bytes, 0
        if self._closed:
            return
        hub = self._hub
        tok = hub._st_flush.begin(len(vec)) if hub._tele.enabled else 0
        try:
            if _FP_EGRESS.action is not None:  # chaos seam (failpoints.py)
                _FP_EGRESS.fire_sync()
            if len(vec) == 1:
                self.writer.write(vec[0])
            else:
                writelines = getattr(self.writer, "writelines", None)
                if writelines is not None:
                    writelines(vec)
                else:
                    self.writer.write(b"".join(vec))
        except Exception:
            self._fail()
            return
        finally:
            if tok:
                hub._st_flush.end(tok)
        self._count(len(vec), n_bytes)

    def _count(self, frames: int, n_bytes: int) -> None:
        self.metrics.inc("net.egress_flushes")
        self.metrics.inc("net.egress_bytes", n_bytes)
        if frames > 1:
            self.metrics.inc("net.egress_coalesced", frames - 1)

    def _fail(self) -> None:
        """A failed write means the connection is done: close the writer so
        the session's read loop reaps it (partial frames must never be
        retried — the stream would desync)."""
        self._closed = True
        if self._inflight is None:
            self._close_fd()  # else at its completion
        try:
            self.writer.close()
        except Exception:
            pass

    # ------------------------------------------------- the native path
    def _take(self) -> bool:
        """Put the vector in flight as the one buffer of a native write;
        False where there is nothing for the thread: the connection is
        closed, the failpoint fired, or no fd was left for the dup (the
        vector then went through the transport)."""
        if self._fd < 0:
            try:
                self._fd = os.dup(self._sock_fd)
            except OSError:
                self._sock_fd = -1
                self.flush()
                return False
        vec, self._vec = self._vec, []
        self._bytes = 0
        if self._closed:
            return False
        try:
            if _FP_EGRESS.action is not None:  # chaos seam (failpoints.py)
                _FP_EGRESS.fire_sync()
        except Exception:
            self._fail()
            return False
        self._inflight = vec[0] if len(vec) == 1 else b"".join(vec)
        self._inflight_frames = len(vec)
        return True

    def _done(self, written: int, err: int) -> None:
        """The native thread posted this connection's write. What it left
        (EAGAIN, a partial write) goes to the asyncio transport, whose
        buffer and writability callback own slow consumers; frames fed
        meanwhile follow at the next turn."""
        data, self._inflight = self._inflight, None
        if self._closed:
            self._close_fd()
            return
        if err and err not in _SOFT_ERRNOS:
            self._fail()
            return
        try:
            if written < len(data):
                self.metrics.inc("net.egress_offloop_partial")
                self.writer.write(memoryview(data)[written:])
        except Exception:
            self._fail()
            return
        if written:
            self.metrics.inc("net.egress_offloop_flushes")
        self._count(self._inflight_frames, len(data))
        if self._vec and not self._dirty:
            self._dirty = True
            self._hub.register(self)

    def close(self) -> None:
        """Drop anything still queued and refuse further writes (the
        socket is gone; a late flush or completion becomes a no-op)."""
        self._closed = True
        self._vec.clear()
        self._bytes = 0
        if self._inflight is None:
            self._close_fd()  # else at its completion

    def _close_fd(self) -> None:
        fd, self._fd = self._fd, -1
        if fd >= 0:
            os.close(fd)


class _WheelEntry:
    __slots__ = ("state", "timeout", "deadline", "slot")

    def __init__(self, state, timeout: float) -> None:
        self.state = state
        self.timeout = timeout
        self.deadline = 0.0
        self.slot: int = -1


class KeepaliveWheel:
    """Hashed timer wheel: one ticking task serves every connection.

    Entries are filed into ``slots[round(deadline / tick) % n_slots]``; each
    tick visits one slot and only touches entries whose deadline cohort
    is due (longer timeouts simply re-file on their wheel round — the
    classic hashed-wheel rounds check, done by deadline comparison).
    Firing re-checks ``state._last_packet`` first, so a connection that
    saw traffic since it was filed is re-filed at its TRUE deadline
    without ever running a coroutine — arm/disarm on packet arrival is
    free because arrival never touches the wheel at all."""

    def __init__(self, metrics, hooks, tick: float = 1.0,
                 n_slots: int = 512) -> None:
        self.metrics = metrics
        self.hooks = hooks
        self.tick = max(0.01, float(tick))
        self.n_slots = n_slots
        self.slots: List[Set[_WheelEntry]] = [set() for _ in range(n_slots)]
        self.sessions = 0  # live armed entries (gauge)
        self.timeouts = 0  # keepalive kills fired (counter)
        self.ticks = 0
        self._task: Optional[asyncio.Task] = None

    # ------------------------------------------------------------- arming
    def _file(self, entry: _WheelEntry, deadline: float) -> None:
        entry.deadline = deadline
        # NEAREST slot, not the containing one: slot k is visited at some
        # now >= k*tick and fires what is due within half a tick, so an
        # entry is caught on that visit only if its deadline lies below
        # (k+0.5)*tick. Filed by floor, a deadline in the upper half of its
        # slot was skipped by an early-in-the-tick visit and waited a whole
        # wheel round (n_slots ticks) to expire.
        entry.slot = int(deadline / self.tick + 0.5) % self.n_slots
        self.slots[entry.slot].add(entry)

    def arm(self, state, timeout: float) -> _WheelEntry:
        """Register one connection; called once at session start (NOT per
        packet — packet arrival only stamps ``_last_packet``)."""
        entry = _WheelEntry(state, timeout)
        self._file(entry, time.monotonic() + timeout)
        self.sessions += 1
        return entry

    def disarm(self, entry: _WheelEntry) -> None:
        if entry.slot >= 0:
            self.slots[entry.slot].discard(entry)
            entry.slot = -1
            self.sessions -= 1

    # ------------------------------------------------------------ ticking
    def start(self) -> None:
        if self._task is None:
            self._task = asyncio.get_running_loop().create_task(
                self._run(), name="keepalive-wheel")

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None

    async def _run(self) -> None:
        cursor = int(time.monotonic() / self.tick)
        while True:
            await asyncio.sleep(self.tick)
            now = time.monotonic()
            target = int(now / self.tick)
            # visit every slot the clock crossed since the last tick (a
            # laggy loop must not skip cohorts)
            while cursor < target:
                cursor += 1
                self.ticks += 1
                self._expire_slot(cursor % self.n_slots, now)

    def _expire_slot(self, idx: int, now: float) -> None:
        slot = self.slots[idx]
        if not slot:
            return
        due = [e for e in slot if e.deadline <= now + self.tick * 0.5]
        for entry in due:
            slot.discard(entry)
            state = entry.state
            idle = now - state._last_packet
            if idle < entry.timeout:
                # saw traffic since filing: re-file at the true deadline —
                # clamped a full tick ahead, or a deadline due within the
                # half-tick early-catch window could land in the slot the
                # cursor just left and miss a whole wheel round
                self._file(entry, max(state._last_packet + entry.timeout,
                                      now + self.tick))
                continue
            entry.slot = -1
            self.sessions -= 1
            asyncio.get_running_loop().create_task(self._fire(entry, idle))

    async def _fire(self, entry: _WheelEntry, idle: float) -> None:
        """Same sequence as SessionState._keepalive_loop: the hook may
        veto the kill (plugins extend keepalive), in which case the entry
        re-arms for another full timeout."""
        state = entry.state
        proceed = await self.hooks.fire(
            HookType.CLIENT_KEEPALIVE, state.s.id, idle, initial=True
        )
        if proceed:
            self.timeouts += 1
            self.metrics.inc("keepalive.timeouts")
            state._closing.set()
        else:
            self._file(entry, time.monotonic() + entry.timeout)
            self.sessions += 1
