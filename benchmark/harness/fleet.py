"""The load generator: publisher and subscriber connections in child processes.

One general generator reads a traffic mix's parameters (``traffic/*.json``).
``loop: closed``: every publisher connection keeps ``inflight`` QoS1
publishes outstanding and sends the next when a PUBACK comes (MQTT's own
flow control; the broker sets the rate).

``loop: open``: publishes fall **due** on the mix's schedule (``schedule``:
bursts that share one instant in every publisher process), at
``rate_publishes_per_s`` over all connections, and the seed draws their
topics. MQTT's flow control stays: a connection keeps at most ``inflight``
publishes unacknowledged. A publish that falls due while its connection's window is
full queues on the connection, first in first out, and leaves when a PUBACK
frees a slot. Each record keeps the due instant beside the send instant,
and every latency is taken from the due one, so it counts the wait. Under
the broker's capacity that is a plain open loop; over it every window is
full and the offered rate only decides how long the due queues grow.

Each process runs one asyncio loop on raw-socket protocols (``mqtt.py``) and
records into flat arrays; nothing here touches JAX or the program's code.
The harness talks to a process over a ``multiprocessing`` pipe: a command
tuple in, one reply out.

Clocks: ``time.perf_counter()`` is CLOCK_MONOTONIC on Linux, one clock for
every process of the host, so a publisher's send instant and a subscriber's
receipt subtract.
"""

from __future__ import annotations

import asyncio
import itertools
import math
import multiprocessing as mp
import time
from array import array
from collections import deque

from harness import generators, mqtt

CONNECT_CHUNK = 64   # connections opened at once
SUBACK_LIMIT = 600.0  # s: a SUBSCRIBE of 1000 filters into a 1M table


# ------------------------------------------------------------- child side
class _Conn(asyncio.Protocol):
    """One MQTT connection: CONNACK / SUBACK futures, the rest to ``on``."""

    def __init__(self, on_packets) -> None:
        self.parser = mqtt.Parser()
        self.on_packets = on_packets
        self.tr = None
        self.waiting = {}
        self.lost = asyncio.get_running_loop().create_future()

    def connection_made(self, tr) -> None:
        self.tr = tr

    def connection_lost(self, exc) -> None:
        if not self.lost.done():
            self.lost.set_result(exc)
        for fut in self.waiting.values():
            if not fut.done():
                fut.set_exception(ConnectionError("connection lost"))

    def expect(self, key):
        fut = self.waiting[key] = asyncio.get_running_loop().create_future()
        return fut

    def data_received(self, data) -> None:
        now = time.perf_counter()
        self.on_packets(self, self.parser.feed(data), now)

    def resolve(self, key, value) -> None:
        fut = self.waiting.pop(key, None)
        if fut is not None and not fut.done():
            fut.set_result(value)


async def _open(port: int, client_id: str, on_packets) -> _Conn:
    loop = asyncio.get_running_loop()
    _tr, c = await loop.create_connection(lambda: _Conn(on_packets),
                                          "127.0.0.1", port)
    fut = c.expect("connack")
    c.tr.write(mqtt.connect(client_id))
    rc = await asyncio.wait_for(fut, 60.0)
    if rc != 0:
        raise RuntimeError(f"{client_id}: CONNACK refused, rc={rc}")
    return c


async def _open_many(port: int, ids, on_packets) -> list:
    out = []
    ids = list(ids)
    for i in range(0, len(ids), CONNECT_CHUNK):
        out += await asyncio.gather(*(
            _open(port, cid, on_packets) for cid in ids[i:i + CONNECT_CHUNK]))
    return out


class _Child:
    """Command loop shared by both kinds of process, plus a CPU-time log:
    (perf_counter, process_time) every 0.2 s, from which the harness takes
    the process's CPU share over the window."""

    def __init__(self, pipe) -> None:
        self.pipe = pipe
        self.cpu = array("d")
        self.quit = asyncio.get_running_loop().create_future()

    async def run(self) -> None:
        loop = asyncio.get_running_loop()
        loop.add_reader(self.pipe.fileno(), self._on_cmd)
        ticker = asyncio.ensure_future(self._tick())
        try:
            await self.quit
        finally:
            ticker.cancel()
            loop.remove_reader(self.pipe.fileno())

    async def _tick(self) -> None:
        while True:
            self.cpu.extend((time.perf_counter(), time.process_time()))
            await asyncio.sleep(0.2)

    def _on_cmd(self) -> None:
        try:
            cmd = self.pipe.recv()
        except EOFError:  # the harness is gone
            cmd = ("quit",)
        if cmd[0] == "quit":
            if not self.quit.done():
                self.quit.set_result(None)
            return
        task = asyncio.ensure_future(getattr(self, "cmd_" + cmd[0])(*cmd[1:]))
        task.add_done_callback(self._reply)

    def _reply(self, task) -> None:
        try:
            self.pipe.send(("ok", task.result()))
        except Exception as e:  # the boundary: report, the harness fails the run
            self.pipe.send(("error", f"{type(e).__name__}: {e}"))


class _Subscribers(_Child):
    def __init__(self, pipe, a) -> None:
        super().__init__(pipe)
        self.a = a
        self.index = {}           # protocol object → subscriber index
        self.ids = array("q")     # publish id of each PUBLISH received
        self.times = array("d")   # its receipt instant
        self.subs = array("l")    # the subscriber connection it came to
        self.sent = 0             # records already handed to the harness

    def on_packets(self, c, packets, now) -> None:
        acks = []
        for typ, flags, body in packets:
            if typ == mqtt.PUBLISH:
                payload, qos, pid = mqtt.publish_fields(flags, body)
                try:
                    ident = int(payload)
                except ValueError:
                    ident = -1  # not a publish of this fleet: unexpected
                self.ids.append(ident)
                self.times.append(now)
                self.subs.append(self.index[c])
                if qos:
                    acks.append(mqtt.puback(pid))
            elif typ == mqtt.SUBACK:
                c.resolve(("suback", (body[0] << 8) | body[1]), body[2:])
            elif typ == mqtt.CONNACK:
                c.resolve("connack", body[1])
        if acks:
            c.tr.write(b"".join(acks))

    async def cmd_prepare(self) -> dict:
        """Make the table from the seed (while the broker is still starting)."""
        a = self.a
        t0 = time.perf_counter()
        self.filters = generators.load(a["generator"])(a["seed"], a["config"]).filters()
        return {"filters": len(self.filters), "seconds": time.perf_counter() - t0}

    async def cmd_load(self) -> dict:
        """Connect this process's subscriber connections and SUBSCRIBE each
        to its share of the table: filter i of the sorted table belongs to
        subscriber ``i % subscribers``."""
        a = self.a
        t0 = time.perf_counter()
        filters = self.filters
        mine = range(a["lo"], a["hi"])
        conns = await _open_many(a["port"], (f"sub-{i}" for i in mine),
                                 self.on_packets)
        for i, c in zip(mine, conns):
            self.index[c] = i

        async def load(i, c) -> int:
            fs = filters[i::a["subscribers"]]
            for k in range(0, len(fs), a["per_packet"]):
                pid = k // a["per_packet"] % 65535 + 1
                fut = c.expect(("suback", pid))
                c.tr.write(mqtt.subscribe(pid, fs[k:k + a["per_packet"]],
                                          a["sub_qos"]))
                codes = await asyncio.wait_for(fut, SUBACK_LIMIT)
                if any(rc >= 0x80 for rc in codes):
                    raise RuntimeError(f"sub-{i}: SUBACK refused a filter")
            return len(fs)

        n = sum(await asyncio.gather(*(load(i, c) for i, c in zip(mine, conns))))
        self.conns = conns
        del self.filters
        return {"subscribed": n, "seconds": time.perf_counter() - t0}

    async def cmd_drain(self) -> dict:
        lo, self.sent = self.sent, len(self.ids)
        return {"ids": self.ids[lo:].tobytes(), "times": self.times[lo:].tobytes(),
                "subs": self.subs[lo:].tobytes(), "cpu": self.cpu.tobytes(),
                "lost": sum(c.lost.done() for c in self.conns)}


def schedule(traffic: dict, proc: int, procs: int, conns: int):
    """An open mix's due publishes of one publisher process, without end:
    ``(seconds after the start instant, connection index)``, the instants
    never falling. A mix gives the same schedule in every run and for every
    seed (the seed draws the topics): every ``burst_size / rate`` seconds
    ``burst_size`` publishes fall due at one instant; process k takes the
    k-th of ``procs`` even shares, on distinct connections, rotating so that
    all are used alike."""
    size, rate = traffic["burst_size"], traffic["rate_publishes_per_s"]
    share = size * (proc + 1) // procs - size * proc // procs
    if share > conns:
        raise RuntimeError(f"publisher process {proc}: {share} publishes of a "
                           f"burst on {conns} connections")
    for k in itertools.count():
        t = k * size / rate
        for j in range(share):
            yield t, (k * share + j) % conns


def due_between(traffic: dict, pub_conns: list, start: float,
                t0: float, t1: float) -> int:
    """An open mix's publishes that fell due in [t0, t1) where the schedule
    started at ``start``, by the schedule itself: sent, queued on a full
    window, or never reached. ``pub_conns``: connections of each process."""
    n = 0
    for k, conns in enumerate(pub_conns):
        for off, _c in schedule(traffic, k, len(pub_conns), conns):
            if start + off >= t1:
                break
            n += start + off >= t0
    return n


class _Publishers(_Child):
    def __init__(self, pipe, a, stop_at) -> None:
        super().__init__(pipe)
        self.a, self.stop_at = a, stop_at
        self.topics = []          # topic of each publish, by record index
        self.t_send = array("d")  # the instant the publish left
        self.t_due = array("d")   # the instant it fell due (closed loop: t_send)
        self.waited = array("b")  # 1: it met a full window and queued
        self.t_ack = array("d")   # PUBACK instant; nan = none (yet)
        self.inflight = 0
        self.sent = 0
        self.pending = {}         # protocol object → {packet id: record}, oldest first
        self.pid = {}             # protocol object → last packet id
        self.out_of_order = 0     # PUBACKs that passed an older publish's
        self.open = a["traffic"]["loop"] == "open"
        self.window = a["traffic"]["inflight"]
        self.queue = {}           # open loop: protocol object → due instants waiting
        self.refill = self._next_queued if self.open else self.send

    def on_packets(self, c, packets, now) -> None:
        for typ, _flags, body in packets:
            if typ == mqtt.PUBACK:
                pending = self.pending[c]
                pid = (body[0] << 8) | body[1]
                # [MQTT-4.6.0-2]: a connection's PUBACKs leave in the order
                # its PUBLISHes came, so each acks the oldest outstanding
                if pid in pending and pid != next(iter(pending)):
                    self.out_of_order += 1
                rec = pending.pop(pid, None)
                if rec is not None:
                    self.t_ack[rec] = now
                    self.inflight -= 1
                    self.refill(c)
            elif typ == mqtt.CONNACK:
                c.resolve("connack", body[1])

    def send(self, c, t_due=None, waited=0) -> bool:
        """One QoS1 publish, unless the window has closed."""
        now = time.perf_counter()
        if now >= self.stop_at.value:
            return False
        a = self.a
        rec = len(self.topics)
        topic = next(self.stream)
        self.topics.append(topic)
        self.t_send.append(now)
        self.t_due.append(now if t_due is None else t_due)
        self.waited.append(waited)
        self.t_ack.append(math.nan)
        pid = self.pid[c] = self.pid[c] % 65535 + 1
        self.pending[c][pid] = rec
        self.inflight += 1
        # the publish id rides the payload; ids of different processes differ
        ident = rec * a["procs"] + a["proc"]
        c.tr.write(mqtt.publish(topic, str(ident).encode(), 1, pid))
        return True

    # ---- the open loop
    def _next_queued(self, c) -> None:
        """A PUBACK freed a slot: the connection's oldest waiting publish."""
        q = self.queue[c]
        if q and self.send(c, q[0], 1):
            q.popleft()

    def _on_due(self) -> None:
        """Every publish due by now leaves, or queues on its connection
        where the window is full. One timer a process, for the next due
        instant. Past ``stop_at`` nothing is sent and the schedule ends;
        what is still queued then was never attempted (the harness counts
        what fell due from the schedule itself)."""
        now = time.perf_counter()
        stop = self.stop_at.value
        t, k = self.next_due
        if now >= stop:
            return
        while t <= now:
            c = self.conns[k]
            q = self.queue[c]
            if q or len(self.pending[c]) >= self.window or not self.send(c, t):
                q.append(t)
            off, k = next(self.coming)
            t = self.start + off
        self.next_due = t, k
        self._arm(min(t, stop))

    def _arm(self, when: float) -> None:
        asyncio.get_running_loop().call_later(
            max(0.0, when - time.perf_counter()), self._on_due)

    async def cmd_prepare(self) -> dict:
        """Draw this process's topic stream ahead of the window (so that the
        send path only pops a list), then connect its connections."""
        a = self.a
        t0 = time.perf_counter()
        gen = generators.load(a["generator"])(a["seed"], a["config"])
        stream = gen.topic_stream(a["seed"] * 1009 + 17 + a["proc"])
        ready = list(itertools.islice(stream, a["pregen"]))
        self.stream = itertools.chain(ready, stream)
        self.conns = await _open_many(
            a["port"], (f"pub-{i}" for i in range(a["lo"], a["hi"])),
            self.on_packets)
        for c in self.conns:
            self.pending[c], self.pid[c], self.queue[c] = {}, 0, deque()
        return {"connections": len(self.conns), "pregen": len(ready),
                "seconds": time.perf_counter() - t0}

    async def cmd_go(self, start: float) -> dict:
        """Closed loop: fill every window now. Open loop: the schedule runs
        from ``start``, an instant of the hosts' one clock that every
        publisher process is given alike."""
        if not self.open:
            for c in self.conns:
                for _ in range(self.window):
                    self.send(c)
            return {}
        a = self.a
        self.start = start
        self.coming = schedule(a["traffic"], a["proc"], a["procs"], len(self.conns))
        off, k = next(self.coming)
        self.next_due = start + off, k
        self._arm(start + off)
        return {}

    async def cmd_drain(self) -> dict:
        """Records since the last drain, and the PUBACK instants of ALL
        records (an ack may come after its record was handed over)."""
        lo, self.sent = self.sent, len(self.topics)
        return {"topics": self.topics[lo:], "t_send": self.t_send[lo:].tobytes(),
                "t_due": self.t_due[lo:].tobytes(),
                "waited": self.waited[lo:].tobytes(),
                "t_ack": self.t_ack.tobytes(), "inflight": self.inflight,
                "out_of_order": self.out_of_order, "cpu": self.cpu.tobytes(),
                "lost": sum(c.lost.done() for c in self.conns)}


def _subscriber_main(pipe, a) -> None:
    async def main():
        await _Subscribers(pipe, a).run()
    asyncio.run(main())


def _publisher_main(pipe, a, stop_at) -> None:
    async def main():
        await _Publishers(pipe, a, stop_at).run()
    asyncio.run(main())


# ----------------------------------------------------------- harness side
def _split(n: int, parts: int):
    """``parts`` contiguous ranges covering ``range(n)``."""
    edges = [n * k // parts for k in range(parts + 1)]
    return list(zip(edges, edges[1:]))


class Fleet:
    """The fleet's processes as the harness sees them."""

    def __init__(self, port: int, seed: int, config: dict, traffic: dict,
                 seconds: float) -> None:
        ctx = mp.get_context("spawn")
        # written once by the harness when the window's end is known; read
        # by every publisher before each send (no lock: one aligned double)
        self.stop_at = ctx.RawValue("d", math.inf)
        base = {"port": port, "seed": seed, "config": config,
                "generator": config["generator"]}
        self.subs, self.pubs = [], []
        for lo, hi in _split(traffic["subscribers"], traffic["subscriber_procs"]):
            a = dict(base, lo=lo, hi=hi, subscribers=traffic["subscribers"],
                     per_packet=traffic["filters_per_subscribe"],
                     sub_qos=traffic["subscribe_qos"])
            self.subs.append(self._start(ctx, _subscriber_main, (a,)))
        procs = traffic["publisher_procs"]
        pregen = int(traffic["pregen_publishes_per_s"] * (seconds + 30) / procs)
        self.traffic, self.start = traffic, None
        self.pub_conns = [hi - lo for lo, hi in _split(traffic["publishers"], procs)]
        for k, (lo, hi) in enumerate(_split(traffic["publishers"], procs)):
            a = dict(base, lo=lo, hi=hi, proc=k, procs=procs, pregen=pregen,
                     traffic=traffic)
            self.pubs.append(self._start(ctx, _publisher_main, (a, self.stop_at)))

    @staticmethod
    def _start(ctx, target, args):
        ours, theirs = ctx.Pipe()
        p = ctx.Process(target=target, args=(theirs, *args), daemon=True)
        p.start()
        theirs.close()
        return p, ours

    @staticmethod
    def tell(procs, *cmd) -> None:
        """Send one command to each process; ``gather`` takes the replies."""
        for _p, pipe in procs:
            pipe.send(cmd)

    @staticmethod
    def gather(procs, what: str, limit: float = 900.0) -> list:
        """→ each process's reply to the command told last, in order."""
        out = []
        for p, pipe in procs:
            if not pipe.poll(limit):
                raise RuntimeError(f"fleet process {p.pid}: no reply to {what!r} "
                                   f"in {limit:.0f}s")
            status, value = pipe.recv()
            if status != "ok":
                raise RuntimeError(f"fleet process {p.pid}: {what}: {value}")
            out.append(value)
        return out

    @classmethod
    def ask(cls, procs, *cmd) -> list:
        cls.tell(procs, *cmd)
        return cls.gather(procs, cmd[0])

    def go(self, lead_s: float) -> None:
        """Start the publishers: a closed loop at once; an open mix's
        schedule ``lead_s`` from now, at one instant of the hosts' one clock
        that every publisher process is given alike."""
        self.start = time.perf_counter() + lead_s
        self.ask(self.pubs, "go", self.start)

    def due_between(self, t0: float, t1: float) -> int:
        return due_between(self.traffic, self.pub_conns, self.start, t0, t1)

    def close(self) -> None:
        for p, pipe in self.subs + self.pubs:
            try:
                pipe.send(("quit",))
            except OSError:
                pass
        for p, pipe in self.subs + self.pubs:
            p.join(20.0)
            if p.is_alive():
                p.kill()
                p.join(20.0)
            pipe.close()
