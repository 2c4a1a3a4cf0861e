"""Per-connection session state machine.

Mirrors `/root/reference/rmqtt/src/session.rs`: the online loop (run_loop
:308-402 — keepalive timer, inflight-retry timer, credit-gated deliver queue,
control messages, socket), publish ingress (:908-1064 — QoS0/1/2 with
in-flight QoS2 dedup, topic-alias resolve, ``$delayed`` parse, hooks, ACL,
retain), the subscribe path (:1276-1371), offline behavior (session expiry +
will-delay timers, :405-494), and takeover transfer (:1374-1427).

The host/TPU split: nothing here touches the device — publishes are handed
to ``SessionRegistry.forwards`` which parks on the micro-batched routing
service (`broker/routing.py`).
"""

from __future__ import annotations

import asyncio
import time
from collections import deque
from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Tuple

from rmqtt_tpu.broker.codec import MqttCodec, packets as pk, props as P
from rmqtt_tpu.broker.codec.primitives import ProtocolViolation
from rmqtt_tpu.broker.delayed import parse_delayed
from rmqtt_tpu.broker.fitter import Limits
from rmqtt_tpu.broker.hooks import HookType
from rmqtt_tpu.broker.inflight import InInflight, MomentStatus, OutEntry, OutInflight
from rmqtt_tpu.broker.queue import DeliverQueue, Hold, Policy
from rmqtt_tpu.broker.telemetry import PROFILER
from rmqtt_tpu.broker.tracing import CURRENT_TRACE
from rmqtt_tpu.broker.types import (
    ConnectInfo,
    Message,
    RC_NOT_AUTHORIZED,
    RC_NO_MATCHING_SUBSCRIBERS,
    RC_PACKET_ID_NOT_FOUND,
    RC_SUCCESS,
    RC_TOPIC_ALIAS_INVALID,
    RC_TOPIC_FILTER_INVALID,
    RC_TOPIC_NAME_INVALID,
    RC_UNSPECIFIED_ERROR,
    now,
)
from rmqtt_tpu.core.topic import (
    InvalidSharedFilter,
    filter_valid,
    parse_limit,
    parse_shared,
    split_levels,
    topic_valid,
)
from rmqtt_tpu.router.base import Id, SubscriptionOptions
from rmqtt_tpu.runtime import (
    INGRESS_DATA,
    INGRESS_FRAMES,
    INGRESS_PAUSED,
    INGRESS_RAW,
)


@dataclass
class DeliverItem:
    """One queued outbound publish (post-fanout, pre-socket)."""

    msg: Message
    qos: int  # effective = min(sub qos, msg qos)
    retain: bool  # retain-as-published / retained-replay flag
    topic_filter: str
    sub_ids: Tuple[int, ...] = ()
    dup: bool = False
    # durable id (broker/durability.py): the journal seq of this QoS1/2
    # delivery's pending record; 0 = not journaled (durability off, QoS0,
    # or a non-persistent session). Rides into the OutEntry so the
    # subscriber's PUBACK/PUBCOMP can journal the matching ack.
    did: int = 0
    # encoded-frame cache SHARED across one publish's fan-out (the fan-out
    # loop passes one dict per message): QoS0 subscribers on the same
    # protocol version reuse identical wire bytes instead of re-encoding
    wire_cache: dict = field(default_factory=dict)
    # active trace of the publish that fanned this item out
    # (broker/tracing.py): the deliver loop runs in another task, so the
    # context rides the item instead of the contextvar
    trace: object = None
    # perf_counter_ns at Session.enqueue (0 with telemetry off): the
    # deliver loop's pop closes the ``deliver.queue_wait`` stage on it
    t_enq: int = 0


def encode_qos0_frame(msg: Message, version: int, retain: bool, rem) -> bytes:
    """The QoS0 fan-out wire frame for one (protocol version, retain flag,
    remaining expiry) — byte-identical for every same-version subscriber (no
    packet id, no per-subscription props, aliases disabled), so it is
    encoded ONCE per publish and reused across the fan-out via the shared
    ``wire_cache`` dict keyed ``(version, retain, rem)``. Shared by the
    in-session fast path below and the intra-node fabric, which ships these
    frames to peer workers so the whole NODE encodes each variant once."""
    props: Dict[int, object] = {
        k: v
        for k, v in msg.properties.items()
        if k in (P.PAYLOAD_FORMAT_INDICATOR, P.CONTENT_TYPE, P.RESPONSE_TOPIC,
                 P.CORRELATION_DATA, P.USER_PROPERTY)
    }
    if rem is not None:
        props[P.MESSAGE_EXPIRY_INTERVAL] = rem
    pub = pk.Publish(
        topic=msg.topic, payload=msg.payload, qos=0,
        retain=retain, dup=False, packet_id=None,
        properties=props if version == pk.V5 else {},
    )
    return MqttCodec(version).encode(pub)


class Session:
    """Durable session state; survives reconnects when expiry > 0."""

    def __init__(
        self,
        ctx,
        id: Id,
        connect_info: ConnectInfo,
        limits: Limits,
        clean_start: bool,
    ) -> None:
        self.ctx = ctx
        self.id = id
        self.client_id = id.client_id
        self.connect_info = connect_info
        self.limits = limits
        self.clean_start = clean_start
        self.created_at = now()
        # original filter string (incl. $share prefix) → options
        self.subscriptions: Dict[str, SubscriptionOptions] = {}
        self.deliver_queue: DeliverQueue[DeliverItem] = DeliverQueue(limits.max_mqueue)
        self.out_inflight = OutInflight(max_inflight=limits.max_inflight)
        # inbound QoS2 window = our advertised Receive Maximum (MQTT-5 3.3.4)
        self.in_qos2 = InInflight(max_size=limits.max_inflight)
        self.connected = False
        self.state: Optional["SessionState"] = None
        self.will: Optional[pk.Will] = connect_info_will(connect_info)
        self._will_task: Optional[asyncio.Task] = None
        self._expiry_task: Optional[asyncio.Task] = None
        # session fencing epoch (cluster/membership.py): every takeover
        # stamps a monotonic (epoch, node_id) via registry.next_fence(), so
        # a healed partition resolves duplicate sessions deterministically
        # — highest fence wins, the stale side self-kicks (exactly once:
        # _fence_kicked guards the racing repair paths)
        self.fence: tuple = (0, id.node_id)
        self._fence_kicked = False
        # stall timer of the publishes held on this session's deliver queue
        # (_enqueue_crowded); None while none is held
        self._hold_timer: Optional[asyncio.TimerHandle] = None

    # ---------------------------------------------------------------- fanout
    def enqueue(self, item: DeliverItem) -> None:
        """Push into the deliver queue (fan-out target, shared.rs:876-963).

        Overload tier (broker/overload.py): at ELEVATED, QoS0 fan-out to a
        SLOW consumer (queue past the shed fraction) is shed before it ever
        lands in the queue; at CRITICAL any backlogged consumer sheds QoS0.
        QoS1/2 keep their at-least-once path (drop policy below). Every
        drop is reason-labeled and, when the publish is traced, stamped as
        an ``overload.shed`` span so the trace says why it never arrived."""
        if not self.connected and self.limits.session_expiry <= 0:
            self.ctx.metrics.drop("no_session")
            hk = self.ctx.hotkeys
            if hk.enabled:  # reason-labeled drops gain a hot-key dimension
                hk.on_drop("no_session", self.client_id)
            return
        if item.qos == 0 and self.connected and self.ctx.overload.should_shed_qos0(
            self.deliver_queue
        ):
            self.ctx.metrics.drop("shed_qos0")
            hk = self.ctx.hotkeys
            if hk.enabled:
                hk.on_drop("shed_qos0", self.client_id)
            if item.trace is not None:
                item.trace.add_wall("overload.shed", 0, {
                    "client": self.client_id, "reason": "shed_qos0",
                    "queue": len(self.deliver_queue),
                    "state": self.ctx.overload.state.name,
                })
            asyncio.get_running_loop().create_task(
                self.ctx.hooks.fire(HookType.MESSAGE_DROPPED, self.id, item.msg, "shed-qos0")
            )
            return
        # durability plane (broker/durability.py): a QoS1/2 delivery bound
        # for a persistent session journals as pending BEFORE it can be
        # acknowledged anywhere — the publisher's PUBACK barrier then rides
        # the group commit. did != 0 marks an already-journaled item
        # (recovery re-enqueue), which must not double-journal.
        dur = self.ctx.durability
        if (dur is not None and item.qos > 0 and item.did == 0
                and self.limits.session_expiry > 0):
            item.did = dur.on_enqueue(self.client_id, item)
        if self.ctx.telemetry.enabled:
            item.t_enq = time.perf_counter_ns()
        # the one compare the common case pays: a queue at most half full
        # takes the entry as it is
        q = self.deliver_queue
        n = len(q)
        if n > q.half:
            self._enqueue_crowded(item, q, n)
        else:
            if not n:
                # an empty queue: the deliver loop is parked on it (or on
                # its way there), so this delivery pays its wake-up
                self.ctx.metrics.inc("deliver.cold_enqueues")
            q.put(item)
        if not self.connected:
            asyncio.get_running_loop().create_task(
                self.ctx.hooks.fire(HookType.OFFLINE_MESSAGE, self.id, item.msg, None)
            )

    def _enqueue_crowded(self, item: DeliverItem, q: DeliverQueue, n: int) -> None:
        """``enqueue`` into a queue more than half full (counted: the
        benchmark's ``deliver.queue_over_half_share_pct``), and what a FULL
        queue does. A named departure from `rmqtt/src/queue.rs`, which drops:

        A QoS1/2 delivery for a connected session goes in past the limit
        on behalf of its publish's ``Hold`` (``SessionState.hold``): the
        publisher's PUBACK/PUBREC waits until this queue is back under its
        limit, when every publish held on it is released together —
        backpressure from the subscriber's drain rate to a closed-loop or
        Receive-Maximum-bound publisher, so an acked message is never paid
        for with a queued one; and publishers that were blocked anyway come
        back side by side, as one batch for the routing service. Only the ack is
        held, never a read loop: the publisher's connection goes on reading
        (its own PUBACKs among them, so a client subscribed to what it
        publishes, or two that feed each other, keep draining the queues
        they wait on).

        Upstream's policy stays the last resort: QoS0 (``DROP_CURRENT``), an
        offline session (``DROP_EARLY``), a publish with no ack to hold
        (wills, delayed and injected publishes, another node's), a publisher
        with ``max_inflight`` acks held already, and a consumer that took
        nothing for a whole retry interval of the outbound window
        (``_holds_due``) — one dead subscriber cannot stall a fleet."""
        self.ctx.metrics.inc("deliver.queue_over_half")
        if n < q.maxlen:
            q.put(item)
            return
        if item.qos and self.connected and not q.stalled:
            msg = item.msg
            pub = (self.ctx.registry.get(msg.from_id.client_id)
                   if msg.from_id is not None else None)
            st = pub.state if pub is not None else None
            hold = st.hold(msg) if st is not None else None
            if hold is not None:
                q.push_over(item, hold)
                if self._hold_timer is None:
                    self._hold_timer = asyncio.get_running_loop().call_later(
                        self.out_inflight.retry_interval, self._holds_due,
                        q.progress)
                return
        policy = Policy.DROP_CURRENT if item.qos == 0 and self.connected else Policy.DROP_EARLY
        dropped = q.push(item, policy)
        if dropped is not None:
            self._queue_full_drop(dropped)

    def _queue_full_drop(self, dropped: DeliverItem) -> None:
        self.ctx.metrics.drop("queue_full")
        hk = self.ctx.hotkeys
        if hk.enabled:
            hk.on_drop("queue_full", self.client_id)
        dur = self.ctx.durability
        if dur is not None and dropped.did:
            # a terminal drop resolves the pending record, or recovery
            # would resurrect a message the broker chose to shed
            dur.on_ack(self.client_id, dropped.did)
        asyncio.get_running_loop().create_task(
            self.ctx.hooks.fire(HookType.MESSAGE_DROPPED, self.id, dropped.msg, "queue-full")
        )

    def _holds_due(self, seen: int) -> None:
        """The stall timer of the held publishes, one retry interval after
        it was armed with the queue's ``progress`` then. Progress since:
        look again in another interval (so a consumer is given up between
        one and two intervals after its last pop). None: the consumer is
        stalled — its holds are released (the publishers get their acks),
        the queue is cut back to its limit oldest first, counted as
        ``queue_full``, and until its next pop a full queue drops again."""
        self._hold_timer = None
        q = self.deliver_queue
        if not q._holds:
            return
        if q.progress != seen:
            self._hold_timer = asyncio.get_running_loop().call_later(
                self.out_inflight.retry_interval, self._holds_due, q.progress)
            return
        q.stalled = True
        self._drop_holds()

    def _drop_holds(self) -> None:
        q = self.deliver_queue
        q.release_all()
        for it in q.trim():
            self._queue_full_drop(it)

    # --------------------------------------------------------------- offline
    def on_disconnect(self, clean: bool, kicked: bool = False) -> None:
        """Socket gone: schedule will + expiry (session.rs:405-494)."""
        self.connected = False
        self.state = None
        # publishes held on this queue wait for a consumer that is gone:
        # release them; a session that lives on offline keeps its limit
        if self._hold_timer is not None:
            self._hold_timer.cancel()
            self._hold_timer = None
        if self.limits.session_expiry > 0:
            self._drop_holds()
        else:
            self.deliver_queue.release_all()
        # durability: anchor the expiry countdown so a broker restart
        # resumes the remaining window instead of a fresh one
        dur = self.ctx.durability
        if dur is not None and self.limits.session_expiry > 0:
            dur.on_session_offline(self.client_id)
        if len(self.out_inflight) and self.limits.session_expiry > 0 and not kicked:
            # unacked QoS1/2 carried into the GENUINE offline path only
            # (hook.rs OfflineInflightMessages; session.rs:277-291): a
            # takeover transfers the window to the new session instead —
            # persisting it too would duplicate deliveries after restart
            inflight_msgs = [e.msg for e in self.out_inflight.entries()]
            asyncio.get_running_loop().create_task(
                self.ctx.hooks.fire(
                    HookType.OFFLINE_INFLIGHT_MESSAGES, self.id, inflight_msgs, None
                )
            )
        if self.will is not None and not clean and not kicked:
            delay = float(self.will.properties.get(P.WILL_DELAY_INTERVAL, 0))
            delay = min(delay, self.limits.session_expiry) if self.limits.session_expiry > 0 else 0.0
            self._will_task = asyncio.get_running_loop().create_task(self._fire_will(delay))
        if self.limits.session_expiry > 0 and not (kicked and self.clean_start):
            self._expiry_task = asyncio.get_running_loop().create_task(
                self._expire(self.limits.session_expiry)
            )
        else:
            asyncio.get_running_loop().create_task(self.ctx.registry.terminate(self, "disconnect"))

    async def _fire_will(self, delay: float) -> None:
        if delay > 0:
            await asyncio.sleep(delay)
        will, self.will = self.will, None
        if will is None:
            return
        msg = Message(
            topic=will.topic,
            payload=will.payload,
            qos=will.qos,
            retain=will.retain,
            properties=dict(will.properties),
            from_id=self.id,
        )
        if will.retain:
            self.ctx.retain.set(will.topic, msg)
        await self.ctx.registry.forwards(msg)

    async def _expire(self, delay: float) -> None:
        await asyncio.sleep(delay)
        await self.ctx.registry.terminate(self, "expired")

    def on_reconnect(self) -> None:
        """Cancel pending offline timers (resumed before expiry)."""
        if self._expiry_task is not None:
            self._expiry_task.cancel()
            self._expiry_task = None
        if self._will_task is not None:
            self._will_task.cancel()
            self._will_task = None

    def transfer_inflight_to_queue(self) -> None:
        """Reconnect redelivery: unacked QoS1/2 → front of queue with DUP
        (session.rs rerelease/reforward :1469-1553)."""
        items = []
        for e in self.out_inflight.drain():
            if e.status is MomentStatus.UNCOMPLETE:
                # QoS2 already PUBREC'd: must resume with PUBREL, keep in window
                self.out_inflight.push(e)
                continue
            items.append(
                DeliverItem(
                    msg=e.msg, qos=e.qos, retain=e.retain, topic_filter="",
                    sub_ids=e.subscription_ids, dup=True, did=e.did,
                )
            )
        q = self.deliver_queue.drain()
        for it in items:
            self.deliver_queue.push(it)
        for it in q:
            self.deliver_queue.push(it)


def connect_info_will(ci: ConnectInfo) -> Optional[pk.Will]:
    return ci.will


def session_snapshot(s: Session, max_queue_items: Optional[int] = None) -> dict:
    """Serializable session state: identity, limits, subscriptions, queued
    AND unacked in-flight messages (the reference's SessionStateTransfer
    payload carries both, session.rs:1374-1427 + OfflineInfo inflight).
    Used by session-storage persistence and cross-node takeover transfer.
    ``max_queue_items`` caps the payload for wire transfer only."""
    from rmqtt_tpu.cluster.messages import msg_to_wire, opts_to_wire

    items = []
    # unacked QoS1/2 go first, flagged DUP for redelivery; QoS2 already
    # PUBREC'd (UNCOMPLETE) would duplicate if replayed — dropped, as the
    # new connection cannot resume the old packet-id handshake
    for e in s.out_inflight.drain():
        if e.status is not MomentStatus.UNCOMPLETE:
            items.append([e.qos, e.retain, "", list(e.subscription_ids), msg_to_wire(e.msg), True])
    for it in s.deliver_queue._q:
        items.append([it.qos, it.retain, it.topic_filter, list(it.sub_ids), msg_to_wire(it.msg), it.dup])
    if max_queue_items is not None:
        items = items[:max_queue_items]
    return {
        "client_id": s.client_id,
        "node_id": s.id.node_id,
        "clean_start": s.clean_start,
        "created_at": s.created_at,
        "session_expiry": s.limits.session_expiry,
        "disconnected_at": time.time(),
        "max_inflight": s.limits.max_inflight,
        "max_mqueue": s.limits.max_mqueue,
        "protocol": s.connect_info.protocol,
        "keepalive": s.connect_info.keepalive,
        "subs": [[tf, opts_to_wire(o)] for tf, o in s.subscriptions.items()],
        "queue": items,
        "fence": list(s.fence),
    }


async def restore_session(ctx, snap: dict, node_id: Optional[int] = None) -> Optional[Session]:
    """Rebuild an OFFLINE session from a snapshot (offline_restart,
    session.rs:516-558): re-registers subscriptions (under ``node_id`` if
    given — the takeover-transfer case re-homes them) and refills the queue.
    Returns None if the snapshot already expired.

    NOTE: broker/durability.py `_restore_sessions` mirrors this for the
    journal-shaped durable state (plus per-item durable ids) — semantic
    fixes here (expiry math, fencing) must propagate there."""
    from rmqtt_tpu.cluster.messages import msg_from_wire, opts_from_wire
    from rmqtt_tpu.core.topic import strip_prefixes

    remaining = snap["session_expiry"] - (time.time() - snap["disconnected_at"])
    if remaining <= 0:
        return None
    sid = Id(node_id if node_id is not None else snap["node_id"], snap["client_id"])
    ci = ConnectInfo(
        id=sid, protocol=snap["protocol"], keepalive=snap["keepalive"], clean_start=False
    )
    limits = Limits(
        keepalive=snap["keepalive"], server_keepalive=False,
        max_inflight=snap["max_inflight"], max_mqueue=snap["max_mqueue"],
        session_expiry=remaining,
        max_message_expiry=ctx.cfg.fitter.max_message_expiry,
        max_topic_aliases_in=0, max_topic_aliases_out=0,
        max_packet_size=ctx.cfg.max_packet_size,
    )
    session = Session(ctx, sid, ci, limits, clean_start=False)
    session.fence = tuple(snap.get("fence", (0, sid.node_id)))
    # the restored fence must also advance the local clock, or the next
    # local takeover could stamp a LOWER fence than the state it resumes
    observe = getattr(ctx.registry, "observe_fence", None)
    if observe is not None:
        observe(session.fence[0])
    ctx.registry._sessions[snap["client_id"]] = session
    for tf, ow in snap["subs"]:
        opts = opts_from_wire(ow)
        try:
            stripped = strip_prefixes(tf)
        except ValueError:
            stripped = tf
        await ctx.registry.subscribe(session, tf, stripped, opts)
    for row in snap["queue"]:
        qos, retain, tf, sub_ids, mw = row[:5]
        dup = bool(row[5]) if len(row) > 5 else False
        msg = msg_from_wire(mw)
        if not msg.is_expired():
            session.deliver_queue.push(
                DeliverItem(msg=msg, qos=qos, retain=retain,
                            topic_filter=tf, sub_ids=tuple(sub_ids), dup=dup)
            )
    session._expiry_task = asyncio.get_running_loop().create_task(session._expire(remaining))
    return session


class SessionState:
    """The online half: socket ↔ session (session.rs run_loop :308-402)."""

    def __init__(self, ctx, session: Session, reader, writer, codec: MqttCodec) -> None:
        self.ctx = ctx
        self.s = session
        self.reader = reader
        self.writer = writer
        self.codec = codec
        self._wlock = asyncio.Lock()
        self._alias_in: Dict[int, str] = {}
        # outbound aliasing (v5): topic → alias, bounded by the client's
        # advertised Topic Alias Maximum (session.rs topic-alias tables)
        self._alias_out: Dict[str, int] = {}
        self._last_packet = time.monotonic()
        self._clean_disconnect = False
        self._kicked = False
        self._closing = asyncio.Event()
        self._disconnect_reason: Optional[int] = None
        # per-stage fast recorder (memoized in the registry; a no-op when
        # telemetry is disabled — the t0 guard means it's never called)
        self._rec_e2e = ctx.telemetry.recorder("publish.e2e")
        self._rec_dqwait = ctx.telemetry.recorder("deliver.queue_wait")
        self._rec_hold = ctx.telemetry.recorder("fanout.hold")
        # busy-clock stages of this connection's share of the served path
        # (telemetry.Stage; every begin/end below guards on tele.enabled)
        stage = ctx.telemetry.stage
        self._st_decode = stage("ingress.decode")
        self._st_publish = stage("ingress.publish")
        self._st_credit = stage("deliver.credit_wait")
        self._st_send = stage("deliver.send")
        self._st_ack_in = stage("ack.in")
        self._st_ack_out = stage("ack.out")
        # clock of the last publish.e2e close: ack.out opens on it
        self._t_e2e_end = 0
        # backpressure from full deliver queues (Session._enqueue_crowded):
        # the QoS1/2 message this connection is fanning out right now, the
        # Hold a full queue made for it, and the acks that wait — in the
        # order their publishes came, each behind its hold (None: it only
        # waits its turn) — for the one task that sends them
        self._pub_msg: Optional[Message] = None
        self._hold: Optional[Hold] = None
        self._held_acks: deque = deque()
        self._held_task: Optional[asyncio.Task] = None
        # the parked deliver loop's credit wait, taken by this read chunk's
        # first PUBACK / PUBCOMP while work is queued (_handle), given back
        # by the refill that spends the credit the chunk freed (_refill)
        self._claimed: Optional[asyncio.Future] = None
        # runs of pipelined publishes (_publish_run): where the registry's
        # forwards is a match and then a synchronous fan-out (the single
        # node's; a cluster's or the fabric's keeps one publish at a time)
        self._runs = getattr(ctx.registry, "run_forwards", False)
        # packets a client pipelined behind CONNECT in the same TCP segment
        # (legal without waiting for CONNACK); replayed by _read_loop
        self.early_packets: list = []
        # coalesced egress (broker/egress.py): one vectored send per loop
        # tick instead of one write per frame, made by the context's hub —
        # on the native egress thread where the connection allows it.
        # buffers_until_drain writers (WsWriter) stay on the legacy path —
        # their transport only flushes on drain(), which the coalescer's
        # tick flush never calls
        self._egress = None
        if (getattr(ctx, "egress_coalesce", False)
                and not getattr(writer, "buffers_until_drain", False)):
            from rmqtt_tpu.broker.egress import EgressBuf

            self._egress = EgressBuf(
                writer, ctx.metrics,
                high_water=getattr(ctx, "egress_high_water", 64 * 1024),
                telemetry=ctx.telemetry,
                hub=getattr(ctx, "egress_hub", None))
        # the connection whose reads the native ingress thread does
        # (broker/ingress.py), from _read_loop's take-over to the close
        self._ingress = None

    # ------------------------------------------------------------------ io
    async def send(self, packet) -> None:
        await self.send_raw(self.codec.encode(packet))

    async def send_raw(self, data: bytes) -> None:
        async with self._wlock:
            if self._write(data):
                await self.writer.drain()

    def _write(self, data: bytes) -> bool:
        """The synchronous half of ``send_raw`` (the caller holds ``_wlock``
        or has seen it free, and does not yield before acting on the
        answer): queue the frame; → True when the caller must
        ``writer.drain()`` under the lock."""
        eb = self._egress
        transport = getattr(self.writer, "transport", None)
        if eb is not None:
            # coalesced path: the frame joins the connection's per-tick
            # vector; the hub's one flush per tick writes everything
            # queued this tick as a single vectored write. Past the
            # high-water mark flush inline and drain — same backpressure
            # the legacy gate applied, now counting our own pending bytes
            # too (the transport can't see frames still in the vector, nor
            # those the native thread is writing).
            eb.feed(data)
            if transport is None:
                eb.flush()
                return True
            if (eb.pending_bytes + transport.get_write_buffer_size()
                    > eb.high_water):
                eb.flush()
                self.ctx.metrics.inc("net.egress_drains")
                return True
            return False
        self.writer.write(data)
        # drain only under backpressure: an await per delivered message
        # halves throughput, and asyncio buffers safely below the
        # high-water mark (the 64KB gate bounds growth between drains).
        # Writers that only flush ON drain (WsWriter) keep draining
        # every send.
        return (getattr(self.writer, "buffers_until_drain", False)
                or transport is None
                or transport.get_write_buffer_size() > 64 * 1024)

    async def close(self, kicked: bool = False) -> None:
        self._kicked = self._kicked or kicked
        self._closing.set()

    # ---------------------------------------------------------------- loop
    async def run(self) -> None:
        s = self.s
        tasks = [
            asyncio.create_task(self._read_loop(), name=f"read:{s.client_id}"),
            asyncio.create_task(self._deliver_loop(), name=f"deliver:{s.client_id}"),
            asyncio.create_task(self._retry_loop(), name=f"retry:{s.client_id}"),
        ]
        timeout = self.ctx.fitter.keepalive_timeout(s.limits.keepalive)
        wheel = getattr(self.ctx, "keepalive_wheel", None)
        wheel_entry = None
        if timeout > 0:
            if wheel is not None:
                # hashed timer wheel: one ticking task per worker instead
                # of one timer coroutine per connection (broker/egress.py)
                wheel_entry = wheel.arm(self, timeout)
            else:
                tasks.append(asyncio.create_task(self._keepalive_loop(timeout)))
        closer = asyncio.create_task(self._closing.wait())
        try:
            done, pending = await asyncio.wait(
                tasks + [closer], return_when=asyncio.FIRST_COMPLETED
            )
            for t in done:
                if t is not closer and t.exception() is not None and not isinstance(
                    t.exception(), (ConnectionError, asyncio.IncompleteReadError)
                ):
                    self.ctx.metrics.inc("session.loop_errors")
        finally:
            for t in tasks + [closer]:
                t.cancel()
            if self._held_task is not None:
                self._held_task.cancel()
            if wheel_entry is not None:
                wheel.disarm(wheel_entry)
            try:
                if self.s.connect_info.protocol == pk.V5 and self._kicked:
                    from rmqtt_tpu.broker.types import RC_SESSION_TAKEN_OVER

                    await asyncio.wait_for(
                        self.send(pk.Disconnect(RC_SESSION_TAKEN_OVER)), timeout=1.0
                    )
            except Exception:
                pass
            if self._egress is not None:
                # push any still-vectored frames (the kicked DISCONNECT
                # above included) into the transport before close(), behind
                # what the native thread is still writing (flush waits)
                self._egress.flush()
                self._egress.close()
            if self._ingress is not None:
                # out of the ingress thread's hands before the socket goes
                self._ingress.detach()
            try:
                self.writer.close()
            except Exception:
                pass
            await self.ctx.hooks.fire(
                HookType.CLIENT_DISCONNECTED, s.id, self._reason_string(), None
            )
            s.on_disconnect(clean=self._clean_disconnect, kicked=self._kicked)

    def _reason_string(self) -> str:
        if self._kicked:
            return "kicked"
        if self._clean_disconnect:
            return "by-client"
        return "socket-closed"

    async def _read_loop(self) -> None:
        early, self.early_packets = self.early_packets, []
        for p in early:
            await self._serve([p])
        if self.codec.pending_error is not None:
            # the pipelined CONNECT burst ended in a malformed frame (even
            # with no valid packets between CONNECT and the bad frame):
            # any valid packets above were processed first, then close
            await self._protocol_error(self.codec.pending_error.reason_code)
            return
        hub = getattr(self.ctx, "ingress_hub", None)
        if hub is None or not hub.eligible(self):
            await self._read_transport()
        # a connection's first chunk (as a rule its SUBSCRIBE, or all a
        # short-lived client ever sends) keeps the transport's path: the
        # take-over costs a handful of system calls and every later read a
        # second thread's wake-up, which the connect phase should not wait
        # for and a connection that says one thing never earns back
        elif (await self._read_transport(first_only=True)
                and await self._read_transport(held_only=True)
                and await self._read_offloop(hub)):
            await self._read_transport()

    async def _read_transport(self, first_only: bool = False,
                              held_only: bool = False) -> bool:
        """Serve the chunks of the asyncio transport's StreamReader: until
        the connection ends (→ False); or — ``first_only`` — one chunk
        (→ True after it); or — ``held_only``, the transport paused for the
        ingress thread's take-over — while the reader holds bytes it had
        read already (→ True once it is empty)."""
        reader = self.reader
        metrics = self.ctx.metrics
        if held_only:
            transport = self.writer.transport
            transport.pause_reading()
        served = 0
        while True:
            if (first_only and served) or (held_only and not reader._buffer):
                return True
            served += 1
            data = await reader.read(65536)
            if held_only:
                # a read that drains the reader may resume the transport:
                # paused again before anything can suspend
                transport.pause_reading()
            if not data:
                return False
            self._last_packet = time.monotonic()
            metrics.inc("net.ingress_reads")
            try:
                packets = self._decode_chunk(data, len(data))
            except ProtocolViolation as e:
                await self._protocol_error(e.reason_code)
                return False
            await self._serve(packets)
            if self.codec.pending_error is not None:
                await self._protocol_error(self.codec.pending_error.reason_code)
                return False

    def _decode_chunk(self, data: bytes, size: int, meta=None, row0: int = 0,
                      nrows: int = 0) -> list:
        """One read chunk's packets, under the ``ingress.decode`` stage:
        ``data`` through ``codec.feed``, or (``meta``) the ingress thread's
        bytes, ``size`` of them whole frames, built from their scan
        records. ``feed``'s contract: a frame that does not decode raises
        if no packet precedes it, else is left as ``codec.pending_error``
        for after the packets."""
        tok = (self._st_decode.begin(size)
               if self.ctx.telemetry.enabled else 0)
        try:
            if meta is None:
                return self.codec.feed(data)
            return self.codec.build(data, meta, row0, nrows)
        finally:
            if tok:
                self._st_decode.end(tok)

    async def _protocol_error(self, reason_code: int) -> None:
        """A frame did not decode (the valid packets before it have been
        handled). v5: name the violation before closing (DISCONNECT 0x95
        packet-too-large / 0x81 malformed; disconnect.rs reasons)."""
        self.ctx.metrics.inc("protocol.errors")
        await self._disconnect_with(reason_code)

    async def _read_offloop(self, hub) -> bool:
        """Give the socket's reads to the native ingress thread
        (broker/ingress.py; the transport is paused and its StreamReader
        empty) and serve what it posts until the connection ends (→ False:
        the read loop is done). → True where nothing could be registered:
        the reads stay the transport's."""
        transport = self.writer.transport
        conn = None
        if not (self.reader.at_eof() or transport.is_closing()):
            # the codec's buffer holds at most the head of a frame now
            conn = hub.attach(self, bytes(self.codec._buf))
        if conn is None:
            transport.resume_reading()
            return True
        self.codec._buf.clear()
        self._ingress = conn
        inbox = conn.inbox
        while True:
            if not inbox:
                if conn.lost:
                    # asyncio closed the connection under us (a write
                    # failed, the writer was closed): what reader.read()
                    # would have told the old loop
                    return False
                await conn.wait()
                continue
            chunks, meta, blob = inbox.popleft()
            i = inbox.popleft()
            flags, size = chunks[i + 1], chunks[i + 4]
            if not flags & INGRESS_DATA:
                # EOF, or recv's error: what reader.read() tells the other
                # loop with b"" or a ConnectionError. run() closes the
                # writer; no turn is spent on letting the transport see it
                # too, while a publish to this client would still find the
                # session connected
                return False
            violation = None
            packets: list = []
            try:
                if flags & INGRESS_RAW:
                    # the scan refused a frame: codec.feed judges the bytes
                    off = chunks[i + 3]
                    packets = self._decode_chunk(blob[off:off + size], size)
                else:
                    packets = self._decode_chunk(
                        blob, size, meta, chunks[i + 5], chunks[i + 6])
                    # the chunks that came behind it while this task waited
                    # its turn (the thread reads a burst segment by
                    # segment) are served with it, as reader.read() would
                    # have given them: what a client has pipelined is
                    # together again, and _handle_all sees its runs
                    while inbox and self.codec.pending_error is None:
                        chunks, i = inbox[0][0], inbox[1]
                        if chunks[i + 1] & ~INGRESS_PAUSED != INGRESS_FRAMES:
                            break  # raw bytes, EOF, an error: a turn of its own
                        _, meta, blob = inbox.popleft()
                        inbox.popleft()
                        more = chunks[i + 4]
                        size += more
                        packets += self._decode_chunk(
                            blob, more, meta, chunks[i + 5], chunks[i + 6])
            except ProtocolViolation as e:
                if flags & INGRESS_RAW or not packets:
                    await self._protocol_error(e.reason_code)
                    return False
                violation = e  # of a later chunk: after the packets before it
            await self._serve(packets)
            if violation is not None:
                await self._protocol_error(violation.reason_code)
                return False
            if self.codec.pending_error is not None:
                await self._protocol_error(self.codec.pending_error.reason_code)
                return False
            conn.ack(size)

    async def _deliver_loop(self) -> None:
        s = self.s
        while True:
            await s.deliver_queue.wait_nonempty()
            await s.deliver_queue.throttle()
            if not s.out_inflight.has_credit():
                # credit-gated (session.rs:362, inflight.rs:319): wake on the
                # ack that frees a slot instead of sleep-polling (which
                # capped QoS1/2 delivery at ~window/10ms per session)
                t0 = time.perf_counter_ns() if self.ctx.telemetry.enabled else 0
                await s.out_inflight.wait_credit()
                if t0:
                    self._st_credit.add_wait(time.perf_counter_ns() - t0)
                continue
            item = s.deliver_queue.pop()
            if item is None:
                continue
            await self._deliver(item)

    async def _deliver(self, item: DeliverItem) -> None:
        s = self.s
        msg = item.msg
        # the expiry hook comes first: a plugin's hook may suspend, so the
        # busy section opens after it (with no hook registered the await
        # returns without yielding and costs the section nothing)
        expired = await self.ctx.hooks.fire(
            HookType.MESSAGE_EXPIRY_CHECK, s.id, msg, initial=msg.is_expired()
        )
        if expired:
            self.ctx.metrics.inc("messages.expired")
            self.ctx.metrics.drop("expired")
            hk = self.ctx.hotkeys
            if hk.enabled:
                hk.on_drop("expired", s.client_id)
            if item.did and self.ctx.durability is not None:
                self.ctx.durability.on_ack(s.client_id, item.did)
            await self.ctx.hooks.fire(HookType.MESSAGE_DROPPED, s.id, msg, "expired")
            return
        # deliver.send: props, OutEntry push, encode, egress feed. ONE
        # clock read opens it and closes deliver.queue_wait (enqueue →
        # here; the pop is a few attribute loads back)
        tok = 0
        if item.t_enq:
            tok = self._st_send.begin(trace=item.trace)
            self._rec_dqwait(abs(tok) - item.t_enq, None, item.trace)
        props: Dict[int, object] = {
            k: v
            for k, v in msg.properties.items()
            if k in (P.PAYLOAD_FORMAT_INDICATOR, P.CONTENT_TYPE, P.RESPONSE_TOPIC,
                     P.CORRELATION_DATA, P.USER_PROPERTY)
        }
        rem = msg.remaining_expiry()
        if rem is not None:
            props[P.MESSAGE_EXPIRY_INTERVAL] = rem
        if item.sub_ids:
            props[P.SUBSCRIPTION_IDENTIFIER] = list(item.sub_ids)
        packet_id = None
        if item.qos > 0:
            packet_id = s.out_inflight.alloc_packet_id()
            if packet_id is None:
                if tok:
                    self._st_send.end(tok)
                if item.did and self.ctx.durability is not None:
                    self.ctx.durability.on_ack(s.client_id, item.did)
                await self.ctx.hooks.fire(HookType.MESSAGE_DROPPED, s.id, msg, "no-packet-id")
                return
            s.out_inflight.push(
                OutEntry(
                    packet_id, msg, item.qos, subscription_ids=item.sub_ids,
                    retain=item.retain, wire_props=dict(props),
                    trace=item.trace, did=item.did,
                )
            )
        # QoS0 fan-out fast path: for subscribers of the same protocol
        # version the wire frame is byte-identical (no packet id, no
        # per-subscription props, alias disabled), so encode ONCE per
        # publish and reuse the bytes across the whole fan-out — the
        # per-delivery encode was the hot loop's dominant cost
        # (shared.rs:876-963's preserialized-clone analogue)
        if (item.qos == 0 and not item.sub_ids and not (
                self.codec.version == pk.V5
                and s.limits.max_topic_aliases_out > 0)):
            key = (self.codec.version, item.retain, rem)
            cache = item.wire_cache
            data = cache.get(key)
            if data is None:
                data = cache[key] = encode_qos0_frame(
                    msg, self.codec.version, item.retain, rem)
            await self._send_staged(data, tok, item.trace, 0)
            await self.ctx.hooks.fire(HookType.MESSAGE_DELIVERED, s.id, msg, None)
            return
        # outbound topic alias AFTER the drop checks: an alias must never be
        # registered for a publish that does not reach the wire (the client
        # would see later empty-topic reuses as 0x94 protocol errors)
        topic_out = msg.topic
        if self.codec.version == pk.V5 and s.limits.max_topic_aliases_out > 0:
            alias = self._alias_out.get(msg.topic)
            if alias is not None:
                props[P.TOPIC_ALIAS] = alias
                topic_out = ""  # established alias: omit the topic bytes
            elif len(self._alias_out) < s.limits.max_topic_aliases_out:
                alias = len(self._alias_out) + 1
                self._alias_out[msg.topic] = alias
                props[P.TOPIC_ALIAS] = alias  # first use carries both
        pub = pk.Publish(
            topic=topic_out,
            payload=msg.payload,
            qos=item.qos,
            retain=item.retain,
            dup=item.dup,
            packet_id=packet_id,
            properties=props if self.codec.version == pk.V5 else {},
        )
        await self._send_staged(self.codec.encode(pub), tok, item.trace, item.qos)
        await self.ctx.hooks.fire(HookType.MESSAGE_DELIVERED, s.id, msg, None)

    async def _send_in_stage(self, data: bytes, st, tok: int) -> int:
        """Queue one encoded frame and close the busy section ``tok`` of
        stage ``st`` (0 = telemetry off); → the section's ns. With the
        write lock free the feed is synchronous and inside the section;
        the clock stops before any await that can suspend (a drain under
        back-pressure, the lock held by a draining sender)."""
        if self._wlock.locked():
            dur = st.end(tok) if tok else 0
            await self.send_raw(data)
            return dur
        drain = self._write(data)
        dur = st.end(tok) if tok else 0
        if drain:
            async with self._wlock:
                await self.writer.drain()
        return dur

    async def _send_staged(self, data: bytes, tok: int, trace, qos: int) -> None:
        """The tail of ``_deliver``: send, close ``deliver.send``, count."""
        dur = await self._send_in_stage(data, self._st_send, tok)
        self.ctx.metrics.inc("messages.delivered")
        hk = self.ctx.hotkeys
        if hk.enabled:  # delivering-subscriber attribution seam
            hk.on_deliver(self.s.client_id)
        if trace is not None and tok and (trace.sampled or trace.slow):
            # the per-subscriber span of a recording trace, on the
            # stage's own clock pair
            trace.add("deliver.send", abs(tok), dur,
                      {"client": self.s.client_id, "qos": qos})

    async def _retry_loop(self) -> None:
        s = self.s
        while True:
            wait = s.out_inflight.next_retry_in()
            if wait is None:
                # empty window: block until a QoS1/2 delivery is in flight
                # instead of waking every retry_interval — at connection
                # scale the idle wakeups alone saturate the core
                await s.out_inflight.wait_nonempty()
                continue
            await asyncio.sleep(wait)
            for e in s.out_inflight.due():
                if not s.out_inflight.mark_retry(e):
                    self.ctx.metrics.drop("retries_exhausted")
                    hk = self.ctx.hotkeys
                    if hk.enabled:
                        hk.on_drop("retries_exhausted", s.client_id)
                    if e.did and self.ctx.durability is not None:
                        # terminal: the broker gave up on this delivery —
                        # recovery must not resurrect it
                        self.ctx.durability.on_ack(s.client_id, e.did)
                    await self.ctx.hooks.fire(
                        HookType.MESSAGE_DROPPED, s.id, e.msg, "retries-exhausted"
                    )
                    continue
                if e.status is MomentStatus.UNCOMPLETE:
                    await self.send(pk.Pubrel(e.packet_id))
                else:
                    # rebuild from the original wire fields; only the expiry
                    # countdown is refreshed
                    props = dict(e.wire_props)
                    rem = e.msg.remaining_expiry()
                    if rem is not None:
                        props[P.MESSAGE_EXPIRY_INTERVAL] = rem
                    await self.send(
                        pk.Publish(
                            topic=e.msg.topic,
                            payload=e.msg.payload,
                            qos=e.qos,
                            dup=True,
                            retain=e.retain,
                            packet_id=e.packet_id,
                            properties=props if self.codec.version == pk.V5 else {},
                        )
                    )

    async def _keepalive_loop(self, timeout: float) -> None:
        while True:
            idle = time.monotonic() - self._last_packet
            if idle >= timeout:
                proceed = await self.ctx.hooks.fire(
                    HookType.CLIENT_KEEPALIVE, self.s.id, idle, initial=True
                )
                if proceed:
                    self.ctx.metrics.inc("keepalive.timeouts")
                    self._closing.set()
                    return
            await asyncio.sleep(max(0.05, timeout - idle))

    # ------------------------------------------------------------- dispatch
    async def _serve(self, packets: list) -> None:
        """One read chunk's packets, then the refill its acks claimed."""
        if len(packets) == 1:
            await self._handle(packets[0])
        else:
            await self._handle_all(packets)
        if self._claimed is not None:
            await self._refill()

    async def _handle_all(self, packets: list) -> None:
        """A read chunk of two or more packets, in order. A PUBLISH with
        another right behind it was pipelined by the client: the two, and
        the PUBLISHes that follow them, are served as a run
        (``_publish_run``); every other packet as it always was. Credit
        the acks before a packet of another kind freed is spent before
        that packet is served (it may suspend)."""
        i, n = 0, len(packets)
        runs = self._runs
        while i < n:
            p = packets[i]
            i += 1
            if self._claimed is not None and not isinstance(p, (pk.Puback, pk.Pubcomp)):
                await self._refill()
            if (runs and i < n and isinstance(p, pk.Publish)
                    and isinstance(packets[i], pk.Publish)):
                i = await self._publish_run(packets, i - 1)
            else:
                await self._handle(p)

    async def _handle(self, p) -> None:
        s = self.s
        if isinstance(p, pk.Publish):
            await self._on_publish(p)
        elif isinstance(p, (pk.Puback, pk.Pubcomp)):
            # ack.in: the window release (and what rides it), up to the
            # acked hook — a plugin's hook may suspend
            tok = self._st_ack_in.begin() if self.ctx.telemetry.enabled else 0
            if self._claimed is None and s.deliver_queue:
                # the deliver loop is parked on a full window with work
                # queued: it sleeps on, and the refill after this chunk's
                # acks spends what they free (_refill)
                self._claimed = s.out_inflight.claim()
            e = s.out_inflight.ack(p.packet_id)
            if e is not None:
                self._record_ack_rtt(e)
                if e.did and self.ctx.durability is not None:
                    self.ctx.durability.on_ack(s.client_id, e.did)
            if tok:
                self._st_ack_in.end(tok)
            if e is not None:
                await self.ctx.hooks.fire(HookType.MESSAGE_ACKED, s.id, e.msg, None)
        elif isinstance(p, pk.Pubrec):
            tok = self._st_ack_in.begin() if self.ctx.telemetry.enabled else 0
            e = s.out_inflight.pubrec(p.packet_id)
            if tok:
                self._st_ack_in.end(tok)
            if e is not None:
                await self.send(pk.Pubrel(p.packet_id))
            elif self.codec.version == pk.V5:
                await self.send(pk.Pubrel(p.packet_id, RC_PACKET_ID_NOT_FOUND))
        elif isinstance(p, pk.Pubrel):
            removed = s.in_qos2.remove(p.packet_id)
            dur = self.ctx.durability
            if (removed and dur is not None
                    and s.limits.session_expiry > 0):
                dur.on_qos2_release(s.client_id, p.packet_id)
                if dur.dirty:
                    # PUBCOMP is the client's license to REUSE this packet
                    # id: the release must be durable first, or a restored
                    # stale window entry would swallow a future publish
                    await dur.barrier()
            await self.send(pk.Pubcomp(p.packet_id))
        elif isinstance(p, pk.Subscribe):
            await self._on_subscribe(p)
        elif isinstance(p, pk.Unsubscribe):
            await self._on_unsubscribe(p)
        elif isinstance(p, pk.Pingreq):
            await self.ctx.hooks.fire(HookType.CLIENT_KEEPALIVE, s.id, 0.0, initial=True)
            await self.send(pk.Pingresp())
        elif isinstance(p, pk.Disconnect):
            from rmqtt_tpu.broker.types import RC_DISCONNECT_WITH_WILL

            self._clean_disconnect = p.reason_code != RC_DISCONNECT_WITH_WILL
            self._disconnect_reason = p.reason_code
            self._closing.set()
        elif isinstance(p, pk.Auth):
            await self._on_auth(p)
        elif isinstance(p, pk.Connect):
            # second CONNECT is a protocol error (MQTT-3.1.0-2)
            self._closing.set()

    async def _refill(self) -> None:
        """Spend the window credit this read chunk's PUBACKs / PUBCOMPs
        freed, here in the read task, with the deliver loop's own steps
        (``_deliver``, in queue order): the frames join the egress job of
        the turn the acks were read in, where waking the loop would cost
        it a loop turn more. The loop's credit wait stays claimed until
        the end, so one task sends at a time even where a hook or a drain
        suspends here; it is given back resolved where a slot is left free
        (the queue ran dry), else parked again — the loop never wakes to
        a full window."""
        s = self.s
        q, fl = s.deliver_queue, s.out_inflight
        w, self._claimed = self._claimed, None
        n = 0
        try:
            while q and fl.has_credit():
                await q.throttle()
                item = q.pop()
                if item is None:
                    break
                n += 1
                await self._deliver(item)
        finally:
            fl.release(w)
            if n:
                self.ctx.metrics.inc("deliver.ack_refills", n)

    def _record_ack_rtt(self, e: OutEntry) -> None:
        """QoS1/2 ack round trip: last (re)delivery → PUBACK/PUBCOMP. Uses
        the inflight entry's ``sent_at`` stamp, so a retried delivery
        measures from its retransmission — the client-visible latency.
        A traced publish gets the same duration as its final span (acks
        land in another task, so the trace ref rides the inflight entry)."""
        tele = self.ctx.telemetry
        if tele.enabled:
            dur = int((time.monotonic() - e.sent_at) * 1e9)
            detail = {"topic": e.msg.topic, "qos": e.qos,
                      "client": self.s.client_id}
            tele.record("deliver.ack_rtt", dur, detail, e.trace)
            if e.trace is not None:
                e.trace.add_wall("deliver.ack_rtt", dur, detail)

    async def _on_auth(self, p: pk.Auth) -> None:
        """v5 re-authentication over the live connection (spec §4.12: client
        AUTH 0x19 starts, 0x18 continues; server answers AUTH until 0x00
        Success or disconnects with the failure code)."""
        from rmqtt_tpu.broker import auth as ea

        s = self.s
        method = p.properties.get(P.AUTHENTICATION_METHOD)
        original = s.connect_info.properties.get(P.AUTHENTICATION_METHOD)
        authenticator = self.ctx.enhanced_auth
        if (
            authenticator is None
            or method is None
            or method != original  # method must not change mid-session (§4.12)
        ):
            await self._disconnect_with(ea.RC_BAD_AUTHENTICATION_METHOD)
            return
        data = p.properties.get(P.AUTHENTICATION_DATA)
        if p.reason_code == ea.RC_RE_AUTHENTICATE:
            rc, out = await authenticator.start(s.connect_info, method, data)
        elif p.reason_code == ea.RC_CONTINUE_AUTHENTICATION:
            rc, out = await authenticator.continue_(s.connect_info, method, data)
        else:
            await self._disconnect_with(0x82)  # protocol error
            return
        if rc in (ea.RC_AUTH_SUCCESS, ea.RC_CONTINUE_AUTHENTICATION):
            props = {P.AUTHENTICATION_METHOD: method}
            if out is not None:
                props[P.AUTHENTICATION_DATA] = out
            await self.send(pk.Auth(rc, props))
        else:
            self.ctx.metrics.inc("auth.failures")
            await self._disconnect_with(rc)

    # -------------------------------------------------------------- publish
    async def _on_publish(self, p: pk.Publish) -> None:
        # ingress.publish: from the decoded PUBLISH to the fan-out's call
        # (alias, hot-key attribution, admission, then _publish_admit)
        tok = (self._st_publish.begin()
               if self.ctx.telemetry.enabled else 0)
        tok = await self._on_publish_staged(p, tok)
        if tok:
            self._st_publish.end(tok)

    async def _on_publish_staged(self, p: pk.Publish, tok: int) -> int:
        """→ the ``ingress.publish`` token where the section is still open (a
        publish refused before the pipeline: a rare path, whose answer may
        be sent inside the section), else 0: ``_publish`` closed it."""
        refusal = self._publish_checks(p)
        if refusal is not None:
            await refusal
            return tok
        accepted, reason = await self._publish(p, tok)
        if p.qos:
            await self._publish_answer(p, accepted, reason)
        return 0

    def _publish_checks(self, p: pk.Publish):
        """What comes before the pipeline: alias, QoS ceiling, QoS2 dedup,
        hot-key attribution, admission, the QoS2 window. → None where the
        publish goes on to ``_publish_admit``, else the awaitable that
        answers it (nothing has been sent yet: a run sends the acks of the
        publishes before it first)."""
        s = self.s
        self.ctx.metrics.inc("publish.received")
        # v5 topic alias resolution (session.rs:994-998)
        if self.codec.version == pk.V5:
            alias = p.properties.get(P.TOPIC_ALIAS)
            if alias is not None:
                if not (1 <= int(alias) <= s.limits.max_topic_aliases_in):
                    return self._disconnect_with(RC_TOPIC_ALIAS_INVALID)
                if p.topic:
                    self._alias_in[int(alias)] = p.topic
                else:
                    topic = self._alias_in.get(int(alias))
                    if topic is None:
                        return self._disconnect_with(RC_TOPIC_ALIAS_INVALID)
                    p.topic = topic
        if p.qos > self.ctx.cfg.max_qos:
            return self._disconnect_with(RC_UNSPECIFIED_ERROR)
        # QoS2 DUP resend of an ALREADY-ACCEPTED publish answers with the
        # dedup PUBREC before admission runs: the retransmit is not new
        # work, and refusing it would strand its in_qos2 entry (the client
        # abandons the flow without PUBREL, shrinking the window forever)
        if p.qos == 2 and p.packet_id in s.in_qos2:
            return self.send(pk.Pubrec(p.packet_id))
        # hot-key attribution ingress seam (broker/hotkeys.py): topic by
        # count AND payload bytes, publishing client. After alias
        # resolution (the key must be the real topic) and the QoS2 dedup
        # check (a DUP resend is not new traffic), BEFORE admission — a
        # rate-limited top talker must still attribute
        hk = self.ctx.hotkeys
        if hk.enabled:
            hk.on_publish(p.topic, s.client_id, len(p.payload))
        # per-client publish admission (broker/overload.py token bucket),
        # AFTER alias resolution (the alias table must stay consistent even
        # across refused publishes) and BEFORE the in_qos2 insert so a
        # refused publish never occupies window state
        ov = self.ctx.overload
        if ov.enabled and not ov.admit_publish(s.client_id):
            return self._refuse_rate_limited(p)
        # QoS2 ingress window insert (session.rs:908-963)
        if p.qos == 2:
            if not s.in_qos2.add(p.packet_id):
                from rmqtt_tpu.broker.types import RC_RECEIVE_MAX_EXCEEDED

                return self.send(pk.Pubrec(p.packet_id, RC_RECEIVE_MAX_EXCEEDED))
            # durability: a persistent publisher's dedup-window entry is
            # journaled BEFORE the fan-out's own pending records — a
            # timer-driven commit landing mid-publish must never persist
            # the fan-out without the window entry, or a post-crash DUP
            # resend would fan out a second time (dup=False) on top of
            # the recovered redelivery. A refusal resolves it
            # (_publish_answer).
            dur = self.ctx.durability
            if dur is not None and s.limits.session_expiry > 0:
                dur.on_qos2_open(s.client_id, p.packet_id)
        return None

    async def _refuse_rate_limited(self, p: pk.Publish) -> None:
        """Admission refused the publish. v5 answers with Quota Exceeded
        (0x97) on PUBACK/PUBREC; v3 has no per-publish reason code, so the
        violating connection is closed."""
        from rmqtt_tpu.broker.types import RC_QUOTA_EXCEEDED

        s = self.s
        self.ctx.metrics.drop("rate_limited")
        hk = self.ctx.hotkeys
        if hk.enabled:
            hk.on_drop("rate_limited", s.client_id)
        await self.ctx.hooks.fire(
            HookType.MESSAGE_DROPPED, s.id,
            Message(topic=p.topic, payload=p.payload, qos=p.qos, from_id=s.id),
            "rate-limited",
        )
        if self.codec.version == pk.V5:
            if p.qos == 1:
                await self.send(pk.Puback(p.packet_id, RC_QUOTA_EXCEEDED))
            elif p.qos == 2:
                await self.send(pk.Pubrec(p.packet_id, RC_QUOTA_EXCEEDED))
            # QoS0: nothing to answer — the drop is counted and traced
        else:
            self._closing.set()

    async def _publish_answer(self, p: pk.Publish, accepted: bool,
                              reason: int) -> None:
        """What follows a QoS1/2 publish's pipeline: the hold its fan-out
        may have met, a refused QoS2's window entry, the durability
        barrier, and the PUBACK / PUBREC — sent, or queued behind a held
        one."""
        s = self.s
        hold = self._hold  # made by a full deliver queue of the fan-out
        if hold is not None:
            self._hold = None
        if p.qos == 2 and not accepted:
            # refused: clear the dedup entry — in memory AND in the
            # journal (before the barrier), so a restored stale entry can
            # never swallow a future publish reusing this packet id
            s.in_qos2.remove(p.packet_id)
            dur = self.ctx.durability
            if dur is not None and s.limits.session_expiry > 0:
                dur.on_qos2_release(s.client_id, p.packet_id)
        # durability ack barrier (broker/durability.py): everything this
        # publish journaled (retained set, per-subscriber pending records,
        # the QoS2 window entry) must be group-committed BEFORE the
        # publisher sees PUBACK/PUBREC — the zero-acked-loss contract
        # across kill -9. Amortized: every concurrent publisher shares one
        # commit; no-op when nothing is buffered. QoS0 has no ack and
        # rides the flush window instead.
        barrier = False
        dur = self.ctx.durability
        if dur is not None and dur.dirty:
            barrier = True
            await dur.barrier()
        # ack.out: the publisher's PUBACK/PUBREC, encode + feed. It opens
        # on the clock read that closed publish.e2e unless a durability
        # barrier suspended in between
        ack = (pk.Puback if p.qos == 1 else pk.Pubrec)(
            p.packet_id, reason if self.codec.version == pk.V5 else 0)
        if hold is not None or self._held_acks:
            # held for deliver-queue room, or behind an ack that is: the
            # read loop goes on, _send_held_acks sends it in its turn
            self._defer_ack(hold, self.codec.encode(ack))
            return
        st = self._st_ack_out
        tok = 0
        if self.ctx.telemetry.enabled:
            tok = st.begin() if barrier else st.begin_at(self._t_e2e_end)
        await self._send_in_stage(self.codec.encode(ack), st, tok)

    # -------------------------------------------- runs of pipelined publishes
    async def _publish_run(self, packets: list, i: int) -> int:
        """Serve the run of PUBLISH packets that starts at ``packets[i]``
        (the next is a PUBLISH too); → the index of the first packet not
        served. Up to ``max_inflight`` of them — the Receive Maximum this
        broker grants — are admitted one by one, as a lone publish is, and
        then enter the routing service together, are fanned out in publish
        order and answered in publish order (``_run_forward``): a
        connection that pipelines is worth a batch to the matcher, where
        one publish at a time it never was more than one topic.

        Nothing overtakes: a publish that is answered without a fan-out
        (refused anywhere on the way, or ``$delayed``) ends the run, and
        the publishes before it are routed, fanned out and answered before
        its own answer goes; every packet that is not a PUBLISH ends it by
        the caller's loop. ``ingress.publish``, ``publish.e2e``, ``ack.out``
        and the trace context are per publish, as on the lone path."""
        ctx = self.ctx
        tele = ctx.telemetry.enabled
        st = self._st_publish
        n = min(len(packets), i + self.s.limits.max_inflight)
        run: list = []  # (packet, message, publish.e2e's t0, trace)
        while i < n:
            p = packets[i]
            if not isinstance(p, pk.Publish):
                break
            i += 1
            tok = st.begin() if tele else 0
            refusal = self._publish_checks(p)
            if refusal is not None:
                if tok:
                    st.end(tok)
                try:
                    await self._run_forward(run)
                except BaseException:
                    refusal.close()  # never awaited: the connection ends
                    raise
                await refusal
                return i
            t0 = time.perf_counter_ns() if tele else 0
            trace = ctx_tok = None
            if t0:
                trace = ctx.tracer.begin(p.topic)
                if trace is not None:
                    ctx_tok = CURRENT_TRACE.set(trace)
            try:
                verdict, msg, tok = await self._publish_admit(p, tok)
            finally:
                if ctx_tok is not None:
                    CURRENT_TRACE.reset(ctx_tok)
            if tok:
                st.end(tok)
            if verdict is not None:
                await self._run_forward(run)
                if t0:
                    self._e2e_done(p, t0, trace)
                if p.qos:
                    await self._publish_answer(p, *verdict)
                return i
            run.append((p, msg, t0, trace))
        await self._run_forward(run)
        return i

    async def _run_forward(self, run: list) -> None:
        """The admitted publishes of a run: their matches awaited together
        (one offer to the routing service), then each fanned out and
        answered, in publish order. The fan-out is synchronous, so the one
        ``_pub_msg`` / ``_hold`` slot serves a publish at a time as on the
        lone path: a full deliver queue holds that publish's ack, and the
        acks of the run's later publishes queue behind it
        (``_publish_answer``); a durability barrier is awaited where it
        falls, before its publish's ack and every later one. Between two
        fan-outs the deliver loops get a turn only where a queue was met
        more than half full (below): a run costs a consumer's queue at
        most that much more room than lone publishes would."""
        if not run:
            return
        ctx = self.ctx
        metrics = ctx.metrics
        metrics.inc("ingress.runs")
        metrics.inc("ingress.run_publishes", len(run))
        registry = ctx.registry
        matched = await ctx.routing.matches_run(
            [e[1] for e in run], [e[3] for e in run])
        crowded = metrics.get("deliver.queue_over_half")
        for (p, msg, t0, trace), (relmap, cache_hit) in zip(run, matched):
            if metrics.get("deliver.queue_over_half") != crowded:
                # the fan-out before this one met a deliver queue more than
                # half full: its deliver loop gets a turn first, as between
                # two lone publishes (``matches_for_fanout``: the yield is
                # load-bearing). Runs of many connections resolve with one
                # dispatch, and their fan-outs back to back overfilled a
                # shared consumer's queue (measured: 100 connections, runs of
                # 16, one QoS0 subscriber: 6,000 of 16,000 dropped, none one
                # at a time)
                await asyncio.sleep(0)
                crowded = metrics.get("deliver.queue_over_half")
            if p.qos:
                # a full deliver queue may hold this publish's ack (hold())
                self._pub_msg = msg
            count = registry.fanout(msg, relmap, cache_hit, trace)
            self._pub_msg = None
            if count == 0:
                await ctx.hooks.fire(
                    HookType.MESSAGE_NONSUBSCRIBED, self.s.id, msg, None)
            if t0:
                self._e2e_done(p, t0, trace)
            if p.qos:
                await self._publish_answer(
                    p, True, RC_SUCCESS if count else RC_NO_MATCHING_SUBSCRIBERS)

    # ------------------------------------------- deliver-queue backpressure
    def hold(self, msg: Message) -> Optional[Hold]:
        """For ``Session._enqueue_crowded``: the Hold of ``msg`` if it is
        the QoS1/2 publish this connection is fanning out (made on the
        first call), else None — as when ``max_inflight`` (the Receive
        Maximum this broker grants) of its acks are held already: a client
        that publishes on regardless is not slowed by one ack more."""
        if msg is not self._pub_msg:
            return None
        h = self._hold
        if h is None:
            if len(self._held_acks) >= self.s.limits.max_inflight:
                return None
            span = None
            if PROFILER.on:
                # not a Stage section (it spans awaits, and holds overlap):
                # its own annotation, closed where the hold ends
                span = PROFILER.annotation("rmqtt/fanout.hold")
                span.__enter__()
            h = self._hold = Hold(time.perf_counter_ns(), span)
            self.ctx.metrics.inc("fanout.held")
        return h

    def _defer_ack(self, hold: Optional[Hold], frame: bytes) -> None:
        self._held_acks.append((hold, frame))
        if self._held_task is None:
            self._held_task = asyncio.get_running_loop().create_task(
                self._send_held_acks(), name=f"held-acks:{self.s.client_id}")

    async def _send_held_acks(self) -> None:
        """Send the held acks in publish order, each when its hold is
        released (the queues it overfilled are back under their limits,
        or gave their consumer up). An ack leaves the chain only once sent,
        so a later publish's ack queues behind it."""
        chain = self._held_acks
        tele = self.ctx.telemetry
        st = self._st_ack_out
        try:
            while chain:
                hold, frame = chain[0]
                if hold is not None:
                    await hold.wait()
                    hold.end_span()
                    if tele.enabled:
                        self._rec_hold(time.perf_counter_ns() - hold.t0, None, None)
                tok = st.begin() if tele.enabled else 0
                await self._send_in_stage(frame, st, tok)
                chain.popleft()
        except OSError:
            pass  # the socket went away: run() is closing the connection
        finally:
            self._held_task = None
            for hold, _frame in chain:  # cancelled: the connection closes
                if hold is not None:
                    hold.end_span()

    async def _publish(self, p: pk.Publish, tok: int = 0) -> Tuple[bool, int]:
        """The ingress pipeline (session.rs _publish :966-1064); ``tok`` is
        the open ``ingress.publish`` section, closed before the fan-out.

        Records the ``publish.e2e`` stage: PUBLISH decode handed to the
        pipeline → the last local forward enqueued (cluster scatter
        included for clustered registries) — the broker's dwell time, the
        number every perf PR reports against.

        Tracing (broker/tracing.py) begins here too: the trace context is
        set for the ingress task so routing / fan-out / cluster scatter
        stamp spans onto it, and finish() decides commit (head-sampled or
        slow) after the e2e duration is known — sharing e2e's timestamp
        pair, so tracing adds no clock reads to this path."""
        ctx = self.ctx
        t0 = time.perf_counter_ns() if ctx.telemetry.enabled else 0
        trace = ctx_tok = None
        if t0:
            trace = ctx.tracer.begin(p.topic)
            if trace is not None:
                ctx_tok = CURRENT_TRACE.set(trace)
        try:
            accepted, reason = await self._publish_inner(p, tok)
        finally:
            if ctx_tok is not None:
                CURRENT_TRACE.reset(ctx_tok)
        if t0:
            self._e2e_done(p, t0, trace)
        return accepted, reason

    def _e2e_done(self, p: pk.Publish, t0: int, trace) -> None:
        """Close ``publish.e2e`` (opened at ``t0``) on one clock read, which
        ``ack.out`` then opens on, and finish the publish's trace."""
        now = self._t_e2e_end = time.perf_counter_ns()
        dur = now - t0
        self._rec_e2e(dur, p.topic, trace)
        if trace is not None:
            trace.add("publish.ingress", t0, dur,
                      {"client": self.s.client_id, "qos": p.qos})
            self.ctx.tracer.finish(trace)

    async def _publish_inner(self, p: pk.Publish, tok: int = 0) -> Tuple[bool, int]:
        """``tok``: the open ``ingress.publish`` section (0 = telemetry
        off); it covers admission and closes before the fan-out."""
        verdict, msg, tok = await self._publish_admit(p, tok)
        if tok:
            self._st_publish.end(tok)
        if verdict is not None:
            return verdict
        metrics = self.ctx.metrics
        metrics.inc("ingress.runs")  # a lone publish is a run of one
        metrics.inc("ingress.run_publishes")
        if p.qos:
            # a full deliver queue may hold this publish's ack (hold())
            self._pub_msg = msg
        count = await self.ctx.registry.forwards(msg)
        self._pub_msg = None
        if count == 0:
            await self.ctx.hooks.fire(HookType.MESSAGE_NONSUBSCRIBED, self.s.id, msg, None)
            return True, RC_NO_MATCHING_SUBSCRIBERS
        return True, RC_SUCCESS

    async def _hook_staged(self, tok: int, htype: HookType, *args, initial=None):
        """``hooks.fire`` from inside the ingress.publish section: a
        registered handler may suspend, so the busy clock stops across it.
        → (the chain's value, the section's live token)."""
        hooks = self.ctx.hooks
        if tok and hooks.has(htype):
            self._st_publish.lap(tok)
            value = await hooks.fire(htype, *args, initial=initial)
            return value, self._st_publish.begin()
        return await hooks.fire(htype, *args, initial=initial), tok

    async def _publish_admit(self, p: pk.Publish, tok: int):
        """Everything between the decoded PUBLISH and the fan-out: topic
        check, publish hook, ACL, retain, $delayed. → (verdict, message,
        token): ``verdict`` is None when the message goes on to
        ``registry.forwards``, else the (accepted, reason) answer."""
        s = self.s
        delay_secs = None
        topic = p.topic
        try:
            delay_secs, topic = parse_delayed(topic)
        except ValueError:
            return (False, RC_TOPIC_NAME_INVALID), None, tok
        if not topic_valid(topic):
            return (False, RC_TOPIC_NAME_INVALID), None, tok
        msg = Message.from_publish(
            p, from_id=s.id, topic=topic, delay_interval=delay_secs,
            expiry_cap=s.limits.max_message_expiry,
        )
        # hook may transform the message (message_publish, session.rs:1008)
        hooked, tok = await self._hook_staged(
            tok, HookType.MESSAGE_PUBLISH, s.id, msg, initial=msg)
        if hooked is None:
            return (False, RC_UNSPECIFIED_ERROR), None, tok
        msg = hooked
        # ACL (message_publish_check_acl, session.rs:1011-1032)
        from rmqtt_tpu.broker.acl import Action

        acl = self.ctx.acl.check(
            Action.PUBLISH, msg.topic, s.connect_info.username, s.client_id
        )
        allow, tok = await self._hook_staged(
            tok, HookType.MESSAGE_PUBLISH_CHECK_ACL, s.id, msg, initial=acl.allow)
        if not allow:
            self.ctx.metrics.inc("publish.acl_denied")
            _, tok = await self._hook_staged(
                tok, HookType.MESSAGE_DROPPED, s.id, msg, "acl-denied")
            return (False, RC_NOT_AUTHORIZED), None, tok
        if msg.retain:
            if not self.ctx.retain.set(msg.topic, msg):
                self.ctx.metrics.inc("retain.refused")
        if delay_secs is not None:
            stripped = replace(msg, retain=False)
            # durability: the PUBACK of a $delayed publish rides the same
            # barrier as everything else, so an acked delayed message
            # survives kill -9 and re-arms with its remaining delay
            dur = self.ctx.durability
            did = dur.on_delayed(delay_secs, stripped) if dur is not None else 0
            if not self.ctx.delayed.push(delay_secs, stripped, did=did):
                if did:
                    dur.on_delayed_done(did)  # refused: resolve the record
                _, tok = await self._hook_staged(
                    tok, HookType.MESSAGE_DROPPED, s.id, msg, "delayed-cap")
                return (False, RC_UNSPECIFIED_ERROR), None, tok
            return (True, RC_SUCCESS), None, tok
        return None, msg, tok

    async def _disconnect_with(self, reason: int) -> None:
        if self.codec.version == pk.V5:
            try:
                await self.send(pk.Disconnect(reason))
            except Exception:
                pass
        self._closing.set()

    # ------------------------------------------------------------ subscribe
    async def _on_subscribe(self, p: pk.Subscribe) -> None:
        s = self.s
        codes = []
        sub_id = None
        if self.codec.version == pk.V5:
            sids = p.properties.get(P.SUBSCRIPTION_IDENTIFIER)
            if sids:
                sub_id = int(sids[0])
        for tf, opts in p.filters:
            code = await self._subscribe_one(tf, opts, sub_id)
            if self.codec.version != pk.V5 and code >= 0x80:
                code = 0x80  # v3.1.1 SUBACK only knows 0x80 for failure
            codes.append(code)
        # durability: a SUBACKed subscription must survive kill -9 — wait
        # for the journaled sub records' group commit (no-op when clean)
        dur = self.ctx.durability
        if dur is not None and dur.dirty:
            await dur.barrier()
        await self.send(pk.Suback(p.packet_id, codes))

    async def _subscribe_one(self, topic_filter: str, opts: pk.SubOpts, sub_id) -> int:
        """session.rs _subscribe :1276-1371."""
        s = self.s
        cfg = self.ctx.cfg
        try:
            if cfg.limit_subscription:
                # $limit/$exclusive prefixes are an opt-in feature, like the
                # reference's limit_subscription listener flag (types.rs:570+)
                limit, unlimited = parse_limit(topic_filter)
            else:
                limit, unlimited = None, topic_filter
            group, stripped = parse_shared(unlimited)
        except InvalidSharedFilter:
            return RC_TOPIC_FILTER_INVALID
        if group is not None and not cfg.shared_subscription:
            from rmqtt_tpu.broker.types import RC_SHARED_SUB_NOT_SUPPORTED

            return RC_SHARED_SUB_NOT_SUPPORTED
        if not filter_valid(stripped):
            return RC_TOPIC_FILTER_INVALID
        if cfg.max_subscriptions and len(s.subscriptions) >= cfg.max_subscriptions:
            from rmqtt_tpu.broker.types import RC_QUOTA_EXCEEDED

            return RC_QUOTA_EXCEEDED
        if cfg.max_topic_levels and len(split_levels(stripped)) > cfg.max_topic_levels:
            return RC_TOPIC_FILTER_INVALID
        # hook + ACL (client_subscribe / client_subscribe_check_acl)
        await self.ctx.hooks.fire(HookType.CLIENT_SUBSCRIBE, s.id, topic_filter, None)
        from rmqtt_tpu.broker.acl import Action

        acl = self.ctx.acl.check(
            Action.SUBSCRIBE, stripped, s.connect_info.username, s.client_id
        )
        allow = await self.ctx.hooks.fire(
            HookType.CLIENT_SUBSCRIBE_CHECK_ACL, s.id, topic_filter, initial=acl.allow
        )
        if not allow:
            return RC_NOT_AUTHORIZED
        qos = min(opts.qos, cfg.max_qos)
        sopts = SubscriptionOptions(
            qos=qos,
            no_local=opts.no_local,
            retain_as_published=opts.retain_as_published,
            retain_handling=opts.retain_handling,
            subscription_ids=(sub_id,) if sub_id is not None else (),
            shared_group=group,
        )
        is_new = topic_filter not in s.subscriptions
        try:
            await self.ctx.registry.subscribe(s, topic_filter, stripped, sopts, limit=limit)
        except Exception as e:
            from rmqtt_tpu.broker.shared import SubscriptionLimitExceeded

            if isinstance(e, SubscriptionLimitExceeded):
                from rmqtt_tpu.broker.types import RC_QUOTA_EXCEEDED

                return RC_QUOTA_EXCEEDED
            # e.g. raft consensus unavailable (no leader / minority partition)
            self.ctx.metrics.inc("subscribe.errors")
            return RC_UNSPECIFIED_ERROR
        await self.ctx.hooks.fire(HookType.SESSION_SUBSCRIBED, s.id, topic_filter, None)
        # retained replay (session.rs:1344-1365; retain-handling v5 3.8.3.1).
        # At ELEVATED+ the retained SCAN fan-out is paused (overload tier:
        # wildcard store scans are deferrable burst work, the live publish
        # path is not) — counted, never silently skipped.
        if group is None and self._should_send_retained(opts, is_new):
            if self.ctx.overload.allow_retained_scan():
                asyncio.get_running_loop().create_task(
                    self._send_retained(stripped, sopts)
                )
            else:
                self.ctx.metrics.inc("overload.retained_scans_paused")
        return qos

    def _should_send_retained(self, opts: pk.SubOpts, is_new: bool) -> bool:
        if not self.ctx.retain.enable:
            return False
        if self.codec.version != pk.V5:
            return True
        if opts.retain_handling == 0:
            return True
        if opts.retain_handling == 1:
            return is_new
        return False

    async def _send_retained(self, topic_filter: str, sopts: SubscriptionOptions) -> None:
        for _topic, msg in await self.ctx.registry.retain_load_with(topic_filter):
            item = DeliverItem(
                msg=msg,
                qos=min(sopts.qos, msg.qos),
                retain=True,  # retained replay always sets RETAIN (3.3.1-8)
                topic_filter=topic_filter,
                sub_ids=sopts.subscription_ids,
            )
            self.s.enqueue(item)

    async def _on_unsubscribe(self, p: pk.Unsubscribe) -> None:
        s = self.s
        codes = []
        for tf in p.filters:
            await self.ctx.hooks.fire(HookType.CLIENT_UNSUBSCRIBE, s.id, tf, None)
            ok = await self.ctx.registry.unsubscribe(s, tf)
            if ok:
                await self.ctx.hooks.fire(HookType.SESSION_UNSUBSCRIBED, s.id, tf, None)
            codes.append(RC_SUCCESS if ok else 0x11)  # 0x11 = no subscription existed
        dur = self.ctx.durability
        if dur is not None and dur.dirty:
            await dur.barrier()
        await self.send(pk.Unsuback(p.packet_id, codes))
