"""Bounded deliver queue with drop policies.

Mirrors `/root/reference/rmqtt/src/queue.rs`: the per-session message queue
between fan-out and the socket writer, bounded, with a drop ``Policy``
(:65-75) — ``DROP_CURRENT`` discards the incoming message (used for QoS0),
``DROP_EARLY`` discards the oldest queued one. An optional token-bucket rate
limit mirrors the ``Limiter``-wrapped receiver (:201-238).

A named departure from upstream: a QoS1/2 delivery for a LIVE session that
finds the queue at its limit is not paid for by a drop. ``push_over`` lets it
in past the limit on behalf of a ``Hold`` — the publish it belongs to, whose
PUBACK/PUBREC the publisher's connection withholds until the hold is
released — and the ``pop`` that brings the queue back under its limit (it has
room again) releases every hold that waits on it, together: the publishers
are acknowledged at the rate the subscriber drains, and their next publishes
arrive side by side, as one batch for the routing service.
The drop policy stays the last resort (``Session.enqueue`` says when).
"""

from __future__ import annotations

import asyncio
import enum
import time
from collections import deque
from typing import Deque, Generic, Optional, TypeVar

T = TypeVar("T")


class Hold:
    """One publish whose acknowledgement waits for deliver-queue room.

    Made by the first full queue the publish's fan-out meets
    (``Session.enqueue``); ``pending`` counts the entries that queues took
    past their limit for it. Each is released once (``release``); at zero
    the publisher's connection, if it waits (``wait``), goes on."""

    __slots__ = ("pending", "t0", "span", "_freed")

    def __init__(self, t0: int, span=None) -> None:
        self.pending = 0
        self.t0 = t0  # perf_counter_ns at the first full queue
        self.span = span  # the open ``rmqtt/fanout.hold`` annotation, if any
        self._freed: Optional[asyncio.Future] = None

    def release(self) -> None:
        self.pending -= 1
        freed = self._freed
        if not self.pending and freed is not None and not freed.done():
            freed.set_result(None)

    async def wait(self) -> None:
        if self.pending:
            self._freed = asyncio.get_running_loop().create_future()
            await self._freed

    def end_span(self) -> None:
        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None


class Policy(enum.Enum):
    DROP_CURRENT = "current"  # drop the new message (queue.rs Policy::Current)
    DROP_EARLY = "early"  # drop the oldest queued message (Policy::Early)


class DeliverQueue(Generic[T]):
    def __init__(self, maxlen: int = 1000, rate_limit: Optional[float] = None) -> None:
        self.maxlen = maxlen
        self.half = maxlen // 2  # Session.enqueue's one compare
        self._q: Deque[T] = deque()
        # the holds of the entries that went in past the limit (push_over),
        # waiting for the queue to be back under it
        self._holds: Deque[Hold] = deque()
        # entries popped while a hold waited: the stall timer of Session
        # compares two readings of it
        self.progress = 0
        # the consumer took nothing for a whole retry interval with holds
        # waiting: until its next pop the drop policy applies again
        self.stalled = False
        self._event = asyncio.Event()
        self._rate_limit = rate_limit
        self._allowance = rate_limit or 0.0
        self._last = time.monotonic()

    def __len__(self) -> int:
        return len(self._q)

    def occupancy(self) -> float:
        """Queue fullness in [0, 1] (overload-controller pressure signal)."""
        return len(self._q) / self.maxlen if self.maxlen else 0.0

    def put(self, item: T) -> None:
        """Enqueue where the caller has seen room (``Session.enqueue``
        compares the length once, against ``half``)."""
        self._q.append(item)
        self._event.set()

    def push(self, item: T, policy: Policy = Policy.DROP_EARLY) -> Optional[T]:
        """Enqueue; returns the dropped item if the queue was full."""
        dropped: Optional[T] = None
        if len(self._q) >= self.maxlen:
            if policy is Policy.DROP_CURRENT:
                return item
            dropped = self._q.popleft()
        self._q.append(item)
        self._event.set()
        return dropped

    def push_over(self, item: T, hold: Hold) -> None:
        """Enqueue past the limit for a held publish (see the module's
        docstring); the pop that brings the queue back under its limit
        releases ``hold``."""
        self.put(item)
        hold.pending += 1
        self._holds.append(hold)

    def pop(self) -> Optional[T]:
        if not self._q:
            self._event.clear()
            return None
        item = self._q.popleft()
        if self._holds or self.stalled:
            # the consumer lives; with room again, nothing waits for it
            self.stalled = False
            self.progress += 1
            if len(self._q) < self.maxlen:
                self.release_all()
        return item

    def release_all(self) -> None:
        """Release every hold that waits here: the queue has room again,
        or its consumer is gone or has stalled."""
        while self._holds:
            self._holds.popleft().release()

    def trim(self) -> list:
        """Cut the queue back to its limit, oldest first (``DROP_EARLY``);
        → what was cut."""
        return [self._q.popleft()
                for _ in range(len(self._q) - self.maxlen)]

    async def wait_nonempty(self) -> None:
        if self._q:
            return
        self._event.clear()
        await self._event.wait()

    async def throttle(self) -> None:
        """Token-bucket pacing of the consumer (queue.rs Limiter)."""
        if not self._rate_limit:
            return
        nw = time.monotonic()
        self._allowance = min(
            self._rate_limit, self._allowance + (nw - self._last) * self._rate_limit
        )
        self._last = nw
        if self._allowance < 1.0:
            await asyncio.sleep((1.0 - self._allowance) / self._rate_limit)
            # re-anchor the accrual clock AFTER the sleep: leaving _last at
            # the pre-sleep stamp double-counted the slept interval (once as
            # the token this wait earned, again as elapsed time on the next
            # call), letting the sustained rate drift to ~2x the limit
            self._last = time.monotonic()
            self._allowance = 0.0
        else:
            self._allowance -= 1.0

    def drain(self) -> Deque[T]:
        q, self._q = self._q, deque()
        self._event.clear()
        self.release_all()
        return q
