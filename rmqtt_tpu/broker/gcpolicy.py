"""The process's collector policy: a full pass that was long is the last one
over that heap.

CPython's cyclic collector stops every thread of the process while it runs,
and a *full* (generation 2) pass walks every tracked object that is not in
the permanent generation. A broker's heap is its subscription table, its
sessions and what it imported: millions of objects that stay. The collector
cannot know that; it walks them again each time a quarter as many young
objects have been promoted (``long_lived_pending``, ``Modules/gcmodule.c``),
and frees almost nothing. On a 1M-row table (4.7M tracked objects, ~330 ns
each) that was five or six stalls of 1.5 s in a 51 s window, on a 100K-row
one (0.6M objects) thirty-odd of 180 ms: the largest single item on the
event loop's thread, and the p99 of every latency a client sees.

The policy owns one entry of ``gc.callbacks`` and two rules, both applied
where a full pass ends, on whichever thread collected:

*Freeze.* A full pass that took longer than ``LONG_PASS_S`` is followed at
once by ``gc.freeze()``: what just survived moves to the permanent generation,
and later passes walk only what was allocated since. Frozen objects still die
by reference count as before. The trigger is the observed length of a pass,
so a table that grows, is rebuilt by a compaction or arrives over a minute of
SUBSCRIBEs is frozen again when the part that is not frozen has become long to
walk; nothing has to know that "the load is done". (Not handed to the event
loop: while a table loads, one turn of the loop lasts tens of seconds, and a
freeze that waits for the turn's end lets every full pass inside it walk the
whole growing heap again. ``gc.freeze()`` splices lists and runs no Python
code; at the "stop" phase the collection is over.)

*Thaw, on a budget.* Cyclic garbage among frozen objects (a closed session's
task and state, a replaced table's internals) must not leak for ever, so the
whole heap is walked again now and then: when a full pass ends past the due
time, ``gc.unfreeze()`` puts the permanent generation back, the collector's
next full pass walks everything, frees what is garbage, and is frozen by the
rule above. A walk of the whole heap that took ``d`` seconds (the count of
what it froze included) is not followed by the next thaw before
``d / THAW_BUDGET`` seconds have passed: the broker grants that share of its
wall clock to full walks of its long-lived heap, whatever the heap's size. The
clock of the rule is the collector itself, so a process that allocates
nothing walks nothing.

Neither constant is an option: both are about what the code can measure (a
pass's length), not about a deployment. The policy is process-global like
the collector, and armed per broker with a reference count like
``HOSTPROF``, so the last ``disarm`` leaves the process as it was found:
callback removed, heap unfrozen.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Callable, Optional

#: A full pass longer than this is the last one over its heap. 20 ms is four
#: switch intervals of the interpreter and a fifth of the median delivery in
#: the saturated cells (~100 ms): a stall that a p99 can feel, where a pass of
#: a few milliseconds is lost among the young passes (1–2 ms each, a hundred
#: a second). It is also long enough that a process with little on its heap (a
#: test's in-process broker, a tool) is never touched.
LONG_PASS_S = 0.020

#: Share of wall clock granted to walks of the whole long-lived heap. It is
#: also the share of publishes that meet such a walk, so it has to stand
#: clearly under the 1 % a p99 leaves out: at half a percent a 1.8 s walk (a
#: 1M-row table, the count included) comes once in six minutes and a 210 ms
#: one (100K rows) once in 42 s, and the tail of a minute's traffic reads at
#: most one of them where it read every full pass before. What it bounds on
#: the other side is how long frozen cyclic garbage is kept: two hundred
#: walks' worth of time.
THAW_BUDGET = 0.005


class GcPolicy:
    """Freezes the heap that survives a long full pass; thaws it on a
    wall-clock budget. ``clock`` and ``collector`` are seams for the tests'
    scripted clock and collector; the process has one instance, ``GCPOLICY``."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 collector=gc) -> None:
        self._clock = clock
        self._gc = collector
        # arm / disarm against a pass that ends on another thread meanwhile
        self._lock = threading.Lock()
        self._arms = 0
        self._t0: Optional[float] = None  # start of the full pass under way
        self._frozen = False              # the policy froze, and has not thawed
        self._due = 0.0                   # earliest instant of the next thaw
        # counters: cumulative over the process, like HOSTPROF's
        self.freezes = 0
        self.thaws = 0
        self.full_pauses = 0
        #: seconds stopped in generation-2 passes, the walks after a thaw
        #: and their counts included
        self.full_pause_s = 0.0
        #: the permanent generation as counted after the last walk of the
        #: whole heap (``gc.get_freeze_count()`` walks it, ~65 ns an object
        #: on a 4.7M-object heap: it is read where a walk five times as long
        #: has just been paid for, inside the same budget, and never when a
        #: surface is served)
        self.frozen_objects = 0

    # ------------------------------------------------------------ lifecycle
    def arm(self) -> None:
        """Count one broker in; the first installs the callback."""
        with self._lock:
            self._arms += 1
            if self._arms == 1:
                self._gc.callbacks.append(self._on_gc)

    def disarm(self) -> None:
        """Count one broker out; the last removes the callback and unfreezes
        what the policy froze."""
        with self._lock:
            if self._arms == 0:
                return
            self._arms -= 1
            if self._arms:
                return
            try:
                self._gc.callbacks.remove(self._on_gc)
            except ValueError:
                pass
            if self._frozen:
                self._gc.unfreeze()
            self._frozen = False
            self.frozen_objects = 0

    # ------------------------------------------------------------- callback
    def _on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` entry; runs on whichever thread collected, and
        never blocks (a collection can start inside ``arm`` itself)."""
        if info["generation"] != 2:
            return
        now = self._clock()
        if phase == "start":
            self._t0 = now
            return
        t0, self._t0 = self._t0, None
        if t0 is None:
            return
        took = now - t0
        self.full_pauses += 1
        self.full_pause_s += took
        if not self._lock.acquire(blocking=False):
            return
        try:
            if not self._arms:
                return
            if self._frozen and now >= self._due:
                # the next full pass walks the whole heap, and freezes it
                self._gc.unfreeze()
                self._frozen = False
                self.thaws += 1
            elif took > LONG_PASS_S:
                whole = not self._frozen
                self._gc.freeze()
                self._frozen = True
                self.freezes += 1
                if whole:
                    self.frozen_objects = self._gc.get_freeze_count()
                    end = self._clock()
                    self.full_pause_s += end - now  # the count stops all too
                    self._due = end + (end - t0) / THAW_BUDGET
        finally:
            self._lock.release()

    # -------------------------------------------------------------- surfaces
    def stats_block(self) -> dict:
        """The five gauges of ``/api/v1/stats``."""
        return {
            "host_gc_freezes": self.freezes,
            "host_gc_thaws": self.thaws,
            "host_gc_frozen_objects": self.frozen_objects,
            "host_gc_full_pauses": self.full_pauses,
            "host_gc_full_pause_ms_total": round(self.full_pause_s * 1e3, 3),
        }

    def snapshot(self) -> dict:
        """What ``/api/v1/host`` shows under ``gc``: the same five, and when
        the next thaw may come."""
        out = {k[len("host_gc_"):]: v for k, v in self.stats_block().items()}
        out["next_thaw_in_s"] = (
            round(max(0.0, self._due - self._clock()), 3)
            if self._frozen else None)
        out["long_pass_ms"] = LONG_PASS_S * 1e3
        out["thaw_budget"] = THAW_BUDGET
        return out


#: process-global, like the collector it steers
GCPOLICY = GcPolicy()
