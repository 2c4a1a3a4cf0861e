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

The policy owns the program's one entry of ``gc.callbacks`` (which also
times every pass for the host profiler, and makes it a span in a profiler
session) and three rules, all applied where a pass ends, on whichever thread
collected:

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

*The young threshold, on the young passes' cost.* CPython starts a
generation-0 pass when the net count of new tracked objects passes
``threshold0`` (700). A broker under load makes a hundred such passes a
second, and a publish lives one publish→PUBACK round trip (~100 ms): every
object of every publish in flight is walked by ten young passes and promoted
while it is still alive, walked again in generation 1, and pushed on into
generation 2, though none of it is cyclic garbage: it dies by reference count
a few passes later. So the policy adapts ``threshold0`` to what young passes
(generations 0 and 1) are seen to cost. At the end of each generation-1 pass
that closes a whole generation-1 period at the present value (at least
``threshold1`` generation-0 passes), the rule reads the young passes'
seconds since the period began over the wall seconds. Above
``YOUNG_BUDGET`` ``threshold0`` doubles, however long the passes are: the
cost falls only once passes come further apart than a publish lives, and on
the way there (twice or four times 700 under 640 publishes in flight) the
passes are both long and frequent, so a rule that stopped at a long pass
would stay where it started. Where the young passes are within that budget
but those longer than ``LONG_PASS_S`` took more than ``THAW_BUDGET`` of the
wall clock, the share a p99 can carry, it halves, never below the value
found at the first ``arm``. (While a table loads everything survives, so
the passes stay dear at any threshold and it rises through the load; once
the load is over a pass walks what is alive in the young generations, not
``threshold0`` objects.) ``threshold1`` and ``threshold2`` stay as they
are: fewer, later generation-0 passes promote fewer live objects, and that
alone makes the older generations' passes rarer. A process whose young
passes are cheap (an idle broker, a tool that allocates little) never sees
the threshold move.

No constant is an option: each is about what the code can measure (a pass's
length, its share of the wall clock), not about a deployment. The policy is
process-global like the collector, and armed per broker with a reference
count like ``HOSTPROF``, so the last ``disarm`` leaves the process as it was
found: callback removed, heap unfrozen, thresholds as they were.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Callable, Optional

from rmqtt_tpu.broker.telemetry import PROFILER

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

#: Share of wall clock the young passes (generations 0 and 1) may take before
#: the generation-0 threshold is doubled. At the interpreter's 700 a broker
#: saturated by the benchmark's fleets spent 9–13 % of its wall clock there
#: (the host of a TPU v5e), walking and promoting the objects of publishes
#: still in flight; at 1 % what is left is under the noise of any rate, and
#: a process whose young passes are cheap never moves. What bounds the other
#: side is the thaw's budget: young passes longer than ``LONG_PASS_S`` may
#: take ``THAW_BUDGET`` of the wall clock, as the walks of the whole heap do,
#: and a threshold whose long passes take more is halved.
YOUNG_BUDGET = 0.01


class GcPolicy:
    """Freezes the heap that survives a long full pass; thaws it on a
    wall-clock budget; sizes the generation-0 threshold by the young passes'
    cost. ``clock`` and ``collector`` are seams for the tests' scripted clock
    and collector; the process has one instance, ``GCPOLICY``.

    Its ``gc.callbacks`` entry is the process's only one of this program: it
    also times every pass for the host profiler (``watch``), and while a
    profiler session is on it makes each pass a span, ``rmqtt/gc.young``
    (generations 0 and 1) or ``rmqtt/gc.full`` (generation 2, the freeze and
    its count included), on the thread that collected."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 collector=gc) -> None:
        self._clock = clock
        self._gc = collector
        # arm / disarm / watch against a pass that ends on another thread
        self._lock = threading.Lock()
        self._arms = 0
        self._installed = False
        #: the host profiler's pause sink while it is started:
        #: ``(generation, seconds, info)`` at the end of every pass; it never
        #: blocks
        self.pauses: Optional[Callable[[int, float, dict], None]] = None
        self._t0: Optional[float] = None  # start of the pass under way
        self._span = None                 # its annotation, while one is open
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
        # the young rule: the thresholds found at the first arm, threshold0
        # as the rule last left it, and the period under way at that value
        # (young seconds, those of passes over LONG_PASS_S, its
        # generation-0 passes, when it began)
        self._found: Optional[tuple] = None
        self._young = 0
        self._young_s = 0.0
        self._long_s = 0.0
        self._young_n = 0
        self._since: Optional[float] = None
        #: raises and lowers of threshold0, cumulative over the process
        self.threshold_changes = 0
        #: the young passes' share of the wall clock over the last whole
        #: period the rule read
        self.young_share = 0.0

    # ------------------------------------------------------------ lifecycle
    def arm(self) -> None:
        """Count one broker in; the first installs the callback and notes
        the collector's thresholds."""
        with self._lock:
            self._arms += 1
            self._install()
            if self._arms == 1:
                self._found = tuple(self._gc.get_threshold())
                self._young = self._found[0]
                self._period(None)

    def disarm(self) -> None:
        """Count one broker out; the last removes the callback (unless the
        host profiler still watches), unfreezes what the policy froze and
        puts back the thresholds it found if the rule moved them."""
        with self._lock:
            if self._arms == 0:
                return
            self._arms -= 1
            if self._arms:
                return
            self._install()
            if self._frozen:
                self._gc.unfreeze()
            self._frozen = False
            self.frozen_objects = 0
            if self._found is not None and self._young != self._found[0]:
                self._gc.set_threshold(*self._found)
            self._found = None

    def watch(self, pauses: Callable[[int, float, dict], None]) -> None:
        """Hand every pass's length to ``pauses`` from now on."""
        with self._lock:
            self.pauses = pauses
            self._install()

    def unwatch(self) -> None:
        with self._lock:
            self.pauses = None
            self._install()

    def _install(self) -> None:
        """The callback is in ``callbacks`` while a broker is armed or the
        host profiler watches; the caller holds the lock."""
        want = bool(self._arms) or self.pauses is not None
        if want and not self._installed:
            self._gc.callbacks.append(self._on_gc)
        elif self._installed and not want:
            try:
                self._gc.callbacks.remove(self._on_gc)
            except ValueError:
                pass
        self._installed = want

    # ------------------------------------------------------------- callback
    def _on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` entry; runs on whichever thread collected, and
        never blocks or raises (a collection can start inside ``arm``
        itself, or inside a span's own open). One clock read at ``start``
        and one at ``stop``, and none for a young pass while no broker is
        armed and no profiler watches."""
        gen = info["generation"]
        if phase == "start":
            if PROFILER.on:
                self._open_span(gen)
            if gen == 2 or self._arms or self.pauses is not None:
                self._t0 = self._clock()
            return
        t0, self._t0 = self._t0, None
        if t0 is not None:
            now = self._clock()
            pauses = self.pauses
            if pauses is not None:
                pauses(gen, now - t0, info)
            if gen == 2:
                self._full_pass(t0, now)
            else:
                self._young_pass(gen, t0, now)
        if self._span is not None:
            self._close_span()

    def _full_pass(self, t0: float, now: float) -> None:
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

    def _young_pass(self, gen: int, t0: float, now: float) -> None:
        """The young rule (module docstring). ``gc.set_threshold`` runs no
        Python code; the lock is only tried, as in ``_full_pass``."""
        if not self._lock.acquire(blocking=False):
            return
        try:
            if not self._arms or self._found is None:
                return
            took = now - t0
            if self._since is None:
                self._since = t0
            self._young_s += took
            if took > LONG_PASS_S:
                self._long_s += took
            if gen == 0:
                self._young_n += 1
                return
            # a generation-1 pass: the end of a period, if it was whole
            if self._young_n < self._found[1] or now <= self._since:
                return
            wall = now - self._since
            self.young_share = self._young_s / wall
            floor = self._found[0]
            if self.young_share > YOUNG_BUDGET and floor > 0:
                self._set_young(2 * self._young, now)
            elif self._long_s / wall > THAW_BUDGET and self._young > floor:
                self._set_young(max(floor, self._young // 2), now)
            else:
                self._period(now)
        finally:
            self._lock.release()

    def _set_young(self, threshold0: int, now: float) -> None:
        """``threshold0`` to ``threshold0``, the other two as they are; a new
        period begins. The caller holds the lock."""
        self._period(now)
        _t0, t1, t2 = self._gc.get_threshold()
        try:
            self._gc.set_threshold(threshold0, t1, t2)
        except OverflowError:  # past a C int: it stays where it is
            return
        self._young = threshold0
        self.threshold_changes += 1

    def _period(self, now: Optional[float]) -> None:
        self._young_s, self._long_s, self._young_n = 0.0, 0.0, 0
        self._since = now

    def _open_span(self, gen: int) -> None:
        # kept off PROFILER's per-thread stack of open sections: a pass can
        # start inside a stage's own open, between its annotation's
        # construction and its push
        try:
            span = PROFILER.annotation(
                "rmqtt/gc.full" if gen == 2 else "rmqtt/gc.young")
            span.__enter__()
            self._span = span
        except Exception:
            self._span = None

    def _close_span(self) -> None:
        span, self._span = self._span, None
        try:
            span.__exit__(None, None, None)
        except Exception:
            pass

    # -------------------------------------------------------------- surfaces
    def stats_block(self) -> dict:
        """The seven gauges of ``/api/v1/stats``."""
        return {
            "host_gc_freezes": self.freezes,
            "host_gc_thaws": self.thaws,
            "host_gc_frozen_objects": self.frozen_objects,
            "host_gc_full_pauses": self.full_pauses,
            "host_gc_full_pause_ms_total": round(self.full_pause_s * 1e3, 3),
            "host_gc_young_threshold": self._gc.get_threshold()[0],
            "host_gc_threshold_changes": self.threshold_changes,
        }

    def snapshot(self) -> dict:
        """What ``/api/v1/host`` shows under ``gc``: the same seven, when
        the next thaw may come, and the young share the rule last read."""
        out = {k[len("host_gc_"):]: v for k, v in self.stats_block().items()}
        out["next_thaw_in_s"] = (
            round(max(0.0, self._due - self._clock()), 3)
            if self._frozen else None)
        out["young_share"] = round(self.young_share, 5)
        out["long_pass_ms"] = LONG_PASS_S * 1e3
        out["thaw_budget"] = THAW_BUDGET
        out["young_budget"] = YOUNG_BUDGET
        return out


#: process-global, like the collector it steers
GCPOLICY = GcPolicy()
