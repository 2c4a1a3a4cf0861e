"""Adaptive hybrid matcher: host trie vs device kernel, chosen by measurement.

The deployed router keeps two match engines for the same filter set: a
host-side trie (µs-scale per topic, the reference's own data structure,
`/root/reference/rmqtt/src/trie.rs:288-408`) and the batched device
automaton (`ops/partitioned.py`). Which one is faster depends on scale and
dispatch cost: at small tables the trie wins at any batch size; at 1M+
wildcard subs the device path wins on bursts (NOTES.md measured both
regimes, through a slow link; PERF.md has the attached chip's constants).
A fixed size threshold can't know which regime it is in — so the hybrid
measures.

Policy:
- batches ≤ ``small_max`` always take the trie (per-message latency
  contract of `rmqtt/src/shared.rs:735-820`; a device dispatch per 1-topic
  publish costs a full round trip);
- larger batches go to whichever path's throughput EMA is higher; every
  ``probe_every``-th large batch runs on the slower path to refresh its
  EMA, so regime changes (table growth, a busier host or device) flip
  the routing within a bounded number of batches;
- with no device matcher (or no trie side) the surviving path serves
  everything;
- no compile on the routing path: while the hybrid may choose (adaptivity
  on, which needs the native mirror) a large batch whose device program
  has no compiled shape yet — a never-seen padded size, candidate-count
  bucket or regrown slot budget, by the device profiler's shape-key
  registry — is answered by the trie, and the same batch is run through
  the matcher on a thread of its own, once, against the live table, which
  compiles what it needs; later batches of that shape go to the device.
  No list made at start can be the right one: the table's chunk count
  changes during a load and slot budgets are discovered by traffic.

``match_submit``/``match_complete`` preserve the device path's pipelining
(dispatch N+1 overlaps compute N) — the bench and the RoutingService both
drive it; trie-served batches complete synchronously inside submit.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from typing import List, Optional, Sequence

import numpy as np

from rmqtt_tpu.broker.devprof import DEVPROF as _DEVPROF, NeedsCompile
from rmqtt_tpu.utils.failpoints import FAILPOINTS

_LOG = logging.getLogger("rmqtt_tpu.ops")


def _padded(n: int) -> int:
    """The device matcher's batch padding (the next power of two)."""
    return 1 << (n - 1).bit_length() if n > 1 else n


EMA_ALPHA = 0.3  # weight of the newest rate sample

#: chaos seams (utils/failpoints.py): fired ONLY on the device branch —
#: trie-served batches are host-side work and genuinely unaffected by a
#: dead/hung accelerator, so injected device faults must not touch them
#: (in particular, `hang` must never run on the event-loop inline path,
#: which is trie-only). One attribute test per batch when off.
_FP_DISPATCH = FAILPOINTS.register("device.dispatch")
_FP_COMPLETE = FAILPOINTS.register("device.complete")


class AdaptiveHybrid:
    def __init__(self, side, matcher, small_max: int = 64,
                 probe_every: int = 64) -> None:
        self.side = side  # NativeTrie-like: .match(topic) -> fid ndarray
        self.matcher = matcher  # device matcher: .match(list) / submit/complete
        self.small_max = small_max
        self.probe_every = probe_every
        self._rate = {"side": None, "device": None}  # EMA topics/s
        self.large_batches = 0
        self._dev_samples = 0  # first device sample includes XLA compile
        self._last_dev_complete = None  # for pipelined-rate attribution
        # which backend served the most recent synchronous match — read by
        # the routing service right after a dispatch (serialized there) so
        # only DEVICE successes reset the failover breaker's consecutive-
        # failure count; trie-served batches are not device evidence
        self.last_backend: Optional[str] = None
        # served-share counters per backend: [batches, topics] — what the
        # device actually served vs the host mirror (device_info() surface)
        self.served = {"side": [0, 0], "device": [0, 0]}
        # per backend: times _bump replaced the rate EMA outright (a single
        # sample 2.5x off the EMA), and large batches sent to the slower
        # path to refresh its EMA — what decides who serves, in numbers
        self.regime_jumps = {"side": 0, "device": 0}
        self.probes = {"side": 0, "device": 0}
        # large batches [batches, topics] the trie answered because their
        # device program was not compiled yet (of ``large_batches`` large ones)
        self.compiling_side = [0, 0]
        # programs being compiled off the routing path: shape key → the
        # batch that met it, run by one worker thread at a time
        self._to_compile: dict = {}
        self._compiler: Optional[threading.Thread] = None
        # busy-clock stages (broker/telemetry.py Stage), wired by the
        # router; the match entry points take ``staged`` from their caller
        self._st_side = self._st_dev = None
        # EMA state is touched from both the submit and the completion
        # executor threads (RoutingService pipelining); the GIL keeps it
        # memory-safe but probe cadence / rate attribution would skew —
        # RLock because _bump_device nests into _bump
        self._lock = threading.RLock()

    def use_stages(self, side, device) -> None:
        self._st_side, self._st_dev = side, device

    # ------------------------------------------------------------- internals
    def _bump(self, key: str, rate: float) -> None:
        with self._lock:
            cur = self._rate[key]
            if cur is None or rate > 2.5 * cur or rate < cur / 2.5:
                # regime jump (compile finished, chip co-located, table grew):
                # converge immediately instead of over many EMA steps
                self._rate[key] = rate
                if cur is not None:
                    self.regime_jumps[key] += 1
            else:
                self._rate[key] = (1 - EMA_ALPHA) * cur + EMA_ALPHA * rate

    def _bump_device(self, n: int, dt: float) -> None:
        """Device samples skip the first call — it includes JIT compile
        (seconds to minutes at scale) and would pin routing to the trie
        for hundreds of probe cycles."""
        with self._lock:
            self._dev_samples += 1
            if self._dev_samples > 1 and dt > 0:
                self._bump("device", n / dt)

    def _note(self, backend: str, n: int) -> None:
        self.last_backend = backend
        with self._lock:
            c = self.served[backend]
            c[0] += 1
            c[1] += n

    def _side_match(self, topics: Sequence[str], staged: bool = False,
                    compiling: bool = False) -> List[np.ndarray]:
        self._note("side", len(topics))
        if compiling:
            with self._lock:
                self.compiling_side[0] += 1
                self.compiling_side[1] += len(topics)
        # one clock pair: the ``routing.match.side`` stage when staged (the
        # rate sample below reuses its reads), else the rate sample's own
        tok = self._st_side.begin(len(topics)) if staged else 0
        t0 = 0.0 if tok else time.perf_counter()
        if len(topics) > 1 and hasattr(self.side, "match_batch"):
            # one native call for the whole batch: the per-topic ctypes
            # round trip (~7µs) would otherwise dominate and misprice the
            # trie side at large batch sizes
            rows = self.side.match_batch(list(topics))
        else:
            rows = [self.side.match(t) for t in topics]
        dt = (self._st_side.end(tok) / 1e9 if tok
              else time.perf_counter() - t0)
        if len(topics) > self.small_max and dt > 0:
            self._bump("side", len(topics) / dt)
        return rows

    def _device_match(self, topics: Sequence[str],
                      staged: bool = False) -> List[np.ndarray]:
        if self._is_compiling(len(topics)):
            return self._side_match(topics, staged, compiling=True)
        if _FP_DISPATCH.action is not None:
            _FP_DISPATCH.fire_sync()
        tok = self._st_dev.begin(len(topics)) if staged else 0
        t0 = time.perf_counter()
        try:
            with self._no_compile():
                rows = self.matcher.match(topics)
        except NeedsCompile as e:
            rows = None
            self._compile_off_path(e.sig, topics)
        finally:
            if tok:
                self._st_dev.end(tok)
        if rows is None:
            return self._side_match(topics, staged, compiling=True)
        self._note("device", len(topics))
        if _FP_COMPLETE.action is not None:
            _FP_COMPLETE.fire_sync()
        with self._lock:
            self._bump_device(len(topics), time.perf_counter() - t0)
            self._last_dev_complete = time.perf_counter()
        return rows

    # ------------------------------------------- no compile on the path
    def _no_compile(self):
        """The rule a device call of the routing path runs under: hand a
        never-compiled shape back (``NeedsCompile``) where the trie can
        answer in its place and the hybrid is free to choose — with
        adaptivity off large batches are PINNED to the device, compile and
        all (``RMQTT_HYBRID_MAX=0``, ``RMQTT_HYBRID_ADAPT=0``); and the
        shape-key registry is the device profiler's, so without it nothing
        can be told."""
        if (self.side is not None and self.probe_every > 0
                and _DEVPROF.enabled):
            return _DEVPROF.compiles("forbid")
        return contextlib.nullcontext()

    def _is_compiling(self, n: int) -> bool:
        """Does a batch of this padded size wait to be compiled already? A
        second one is then not encoded just to learn the same."""
        if not self._to_compile:
            return False
        with self._lock:
            return any(_padded(len(t)) == _padded(n)
                       for t in self._to_compile.values())

    def _compile_off_path(self, sig, topics: Sequence[str]) -> None:
        """Have the program ``sig`` compiled, once: the batch that met it
        joins the worker's list (one thread, started here when none runs,
        gone when the list is empty)."""
        with self._lock:
            if sig in self._to_compile:
                return
            self._to_compile[sig] = list(topics)
            if self._compiler is None:
                self._compiler = threading.Thread(
                    target=self._compile_loop, name="rmqtt-compile",
                    daemon=True)
                self._compiler.start()

    def _compile_loop(self) -> None:
        while True:
            with self._lock:
                if not self._to_compile:
                    self._compiler = None
                    return
                sig, topics = next(iter(self._to_compile.items()))
            try:
                # the whole round trip: what the submit half needs and what
                # the complete half regrows both compile here. The result
                # is dropped, and no rate sample is taken: compile seconds
                # say nothing of what a batch costs
                with _DEVPROF.compiles("off_path"):
                    self.matcher.match(topics)
            except Exception:
                _LOG.exception("compiling %s off the routing path failed; "
                               "the next batch of its shape tries again",
                               sig[0])
            with self._lock:
                del self._to_compile[sig]

    def _pick(self) -> str:
        """Route a large batch; probes keep the loser's EMA fresh."""
        if self.probe_every <= 0:
            return "device"  # adaptivity off: fixed size threshold only
        with self._lock:
            self.large_batches += 1
            s, d = self._rate["side"], self._rate["device"]
            if d is None:
                return "device"
            if s is None:
                return "side"
            if self.large_batches % self.probe_every == 0:
                slower = "side" if s < d else "device"  # probe the slower path
                self.probes[slower] += 1
                return slower
            return "side" if s >= d else "device"

    # ------------------------------------------------------------------ api
    def set_small_max(self, n: int) -> int:
        """Knob seam (broker/knobs.py via XlaRouter.set_hybrid_max): move
        the trie-vs-device threshold live; → the old value. The EMA state
        deliberately survives — the rates measured per path stay valid,
        only the boundary between them moves."""
        old = self.small_max
        self.small_max = max(0, int(n))
        return old

    @property
    def choice(self) -> Optional[str]:
        """Current steady-state routing for large batches (None = unprimed)."""
        s, d = self._rate["side"], self._rate["device"]
        if s is None or d is None:
            return None
        return "side" if s >= d else "device"

    def match(self, topics: Sequence[str],
              staged: bool = False) -> List[np.ndarray]:
        """``staged``: the caller has telemetry on and wired — time the
        backend that serves as its ``routing.match.*`` stage."""
        if self.side is None:
            return self._device_match(topics, staged)
        if self.matcher is None or len(topics) <= self.small_max:
            return self._side_match(topics, staged)
        if self._pick() == "side":
            return self._side_match(topics, staged)
        return self._device_match(topics, staged)

    def match_submit(self, topics: Sequence[str], staged: bool = False):
        """Pipelined form: device submissions stay asynchronous; trie-served
        batches resolve inside submit (they are µs-scale). The device
        stage's busy time is the submit half plus the complete half — the
        wait between them is the device's and the completion queue's."""
        if self.side is None or (
            self.matcher is not None and len(topics) > self.small_max
            and self._pick() == "device"
        ):
            if hasattr(self.matcher, "match_submit"):
                if self._is_compiling(len(topics)):
                    return ("sync", self._side_match(topics, staged, True))
                if _FP_DISPATCH.action is not None:
                    _FP_DISPATCH.fire_sync()
                tok = self._st_dev.begin(len(topics)) if staged else 0
                try:
                    with self._no_compile():
                        payload = self.matcher.match_submit(topics)
                except NeedsCompile as e:
                    payload = None
                    self._compile_off_path(e.sig, topics)
                finally:
                    if tok:
                        self._st_dev.lap(tok)
                if payload is None:
                    return ("sync", self._side_match(topics, staged, True))
                self._note("device", len(topics))
                return ("device", payload, topics, time.perf_counter())
            return ("sync", self._device_match(topics, staged))
        return ("sync", self._side_match(topics, staged))

    def match_complete(self, handle, staged: bool = False) -> List[np.ndarray]:
        if handle[0] == "sync":
            return handle[1]
        _kind, payload, topics, t_submit = handle
        n = len(topics)
        if _FP_COMPLETE.action is not None:
            _FP_COMPLETE.fire_sync()
        tok = self._st_dev.begin(n) if staged else 0
        try:
            with self._no_compile():
                rows = self.matcher.match_complete(payload)
        except NeedsCompile as e:
            # the batch overflowed its slot budget and the regrown program
            # is not compiled: the device's answer is dropped for the trie's
            rows = None
            self._compile_off_path(e.sig, topics)
        finally:
            if tok:
                self._st_dev.end(tok)
        if rows is None:
            with self._lock:  # submit counted it for the device
                c = self.served["device"]
                c[0] -= 1
                c[1] -= n
            return self._side_match(topics, staged, compiling=True)
        now = time.perf_counter()
        with self._lock:
            last = self._last_dev_complete
            if last is not None and last > t_submit:
                # a device completion landed after this submit: the pipeline
                # is overlapped, so the inter-completion gap IS the per-batch
                # cost
                self._bump_device(n, now - last)
            else:
                # lone dispatch (e.g. a probe among trie-served batches): the
                # serial round trip is the honest rate
                self._bump_device(n, now - t_submit)
            self._last_dev_complete = now
        return rows
