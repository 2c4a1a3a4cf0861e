"""Broker-wide latency telemetry: log2 histograms + slow-op ring log.

The counter surface (`broker/metrics.py`) says how OFTEN things happen;
this layer says how LONG they take. Three pieces:

``Histogram``
    A fixed power-of-two-bucket latency histogram (ns resolution).
    Bucket ``i`` covers ``[2^i, 2^(i+1))`` ns (bucket 0 additionally
    absorbs 0), the top bucket absorbs overflow (2^39 ns ≈ 9 min — far
    past anything a broker op should take). Recording is two int ops
    (``bit_length`` + list increment); quantile estimation walks the 40
    counts and returns the containing bucket's upper bound, so an
    estimate always brackets the exact sorted-oracle value within one
    bucket boundary (a factor of 2). Histograms MERGE by bucket-wise
    addition — the property that makes per-node histograms summable
    cluster-wide (`/api/v1/latency/sum`) and across scrape intervals,
    which order statistics (raw percentiles) never are.

``Telemetry``
    The stage registry. The hot-path contract is near-zero overhead:

    - enabled: ONE ``perf_counter_ns()`` pair + one ``record()`` (a dict
      lookup, a bit_length, two int adds, one compare) per stage;
    - disabled: hot paths guard on ``tele.enabled`` so the cost is a
      single attribute load + branch — no timestamp is ever taken, no
      histogram is touched, no slow-log append happens (the acceptance
      bar for ``[observability] enable = false``).

    ``span()`` wraps the pair as a context manager — the API plugins and
    extensions should reach for when timing their own stages (the built-in
    hot paths inline the pair + ``recorder()`` instead, where the context
    manager's enter/exit dispatch would be measurable); when disabled it
    returns a shared no-op object.

slow-op ring
    A bounded ``deque`` capturing any nanosecond-stage op at or over
    ``slow_ms`` with op name, duration, timestamp and caller detail
    (topic, batch size, cache hit/miss) — the "what was that stall?"
    log that histograms by design cannot answer.

``Stage``
    The served path's BUSY clock: one ``perf_counter_ns`` pair around a
    section that holds no ``await`` that can suspend, so on the loop thread
    the sum is work and never suspension (a histogram stage around an
    ``await`` times the queue it parks in). ``count`` and ``busy_ns`` are
    cumulative and ride ``/api/v1/stats`` as flat monotone numbers
    (``stage_stats``), beside the cumulative bucket counts of the
    ``WINDOW_HISTS`` — a reader subtracts two snapshots and has the
    window's busy share, and the window's quantile from the bucket deltas
    (``bucket_quantile``). When, and only when, a JAX profiler session is
    on (``PROFILER.poll``, asked once per loop turn / routing dispatch) the
    same section is also a ``jax.profiler.TraceAnnotation("rmqtt/<stage>")``
    so it lands on the host plane of the trace that holds the device's
    operations, on the thread that ran it. The same session puts every
    callback of the event loop (``handle_spans``) and every pass of the
    cyclic collector (``broker/gcpolicy.py``) under a span of its own, so
    that the loop thread's time between stages has names too.

Stage names are pre-registered (``STAGES``, ``SERVED_STAGES``) so every
surface — JSON endpoints, Prometheus, $SYS, the dashboard — is
shape-stable whether or not traffic (or telemetry itself) has happened yet.
"""

from __future__ import annotations

import asyncio
import re
import selectors
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional

from rmqtt_tpu.broker.tracing import CURRENT_TRACE

NBUCKETS = 40  # [2^0, 2^40) ns ≈ up to ~18 min; top bucket absorbs overflow

# canonical broker stages (unit: ns unless listed in UNITS)
STAGES = (
    "connect.handshake",   # accept → CONNACK sent (server.py)
    "publish.e2e",         # publish ingress → last forward enqueued (shared.py)
    "publish.cache_hit",   # match-cache hit path: lookup+derive+collapse
    "publish.cache_miss",  # miss path: full batcher round trip
    "routing.queue_wait",  # batcher ingress-queue park time per item
    "routing.match",       # per-dispatch backend match latency (batch)
    "routing.batch_size",  # dispatch batch-size distribution (count, not ns)
    "deliver.queue_wait",  # Session.enqueue → deliver_queue.pop() per item
    "deliver.ack_rtt",     # QoS1/2 delivery → PUBACK/PUBCOMP round trip
    "fanout.hold",         # a publish's ack held for deliver-queue room → released
    "kernel.dispatch",     # router kernel/trie match call (native/xla)
)

UNITS: Dict[str, str] = {"routing.batch_size": "count"}

# the served path's busy stages, entry point down (see ``Stage``); the
# section each one brackets is named at its call site
SERVED_STAGES = (
    "ingress.collect",       # IngressHub._on_ready: one turn's chunks (ingress.py)
    "ingress.decode",        # codec.feed / codec.build per read chunk (session.py)
    "ingress.publish",       # _publish_inner up to registry.forwards
    "ingress.run",           # RoutingService.matches_run: a run's topics offered
    "routing.cache_hit",     # a match-cache hit: lookup, derive, collapse (routing.py)
    "routing.plan",          # RoutingService._plan(batch)
    "routing.match.side",    # AdaptiveHybrid._side_match (host trie mirror)
    "routing.match.device",  # device submit half + complete half (hybrid)
    "matcher.encode",        # ops/partitioned.py: the four stage_ns sections
    "matcher.dispatch",
    "matcher.fetch",
    "matcher.decode",
    "matcher.compile",       # a jit seam call whose shape key was never seen
    "matcher.compile_offpath",  # ops/hybrid.py's worker: a batch run to compile
    "routing.expand",        # XlaRouter._expand (relations expansion)
    "routing.resolve",       # RoutingService._resolve (futures, cache fill)
    "fanout.enqueue",        # SessionRegistry.forwards' _deliver_local loop
    "deliver.credit_wait",   # out_inflight.wait_credit(): a WAIT, not busy
    "deliver.send",          # _deliver: props, OutEntry, encode, feed
    "egress.flush",          # a transport write, or a turn's hand-off (egress.py)
    "ack.in",                # subscriber PUBACK/PUBREC/PUBCOMP → window release
    "ack.out",               # publisher's PUBACK/PUBREC encode + send
    "stats.snapshot",        # ServerContext.stats(): the 5 s tick, a scrape
    "hotkeys.fold",          # HotkeysService.drain(): buffered events folded
)
# these run on the loop thread for some batches and on an executor thread
# for others: the loop thread is the wall and an executor thread's wall
# holds GIL wait, so the two are kept apart (``*_exec_*`` keys)
BY_THREAD = frozenset(
    ("routing.match.side", "routing.match.device", "routing.expand"))
# executor-thread stages by nature (several threads at once: locked adds)
_OFF_LOOP = frozenset(s for s in SERVED_STAGES if s.startswith("matcher."))
# a suspension, timed across its await: counted apart from busy time and
# never a span (another task's sections would interleave with it)
_WAITS = frozenset(("deliver.credit_wait",))
# histograms whose cumulative bucket counts ride the flat stats body, so a
# reader can take the quantile of a WINDOW (the *_p99_ms gauges are since
# process start and cannot be subtracted)
WINDOW_HISTS = ("routing.queue_wait", "deliver.queue_wait", "publish.e2e",
                "fanout.hold")

# recorder buffer fold threshold: big enough to amortize the fold loop,
# small enough that a mid-burst fold stall is microseconds
_FOLD_AT = 512


def _slow_entry(name: str, dur_ns: int, detail: Any, trace: Any) -> dict:
    """One slow-op ring row (cold path — only built at/over ``slow_ms``).
    Falls back to the tracing contextvar so entries recorded in the
    publish-ingress task gain the active trace id (broker/tracing.py);
    cross-task recorders pass their trace explicitly."""
    if trace is None:
        trace = CURRENT_TRACE.get()
    entry = {
        "op": name,
        "ms": round(dur_ns / 1e6, 3),
        "ts": round(time.time(), 3),
        "detail": detail,
    }
    if trace is not None:
        entry["trace"] = trace.tid
    return entry


def prom_sanitize(name: str) -> str:
    """Exposition-format metric-name scrub: grammar allows [a-zA-Z0-9_:];
    metric keys here are dotted and plugin counters may carry arbitrary
    chars. Single definition shared by every exporter."""
    return re.sub(r"[^a-zA-Z0-9_]", "_", name)


class Histogram:
    """Fixed log2-bucket histogram; ns-resolution; mergeable by addition.

    ``count`` is DERIVED from the buckets on read: the recording paths run
    per publish, and one fewer read-modify-write per record is a measured
    win (bench cfg7); every read path is cold."""

    __slots__ = ("counts", "sum")

    def __init__(self) -> None:
        self.counts: List[int] = [0] * NBUCKETS
        self.sum = 0

    @property
    def count(self) -> int:
        return sum(self.counts)

    @staticmethod
    def bucket_index(value: int) -> int:
        if value <= 1:
            return 0
        return min(value.bit_length() - 1, NBUCKETS - 1)

    @staticmethod
    def bucket_upper(i: int) -> int:
        """Exclusive upper bound of bucket ``i`` (top bucket: +inf proxy)."""
        return 1 << (i + 1)

    def record(self, value: int) -> None:
        self.counts[self.bucket_index(value)] += 1
        self.sum += value

    def quantile(self, q: float) -> float:
        """Upper bound of the bucket holding the q-th sample (0 if empty).

        The exact q-th order statistic lies in the same bucket, so the
        estimate is exact-to-one-bucket: ``upper/2 <= exact < upper``
        (bucket 0: ``0 <= exact < 2``)."""
        total = self.count
        if total == 0:
            return 0.0
        rank = max(1, int(q * total + 0.999999))  # ceil, 1-based
        rank = min(rank, total)
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            if acc >= rank:
                return float(self.bucket_upper(i))
        return float(self.bucket_upper(NBUCKETS - 1))

    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0

    def merge(self, other: "Histogram") -> "Histogram":
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.sum += other.sum
        return self

    def to_json(self) -> dict:
        return {"count": self.count, "sum": self.sum, "buckets": list(self.counts)}

    @classmethod
    def from_json(cls, d: dict) -> "Histogram":
        h = cls()
        buckets = list(d.get("buckets", ()))[:NBUCKETS]
        h.counts[: len(buckets)] = [int(b) for b in buckets]
        h.sum = int(d.get("sum", 0))
        return h

    def snapshot(self, unit: str = "ns") -> dict:
        """JSON row for the admin surfaces: counts + quantile estimates in
        the recorded unit (callers convert ns → ms for display)."""
        return {
            "count": self.count,
            "sum": self.sum,
            "unit": unit,
            "mean": round(self.mean(), 1),
            "p50": self.quantile(0.50),
            "p90": self.quantile(0.90),
            "p99": self.quantile(0.99),
            "p999": self.quantile(0.999),
            "buckets": list(self.counts),
        }


def bucket_quantile(counts: Iterable[int], q: float) -> float:
    """``Histogram.quantile`` over bare bucket counts — e.g. the DELTAS of
    two snapshots of a stage's cumulative buckets, which are the histogram
    of the samples between them. → the upper edge (ns) of the bucket that
    holds the q-th sample, exact to a factor of 2; 0.0 when empty."""
    h = Histogram()
    h.counts = list(counts)
    return h.quantile(q)


class _Profiler:
    """Is a JAX profiler session on in this process? Learned from the
    profiler itself (``TraceMe.is_enabled``, ~40 ns), asked once per loop
    turn / routing dispatch — never per span; a stage boundary reads the
    cached ``on``. jax is never imported from here: a broker on the trie
    router does not load it, and no session can be on without it."""

    __slots__ = ("on", "annotation", "loop_tid", "handles", "_tls")

    def __init__(self) -> None:
        self.on = False
        self.annotation = None  # jax.profiler.TraceAnnotation, once jax is loaded
        self.loop_tid = threading.get_ident()  # re-bound by Telemetry.bind_loop
        self.handles = False  # the loop's callbacks are spans (handle_spans)
        self._tls = threading.local()  # per thread: open annotations, batch seq

    def poll(self) -> bool:
        cls = self.annotation
        if cls is None:
            mod = sys.modules.get("jax.profiler")
            if mod is None:
                return False
            cls = self.annotation = mod.TraceAnnotation
        on = self.on = cls.is_enabled()
        return on

    # -- reached only while a session is on (or to close what it opened)
    def open(self, span: str, n: int, batch: int, trace: Any) -> None:
        tls = self._tls
        if not batch:
            batch = getattr(tls, "batch", 0)
        if trace is None:
            trace = CURRENT_TRACE.get()
        kw = {}
        if batch:
            kw["batch"] = batch
        if n:
            kw["n"] = n
        if trace is not None and trace.sampled:
            kw["tid"] = trace.tid
        ann = self.annotation(span, **kw)
        ann.__enter__()
        try:
            tls.open.append(ann)
        except AttributeError:
            tls.open = [ann]

    def close(self) -> None:
        self._tls.open.pop().__exit__(None, None, None)

    def set_batch(self, seq: int) -> None:
        self._tls.batch = seq


#: process-global like the profiler session it mirrors
PROFILER = _Profiler()

_STOCK_RUN = asyncio.events.Handle._run
# span name by qualname: a Task's coroutine, any other callback
_TASK_SPANS: Dict[str, str] = {}
_CB_SPANS: Dict[str, str] = {}


def _traced_run(self) -> None:
    """``Handle._run`` while a session is on: the callback is a span, on the
    loop thread, that the stages it runs nest in — ``rmqtt/loop.task/<the
    coroutine's __qualname__>`` for a Task's step or wake-up,
    ``rmqtt/loop.cb/<the callback's __qualname__>`` for anything else."""
    cb = self._callback
    owner = getattr(cb, "__self__", None)
    if isinstance(owner, asyncio.Task):
        coro = owner.get_coro()
        q = getattr(coro, "__qualname__", None) or type(coro).__qualname__
        name = _TASK_SPANS.get(q)
        if name is None:
            name = _TASK_SPANS[q] = "rmqtt/loop.task/" + q
    else:
        q = getattr(cb, "__qualname__", None) or type(cb).__qualname__
        name = _CB_SPANS.get(q)
        if name is None:
            name = _CB_SPANS[q] = "rmqtt/loop.cb/" + q
    ann = PROFILER.annotation(name)
    ann.__enter__()
    try:
        _STOCK_RUN(self)
    finally:
        ann.__exit__(None, None, None)


def handle_spans(on: bool) -> None:
    """Put the span-making ``Handle._run`` in (``on``) or the stock one back:
    process-wide, like the profiler session it follows."""
    asyncio.events.Handle._run = _traced_run if on else _STOCK_RUN
    PROFILER.handles = on


class IdleSpanSelector(selectors.DefaultSelector):
    """The event loop's selector (``server.py`` hands it to
    ``asyncio.run(loop_factory=...)``): one profiler poll per loop turn,
    and while a session is on a span around every ``select``, on the same
    clock as the stages: ``rmqtt/loop.idle`` where it may block (the loop
    has nothing queued and waits for the network), ``rmqtt/loop.poll``
    where it may not (work is queued: the span is the system call and
    re-taking the GIL after it). Spans only, no counter. The poll also
    puts the loop's callbacks under spans of their own when it first sees
    a session on, and takes them out when it first sees it off
    (``handle_spans``): with no session the stock ``Handle._run`` runs."""

    def select(self, timeout=None):
        if not PROFILER.poll():
            if PROFILER.handles:
                handle_spans(False)
            return super().select(timeout)
        if not PROFILER.handles:
            handle_spans(True)
        poll = timeout is not None and timeout <= 0
        with PROFILER.annotation("rmqtt/loop.poll" if poll else "rmqtt/loop.idle"):
            return super().select(timeout)


class Stage:
    """One served-path stage: cumulative ``count`` / ``busy_ns`` from one
    ``perf_counter_ns`` pair per section, and a span on the profiler's
    clock while a session is on.

        tok = st.begin(n)   # clock read (+ annotation entered, if tracing)
        ...                 # the section: no await that can suspend
        st.end(tok)         # clock read, count += 1, busy_ns += the section

    Where a section has to cross an await that can suspend (``send_raw``
    under back-pressure, the device between submit and complete),
    ``lap(tok)`` stops the clock before it and a fresh ``begin`` starts it
    again after; only ``end`` counts. The token is the start clock,
    NEGATED when an annotation was opened for the section — so ``end``
    closes exactly what ``begin`` opened even if the session starts or
    stops in between, with nothing allocated and no second lookup.

    Stages nest only where the outer one is told: a section opened inside
    another is its own stage's, and the outer ``end(tok, inner)`` leaves out
    the ``inner`` ns its nested sections took (their spans nest too, and
    ``host_spans`` names an instant by the innermost).

    Callers guard on ``Telemetry.enabled`` exactly like the histogram
    stages: disabled, no boundary reads a clock or builds an object."""

    __slots__ = ("name", "span", "count", "busy_ns", "xcount", "xbusy_ns",
                 "by_thread", "_lock", "_keys")

    def __init__(self, name: str) -> None:
        self.name = name
        self.span = "rmqtt/" + name
        # its flat keys on the stats surface (``Telemetry.stage_stats``)
        key = "stage_" + prom_sanitize(name)
        kind = "wait" if name in _WAITS else "busy"
        self._keys = (f"{key}_count", f"{key}_{kind}_ms_total",
                      f"{key}_exec_count", f"{key}_exec_busy_ms_total")
        self.count = 0
        self.busy_ns = 0
        # executor-thread share of a BY_THREAD stage (count/busy_ns are
        # then the loop thread's alone)
        self.xcount = 0
        self.xbusy_ns = 0
        self.by_thread = name in BY_THREAD
        # `+=` is a read-modify-write: stages that several threads run at
        # once take a lock (per batch, never per publish)
        self._lock = (threading.Lock()
                      if self.by_thread or name in _OFF_LOOP else None)

    def begin(self, n: int = 0, batch: int = 0, trace: Any = None) -> int:
        if PROFILER.on:
            PROFILER.open(self.span, n, batch, trace)
            return -time.perf_counter_ns()
        return time.perf_counter_ns()

    def begin_at(self, t0: int) -> int:
        """``begin`` on a clock read the caller already took (``t0`` > 0):
        boundaries that coincide share one read."""
        if PROFILER.on:
            PROFILER.open(self.span, 0, 0, None)
            return -t0
        return t0

    def lap(self, tok: int) -> int:
        """Stop the clock without counting; → the section's ns."""
        return self._close(tok, 0)

    def end(self, tok: int, inner: int = 0) -> int:
        """Stop the clock and count one pass; → the section's ns. ``inner``:
        the ns of another stage's sections nested inside this one, which
        that stage owns and this one does not count."""
        return self._close(tok, 1, inner)

    def leave_out(self, inner: int) -> None:
        """``end``'s ``inner`` for a section this stage has open on the loop
        thread, from code nested in it that the section's ``end`` never
        sees (a hot-key seam that folds its full buffer)."""
        self.busy_ns -= inner

    def _close(self, tok: int, n: int, inner: int = 0) -> int:
        now = time.perf_counter_ns()
        if tok < 0:
            PROFILER.close()
            tok = -tok
        dt = now - tok - inner
        lock = self._lock
        if lock is None:
            self.count += n
            self.busy_ns += dt
            return dt
        with lock:
            if self.by_thread and threading.get_ident() != PROFILER.loop_tid:
                self.xcount += n
                self.xbusy_ns += dt
            else:
                self.count += n
                self.busy_ns += dt
        return dt

    def add_wait(self, dur_ns: int) -> None:
        """A suspension timed across its await (``_WAITS``): no span."""
        self.count += 1
        self.busy_ns += dur_ns


class _Span:
    """Enabled-mode timer: one perf_counter_ns pair around the block."""

    __slots__ = ("_tele", "_name", "_detail", "_t0")

    def __init__(self, tele: "Telemetry", name: str, detail: Any) -> None:
        self._tele = tele
        self._name = name
        self._detail = detail

    def __enter__(self) -> "_Span":
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self._tele.record(self._name, time.perf_counter_ns() - self._t0, self._detail)
        return False


class _NullSpan:
    """Disabled-mode span: never takes a timestamp."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


class Telemetry:
    """Per-node latency registry: stage histograms + the slow-op ring."""

    __slots__ = ("enabled", "slow_ms", "slow_ns", "slow_ops", "_h",
                 "_recorders", "_folds", "_reg_lock", "_stages", "batch_seq")

    def __init__(
        self,
        enabled: bool = True,
        slow_ms: float = 100.0,
        slow_log_max: int = 256,
        stages: Iterable[str] = STAGES,
    ) -> None:
        self.enabled = enabled
        self.slow_ms = slow_ms
        self.slow_ns = int(slow_ms * 1e6)
        self.slow_ops: deque = deque(maxlen=max(1, slow_log_max))
        self._h: Dict[str, Histogram] = {name: Histogram() for name in stages}
        self._stages: Dict[str, Stage] = {n: Stage(n) for n in SERVED_STAGES}
        # the routing service's dispatch counter at the batch now being
        # matched: router-side spans read it so one batch's spans share
        # ``batch=<seq>`` across threads (dispatches are serialized)
        self.batch_seq = 0
        self._recorders: Dict[str, Callable] = {}
        self._folds: Dict[str, Callable[[], None]] = {}
        # guards recorder CREATION (rare): first calls can come from
        # executor threads (kernel.dispatch), and an unlocked insert could
        # both race flush()'s iteration and build duplicate closures whose
        # buffered samples would never fold
        self._reg_lock = threading.Lock()

    def hist(self, name: str) -> Histogram:
        h = self._h.get(name)
        if h is None:
            h = self._h[name] = Histogram()
        return h

    def stage(self, name: str) -> Stage:
        """The busy-clock stage ``name`` (memoized; callers keep the object
        and guard their ``begin``/``end`` on ``self.enabled``)."""
        st = self._stages.get(name)
        if st is None:
            with self._reg_lock:
                st = self._stages.setdefault(name, Stage(name))
        return st

    @staticmethod
    def bind_loop() -> None:
        """Call ON the event-loop thread: BY_THREAD stages tell the loop's
        busy time from an executor thread's by it."""
        PROFILER.loop_tid = threading.get_ident()

    def batch_begin(self, seq: int = 0) -> int:
        """Router side of a routing batch, on whichever thread runs it: →
        the batch's seq while a profiler session is on (else 0, and
        nothing is touched). Spans opened on this thread until
        ``batch_end`` carry it. ``seq``: what an earlier ``batch_begin``
        returned, where the same batch is picked up again on another
        thread (the device path's complete half)."""
        if not seq:
            if not PROFILER.on:
                return 0
            seq = self.batch_seq
        PROFILER.set_batch(seq)
        return seq

    @staticmethod
    def batch_end(seq: int) -> None:
        if seq:
            PROFILER.set_batch(0)

    def record(self, name: str, dur_ns: int, detail: Any = None,
               trace: Any = None) -> None:
        """Record one op. Callers on hot paths guard with ``self.enabled``
        (so the disabled cost is one branch); the guard here keeps
        un-guarded callers correct, not fast. The histogram update is
        inlined (not ``hist().record()``) — this runs several times per
        publish and the two extra method dispatches measurably widen the
        telemetry-on overhead (bench cfg7)."""
        if not self.enabled:
            return
        try:
            h = self._h[name]
        except KeyError:
            h = self._h[name] = Histogram()
        i = dur_ns.bit_length() - 1
        if i < 0:
            i = 0
        elif i >= NBUCKETS:
            i = NBUCKETS - 1
        h.counts[i] += 1
        h.sum += dur_ns
        # non-ns stages (batch size) are not durations: never slow-log
        if dur_ns >= self.slow_ns and name not in UNITS:
            self.slow_ops.append(_slow_entry(name, dur_ns, detail, trace))

    def span(self, name: str, detail: Any = None):
        """Context-manager timer; a shared no-op when disabled."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, detail)

    def recorder(self, name: str) -> Callable[[int, Any], None]:
        """A per-stage fast-path recorder closure (memoized per stage).

        ``record()`` pays a name lookup + several attribute loads + the
        histogram update per call; at publish rates that is the single
        biggest telemetry cost (bench cfg7). The recorder instead buffers
        the raw duration with one C-level ``deque.append`` and folds the
        buffer into the histogram AMORTIZED — every ``_FOLD_AT`` ops or on
        the next read (``flush()``) — so the per-op cost is an append, one
        slow-threshold compare (slow ops keep their true timestamps and
        details, checked eagerly), and a length check. Totals stay exact:
        folding only defers the bucket increments, it never drops them.
        When disabled this returns a shared no-op so un-guarded calls
        stay correct."""
        rec = self._recorders.get(name)  # lock-free fast path (dict get)
        if rec is not None:
            return rec
        with self._reg_lock:
            return self._make_recorder(name)

    def _make_recorder(self, name: str) -> Callable[[int, Any], None]:
        rec = self._recorders.get(name)  # re-check under the lock
        if rec is not None:
            return rec
        if not self.enabled:
            rec = self._recorders[name] = (
                lambda dur_ns, detail=None, trace=None: None)
            return rec
        h = self.hist(name)
        counts = h.counts
        slow_ns = self.slow_ns
        slow_ops = self.slow_ops
        is_ns = name not in UNITS
        top = NBUCKETS - 1
        pending: deque = deque()
        append = pending.append
        popleft = pending.popleft
        fold_lock = threading.Lock()

        def fold() -> None:
            # executor threads record concurrently with the loop (kernel
            # dispatch runs off-loop): the hot append is GIL-atomic, and
            # the lock serializes the bucket/sum read-modify-writes so a
            # concurrent double-fold can't lose increments — totals stay
            # exact. Cold: taken every _FOLD_AT ops or per read.
            with fold_lock:
                s = 0
                while True:
                    try:
                        v = popleft()
                    except IndexError:
                        break
                    i = v.bit_length() - 1
                    counts[0 if i < 0 else (top if i > top else i)] += 1
                    s += v
                h.sum += s

        self._folds[name] = fold

        def rec(dur_ns: int, detail: Any = None, trace: Any = None) -> None:
            append(dur_ns)
            if dur_ns >= slow_ns and is_ns:
                slow_ops.append(_slow_entry(name, dur_ns, detail, trace))
            if len(pending) >= _FOLD_AT:
                fold()

        self._recorders[name] = rec
        return rec

    def flush(self) -> None:
        """Fold every recorder's pending samples into its histogram; all
        read paths call this, so readers always see exact totals. The
        list() snapshot keeps a concurrent first-recorder registration
        (executor thread) from invalidating the iteration."""
        for fold in list(self._folds.values()):
            fold()

    # ------------------------------------------------------------- surfaces
    def p_ms(self, name: str, q: float) -> float:
        """Quantile of a ns-stage in milliseconds (admin/stat gauges)."""
        self.flush()
        return round(self.hist(name).quantile(q) / 1e6, 3)

    def stage_stats(self) -> Dict[str, float]:
        """The flat, monotone face of the stage layer on the stats surface
        (``ServerContext.stats``): per stage ``stage_<name>_count`` and
        ``stage_<name>_busy_ms_total`` (``_wait_ms_total`` for a wait;
        ``_exec_*`` twins for the BY_THREAD stages). Beside them
        ``host_loop_cpu_ms_total`` — ``time.thread_time`` of the CALLING
        thread, which for the admin API and the history collector is the
        event loop's — and ``host_proc_cpu_ms_total``: how busy the one
        Python thread is, at no cost to the hot path. Shape-stable: every
        key is present, all zeros, when disabled. Every key sums across
        nodes under ``/stats/sum``'s suffix rules."""
        on = self.enabled
        out: Dict[str, float] = {
            "host_loop_cpu_ms_total":
                round(time.thread_time_ns() / 1e6, 3) if on else 0.0,
            "host_proc_cpu_ms_total":
                round(time.process_time_ns() / 1e6, 3) if on else 0.0,
        }
        for name in SERVED_STAGES:
            st = self._stages[name]
            count, ms, xcount, xms = st._keys
            out[count] = st.count
            out[ms] = round(st.busy_ns / 1e6, 3)
            if st.by_thread:
                out[xcount] = st.xcount
                out[xms] = round(st.xbusy_ns / 1e6, 3)
        return out

    def bucket_stats(self) -> Dict[str, int]:
        """The 40 cumulative bucket counts ``hist_<name>_b<i>`` of each
        WINDOW_HISTS histogram, flat and monotone: a reader of
        ``/api/v1/stats`` subtracts two snapshots and has the histogram of
        the samples between them. Served by the admin API's stats bodies
        only (the history timeline and the scrape have the histograms'
        own surfaces). All zeros, same keys, when disabled."""
        self.flush()
        return {f"hist_{prom_sanitize(name)}_b{i:02d}": c
                for name in WINDOW_HISTS
                for i, c in enumerate(self.hist(name).counts)}

    def snapshot(self) -> dict:
        """The `/api/v1/latency` body: shape-stable in disabled mode (all
        pre-registered stages present with zero counts, empty slow log)."""
        self.flush()
        return {
            "enabled": self.enabled,
            "slow_threshold_ms": self.slow_ms,
            "histograms": {
                name: h.snapshot(UNITS.get(name, "ns"))
                for name, h in sorted(list(self._h.items()))
            },
            "slow_ops": list(self.slow_ops),
        }

    @staticmethod
    def merge_snapshots(base: dict, others: Iterable[dict]) -> dict:
        """Cluster-wide merge (`/api/v1/latency/sum`): bucket-wise addition
        of each node's histograms — the whole point of fixed buckets."""
        others = list(others)
        merged: Dict[str, Histogram] = {}
        units: Dict[str, str] = {}
        for snap in [base, *others]:
            for name, row in (snap.get("histograms") or {}).items():
                units.setdefault(name, row.get("unit", "ns"))
                h = merged.get(name)
                if h is None:
                    merged[name] = Histogram.from_json(row)
                else:
                    h.merge(Histogram.from_json(row))
        return {
            "nodes": 1 + len(others),
            "enabled": bool(base.get("enabled", False)),
            "histograms": {
                name: h.snapshot(units.get(name, "ns"))
                for name, h in sorted(merged.items())
            },
        }

    def prometheus_lines(self, labels: str) -> List[str]:
        """Exposition-format histogram families. ``labels`` is the shared
        label body (e.g. ``node="1"``). ns stages export in SECONDS (the
        Prometheus base-unit convention) as ``rmqtt_latency_<stage>_seconds``;
        count stages export raw as ``rmqtt_<stage>``."""
        self.flush()
        out: List[str] = []
        for name, h in sorted(self._h.items()):
            unit = UNITS.get(name, "ns")
            safe = prom_sanitize(name)
            if unit == "ns":
                metric = f"rmqtt_latency_{safe}_seconds"
                scale = 1e-9
            else:
                metric = f"rmqtt_{safe}"
                scale = 1.0
            out.append(f"# TYPE {metric} histogram")
            acc = 0
            for i, c in enumerate(h.counts):
                acc += c
                # exposition `le` is INCLUSIVE; our buckets have exclusive
                # uppers, so bucket i's inclusive max is upper-1 (a
                # boundary-exact sample — e.g. a 64-item batch — belongs
                # to the next bucket and must not be claimed by this le)
                le = format((h.bucket_upper(i) - 1) * scale, "g")
                out.append(f'{metric}_bucket{{{labels},le="{le}"}} {acc}')
            out.append(f'{metric}_bucket{{{labels},le="+Inf"}} {h.count}')
            out.append(f"{metric}_sum{{{labels}}} {format(h.sum * scale, 'g')}")
            out.append(f"{metric}_count{{{labels}}} {h.count}")
        return out


# module-level disabled singleton: subsystems constructed without a broker
# context (bare RoutingService in unit tests, standalone routers) share one
# no-op registry instead of None-checking on the hot path
NULL_TELEMETRY = Telemetry(enabled=False, slow_log_max=1)
