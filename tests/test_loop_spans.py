"""Names for the event loop's time between stages.

- the collector's one ``gc.callbacks`` entry (``broker/gcpolicy.py``): both
  sets of counters from one clock pair a pass, no clock read for a young pass
  nobody times (no broker armed, no profiler watching), and while a profiler session is on one balanced
  ``rmqtt/gc.young`` / ``rmqtt/gc.full`` span a pass — none without one, and
  still balanced where a collection starts inside a stage's own open;
- the loop's callbacks (``broker/telemetry.py`` ``handle_spans``): the span
  wrapper is in ``Handle._run`` only while the selector's poll sees a session
  on, and names a Task's step by its coroutine;
- the ``stats.snapshot`` and ``hotkeys.fold`` stages, a fold counted once
  where it runs inside another stage's section;
- the loop-lag sampler's cumulative buckets on ``/api/v1/stats``.
"""

import asyncio
import gc
import time

import pytest

from rmqtt_tpu.broker import hotkeys as HK
from rmqtt_tpu.broker import telemetry as T
from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.gcpolicy import GCPOLICY, GcPolicy
from rmqtt_tpu.broker.hostprof import HOSTPROF
from rmqtt_tpu.broker.http_api import stats_body


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what is built,
    entered and left; ``on_build`` runs inside the constructor."""

    built: list = []
    open_now = 0
    enabled = False
    on_build = None

    def __init__(self, name, **kw):
        cls = type(self)
        cls.built.append(name)
        if cls.on_build is not None:
            cls.on_build(name)

    def __enter__(self):
        type(self).open_now += 1
        return self

    def __exit__(self, *exc):
        type(self).open_now -= 1
        return False

    @classmethod
    def is_enabled(cls):
        return cls.enabled


@pytest.fixture
def annotation(monkeypatch):
    _Annotation.built, _Annotation.open_now = [], 0
    _Annotation.enabled, _Annotation.on_build = False, None
    monkeypatch.setattr(T.PROFILER, "annotation", _Annotation)
    monkeypatch.setattr(T.PROFILER, "on", False)
    yield _Annotation
    T.PROFILER.on = False
    T.handle_spans(False)


class Clock:
    """A scripted clock that counts its readings."""

    def __init__(self) -> None:
        self.t, self.reads = 1000.0, 0

    def __call__(self) -> float:
        self.reads += 1
        return self.t


class Collector:
    def __init__(self) -> None:
        self.callbacks, self.calls = [], []

    def freeze(self):
        self.calls.append("freeze")

    def unfreeze(self):
        self.calls.append("unfreeze")

    def get_freeze_count(self):
        self.calls.append("count")
        return 7

    def get_threshold(self):
        return (700, 10, 10)

    def set_threshold(self, *threshold):
        self.calls.append("threshold")


def _pass(pol, clock, gen, took):
    pol._on_gc("start", {"generation": gen})
    clock.t += took
    pol._on_gc("stop", {"generation": gen, "collected": 1, "uncollectable": 0})


# ------------------------------------------------------ one gc.callbacks entry
def test_one_callback_times_every_pass_for_both_sets_of_counters(annotation):
    clock, coll = Clock(), Collector()
    pol = GcPolicy(clock=clock, collector=coll)
    seen = []
    pol.watch(lambda gen, took, info: seen.append((gen, took, info["collected"])))
    pol.arm()
    assert coll.callbacks == [pol._on_gc]  # one entry for the policy and the profiler
    _pass(pol, clock, 0, 0.002)
    assert clock.reads == 2 and seen == [(0, pytest.approx(0.002), 1)]
    assert pol.full_pauses == 0
    _pass(pol, clock, 2, 0.5)  # long: frozen at once, the count read after
    assert coll.calls == ["freeze", "count"]
    assert clock.reads == 5  # start, stop, and the policy's budget after the count
    assert seen[-1] == (2, pytest.approx(0.5), 1)  # the pass alone, as before
    assert pol.full_pauses == 1 and pol.full_pause_s == pytest.approx(0.5)
    pol.unwatch()
    assert coll.callbacks == [pol._on_gc]  # the policy still holds it
    pol.disarm()
    assert coll.callbacks == []
    assert annotation.built == []  # no session: no span was built


def test_a_young_pass_nobody_times_reads_no_clock_and_builds_nothing(annotation):
    clock, coll = Clock(), Collector()
    pol = GcPolicy(clock=clock, collector=coll)
    for gen in (0, 1):  # no broker armed, no profiler watching
        _pass(pol, clock, gen, 1.0)
    assert clock.reads == 0 and annotation.built == []
    pol.arm()
    _pass(pol, clock, 0, 0.001)  # armed, the young rule times it: one pair
    assert clock.reads == 2 and annotation.built == []
    _pass(pol, clock, 2, 0.001)  # a full pass is the policy's: one clock pair
    assert clock.reads == 4 and annotation.built == []
    assert coll.calls == []
    pol.disarm()


def test_the_host_profiler_and_the_policy_share_the_one_entry():
    ours = lambda: [cb for cb in gc.callbacks  # noqa: E731
                    if getattr(cb, "__self__", None) is GCPOLICY]
    found = len(ours())

    async def run():
        ctx = ServerContext(BrokerConfig(port=0, host_profile=True))
        ctx.start()
        try:
            assert HOSTPROF._gc_installed and len(ours()) == 1
            before = sum(HOSTPROF.gc_pauses.values())
            gc.collect()
            assert sum(HOSTPROF.gc_pauses.values()) > before
        finally:
            await ctx.stop()

    prior = HOSTPROF.enabled
    try:
        asyncio.run(asyncio.wait_for(run(), 30))
    finally:
        HOSTPROF.configure(enabled=prior)
    assert len(ours()) == found


@pytest.mark.parametrize("gen, span", [(0, "rmqtt/gc.young"), (2, "rmqtt/gc.full")])
def test_a_forced_collection_is_one_balanced_span(annotation, gen, span):
    # watched but not armed: the pass is timed and spanned, the heap of the
    # test process is never frozen
    pol = GcPolicy(clock=Clock(), collector=gc)
    sink = []
    annotation.enabled = True
    T.PROFILER.poll()
    pol.watch(lambda g, took, info: sink.append(g))
    try:
        gc.collect(gen)
    finally:
        pol.unwatch()
    assert pol._on_gc not in gc.callbacks
    # (a young pass may also come by itself meanwhile; a full one does not)
    assert annotation.built.count(span) >= 1 and gen in sink
    if gen == 2:
        assert annotation.built.count(span) == 1
    assert annotation.open_now == 0 and pol._span is None


def test_with_no_session_a_forced_collection_builds_no_annotation(annotation):
    pol = GcPolicy(clock=Clock(), collector=gc)
    pol.watch(lambda g, took, info: None)
    try:
        gc.collect()
    finally:
        pol.unwatch()
    assert annotation.built == [] and annotation.open_now == 0


def test_a_collection_inside_a_stages_own_open_stays_balanced(annotation):
    """The stage's annotation is being built when the collector starts (an
    allocation tips it): the pass's span opens and closes inside, apart from
    the stage's stack, and the stage still closes what it opened."""
    pol = GcPolicy(clock=Clock(), collector=gc)
    annotation.enabled = True
    T.PROFILER.poll()

    def collect_once(name):
        if name == "rmqtt/ingress.publish":
            annotation.on_build = None
            gc.collect()

    annotation.on_build = collect_once
    pol.watch(lambda g, took, info: None)
    try:
        st = T.Stage("ingress.publish")
        tok = st.begin()
        assert annotation.open_now == 1 and len(T.PROFILER._tls.open) == 1
        st.end(tok)
    finally:
        pol.unwatch()
    assert annotation.built == ["rmqtt/ingress.publish", "rmqtt/gc.full"]
    assert annotation.open_now == 0 and T.PROFILER._tls.open == []
    assert st.count == 1


# ----------------------------------------------------- the loop's callbacks
async def _named_step():
    await asyncio.sleep(0)


def _plain_callback(out):
    out.append(1)


def test_handle_spans_follow_the_session_and_name_their_callbacks(annotation):
    stock = asyncio.events.Handle._run
    out = []

    async def main():
        loop = asyncio.get_running_loop()
        await asyncio.sleep(0.01)  # a few turns with no session
        assert asyncio.events.Handle._run is stock and annotation.built == []
        annotation.enabled = True
        await asyncio.sleep(0.01)  # the next poll sees it on
        assert asyncio.events.Handle._run is T._traced_run
        await asyncio.create_task(_named_step())
        loop.call_soon(_plain_callback, out)
        await asyncio.sleep(0.01)
        annotation.enabled = False
        await asyncio.sleep(0.01)  # the next poll sees it off
        assert asyncio.events.Handle._run is stock

    loop = asyncio.SelectorEventLoop(T.IdleSpanSelector())
    try:
        loop.run_until_complete(main())
    finally:
        loop.close()
    assert out == [1] and annotation.open_now == 0
    assert "rmqtt/loop.task/_named_step" in annotation.built
    assert "rmqtt/loop.cb/_plain_callback" in annotation.built
    # the span names are kept, one a qualname
    assert T._TASK_SPANS["_named_step"] == "rmqtt/loop.task/_named_step"
    assert T._CB_SPANS["_plain_callback"] == "rmqtt/loop.cb/_plain_callback"
    assert not T.PROFILER.handles


# ----------------------------------------------------------- the two stages
def test_the_stats_snapshot_is_a_stage_and_leaves_its_fold_out(monkeypatch):
    ctx = ServerContext(BrokerConfig(port=0, hotkeys_enable=True))
    snap, fold = (ctx.telemetry.stage("stats.snapshot"),
                  ctx.telemetry.stage("hotkeys.fold"))
    ctx.stats()
    assert (snap.count, fold.count) == (1, 0)  # nothing buffered: no fold
    hk = ctx.hotkeys
    real = hk._fold

    def slow_fold():
        time.sleep(0.05)
        real()

    monkeypatch.setattr(hk, "_fold", slow_fold)
    hk.on_publish("a/b", "c1", 3)
    busy = snap.busy_ns
    t0 = time.perf_counter_ns()
    ctx.stats()
    wall = time.perf_counter_ns() - t0
    assert (snap.count, fold.count) == (2, 1)
    assert fold.busy_ns >= 50_000_000
    assert snap.busy_ns - busy + fold.busy_ns <= wall  # no ns counted twice
    assert snap.busy_ns - busy < 50_000_000
    keys = ctx.telemetry.stage_stats()
    assert keys["stage_stats_snapshot_count"] == 2
    assert keys["stage_hotkeys_fold_count"] == 1


def test_a_fold_forced_inside_ingress_publish_is_left_out_of_it(monkeypatch):
    ctx = ServerContext(BrokerConfig(port=0, hotkeys_enable=True))
    monkeypatch.setattr(HK, "_PENDING_MAX", 4)
    hk = ctx.hotkeys
    real = hk._fold
    monkeypatch.setattr(hk, "_fold", lambda: (time.sleep(0.05), real()))
    pub, fold = (ctx.telemetry.stage("ingress.publish"),
                 ctx.telemetry.stage("hotkeys.fold"))
    for i in range(4):  # four publishes; the fourth fills the buffer
        t0 = time.perf_counter_ns()
        tok = pub.begin()
        hk.on_publish(f"t/{i}", "c1", 1)
        pub.end(tok)
        wall = time.perf_counter_ns() - t0
    assert (pub.count, fold.count) == (4, 1)
    assert fold.busy_ns >= 50_000_000 and wall >= 50_000_000
    assert 0 <= pub.busy_ns < 50_000_000  # the fold is the fold's alone
    assert hk.spaces["topics"].total() == 4


def test_with_telemetry_off_the_fold_reads_no_clock(monkeypatch):
    ctx = ServerContext(BrokerConfig(port=0, hotkeys_enable=True,
                                     telemetry_enable=False))
    reads = []
    real = time.perf_counter_ns
    monkeypatch.setattr(time, "perf_counter_ns", lambda: reads.append(1) or real())
    ctx.hotkeys.on_publish("a/b", "c1", 3)
    assert ctx.hotkeys.drain() == 0
    ctx.stats()
    monkeypatch.undo()
    assert reads == []
    assert ctx.hotkeys.spaces["topics"].total() == 1


# -------------------------------------------------------- the loop's wait
@pytest.fixture
def lag_prof():
    prior = HOSTPROF.enabled
    HOSTPROF.reset()
    HOSTPROF.configure(enabled=True)
    yield HOSTPROF
    HOSTPROF.reset()
    HOSTPROF.configure(enabled=prior)


def test_the_lag_buckets_ride_the_stats_body_and_rise_with_lag(lag_prof):
    ctx = ServerContext(BrokerConfig(port=0))
    keys = [f"hist_host_loop_lag_b{i:02d}" for i in range(T.NBUCKETS)]
    before = stats_body(ctx)
    assert all(before[k] == 0 for k in keys)
    lag_prof.note_lag(300_000_000)  # one tick 300 ms late: bucket 28
    lag_prof.note_lag(1_000)
    after = stats_body(ctx)
    assert [after[k] - before[k] for k in keys] == [
        int(i in (9, 28)) for i in range(T.NBUCKETS)]
    lag_prof.configure(enabled=False)
    off = stats_body(ctx)
    assert set(k for k in off if k.startswith("hist_host_loop_lag_")) == set(keys)
    assert not any(off[k] for k in keys)
