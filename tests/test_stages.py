"""The served path's stage layer (``broker/telemetry.py`` ``Stage``).

- every stage is pre-registered, and its flat keys are on ``/api/v1/stats``
  with zeros, whether telemetry is on or off;
- ``[observability] enable = false``: no new boundary reads a clock or opens
  a span;
- with no profiler session on, no ``TraceAnnotation`` is ever constructed;
  with one on, the same sections are ``rmqtt/<stage>`` spans;
- the bucket deltas of two snapshots give the quantile of the samples taken
  between them;
- on a live broker one QoS1 publish to one QoS1 subscriber passes each
  boundary of the served path the stated number of times;
- the device programs carry their named scopes.
"""

import asyncio
import json
import random
import time

import pytest

from rmqtt_tpu.broker import telemetry as T
from rmqtt_tpu.broker.codec import packets as pk
from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.http_api import HttpApi
from rmqtt_tpu.broker.server import MqttBroker

from tests.mqtt_client import TestClient
from tests.test_http_plugins import http_get

# what ONE QoS1 publish to ONE QoS1 subscriber passes, on a broker whose
# router is the device router (small batches: the host mirror serves,
# inline on the loop thread). Two chunks come in (the PUBLISH, the
# subscriber's PUBACK) and two vectored writes go out (the delivery, the
# publisher's PUBACK); every other boundary is passed once.
ONE_PUBLISH = {
    "ingress.decode": 2, "ingress.publish": 1, "routing.plan": 1,
    "routing.match.side": 1, "routing.expand": 1, "routing.resolve": 1,
    "fanout.enqueue": 1, "deliver.send": 1, "egress.flush": 2,
    "ack.in": 1, "ack.out": 1,
}


def _key(stage: str) -> str:
    return "stage_" + stage.replace(".", "_")


# ------------------------------------------------------------ registration
@pytest.mark.parametrize("enabled", [True, False])
@pytest.mark.parametrize("stage", T.SERVED_STAGES)
def test_stage_is_registered_and_flat(stage, enabled):
    tele = T.Telemetry(enabled=enabled)
    st = tele.stage(stage)
    assert st is tele.stage(stage) and st.span == "rmqtt/" + stage
    flat = tele.stage_stats()
    kind = "wait" if stage == "deliver.credit_wait" else "busy"
    assert flat[_key(stage) + "_count"] == 0
    assert flat[f"{_key(stage)}_{kind}_ms_total"] == 0
    if stage in T.BY_THREAD:
        assert flat[_key(stage) + "_exec_count"] == 0
        assert flat[_key(stage) + "_exec_busy_ms_total"] == 0


@pytest.mark.parametrize("hist", T.WINDOW_HISTS)
def test_window_histogram_buckets_are_flat(hist):
    tele = T.Telemetry()
    tele.record(hist, 1500)  # bucket 10: [1024, 2048) ns
    flat = tele.bucket_stats()
    key = "hist_" + hist.replace(".", "_") + "_b"
    assert [flat[f"{key}{i:02d}"] for i in range(T.NBUCKETS)] \
        == [int(i == 10) for i in range(T.NBUCKETS)]
    off = T.Telemetry(enabled=False)
    assert set(off.bucket_stats()) == set(flat)
    assert not any({**off.bucket_stats(), **off.stage_stats()}.values())


def test_cpu_totals_are_monotone_and_zero_when_disabled():
    tele = T.Telemetry()
    a = tele.stage_stats()
    sum(i * i for i in range(200_000))  # burn some CPU on this thread
    b = tele.stage_stats()
    for k in ("host_loop_cpu_ms_total", "host_proc_cpu_ms_total"):
        assert b[k] > a[k] > 0


# ------------------------------------------------------------ window deltas
@pytest.mark.parametrize("q", [0.5, 0.9, 0.99])
def test_bucket_deltas_give_the_windows_quantile(q):
    rng = random.Random(25)
    tele = T.Telemetry()
    rec = tele.recorder("deliver.queue_wait")
    for _ in range(3000):  # before the window: long waits
        rec(int(10 ** rng.uniform(7, 9)))
    before = tele.bucket_stats()
    window = [int(10 ** rng.uniform(3, 6)) for _ in range(2000)]
    for v in window:
        rec(v)
    after = tele.bucket_stats()
    keys = [f"hist_deliver_queue_wait_b{i:02d}" for i in range(T.NBUCKETS)]
    est = T.bucket_quantile([after[k] - before[k] for k in keys], q)
    exact = sorted(window)[max(1, int(q * len(window) + 0.999999)) - 1]
    assert est / 2 <= exact < est  # the bucket that holds it
    # ... where the since-start histogram answers for another population
    assert tele.hist("deliver.queue_wait").quantile(q) > 10 * est


# --------------------------------------------------- the Stage object itself
def test_stage_counts_one_pass_and_laps_without_counting():
    st = T.Stage("deliver.send")
    tok = st.begin()
    assert st.lap(tok) >= 0 and st.count == 0
    busy = st.busy_ns
    tok = st.begin_at(time.perf_counter_ns())
    assert st.end(tok) >= 0
    assert st.count == 1 and st.busy_ns >= busy


def test_a_nested_stage_owns_its_time_and_the_outer_leaves_it_out(annotation):
    """``routing.cache_hit`` inside ``ingress.run`` (a run's hits): the outer
    ``end(tok, inner)`` counts its section less the inner sections, and the
    two spans nest (the inner is closed first)."""
    annotation.enabled = True
    T.PROFILER.poll()
    outer, inner = T.Stage("ingress.run"), T.Stage("routing.cache_hit")
    tok = outer.begin(4)
    t0 = time.perf_counter_ns()
    while time.perf_counter_ns() - t0 < 2_000_000:  # 2 ms before the hit
        pass
    itok = inner.begin_at(time.perf_counter_ns())
    assert annotation.open_now == 2
    while time.perf_counter_ns() - t0 < 5_000_000:  # the hit: 3 ms more
        pass
    took = inner.end(itok)
    assert annotation.open_now == 1
    net = outer.end(tok, took)
    assert annotation.open_now == 0
    assert (outer.count, inner.count) == (1, 1)
    assert outer.busy_ns == net and inner.busy_ns == took >= 3_000_000
    # the outer holds the 2 ms before the hit and its wall less the hit
    assert 2_000_000 <= net and net + took <= time.perf_counter_ns() + tok
    assert [name for name, _kw in annotation.built] == [
        "rmqtt/ingress.run", "rmqtt/routing.cache_hit"]


def test_by_thread_stage_keeps_executor_time_apart():
    import threading

    T.Telemetry.bind_loop()
    st = T.Stage("routing.expand")
    st.end(st.begin())
    t = threading.Thread(target=lambda: st.end(st.begin()))
    t.start()
    t.join()
    assert (st.count, st.xcount) == (1, 1)


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts what is built."""

    built: list = []
    open_now = 0
    enabled = False

    def __init__(self, name, **kw):
        type(self).built.append((name, kw))

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
    _Annotation.built, _Annotation.open_now, _Annotation.enabled = [], 0, False
    monkeypatch.setattr(T.PROFILER, "annotation", _Annotation)
    monkeypatch.setattr(T.PROFILER, "on", False)
    yield _Annotation
    T.PROFILER.on = False


@pytest.mark.parametrize("stage", [s for s in T.SERVED_STAGES
                                   if s != "deliver.credit_wait"])
def test_a_span_only_while_a_profiler_session_is_on(stage, annotation):
    st = T.Telemetry().stage(stage)
    st.end(st.begin(3, 7))
    assert T.PROFILER.poll() is False and annotation.built == []
    annotation.enabled = True
    assert T.PROFILER.poll() is True
    tok = st.begin(3, 7)
    annotation.enabled = False
    T.PROFILER.poll()  # the session ends inside the section: end still closes
    assert annotation.open_now == 1
    st.end(tok)
    assert annotation.open_now == 0
    assert annotation.built == [("rmqtt/" + stage, {"batch": 7, "n": 3})]
    assert st.count == 2


def test_batch_seq_rides_the_thread(annotation):
    tele = T.Telemetry()
    tele.batch_seq = 41
    assert tele.batch_begin() == 0  # no session: nothing is touched
    annotation.enabled = True
    T.PROFILER.poll()
    seq = tele.batch_begin()
    st = tele.stage("matcher.encode")
    st.end(st.begin(16))
    tele.batch_end(seq)
    st.end(st.begin(16))
    assert annotation.built == [("rmqtt/matcher.encode", {"batch": 41, "n": 16}),
                                ("rmqtt/matcher.encode", {"n": 16})]


# ------------------------------------------------------------- a live broker
async def _one_publish(cfg: dict, probe):
    """Start a ``--router xla`` broker in this process, connect a QoS1
    subscriber and a publisher, let the connects and the SUBSCRIBE settle,
    then → (stats before, stats after) ONE QoS1 publish that the subscriber
    receives and PUBACKs. ``probe(broker)`` runs just before the publish."""
    b = MqttBroker(ServerContext(BrokerConfig(port=0, router="xla", **cfg)))
    api = HttpApi(b.ctx, port=0)
    await b.start()
    await api.start()
    try:
        sub = await TestClient.connect(b.port, "stage-sub", version=pk.V311)
        await sub.subscribe("fleet/+/state", qos=1)
        publ = await TestClient.connect(b.port, "stage-pub", version=pk.V311)
        await asyncio.sleep(0.2)

        async def stats():
            _status, body = await http_get(api.bound_port, "/api/v1/stats")
            return json.loads(body)[0]["stats"]

        before = await stats()
        probe(b)
        await publ.publish("fleet/d1/state", b"1", qos=1)  # waits for the PUBACK
        got = await asyncio.wait_for(sub.publishes.get(), 5.0)
        assert got.topic == "fleet/d1/state" and got.qos == 1
        for _ in range(100):  # the subscriber's PUBACK reaches the broker
            if not len(b.ctx.registry.get("stage-sub").out_inflight):
                break
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.05)
        return before, await stats()
    finally:
        await api.stop()
        await b.stop()


@pytest.fixture(scope="module")
def one_publish():
    built = []

    def probe(_b, _orig=T._Profiler.open):
        T._Profiler.open = lambda *a, **k: built.append(a) or _orig(*a, **k)

    orig = T._Profiler.open
    try:
        before, after = asyncio.run(asyncio.wait_for(_one_publish({}, probe), 60))
    finally:
        T._Profiler.open = orig
    return before, after, built


@pytest.mark.parametrize("stage", sorted(ONE_PUBLISH))
def test_one_qos1_publish_passes_each_boundary(one_publish, stage):
    before, after, _built = one_publish
    k = _key(stage) + "_count"
    assert after[k] - before[k] == ONE_PUBLISH[stage]
    assert after[_key(stage) + "_busy_ms_total"] >= before[_key(stage) + "_busy_ms_total"]


def test_one_publish_waits_are_in_the_window_histograms(one_publish):
    before, after, built = one_publish
    for hist in ("routing_queue_wait", "deliver_queue_wait", "publish_e2e"):
        keys = [f"hist_{hist}_b{i:02d}" for i in range(T.NBUCKETS)]
        assert sum(after[k] - before[k] for k in keys) == 1, hist
    assert after["stage_deliver_credit_wait_count"] == before["stage_deliver_credit_wait_count"]
    assert after["host_loop_cpu_ms_total"] > before["host_loop_cpu_ms_total"]
    assert built == []  # no profiler session: not one annotation was opened


def test_disabled_reads_no_clock_at_any_boundary(monkeypatch):
    """``[observability] enable = false``: the publish passes every boundary
    and none of them reads the clock, opens a span or counts."""
    reads = []
    real = time.perf_counter_ns

    def probe(_b):
        monkeypatch.setattr(time, "perf_counter_ns",
                            lambda: reads.append(1) or real())
        monkeypatch.setattr(T.Stage, "begin", lambda *a, **k: reads.append("begin") or 0)
        monkeypatch.setattr(T.Stage, "begin_at", lambda *a, **k: reads.append("begin") or 0)

    before, after = asyncio.run(asyncio.wait_for(_one_publish(
        {"telemetry_enable": False, "device_profile": False,
         "host_profile": False, "history_enable": False, "slo_enable": False},
        probe), 60))
    monkeypatch.undo()
    assert reads == []
    assert not any(v for k, v in after.items()
                   if k.startswith(("stage_", "hist_", "host_loop_cpu", "host_proc_cpu")))
    assert set(after) == set(before)


# ---------------------------------------------------------- device programs
@pytest.fixture(scope="module")
def fused_hlo():
    import jax

    from rmqtt_tpu.ops import partitioned as P

    table = P.PartitionedTable()
    for i in range(40):
        table.add(f"fleet/d{i}/+")
    matcher = P.PartitionedMatcher(table)
    matcher.match(["fleet/d1/state"])  # uploads the table and the fid map
    ttok, tlen, tdollar, chunk_ids, _nc = table.encode_topics(
        ["fleet/d1/state"], pad_batch_to=8)
    tiles, fids = matcher._refresh(), matcher._dev_fids
    lay = matcher._dev_playout
    if lay is not None:
        _lay, ttok = table.translate_packed(ttok)
    fn = jax.jit(P.match_fused_impl, static_argnames=("budget", "layout"))
    return fn.lower(tiles, fids, ttok, tlen, tdollar, chunk_ids, budget=256,
                    layout=lay).compile().as_text()


@pytest.mark.parametrize("scope", ["scan", "compact", "resolve", "sort", "counts"])
def test_match_program_carries_its_named_scopes(fused_hlo, scope):
    assert f'op_name="jit(match_fused_impl)/{scope}/' in fused_hlo
