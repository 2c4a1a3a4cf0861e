"""Telemetry-history plane tests (broker/history.py + surfaces).

Tiers:
- Merge-cell semantics (_merge_value / _sum_value) and the EWMA+MAD
  baseline: flat series never breach, a genuine step does.
- Collector rows: every stats() gauge rides, counter deltas become
  per-second rates, device/host rollup summaries and SLO burns land.
- Persistence: CRC-framed segments, rotation + retention, torn-tail
  recovery (the kill-9 crash model: truncate mid-frame, every intact
  frame survives), restart serving the pre-restart timeline over the
  live /api/v1/history.
- Cluster: two REAL meshed nodes, /api/v1/history/sum over the what=
  DATA path (counters sum, quantiles average, nodes=2).
- Anomaly E2E: the history.collect failpoint inflates the collector's
  own latency series → annotation row + slow-op ring row + the
  SERVER_ANOMALY hook + rmqtt_history_anomalies_total on the scrape,
  with ops_doctor's timeline rendering the correlated dump refs.
- Disabled pin: history=false is shape-stable and spawns no task.
"""

import asyncio
import json
import os
import re

from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.history import (
    TRACKED_SERIES,
    HistoryService,
    _Baseline,
    _merge_value,
    _sum_value,
    load_dir,
    read_segment,
)
from rmqtt_tpu.broker.hooks import HookType
from rmqtt_tpu.broker.http_api import HttpApi
from rmqtt_tpu.broker.server import MqttBroker
from rmqtt_tpu.utils.failpoints import FAILPOINTS

from tests.mqtt_client import TestClient
from tests.test_http_plugins import http_get


def _ctx(**kw):
    return ServerContext(BrokerConfig(port=0, **kw))


# ---------------------------------------------------------- merge semantics
def test_merge_value_semantics():
    # numeric: average; states: worst; sparse histograms: key-add
    assert _merge_value("publish_e2e_p99_ms", [1.0, 3.0]) == 2.0
    assert _merge_value("overload_state", [0, 2, 1]) == 2
    assert _merge_value("slo_state_value", [1, 0]) == 1
    assert _merge_value("device.batch_hist",
                        [{"64": 2, "128": 1}, {"64": 3}]) == {
        "64": 5, "128": 1}
    assert _merge_value("x", ["a", "b"]) == "a"  # non-numeric passthrough
    assert _merge_value("x", []) is None


def test_sum_value_counters_sum_quantiles_average():
    # counters SUM across nodes ...
    assert _sum_value("history_samples", [10, 5]) == 15
    assert _sum_value("connections", [3, 4]) == 7
    # ... but quantiles / rates / burns / t average, states stay worst
    assert _sum_value("publish_e2e_p99_ms", [1.0, 3.0]) == 2.0
    assert _sum_value("publish.received.rate", [100.0, 300.0]) == 200.0
    assert _sum_value("slo.delivery.fast_burn", [0.0, 2.0]) == 1.0
    assert _sum_value("t", [10.0, 20.0]) == 15.0
    assert _sum_value("overload_state", [0, 2]) == 2
    assert _sum_value("device.batch_hist", [{"64": 1}, {"64": 1}]) == {
        "64": 2}


def test_baseline_flat_series_never_breaches():
    bl = _Baseline()
    for _ in range(100):
        resid, mean, dev = bl.observe(5.0)
        assert resid == 0.0  # zero-change series: residual exactly 0
    assert bl.mean == 5.0 and bl.dev == 0.0


def test_baseline_detects_step_then_adapts():
    bl = _Baseline()
    for _ in range(20):
        bl.observe(10.0)
    # a 10x step: residual far beyond k*max(dev, 5% of mean)
    resid, mean, dev = bl.observe(100.0)
    assert resid == 90.0 and mean == 10.0
    assert resid > 6.0 * max(dev, 0.05 * abs(mean), 1e-3)
    # sustained at the new level the baseline adapts (episode, not a
    # permanent alarm): residual shrinks toward 0
    for _ in range(30):
        resid, mean, dev = bl.observe(100.0)
    assert resid < 1.0 and abs(bl.mean - 100.0) < 1.0


# -------------------------------------------------------------- collector
def test_collect_once_row_shape_and_rates():
    ctx = _ctx(history_interval_s=0.5)
    hist = ctx.history
    r1 = hist.collect_once()
    # every stats() gauge rides the row (the cross-plane surface)
    for key in ("connections", "publish_e2e_p99_ms", "routing_match_p99_ms",
                "host_loop_lag_p99_ms", "slo_state", "overload_state",
                "rss_mb", "history_samples"):
        assert key in r1, key
    assert r1["history.collect_ms"] >= 0.0
    # first sample has no previous counters: rates pinned to 0
    assert r1["publish.received.rate"] == 0.0
    # second sample: counter delta / wall delta
    ctx.metrics.inc("publish.received", 500)
    ctx.metrics.inc("messages.delivered", 400)
    hist._last_t -= 1.0  # pretend the previous sample was 1s ago
    r2 = hist.collect_once()
    assert r2["publish.received.rate"] > 0.0
    assert r2["messages.delivered.rate"] > 0.0
    assert hist.samples_total == 2 and len(hist.ring) == 2
    # SLO burns ride per objective
    assert any(k.startswith("slo.") and k.endswith("_burn") for k in r2)


def test_ring_bounded_and_query_filters():
    ctx = _ctx(history_ring_max=8)
    hist = ctx.history
    for i in range(30):
        row = hist.collect_once()
        row["t"] = 1000.0 + i  # deterministic timeline for the filters
    assert len(hist.ring) == 8  # bounded: maxlen wins
    snap = hist.query(frm=1024.0, to=1027.0)
    assert snap["count"] == 4
    assert [r["t"] for r in snap["samples"]] == [1024.0, 1025.0,
                                                 1026.0, 1027.0]
    # series projection: t always rides
    snap = hist.query(series="rss_mb,publish_e2e_p99_ms")
    assert snap["series"] == ["rss_mb", "publish_e2e_p99_ms"]
    for r in snap["samples"]:
        assert set(r) == {"t", "rss_mb", "publish_e2e_p99_ms"}
    # step downsampling: rows t=1022..1029 at step=4 → buckets
    # 1020 (n=2), 1024 (n=4), 1028 (n=2)
    snap = hist.query(step=4.0)
    assert snap["count"] == 3
    assert [r["n"] for r in snap["samples"]] == [2, 4, 2]
    assert [r["t"] for r in snap["samples"]] == [1020.0, 1024.0, 1028.0]


def test_merge_snapshots_two_nodes():
    a, b = _ctx(node_id=1), _ctx(node_id=2)
    for ctxx in (a, b):
        for _ in range(2):
            row = ctxx.history.collect_once()
            row["t"] = 1000.0  # same bucket on both nodes
    merged = HistoryService.merge_snapshots(
        a.history.query(), [b.history.query()])
    assert merged["nodes"] == 2 and merged["count"] == 1
    row = merged["samples"][0]
    assert row["n"] == 4 and row["t"] == 1000.0
    # counters SUM across nodes: the history_samples gauge reads 0 then
    # 1 on each node (stats snapshots precede the increment) → 2 total
    assert row["history_samples"] == 2
    # quantiles average, not sum
    vals = [r["publish_e2e_p99_ms"]
            for ctxx in (a, b) for r in ctxx.history.ring]
    assert row["publish_e2e_p99_ms"] == round(sum(vals) / 4, 3)


# ------------------------------------------------------------- persistence
def test_segments_rotate_and_retain(tmp_path):
    d = str(tmp_path / "hist")
    ctx = _ctx(history_dir=d, history_segment_rows=16,
               history_retention_segments=2)
    hist = ctx.history
    for _ in range(80):  # 5 segments of 16 rows
        hist.collect_once()
    hist._close_segment()
    names = sorted(n for n in os.listdir(d) if n.endswith(".hist"))
    assert len(names) <= 3  # retention pruned the oldest (2 + active)
    assert hist.retention_deleted >= 1
    rows, anoms, torn = load_dir(d)
    # the retained window: at least one full segment, nothing torn
    assert torn == 0 and 16 <= len(rows) <= 32


def test_torn_tail_recovery(tmp_path):
    """The kill-9 crash model: a segment truncated mid-frame loses ONLY
    the torn tail — every CRC-intact frame before it reads back."""
    d = str(tmp_path / "hist")
    ctx = _ctx(history_dir=d)
    hist = ctx.history
    for _ in range(10):
        hist.collect_once()
    hist._close_segment()
    seg = os.path.join(d, sorted(os.listdir(d))[-1])
    size = os.path.getsize(seg)
    with open(seg, "r+b") as f:
        f.truncate(size - 7)  # mid-frame: kills the last record
    rows, anoms, torn = read_segment(seg)
    assert len(rows) == 9 and torn == 1
    # corrupt length field: scanner stops, keeps the intact prefix
    with open(seg, "ab") as f:
        f.write(b"\xff" * 32)
    rows2, _, torn2 = read_segment(seg)
    assert len(rows2) == 9 and torn2 == 1
    # a fresh context over the same dir recovers the intact frames
    ctx2 = _ctx(history_dir=d)
    assert ctx2.history.recovered_rows == 9
    assert ctx2.history.torn_tails == 1
    assert len(ctx2.history.ring) == 9
    ctx2.history._close_segment()


def test_restart_serves_prerestart_timeline(tmp_path):
    """Acceptance drill: populate history_dir, stop the broker, start a
    NEW broker over the same dir — the live /api/v1/history must serve
    the pre-restart timeline."""
    d = str(tmp_path / "hist")

    async def run():
        cfg = dict(history_dir=d, history_interval_s=0.5)
        b = MqttBroker(ServerContext(BrokerConfig(port=0, **cfg)))
        await b.start()
        marks = []
        for _ in range(6):
            marks.append(b.ctx.history.collect_once()["t"])
        await b.stop()

        b2 = MqttBroker(ServerContext(BrokerConfig(port=0, **cfg)))
        api = HttpApi(b2.ctx, port=0)
        await b2.start()
        await api.start()
        try:
            assert b2.ctx.history.recovered_rows >= 6
            status, body = await http_get(api.bound_port, "/api/v1/history")
            assert status == 200
            snap = json.loads(body)
            assert snap["schema"] == "rmqtt_tpu.history_sample/1"
            got = {r["t"] for r in snap["samples"]}
            assert set(marks) <= got  # pre-restart rows served live
            assert snap["persistence"]["recovered_rows"] >= 6
            # the recovered rows ride the stats gauge too
            st = b2.ctx.stats().to_json()
            assert st["history_recovered_rows"] >= 6
        finally:
            await api.stop()
            await b2.stop()

    asyncio.run(run())


# ----------------------------------------------------------------- cluster
def test_history_sum_two_live_nodes():
    """Two REAL meshed nodes: /api/v1/history/sum fans the what=history
    DATA query to the peer and merges both timelines."""
    from tests.test_cluster import link, make_node

    async def run():
        brokers = [await make_node(i + 1) for i in range(2)]
        clusters = await link(brokers)
        api = HttpApi(brokers[0].ctx, port=0)
        await api.start()
        try:
            for b in brokers:
                for _ in range(2):
                    b.ctx.history.collect_once()
            status, body = await http_get(
                api.bound_port, "/api/v1/history/sum")
            assert status == 200
            merged = json.loads(body)
            assert merged["nodes"] == 2
            assert merged["count"] >= 1
            # both nodes' samples land in the same wall-clock bucket:
            # the per-node history_samples counter (2 each) sums to 4
            top = max(merged["samples"], key=lambda r: r["n"])
            assert top["n"] >= 2
            assert top["history_samples"] >= 4
        finally:
            await api.stop()
            for c in clusters:
                await c.stop()
            for b in brokers:
                await b.stop()

    asyncio.run(run())


# ------------------------------------------------------------- anomaly e2e
def test_forced_anomaly_end_to_end():
    """The history.collect failpoint inflates the collector's own
    latency series; the breach must land everywhere the design says:
    annotation row, slow-op ring, SERVER_ANOMALY hook, the scrape
    counter, and the ops_doctor timeline — correlated with a device
    dump recorded in the same window."""

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, history_interval_s=0.5, history_anomaly_k=4.0,
            history_anomaly_warmup=4)))
        api = HttpApi(b.ctx, port=0)
        await b.start()
        await api.start()
        hist = b.ctx.history
        fired = []

        async def on_anomaly(_ht, args, _prev):
            fired.append(args)
            return None

        b.ctx.hooks.register(HookType.SERVER_ANOMALY, on_anomaly)
        try:
            # settle the baseline well past warmup
            for _ in range(8):
                hist.collect_once()
            # a device dump "lands" in the correlation window
            from rmqtt_tpu.broker.devprof import DEVPROF

            DEVPROF.dumps_log.append({
                "ts": __import__("time").time(),
                "reason": "test-retrace-storm", "path": "/tmp/d.json"})
            FAILPOINTS.configure({"history.collect": "times(1, delay(80))"})
            try:
                row = hist.collect_once()
            finally:
                FAILPOINTS.clear_all()
                DEVPROF.dumps_log.pop()
            assert row["history.collect_ms"] >= 80.0
            await asyncio.sleep(0.05)  # let the hook task run

            # the FORCED anomaly: a slow collect_once under load (5 ms
            # against a ~1 ms baseline) may have fired one of its own first
            forced = [x for x in hist.anomalies
                      if x["series"] == "history.collect_ms"
                      and x["value"] >= 80.0]
            assert forced, f"no forced anomaly recorded: {hist.anomalies}"
            a = forced[-1]
            assert a["factor"] > 1.0
            # the correlated dump rode the annotation by reference
            assert any(d["plane"] == "device"
                       and d["reason"] == "test-retrace-storm"
                       for d in a["dumps"])
            # slow-op ring: the shared correlation timeline
            assert any(op["op"] == "history.anomaly"
                       for op in b.ctx.telemetry.slow_ops)
            # SERVER_ANOMALY hook payload
            assert fired, "SERVER_ANOMALY hook did not fire"
            hooked = [f for f in fired
                      if f[0] == "history.collect_ms" and f[1] >= 80.0]
            assert hooked, f"the forced anomaly did not reach the hook: {fired}"
            assert hooked[-1][2]["series"] == "history.collect_ms"
            # counters: stats gauge + the per-series scrape family
            assert b.ctx.stats().to_json()["history_anomalies"] >= 1
            status, body = await http_get(api.bound_port,
                                          "/metrics/prometheus")
            text = body.decode()
            assert "# TYPE rmqtt_history_anomalies_total counter" in text
            m = re.search(r'rmqtt_history_anomalies_total\{node="1",'
                          r'series="history\.collect_ms"\} (\d+)', text)
            assert m and int(m.group(1)) >= 1, text
            assert "rmqtt_history_samples_recorded_total" in text
            # anomalies ride the query body
            status, body = await http_get(api.bound_port, "/api/v1/history")
            snap = json.loads(body)
            assert any(x["series"] == "history.collect_ms"
                       and x["value"] >= 80.0 for x in snap["anomalies"])
            # ops_doctor renders the step + its correlated dump
            import importlib.util
            import pathlib

            path = (pathlib.Path(__file__).parent.parent / "scripts"
                    / "ops_doctor.py")
            spec = importlib.util.spec_from_file_location("ops_doctor", path)
            od = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(od)
            lines = od.timeline_lines(snap, b.ctx.telemetry.slow_ops)
            joined = "\n".join(lines)
            assert "history.collect_ms" in joined
            assert "stepped" in joined
            assert "/tmp/d.json" in joined
        finally:
            await api.stop()
            await b.stop()

    asyncio.run(run())


def test_anomaly_zero_change_pin():
    """A perfectly flat tracked series must NEVER breach — the deviation
    floor is strictly positive and the residual is exactly zero."""
    ctx = _ctx(history_anomaly_warmup=2)
    hist = ctx.history
    for i in range(50):
        row = {"t": 1000.0 + i, **{s: 7.0 for s in TRACKED_SERIES}}
        hist._annotate(row)
    assert not hist.anomalies


# ---------------------------------------------------------------- disabled
def test_disabled_shape_stable():
    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, history_enable=False)))
        api = HttpApi(b.ctx, port=0)
        await b.start()
        await api.start()
        try:
            assert b.ctx.history._task is None  # no collector task
            assert b.ctx.history.collect_once() is None
            status, body = await http_get(api.bound_port, "/api/v1/history")
            assert status == 200
            snap = json.loads(body)
            assert snap["enabled"] is False
            assert snap["count"] == 0 and snap["samples"] == []
            assert snap["anomalies"] == []
            assert snap["persistence"]["dir"] is None
            # /sum stays shape-stable too
            status, body = await http_get(api.bound_port,
                                          "/api/v1/history/sum")
            merged = json.loads(body)
            assert merged["nodes"] == 1 and merged["enabled"] is False
            # gauges present, zero; scrape families present, zero
            st = b.ctx.stats().to_json()
            assert st["history_samples"] == 0
            assert st["history_anomalies"] == 0
            status, body = await http_get(api.bound_port,
                                          "/metrics/prometheus")
            text = body.decode()
            assert ('rmqtt_history_samples_recorded_total{node="1"} 0'
                    in text)
        finally:
            await api.stop()
            await b.stop()

    asyncio.run(run())


# -------------------------------------------------------------------- conf
def test_conf_history_knobs(tmp_path):
    from rmqtt_tpu import conf

    p = tmp_path / "h.toml"
    p.write_text("""
[observability]
history = true
history_interval_s = 2.5
history_ring_max = 100
history_dir = "/tmp/hx"
history_segment_rows = 64
history_retention_segments = 4
history_anomaly = false
history_anomaly_k = 8.0
history_anomaly_warmup = 12
device_rollup_max = 50
host_rollup_max = 60
""")
    cfg = conf.load(str(p)).broker
    assert cfg.history_enable is True
    assert cfg.history_interval_s == 2.5
    assert cfg.history_ring_max == 100
    assert cfg.history_dir == "/tmp/hx"
    assert cfg.history_segment_rows == 64
    assert cfg.history_retention_segments == 4
    assert cfg.history_anomaly_enable is False
    assert cfg.history_anomaly_k == 8.0
    assert cfg.history_anomaly_warmup == 12
    assert cfg.device_rollup_max == 50
    assert cfg.host_rollup_max == 60


# ------------------------------------------------------------ live traffic
def test_live_broker_timeline_sees_traffic():
    """Real MQTT traffic between two collected samples shows up as a
    positive delivered-rate on the timeline."""

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, history_interval_s=0.5)))
        await b.start()
        try:
            hist = b.ctx.history
            hist.collect_once()
            sub = await TestClient.connect(b.port, "h-sub")
            await sub.subscribe("h/#", qos=0)
            publ = await TestClient.connect(b.port, "h-pub")
            for i in range(20):
                await publ.publish(f"h/{i}", b"x", qos=0)
            for _ in range(20):
                await sub.recv()
            hist._last_t -= 0.5  # guarantee a nonzero wall delta
            row = hist.collect_once()
            assert row["publish.received.rate"] > 0.0
            assert row["messages.delivered.rate"] > 0.0
        finally:
            await b.stop()

    asyncio.run(run())
