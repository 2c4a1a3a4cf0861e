"""Device-plane flight recorder tests (broker/devprof.py + surfaces).

Tiers:
- shape-key registry semantics against the matcher stack: first-seen
  signatures are traces, repeats are cache hits; a batch-size sweep across
  pow2 boundaries forces a RETRACE STORM (counted, slow-ring annotated,
  auto-dumped); steady dirty-chunk churn produces ZERO new traces —
  pinning PR5's one-compiled-scatter claim in the profiler's terms;
- rollup quantiles vs a sorted oracle (log2-bucket bracket, like the
  telemetry histograms they reuse);
- HBM occupancy model vs the jax live-array census;
- disabled-mode pins: instrumented seams never enter the profiler
  (PR6-style never-entered + micro-cost pin), surfaces stay shape-stable;
- live e2e: /api/v1/device (+ /device/sum), exposition grammar,
  $SYS/brokers/<n>/device/#, the what=device cluster DATA query, and the
  [observability] device knobs.
"""

import asyncio
import json
import time

import pytest

from rmqtt_tpu.broker.devprof import DEVPROF, DeviceProfiler
from rmqtt_tpu.broker.telemetry import Telemetry
from rmqtt_tpu.ops.partitioned import PartitionedMatcher, PartitionedTable


@pytest.fixture
def prof():
    """Clean process-global profiler for the test, restored after."""
    prior = (DEVPROF.enabled, DEVPROF.telemetry, DEVPROF.dump_dir,
             DEVPROF.hbm_provider, DEVPROF.storm_n, DEVPROF.storm_window,
             DEVPROF.interval_s)
    DEVPROF.reset()
    DEVPROF.configure(enabled=True, telemetry=None, dump_dir=None,
                      hbm_provider=None, storm_n=8, storm_window=10.0,
                      interval_s=5.0)
    yield DEVPROF
    DEVPROF.reset()
    DEVPROF.configure(enabled=prior[0], telemetry=prior[1],
                      dump_dir=prior[2], hbm_provider=prior[3],
                      storm_n=prior[4], storm_window=prior[5],
                      interval_s=prior[6])


def _matcher(nfilters: int = 4):
    t = PartitionedTable()
    fids = [t.add(f"a/b/c{i}") for i in range(nfilters)]
    m = PartitionedMatcher(t)
    return t, m, fids


# ------------------------------------------------------- registry semantics


def test_shape_registry_hit_vs_trace(prof):
    """First dispatch of a signature records traces; an identical repeat
    records ONLY cache hits (the jit executable cache is signature-keyed,
    and the registry mirrors exactly that key)."""
    _t, m, _ = _matcher()
    m.match(["a/b/c0", "x/y"])
    m.match(["a/b/c0", "x/y"])  # decide-consumed batch; now steady
    t0, h0 = prof.traces, prof.cache_hits
    m.match(["a/b/c0", "x/y"])
    assert prof.traces == t0, "steady repeat must not trace"
    assert prof.cache_hits > h0
    assert prof.dispatches >= 3
    # flight records carry the compile classification + pad accounting
    rec = prof.flight()[-1]
    assert rec["compile"] == "hit" and rec["batch"] == 2
    assert rec["padded"] >= rec["batch"] and "total_ms" in rec


def test_forced_retrace_storm_detected_and_dumped(prof, tmp_path):
    """A batch-size sweep across pow2 boundaries with the pad floor
    disabled (floor 1) compiles a fresh executable per shape → the storm
    detector fires, annotates the slow ring, and auto-dumps a flight
    artifact that contains the storm + the sweep's records."""
    tele = Telemetry(enabled=True, slow_ms=1e9)
    prof.configure(storm_n=4, storm_window=120.0, telemetry=tele,
                   dump_dir=str(tmp_path))
    _t, m, _ = _matcher()
    m._fused = False  # one kernel family → the sweep count is deterministic
    for b in (1, 2, 4, 8, 16):  # each pow2 shape = a distinct jit signature
        m.match(["a/b/c0"] * b)
    assert prof.traces >= 4
    assert prof.storms >= 1
    snap = prof.snapshot()
    assert snap["compile"]["storms"] >= 1
    assert snap["compile"]["last_storm"]["traces_in_window"] >= 4
    # slow-ring annotation (the stall timeline operators read)
    assert any(op["op"] == "device.retrace_storm" for op in tele.slow_ops)
    # auto-dumped artifact on disk, schema-tagged, carrying the ring
    # (the dump runs on a daemon thread — it must not block the match
    # path — so poll briefly)
    deadline = time.time() + 10
    dumps: list = []
    while not dumps and time.time() < deadline:
        dumps = list(tmp_path.glob("devprof_retrace_storm_*.json"))
        time.sleep(0.05)
    assert dumps, "storm must auto-dump a flight artifact"
    dump = json.loads(dumps[0].read_text())
    assert dump["schema"] == "rmqtt_tpu.devprof_dump/1"
    assert dump["snapshot"]["compile"]["storms"] >= 1
    assert dump["flight"], "the dump must carry flight records"


def test_steady_churn_zero_new_traces(prof):
    """PR5's one-compiled-scatter claim, now checkable: steady dirty-chunk
    churn (add/remove + match at a fixed batch size) reuses ONE compiled
    scatter and ONE compiled match executable — zero new traces after
    warmup."""
    prof.configure(storm_n=100)  # warmup's first-compile burst is not a storm
    t, m, fids = _matcher(8)
    topics = ["a/b/c0", "a/b/c1", "nope/x", "a/b/c2"]

    def cycle():
        fid = t.add("a/b/churn")
        t.remove(fid)
        m.match(topics)

    m.match(topics)  # compile the match shapes (incl. fused verify)
    for _ in range(4):  # warm the delta-scatter signatures
        cycle()
    tr0 = prof.traces
    for _ in range(6):
        cycle()
    assert prof.traces == tr0, "steady churn must not retrace"
    assert prof.storms == 0
    # ...and the churn actually exercised the delta path
    assert m.delta_uploads > 0
    snap = prof.snapshot()
    assert snap["uploads"]["delta"] > 0
    assert snap["uploads"]["delta_bytes"] > 0


# ------------------------------------------------------------- rollups


def test_rollup_quantiles_vs_oracle(prof):
    """Interval rollup p50/p99 bracket the exact sorted oracle within one
    log2 bucket (the telemetry Histogram property, reused here)."""
    import random

    rng = random.Random(3)
    prof.configure(interval_s=3600.0)  # one bucket for the whole test
    samples = [int(10 ** rng.uniform(3, 9)) for _ in range(500)]
    for ns in samples:
        prof.note_dispatch({"batch": 2, "padded": 4, "fused": False}, ns)
    row = prof.snapshot()["dispatch"]["rollups"][-1]
    s = sorted(samples)

    def oracle(q):
        return s[max(0, min(len(s) - 1, int(q * len(s) + 0.999999) - 1))]

    for q, key in ((0.5, "p50_ms"), (0.99, "p99_ms")):
        est_ns = row[key] * 1e6
        exact = oracle(q)
        assert exact < est_ns <= 2 * exact + 2, (q, exact, est_ns)
    assert row["dispatches"] == 500
    assert row["pad_waste"] == 0.5  # 2 real rows of 4 padded, every batch
    d = prof.snapshot()["dispatch"]
    assert d["items"] == 1000 and d["padded_items"] == 2000


# ------------------------------------------------------------- HBM model


def test_hbm_model_reconciles_live_arrays(prof):
    """The occupancy model equals the resident device arrays' bytes
    exactly, and the jax live-array census is an upper bound (jax holds
    more than the table: in-flight topic uploads, jit constants)."""
    _t, m, _ = _matcher()
    m.match(["a/b/c0"])
    bd = m.hbm_breakdown()
    want = int(m._dev_arrays.nbytes) + (
        int(m._dev_fids.nbytes) if m._dev_fids is not None else 0)
    assert bd["total_bytes"] == want > 0
    assert bd["tiles_bytes"] > 0
    assert bd["layout"] in ("packed", "legacy")
    assert bd["legacy_tiles_bytes_model"] > 0
    prof.configure(hbm_provider=m.hbm_breakdown)
    snap = prof.hbm_snapshot()
    assert snap["modeled_bytes"] == want
    if snap.get("live_arrays_bytes") is not None:
        assert snap["live_arrays_bytes"] >= snap["modeled_bytes"]
        assert snap["live_arrays"] >= 1


# ------------------------------------------------------ disabled-mode pins


def test_disabled_never_enters_profiler(prof, monkeypatch):
    """Off discipline: the ONLY hot-path state is the ``.enabled``
    attribute — no instrumented seam may reach note_jit/note_dispatch/
    note_upload (PR6 fire-never-entered style: any entry is an immediate
    failure)."""
    prof.configure(enabled=False)

    def boom(*a, **kw):
        raise AssertionError("profiler entered while disabled")

    monkeypatch.setattr(DEVPROF, "note_jit", boom)
    monkeypatch.setattr(DEVPROF, "note_dispatch", boom)
    monkeypatch.setattr(DEVPROF, "note_upload", boom)
    t, m, fids = _matcher()
    out = m.match(["a/b/c0", "x/y"])
    assert len(out) == 2
    fid = t.add("a/b/extra")
    m.match(["a/b/c0", "x/y"])  # delta-refresh seam included
    t.remove(fid)
    assert prof.flight() == []


def test_disabled_guard_micro_cost_pin(prof):
    """The disabled guard is one attribute load + branch; pin its cost the
    PR6 way so a future 'cheap' addition to the guard shows up."""
    prof.configure(enabled=False)
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        if DEVPROF.enabled:  # the exact guard the jit seams use
            raise AssertionError
    per_iter = (time.perf_counter() - t0) / n
    assert per_iter < 2e-6, f"{per_iter * 1e9:.0f}ns per disabled check"


def test_disabled_snapshot_shape_stable(prof):
    """Every surface key exists (zeros) with the profiler off — dashboards
    and the exposition scrape see one shape either way."""
    prof.configure(enabled=False)
    snap = prof.snapshot()
    assert snap["enabled"] is False
    assert snap["compile"]["traces"] == 0
    assert snap["compile"]["storms"] == 0
    assert snap["dispatch"]["dispatches"] == 0
    assert snap["dispatch"]["rollups"] == []
    assert snap["uploads"] == {"delta": 0, "full": 0,
                               "delta_bytes": 0, "full_bytes": 0}
    assert "hbm" in snap and "modeled_bytes" in snap["hbm"]
    lines = prof.prometheus_lines('node="1"')
    assert any(l.startswith("rmqtt_device_jit_traces_total{") for l in lines)
    merged = DeviceProfiler.merge_snapshots(snap, [snap])
    assert merged["nodes"] == 2 and merged["compile"]["traces"] == 0


# ------------------------------------------------------------- pad floor


def test_pad_floor_logged_and_annotated(prof, caplog):
    """Prewarm latches the sticky pad floor; the change is logged with the
    waste fraction and annotated on the slow ring (the 'why does cfg1 pay
    what it pays' breadcrumb)."""
    tele = Telemetry(enabled=True, slow_ms=1e9)
    prof.configure(telemetry=tele)
    _t, m, _ = _matcher()
    with caplog.at_level("INFO", logger="rmqtt_tpu.devprof"):
        m.prewarm()
    assert m._pad_floor == 8
    assert prof.pad_floor == 8
    assert any("pad floor" in r.message for r in caplog.records)
    entries = [op for op in tele.slow_ops if op["op"] == "device.pad_floor"]
    assert entries and entries[-1]["detail"]["floor"] == 8


# ------------------------------------------------------------ live surfaces


def test_device_endpoint_exposition_and_sum_live():
    """/api/v1/device + /device/sum + rmqtt_device_* exposition grammar on
    a live broker (trie router: the surface must be shape-stable without a
    device matcher too)."""
    from tests.test_http_plugins import http_get
    from tests.test_telemetry import (_EXPOSITION_COMMENT,
                                      _EXPOSITION_SAMPLE)
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.broker.http_api import HttpApi
    from rmqtt_tpu.broker.server import MqttBroker

    async def run():
        DEVPROF.reset()
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        assert DEVPROF.enabled  # device_profile defaults on
        # synthetic device activity so the counters are nonzero on the wire
        DEVPROF.note_jit("match_global", ((8, 4), "budget"), 2_000_000)
        DEVPROF.note_jit("match_global", ((8, 4), "budget"), 1_000)
        DEVPROF.note_dispatch({"batch": 3, "padded": 8, "fused": True},
                              5_000_000)
        api = HttpApi(b.ctx, port=0)
        await b.start()
        await api.start()
        try:
            st, body = await http_get(api.bound_port, "/api/v1/device")
            assert st == 200
            snap = json.loads(body)
            assert snap["node"] == 1 and snap["enabled"] is True
            assert snap["compile"]["traces"] == 1
            assert snap["compile"]["cache_hits"] == 1
            assert snap["compile"]["kernels"]["match_global"]["traces"] == 1
            assert snap["dispatch"]["dispatches"] == 1
            assert snap["dispatch"]["pad_waste"] == round(1 - 3 / 8, 4)
            assert "flight" not in snap  # ring only on request
            st, body = await http_get(api.bound_port,
                                      "/api/v1/device?flight=1")
            assert json.loads(body)["flight"][-1]["batch"] == 3
            st, body = await http_get(api.bound_port, "/api/v1/device/sum")
            merged = json.loads(body)
            assert merged["nodes"] == 1
            assert merged["compile"]["traces"] == 1
            assert merged["dispatch"]["pad_waste"] == round(1 - 3 / 8, 4)
            st, body = await http_get(api.bound_port, "/metrics/prometheus")
            lines = body.decode().strip().split("\n")
            for line in lines:
                if line.startswith("#"):
                    assert _EXPOSITION_COMMENT.match(line), line
                else:
                    assert _EXPOSITION_SAMPLE.match(line), line
            text = "\n".join(lines)
            assert 'rmqtt_device_jit_traces_total{node="1"} 1' in text
            assert 'rmqtt_device_kernel_traces_total{node="1",kernel="match_global"} 1' in text
            assert "rmqtt_device_hbm_modeled_bytes" in text
            # stats gauges ride the same activity
            st, body = await http_get(api.bound_port, "/api/v1/stats")
            stats = json.loads(body)[0]["stats"]
            assert stats["device_jit_traces"] == 1
            assert stats["device_jit_cache_hits"] == 1
            for k in ("routing_stage_encode_ms_total",
                      "routing_stage_dispatch_ms_total",
                      "routing_stage_fetch_ms_total",
                      "routing_stage_decode_ms_total",
                      "device_retrace_storms", "device_hbm_modeled_mb"):
                assert k in stats, k
        finally:
            await api.stop()
            await b.stop()
            DEVPROF.reset()
            DEVPROF.configure(enabled=False)

    asyncio.run(asyncio.wait_for(run(), 30))


def test_xla_router_dispatch_reaches_device_surface():
    """End-to-end through the real device matcher: an all-device broker
    (RMQTT_HYBRID_MAX=0) routes one publish through the XLA path and the
    profiler sees the dispatch + the stage-timing promotion fills the
    routing_stage_* gauges."""
    import os

    from tests.mqtt_client import TestClient
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.broker.server import MqttBroker

    async def run():
        DEVPROF.reset()
        os.environ["RMQTT_HYBRID_MAX"] = "0"
        try:
            ctx = ServerContext(BrokerConfig(port=0, router="xla",
                                             route_cache=False,
                                             routing_prewarm=False))
            b = MqttBroker(ctx)
            await b.start()
            try:
                sub = await TestClient.connect(b.port, "dev-sub")
                await sub.subscribe("d/#", qos=0)
                publ = await TestClient.connect(b.port, "dev-pub")
                await publ.publish("d/1", b"x", qos=1)
                p = await sub.recv(timeout=10.0)
                assert p.topic == "d/1"
                # the dispatch crossed the device plane: profiler saw it
                deadline = time.time() + 10
                while DEVPROF.dispatches == 0 and time.time() < deadline:
                    await asyncio.sleep(0.05)
                assert DEVPROF.dispatches >= 1
                assert DEVPROF.traces >= 1
                st = ctx.routing.stats()
                total_stage = (st["routing_stage_encode_ms_total"]
                               + st["routing_stage_dispatch_ms_total"]
                               + st["routing_stage_fetch_ms_total"]
                               + st["routing_stage_decode_ms_total"])
                assert total_stage > 0  # device_profile promoted stage_timing
                rec = DEVPROF.flight()[-1]
                assert "stage_ns" in rec and rec["batch"] >= 1
            finally:
                await b.stop()
        finally:
            os.environ.pop("RMQTT_HYBRID_MAX", None)
            DEVPROF.reset()
            DEVPROF.configure(enabled=False)

    asyncio.run(asyncio.wait_for(run(), 120))


def test_device_surface_names_the_words_producer():
    """``/api/v1/device`` ``backend`` of an xla broker: the matcher has one
    words producer, and the surface (read by ``chip_smoke.py`` and the
    benchmark's ``backend`` line) still says which."""
    from tests.test_http_plugins import http_get
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.broker.http_api import HttpApi
    from rmqtt_tpu.broker.server import MqttBroker

    async def run():
        DEVPROF.reset()
        ctx = ServerContext(BrokerConfig(port=0, router="xla",
                                         routing_prewarm=False))
        b = MqttBroker(ctx)
        api = HttpApi(ctx, port=0)
        await b.start()
        await api.start()
        try:
            st, body = await http_get(api.bound_port, "/api/v1/device")
            assert st == 200
            be = json.loads(body)["backend"]
            assert be["matcher"] == "PartitionedMatcher"
            assert be["words_producer"]["name"] == "lax"
            assert be["words_producer"]["why"]
        finally:
            await api.stop()
            await b.stop()
            DEVPROF.reset()
            DEVPROF.configure(enabled=False)

    asyncio.run(asyncio.wait_for(run(), 60))


def test_sys_topic_device_tree():
    """$SYS/brokers/<n>/device/#: compile + hbm + dispatch rows while the
    profiler is enabled."""
    from tests.mqtt_client import TestClient
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.broker.server import MqttBroker
    from rmqtt_tpu.plugins.sys_topic import SysTopicPlugin

    async def run():
        DEVPROF.reset()
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        DEVPROF.note_jit("match_global", ("k",), 1_000_000)
        b.ctx.plugins.register(SysTopicPlugin(b.ctx, {"publish_interval": 0.2}))
        await b.start()
        try:
            sub = await TestClient.connect(b.port, "sys-dev-sub")
            await sub.subscribe("$SYS/brokers/+/device/#", qos=0)
            got = {}
            for _ in range(10):
                try:
                    p = await sub.recv(timeout=2.0)
                except asyncio.TimeoutError:
                    break
                got[p.topic] = json.loads(p.payload)
                if len(got) >= 3:
                    break
            comp = got.get("$SYS/brokers/1/device/compile")
            assert comp is not None and comp["traces"] == 1
            assert "kernels" not in comp  # per-key detail stays on the API
            assert "$SYS/brokers/1/device/hbm" in got
            disp = got.get("$SYS/brokers/1/device/dispatch")
            assert disp is not None and "pad_floor" in disp
        finally:
            await b.stop()
            DEVPROF.reset()
            DEVPROF.configure(enabled=False)

    asyncio.run(asyncio.wait_for(run(), 30))


def test_cluster_data_query_serves_device():
    """The what=device DATA handler returns this node's snapshot for
    /api/v1/device/sum (both cluster modes share handle_common_message)."""
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.cluster import messages as M
    from rmqtt_tpu.cluster.broadcast import handle_common_message

    async def run():
        DEVPROF.reset()
        ctx = ServerContext(BrokerConfig())
        DEVPROF.note_jit("match_fused", ("x",), 500_000)
        try:
            reply = await handle_common_message(ctx, M.DATA,
                                                {"what": "device"})
            assert "device" in reply
            assert reply["device"]["compile"]["traces"] == 1
            merged = DeviceProfiler.merge_snapshots(
                DEVPROF.snapshot(), [reply["device"]])
            assert merged["nodes"] == 2
            assert merged["compile"]["traces"] == 2  # both "nodes" summed
        finally:
            DEVPROF.reset()
            DEVPROF.configure(enabled=False)

    asyncio.run(run())


# ----------------------------------------------------------------- config


def test_conf_device_knobs(tmp_path):
    from rmqtt_tpu import conf

    p = tmp_path / "dev.toml"
    p.write_text(
        "[observability]\ndevice_profile = false\ndevice_ring = 64\n"
        "recompile_storm_n = 5\nrecompile_storm_window = 3.5\n"
    )
    s = conf.load(str(p))
    assert s.broker.device_profile is False
    assert s.broker.device_ring == 64
    assert s.broker.device_storm_n == 5
    assert s.broker.device_storm_window == 3.5
    bad = tmp_path / "bad.toml"
    bad.write_text("[observability]\ndevice_rings = 1\n")
    with pytest.raises(ValueError, match="observability"):
        conf.load(str(bad))


# ------------------------------------------------------------------ report


def test_devprof_report_renders(prof, tmp_path):
    """scripts/devprof_report.py renders a dump into the operator tables
    (top shape keys, stage breakdown, timeline)."""
    import importlib.util
    import os
    import sys

    spec = importlib.util.spec_from_file_location(
        "devprof_report",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "devprof_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    prof.note_jit("match_global", ((8, 4),), 3_000_000)
    prof.note_dispatch(
        {"batch": 2, "padded": 8, "fused": True,
         "stage_ns": {"encode": 1000, "dispatch": 2000, "fetch": 3000,
                      "decode": 4000}},
        6_000_000)
    path = prof.dump_to(str(tmp_path / "d.json"), "unit-test")
    assert path is not None
    text = mod.render(json.loads((tmp_path / "d.json").read_text()))
    assert "top shape keys by trace" in text
    assert "match_global" in text
    assert "stage-time breakdown" in text
    assert "decode" in text
    assert "dispatch timeline" in text
    assert "flight ring tail" in text
    # CLI entry parses too
    sys_argv = sys.argv
    try:
        sys.argv = ["devprof_report.py", str(tmp_path / "d.json")]
        assert mod.main() == 0
    finally:
        sys.argv = sys_argv


def test_stats_class_shape():
    """New gauges exist on a bare Stats (tier-1 pins the surface shape for
    /stats, the dashboard KEYS and $SYS before any traffic)."""
    from rmqtt_tpu.broker.metrics import Stats

    j = Stats().to_json()
    for k in ("routing_stage_encode_ms_total", "routing_stage_dispatch_ms_total",
              "routing_stage_fetch_ms_total", "routing_stage_decode_ms_total",
              "routing_fused_batches", "device_jit_traces",
              "device_jit_cache_hits", "device_retrace_storms",
              "device_hbm_modeled_mb"):
        assert k in j, k
