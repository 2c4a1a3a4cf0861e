"""Host-plane flight recorder tests (broker/hostprof.py + surfaces).

Tiers:
- loop-lag semantics: laggy ticks, forced lag-storm detection (counted,
  slow-ring annotated, auto-dumped, the artifact renders);
- blocking-call detector LIVE: a deliberately wedged event loop produces
  a counted incident whose captured frame stack names the culprit, a
  slow-ring annotation and a finalized episode duration;
- GC forensics: gc.callbacks pauses per generation + the
  gc-during-dispatch correlation detail on the slow ring;
- trigger pins: a forced SLO BURNING transition and a forced overload
  CRITICAL escalation each freeze the host flight recorder (rate-limited
  auto_dump), the acceptance contract of the observability PR;
- disabled-mode pins: fire-never-entered, micro guard cost, shape-stable
  surfaces;
- live e2e: /api/v1/host (+ /host/sum), rmqtt_host_* exposition grammar,
  $SYS/brokers/<n>/host/#, the what=host cluster DATA query, stats()
  gauges, [observability] host knobs, and scripts/ops_doctor.py against
  the live API.
"""

import asyncio
import gc
import json
import time

import pytest

from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.hostprof import HOSTPROF, HostProfiler
from rmqtt_tpu.broker.telemetry import Telemetry


def _ops_doctor():
    """Load scripts/ops_doctor.py as a module (not on sys.path)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "ops_doctor",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "ops_doctor.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def prof():
    """Clean process-global profiler for the test, restored after."""
    prior = (HOSTPROF.enabled, HOSTPROF.telemetry, HOSTPROF.dump_dir,
             HOSTPROF.dispatch_probe, HOSTPROF.block_ms,
             HOSTPROF.lag_storm_n, HOSTPROF.lag_storm_window,
             HOSTPROF.tick_s, HOSTPROF.interval_s, HOSTPROF.gc_slow_ms)
    HOSTPROF.reset()
    HOSTPROF.configure(enabled=True, telemetry=None, dump_dir=None,
                       dispatch_probe=None, block_ms=150.0, lag_storm_n=8,
                       lag_storm_window=10.0, tick_s=0.05, interval_s=5.0,
                       gc_slow_ms=5.0)
    yield HOSTPROF
    HOSTPROF.reset()
    HOSTPROF.configure(enabled=prior[0], telemetry=prior[1],
                       dump_dir=prior[2], dispatch_probe=prior[3],
                       block_ms=prior[4], lag_storm_n=prior[5],
                       lag_storm_window=prior[6], tick_s=prior[7],
                       interval_s=prior[8], gc_slow_ms=prior[9])


# --------------------------------------------------------------- loop lag


def test_lag_accounting_and_forced_storm(prof, tmp_path):
    """Driven lag samples: sub-threshold ticks count but aren't laggy; a
    burst of ticks at/over block_ms inside the window is a LAG STORM —
    counted, slow-ring annotated, auto-dumped with the dump schema, and
    the artifact renders through ops_doctor's dump renderer."""
    tele = Telemetry(enabled=True, slow_ms=1e9)
    prof.configure(block_ms=100.0, lag_storm_n=4, lag_storm_window=60.0,
                   telemetry=tele, dump_dir=str(tmp_path))
    for _ in range(10):
        prof.note_lag(int(1e6))  # 1ms: healthy
    assert prof.ticks == 10 and prof.laggy_ticks == 0 and prof.lag_storms == 0
    for _ in range(4):
        prof.note_lag(int(120e6))  # 120ms: laggy
    assert prof.laggy_ticks == 4
    assert prof.lag_storms == 1
    snap = prof.snapshot()
    assert snap["loop"]["storms"] == 1
    assert snap["loop"]["last_storm"]["laggy_in_window"] >= 4
    assert snap["loop"]["max_lag_ms"] == 120.0
    assert any(op["op"] == "host.lag_storm" for op in tele.slow_ops)
    # auto-dump lands on disk (daemon thread: poll briefly)
    deadline = time.time() + 10
    dumps: list = []
    while not dumps and time.time() < deadline:
        dumps = list(tmp_path.glob("hostprof_lag_storm_*.json"))
        time.sleep(0.05)
    assert dumps, "lag storm must auto-dump a host artifact"
    dump = json.loads(dumps[0].read_text())
    assert dump["schema"] == "rmqtt_tpu.hostprof_dump/1"
    assert dump["snapshot"]["loop"]["storms"] == 1
    assert dump["slow_ops"], "the dump carries the correlated slow ring"
    text = _ops_doctor().render_host_dump(dump)
    assert "lag" in text and "storms" in text and "host timeline" in text


def test_lag_histogram_brackets_oracle(prof):
    """Lag quantiles ride the PR 2 log2 Histogram: p99 brackets the exact
    sorted oracle within one bucket (the property every mergeable
    histogram in the repo shares)."""
    import random

    rng = random.Random(11)
    samples = [int(10 ** rng.uniform(3, 8)) for _ in range(400)]
    for ns in samples:
        prof.note_lag(ns)
    s = sorted(samples)
    est = prof.lag_hist.quantile(0.99)
    exact = s[max(0, min(len(s) - 1, int(0.99 * len(s) + 0.999999) - 1))]
    assert exact < est <= 2 * exact + 2


# --------------------------------------------------------- blocking detector


def _blocking_victim_sleep(seconds: float) -> None:
    """The culprit the watchdog must name in its captured stack."""
    time.sleep(seconds)


def test_blocking_call_detector_live(prof, tmp_path):
    """A deliberately wedged loop: the watchdog thread captures the loop
    thread's frame stack MID-BLOCK into the incident ring, the episode
    finalizes with its real duration, annotates the slow ring and
    auto-dumps — 'who wedged the loop' answerable from the artifact."""
    tele = Telemetry(enabled=True, slow_ms=1e9)
    prof.configure(tick_s=0.01, block_ms=60.0, telemetry=tele,
                   dump_dir=str(tmp_path), interval_s=0.5)

    async def run():
        prof.start()
        try:
            await asyncio.sleep(0.2)  # healthy baseline ticks
            _blocking_victim_sleep(0.3)  # wedge the loop
            # resume; give the watchdog a few periods to finalize
            for _ in range(40):
                await asyncio.sleep(0.02)
                if prof.blocked_calls and not prof._in_block:
                    break
        finally:
            await prof.stop()

    asyncio.run(asyncio.wait_for(run(), 30))
    assert prof.blocked_calls == 1
    snap = prof.snapshot()
    inc = snap["block"]["incidents"][-1]
    assert inc["kind"] == "blocking_call" and inc["ongoing"] is False
    # finalized duration covers the real episode (0.3s sleep), not just
    # the watchdog's first observation
    assert 200.0 <= inc["blocked_ms"] <= 2000.0
    stack = "\n".join(inc["stack"])
    assert "_blocking_victim_sleep" in stack, "stack must name the culprit"
    assert snap["block"]["longest_block_ms"] == inc["blocked_ms"]
    rows = [op for op in tele.slow_ops if op["op"] == "host.blocked"]
    assert rows and rows[-1]["detail"]["blocked_ms"] == inc["blocked_ms"]
    deadline = time.time() + 10
    dumps: list = []
    while not dumps and time.time() < deadline:
        dumps = list(tmp_path.glob("hostprof_blocking_call_*.json"))
        time.sleep(0.05)
    assert dumps, "a blocking episode must auto-dump"
    text = _ops_doctor().render_host_dump(json.loads(dumps[0].read_text()))
    assert "_blocking_victim_sleep" in text  # the rendered postmortem


# ----------------------------------------------------------------- GC seam


def test_gc_pauses_counted_with_dispatch_correlation(prof):
    """gc.callbacks forensics: pauses count per generation with duration
    histograms, and a pause at/over gc_slow_ms lands on the slow ring
    carrying the in-dispatch correlation from the wired probe."""
    tele = Telemetry(enabled=True, slow_ms=1e9)
    prof.configure(telemetry=tele, gc_slow_ms=0.0001,
                   dispatch_probe=lambda: 3)

    async def run():
        prof.start()
        try:
            gc.collect(0)
            gc.collect(2)
        finally:
            await prof.stop()

    asyncio.run(asyncio.wait_for(run(), 30))
    snap = prof.snapshot()["gc"]
    assert snap["pauses"] >= 2
    assert snap["generations"]["2"]["pauses"] >= 1
    assert snap["generations"]["2"]["pause_ms_total"] >= 0
    rows = [op for op in tele.slow_ops if op["op"] == "host.gc_pause"]
    assert rows, "a slow pause must annotate the ring"
    assert rows[-1]["detail"]["in_dispatch"] == 3  # the wired probe
    assert rows[-1]["detail"]["generation"] in (0, 1, 2)
    # the callback uninstalled with the last stop (no leak across tests)
    assert prof._gc_cb not in gc.callbacks


@pytest.mark.timeout(20)
def test_gc_callback_never_blocks_on_the_profiler_lock(prof):
    """A collection can start inside one of the profiler's own locked
    sections, on the thread holding the lock (any allocation there): the
    gc hook must queue its sample instead of acquiring — a blocking acquire
    deadlocked a broker under a 1M-subscription load. The queued sample is
    folded by the next callback that finds the lock free."""
    with prof._lock:  # what auto_dump/snapshot hold while they allocate
        prof._gc_cb(0, 0.001, {"generation": 0, "collected": 3})
    assert prof.gc_pauses[0] == 0 and len(prof._gc_pending) == 1
    prof._gc_cb(0, 0.002, {"generation": 0, "collected": 4})
    assert prof.gc_pauses[0] == 2 and prof.gc_collected[0] == 7
    assert prof.gc_pause_ns[0] == 3_000_000
    assert not prof._gc_pending


# ------------------------------------------------------------ trigger pins


def test_slo_burning_transition_freezes_host_recorder(prof):
    """Acceptance pin: a forced SLO BURNING transition auto-dumps the
    host-plane flight recorder (reason slo_burning, rate-limited)."""
    from rmqtt_tpu.broker.slo import SloEngine, SloState

    cfg = BrokerConfig(
        slo_sample_interval=1.0, slo_fast_window_s=10.0,
        slo_slow_window_s=40.0, slo_burn_alert=2.0,
        slo_objectives=[{"name": "avail", "kind": "availability",
                         "target": 0.9}])
    ctx = ServerContext(cfg)
    # ServerContext wired its own telemetry/probe; keep the test's state
    prof.configure(telemetry=None, dump_dir=None)
    t = [0.0]
    eng = SloEngine(ctx, cfg, clock=lambda: t[0])
    for _ in range(10):
        ctx.metrics.inc("messages.delivered", 10)
        eng.tick()
        t[0] += 1.0
    assert eng._states[0] is SloState.OK and not prof.dumps_log
    ctx.metrics.inc("messages.delivered", 50)
    ctx.metrics.drop("queue_full", 50)
    eng.tick()
    assert eng._states[0] is SloState.BURNING
    deadline = time.time() + 10
    while not prof.dumps_log and time.time() < deadline:
        time.sleep(0.02)  # auto_dump offloads to a daemon thread
    assert prof.dumps_log and prof.dumps_log[-1]["reason"] == "slo_burning"
    assert prof.last_dump["schema"] == "rmqtt_tpu.hostprof_dump/1"


def test_overload_critical_escalation_freezes_host_recorder(prof):
    """Acceptance pin: an overload CRITICAL escalation auto-dumps the
    host recorder; an ELEVATED one does not."""
    from rmqtt_tpu.broker.overload import OverloadState

    ctx = ServerContext(BrokerConfig(overload_enable=True))
    prof.configure(telemetry=None, dump_dir=None)
    ctx.overload._transition(OverloadState.NORMAL, OverloadState.ELEVATED)
    time.sleep(0.1)
    assert not prof.dumps_log  # ELEVATED is not an incident
    ctx.overload._transition(OverloadState.ELEVATED, OverloadState.CRITICAL)
    deadline = time.time() + 10
    while not prof.dumps_log and time.time() < deadline:
        time.sleep(0.02)
    assert prof.dumps_log
    assert prof.dumps_log[-1]["reason"] == "overload_critical"


# ------------------------------------------------------ disabled-mode pins


def test_disabled_never_enters_profiler(prof, monkeypatch):
    """Off discipline: the ONLY hot-path state is the ``.enabled``
    attribute — no trigger seam may reach note_lag/auto_dump/start, and
    ServerContext.start must not arm a sampler, a watchdog or a gc
    callback (PR 6 fire-never-entered style)."""
    from rmqtt_tpu.broker.overload import OverloadState
    from rmqtt_tpu.broker.slo import SloState

    prof.configure(enabled=False)

    def boom(*a, **kw):
        raise AssertionError("host profiler entered while disabled")

    monkeypatch.setattr(HOSTPROF, "note_lag", boom)
    monkeypatch.setattr(HOSTPROF, "auto_dump", boom)
    monkeypatch.setattr(HOSTPROF, "_gc_cb", boom)

    async def run():
        ctx = ServerContext(BrokerConfig(host_profile=False,
                                         overload_enable=True))
        ctx.start()
        try:
            assert HOSTPROF._task is None, "sampler armed while disabled"
            assert not HOSTPROF._gc_installed
            gc.collect()
            # the trigger seams guard on .enabled before auto_dump
            ctx.overload._transition(OverloadState.NORMAL,
                                     OverloadState.CRITICAL)
            ctx.slo._transition(ctx.slo.objectives[0], 0, SloState.OK,
                                SloState.BURNING)
            await asyncio.sleep(0.1)
        finally:
            await ctx.stop()

    asyncio.run(asyncio.wait_for(run(), 30))


def test_disabled_guard_micro_cost_pin(prof):
    prof.configure(enabled=False)
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        if HOSTPROF.enabled:  # the exact guard the trigger seams use
            raise AssertionError
    per_iter = (time.perf_counter() - t0) / n
    assert per_iter < 2e-6, f"{per_iter * 1e9:.0f}ns per disabled check"


def test_disabled_snapshot_shape_stable(prof):
    """Every surface key exists (zeros) with the profiler off."""
    prof.configure(enabled=False)
    snap = prof.snapshot()
    assert snap["enabled"] is False
    assert snap["loop"]["ticks"] == 0 and snap["loop"]["storms"] == 0
    assert snap["gc"]["pauses"] == 0
    assert snap["block"]["blocked_calls"] == 0
    assert snap["block"]["incidents"] == []
    assert snap["rollups"] == []
    assert "fds" in snap["proc"] and "executor" in snap["proc"]
    lines = prof.prometheus_lines('node="1"')
    assert any(l.startswith("rmqtt_host_loop_ticks_total{") for l in lines)
    assert any("rmqtt_host_loop_lag_seconds_bucket" in l for l in lines)
    merged = HostProfiler.merge_snapshots(snap, [snap])
    assert merged["nodes"] == 2 and merged["loop"]["ticks"] == 0


def test_merge_snapshots_bucket_addition(prof):
    """/api/v1/host/sum semantics: lag histograms merge by bucket
    addition (exactly the latency /sum property), counters sum, max lag
    merges by max."""
    prof.note_lag(int(1e6))
    prof.note_lag(int(8e6))
    a = prof.snapshot()
    prof.reset()
    prof.configure(enabled=True)
    prof.note_lag(int(200e6))
    b = prof.snapshot()
    merged = HostProfiler.merge_snapshots(a, [b])
    assert merged["nodes"] == 2
    assert merged["loop"]["ticks"] == 3
    assert merged["loop"]["lag_hist"]["count"] == 3
    assert merged["loop"]["max_lag_ms"] == 200.0
    # bucket-exact: merged counts equal the element-wise sum
    import numpy as np

    assert (np.array(merged["loop"]["lag_hist"]["buckets"])
            == np.array(a["loop"]["lag_hist"]["buckets"])
            + np.array(b["loop"]["lag_hist"]["buckets"])).all()


# ------------------------------------------------------------ live surfaces


def test_host_endpoint_exposition_and_sum_live():
    """/api/v1/host + /host/sum + rmqtt_host_* exposition grammar + stats
    gauges + ops_doctor.collect/render against a live broker."""
    from tests.test_http_plugins import http_get
    from tests.test_telemetry import _EXPOSITION_COMMENT, _EXPOSITION_SAMPLE
    from rmqtt_tpu.broker.http_api import HttpApi
    from rmqtt_tpu.broker.server import MqttBroker

    async def run():
        HOSTPROF.reset()
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        assert HOSTPROF.enabled  # host_profile defaults on
        api = HttpApi(b.ctx, port=0)
        await b.start()
        await api.start()
        try:
            assert HOSTPROF._task is not None  # sampler armed
            await asyncio.sleep(0.3)  # a few ticks
            st, body = await http_get(api.bound_port, "/api/v1/host")
            assert st == 200
            snap = json.loads(body)
            assert snap["node"] == 1 and snap["enabled"] is True
            assert snap["loop"]["ticks"] >= 1
            assert snap["proc"]["fds"] > 0
            assert "lag_hist" in snap["loop"]
            st, body = await http_get(api.bound_port, "/api/v1/host/sum")
            merged = json.loads(body)
            assert merged["nodes"] == 1
            assert merged["loop"]["ticks"] == merged["loop"]["lag_hist"]["count"]
            st, body = await http_get(api.bound_port, "/metrics/prometheus")
            lines = body.decode().strip().split("\n")
            for line in lines:
                if line.startswith("#"):
                    assert _EXPOSITION_COMMENT.match(line), line
                else:
                    assert _EXPOSITION_SAMPLE.match(line), line
            text = "\n".join(lines)
            assert "rmqtt_host_loop_ticks_total" in text
            assert 'rmqtt_host_gc_pauses_total{node="1",generation="2"}' in text
            assert "rmqtt_host_loop_lag_seconds_bucket" in text
            assert "rmqtt_host_open_fds" in text
            st, body = await http_get(api.bound_port, "/api/v1/stats")
            stats = json.loads(body)[0]["stats"]
            for k in ("host_loop_lag_p99_ms", "host_loop_laggy_ticks",
                      "host_lag_storms", "host_blocked_calls",
                      "host_gc_pauses", "host_gc_pause_ms_total",
                      "host_open_fds", "host_threads"):
                assert k in stats, k
            assert stats["host_open_fds"] > 0
            # ops_doctor against the live API: every plane reachable
            doctor = _ops_doctor()
            loop = asyncio.get_running_loop()
            planes = await loop.run_in_executor(
                None, doctor.collect, f"http://127.0.0.1:{api.bound_port}")
            assert not any(isinstance(p, dict) and p.get("_error")
                           for p in planes.values()), planes
            text, findings = doctor.render(planes)
            assert "host" in text and "ops doctor" in text
        finally:
            await api.stop()
            await b.stop()
            assert HOSTPROF._task is None  # refcount released
            HOSTPROF.reset()
            HOSTPROF.configure(enabled=False)

    asyncio.run(asyncio.wait_for(run(), 60))


def test_sys_topic_host_tree():
    """$SYS/brokers/<n>/host/{loop,gc,incidents} while the profiler is
    enabled; incident rows ship WITHOUT their frame stacks (API-only)."""
    from tests.mqtt_client import TestClient
    from rmqtt_tpu.broker.server import MqttBroker
    from rmqtt_tpu.plugins.sys_topic import SysTopicPlugin

    async def run():
        HOSTPROF.reset()
        b = MqttBroker(ServerContext(BrokerConfig(port=0)))
        HOSTPROF.incidents.append({"kind": "blocking_call", "ts": 1.0,
                                   "blocked_ms": 9.9, "ongoing": False,
                                   "stack": ["File x, line 1"]})
        b.ctx.plugins.register(SysTopicPlugin(b.ctx, {"publish_interval": 0.2}))
        await b.start()
        try:
            sub = await TestClient.connect(b.port, "sys-host-sub")
            await sub.subscribe("$SYS/brokers/+/host/#", qos=0)
            got = {}
            for _ in range(10):
                try:
                    p = await sub.recv(timeout=2.0)
                except asyncio.TimeoutError:
                    break
                got[p.topic] = json.loads(p.payload)
                if len(got) >= 3:
                    break
            lp = got.get("$SYS/brokers/1/host/loop")
            assert lp is not None and "ticks" in lp
            assert "lag_hist" not in lp  # raw buckets stay on the API
            assert "$SYS/brokers/1/host/gc" in got
            inc = got.get("$SYS/brokers/1/host/incidents")
            assert inc is not None and inc["blocked_calls"] == 0
            assert inc["incidents"] and "stack" not in inc["incidents"][-1]
        finally:
            await b.stop()
            HOSTPROF.reset()
            HOSTPROF.configure(enabled=False)

    asyncio.run(asyncio.wait_for(run(), 30))


def test_cluster_data_query_serves_host():
    """The what=host DATA handler returns this node's snapshot for
    /api/v1/host/sum (both cluster modes share handle_common_message)."""
    from rmqtt_tpu.cluster import messages as M
    from rmqtt_tpu.cluster.broadcast import handle_common_message

    async def run():
        HOSTPROF.reset()
        ctx = ServerContext(BrokerConfig())
        HOSTPROF.note_lag(int(5e6))
        try:
            reply = await handle_common_message(ctx, M.DATA,
                                                {"what": "host"})
            assert "host" in reply
            assert reply["host"]["loop"]["ticks"] == 1
            merged = HostProfiler.merge_snapshots(
                HOSTPROF.snapshot(), [reply["host"]])
            assert merged["nodes"] == 2
            assert merged["loop"]["ticks"] == 2
        finally:
            HOSTPROF.reset()
            HOSTPROF.configure(enabled=False)

    asyncio.run(run())


# ----------------------------------------------------------------- config


def test_conf_host_knobs(tmp_path):
    from rmqtt_tpu import conf

    p = tmp_path / "host.toml"
    p.write_text(
        "[observability]\nhost_profile = false\nblock_ms = 80.0\n"
        "lag_storm_n = 5\nlag_storm_window = 3.5\n"
    )
    s = conf.load(str(p))
    assert s.broker.host_profile is False
    assert s.broker.host_block_ms == 80.0
    assert s.broker.host_lag_storm_n == 5
    assert s.broker.host_lag_storm_window == 3.5
    bad = tmp_path / "bad.toml"
    bad.write_text("[observability]\nhost_profiles = 1\n")
    with pytest.raises(ValueError, match="observability"):
        conf.load(str(bad))


# -------------------------------------------------------------- ops doctor


def test_ops_doctor_correlation_and_findings():
    """Pure render pass over synthetic planes: the cross-plane join lines
    up a p99 burst with a gen2 GC pause + lag storm inside the window and
    calls the device plane clean; findings rank CRIT first."""
    doctor = _ops_doctor()
    t0 = 1_700_000_000.0
    planes = {
        "stats": [{"node": 1, "stats": {}}],
        "latency": {
            "histograms": {
                "publish.e2e": {"count": 1000, "p50": 2e6, "p99": 412e6},
            },
            "slow_ops": [
                {"op": "publish.e2e", "ms": 412.0, "ts": t0 + 0.2,
                 "detail": "t/1"},
                {"op": "host.gc_pause", "ms": 48.0, "ts": t0 + 0.5,
                 "detail": {"generation": 2, "pause_ms": 48.0,
                            "collected": 120_000, "in_dispatch": 2}},
                {"op": "host.lag_storm", "ms": 0.0, "ts": t0 + 1.0,
                 "detail": {"laggy_in_window": 9, "window_s": 10.0}},
                {"op": "publish.e2e", "ms": 250.0, "ts": t0 + 400.0,
                 "detail": "t/2"},  # far away: its own episode
            ],
        },
        "slo": {"state": "BURNING", "objectives": [
            {"name": "publish-e2e-p99", "state": "BURNING", "state_value": 1,
             "fast": {"burn_rate": 6.0}, "slow": {"burn_rate": 0.4},
             "budget_remaining": 0.6}]},
        "device": {"compile": {"traces": 3, "storms": 0},
                   "dispatch": {"dispatches": 500, "p99_ms": 2.0,
                                "fused": 500, "pad_waste": 0.1},
                   "hbm": {"modeled_bytes": 1 << 20}},
        "host": {"loop": {"lag_p99_ms": 180.0, "max_lag_ms": 900.0,
                          "storms": 1, "laggy_ticks": 9},
                 "gc": {"pauses": 40, "pause_ms_total": 300.0,
                        "generations": {"2": {"pauses": 3, "p99_ms": 48.0}}},
                 "block": {"blocked_calls": 0, "longest_block_ms": 0.0,
                           "incidents": []},
                 "proc": {"fds": 64, "rss_mb": 120.0}},
        "overload": {"state": "NORMAL", "state_value": 0, "breakers": {}},
        "failover": {"state": "device", "state_value": 0},
        "fabric": {"enabled": False},
        "durability": {"enabled": False},
        "cluster": {"enabled": False},
    }
    text, findings = doctor.render(planes)
    assert findings, "burning slo + host pathology must produce findings"
    planes_with = {f["plane"] for f in findings}
    assert {"slo", "host", "latency"} <= planes_with
    # the correlation line: burst + gc pause + lag storm, device clean
    assert "coincides with" in text
    corr = [ln for ln in text.splitlines() if "coincides with" in ln]
    assert any("GC pause 48.0ms" in ln and "lag storm" in ln
               and "device plane clean" in ln for ln in corr), corr
    assert any("during 2 in-flight dispatches" in ln for ln in corr)
    # far-away slow op is NOT merged into the episode
    assert all("t/2" not in ln for ln in corr)
    # healthy planes render ok
    assert "[ok  ] device" in text
    # no findings on an all-healthy snapshot
    healthy = json.loads(json.dumps(planes))
    healthy["slo"] = {"state": "OK", "objectives": []}
    healthy["host"] = {"loop": {"storms": 0}, "gc": {}, "block": {},
                       "proc": {}}
    healthy["latency"]["histograms"]["publish.e2e"]["p99"] = 2e6
    _text2, findings2 = doctor.render(healthy)
    assert findings2 == []


def test_ops_doctor_enabled_plane_shapes():
    """The cluster/fabric/durability rules against the REAL enabled-mode
    snapshot shapes (membership.peers is a LIST, fabric counters nest,
    durability journal nests — the schemas the review pass found the
    first draft had guessed wrong)."""
    doctor = _ops_doctor()
    planes = {
        "stats": [{"node": 1, "stats": {}}],
        "latency": {"histograms": {}, "slow_ops": []},
        "slo": {"state": "OK", "objectives": []},
        "device": {}, "host": {}, "overload": {}, "failover": {},
        # the shapes the live APIs actually serve (cluster/membership.py
        # snapshot, broker/fabric.py snapshot, broker/durability.py
        # snapshot)
        "cluster": {"enabled": True, "membership": {
            "transitions": 3,
            "peers": [
                {"node": 2, "state": "ALIVE", "state_value": 0},
                {"node": 3, "state": "SUSPECT", "state_value": 1},
            ]}},
        "fabric": {"enabled": True, "role": "worker", "table_gen": 7,
                   "counters": {"batches": 10, "submit_fallbacks": 4}},
        "durability": {"enabled": True, "commits": 9, "recovery_ms": 5.0,
                       "journal": {"len": 123, "seq": 200}},
    }
    text, findings = doctor.render(planes)
    by_plane = {f["plane"]: f for f in findings}
    assert "cluster" in by_plane and "[3]" in by_plane["cluster"]["msg"]
    assert by_plane["cluster"]["severity"] == "CRIT"
    assert "fabric" in by_plane and "4 fabric submit" in by_plane["fabric"]["msg"]
    assert "journal 123 rows" in text
    assert "2 peers" in text and "3=SUSPECT" in text
    assert "fallbacks 4" in text
