"""Surface-parity + full-scrape exposition pins.

The house pattern says every `stats()` gauge rides four surfaces: the
JSON APIs (generic — `Stats.to_json()` feeds them all), the Prometheus
exposition (generic gauge loop), the dashboard (KEYS grid or a dedicated
card) and the README surface docs. Until now that parity was hand-
maintained per PR (devprof/fabric/durability each re-did it); these
tests turn the convention into CI:

- ``test_stats_gauges_cover_every_surface`` — every Stats key must be in
  the dashboard KEYS grid (or the documented card-rendered exemption
  set), every KEYS entry must be a real gauge (no dead keys), and every
  gauge must be named in README verbatim or covered by a documented
  ``family_*`` wildcard.
- ``test_full_scrape_grammar_all_planes`` — ONE live scrape with every
  plane enabled at once (telemetry, tracing, slo, devprof, hostprof,
  overload, durability, failpoints armed) validated promtool-style:
  line grammar, TYPE-before-samples, NO duplicate TYPE (the bug class
  PR 7 caught by hand), counter families end in ``_total``, histogram
  sample suffixes are declared by their family.
"""

import asyncio
import json
import re

from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.metrics import Stats

# gauges intentionally NOT in the dashboard KEYS grid because a dedicated
# card/section renders them (LAT_STAGES latency cards, the overload/SLO/
# host-plane card rows, enable-flag cards); adding a gauge here requires
# actually rendering it somewhere else on the dashboard
DASH_CARD_RENDERED = {
    # latency cards (LAT_STAGES, fed by /api/v1/latency)
    "routing_match_p50_ms", "routing_match_p99_ms",
    "routing_queue_wait_p50_ms", "routing_queue_wait_p99_ms",
    "publish_e2e_p50_ms", "publish_e2e_p99_ms",
    # overload cards (state/transitions/breakers from /api/v1/overload)
    "overload_state", "overload_transitions", "overload_open_breakers",
    # host-plane card (loop lag p99 from /api/v1/host)
    "host_loop_lag_p99_ms",
    # autotune cards (state/decisions/commits/rollbacks + last decision
    # from /api/v1/autotune)
    "autotune_decisions", "autotune_commits", "autotune_rollbacks",
    # enable flags rendered as card presence, not numbers
    "fabric_enabled", "fabric_owner", "durability_enabled",
}


def _dashboard_keys():
    from rmqtt_tpu.broker.http_api import _DASHBOARD_HTML

    html = _DASHBOARD_HTML.decode()
    m = re.search(r"const KEYS=\[(.*?)\];", html, re.S)
    assert m, "dashboard KEYS grid not found"
    return set(re.findall(r'"([a-z0-9_]+)"', m.group(1)))


def test_stats_gauges_cover_every_surface():
    import os

    keys = set(Stats().to_json())
    dash = _dashboard_keys()

    dead = dash - keys
    assert not dead, f"dashboard KEYS with no Stats gauge behind them: " \
                     f"{sorted(dead)}"
    overlap = dash & DASH_CARD_RENDERED
    assert not overlap, f"both in KEYS and exempted-as-card-rendered: " \
                        f"{sorted(overlap)}"
    unrendered = keys - dash - DASH_CARD_RENDERED
    assert not unrendered, (
        f"stats gauges on no dashboard surface (add to KEYS or render a "
        f"card + exempt): {sorted(unrendered)}")

    readme = open(os.path.join(os.path.dirname(__file__), "..",
                               "README.md")).read()
    # README covers a gauge verbatim or via a documented `family_*`
    # wildcard (the "Observability index" section's gauge-family list)
    prefixes = {p[:-1] for p in re.findall(r"`([a-z0-9_]+_)\*`", readme)}
    verbatim = set(re.findall(r"`([a-z0-9_]+)`", readme))
    undocumented = [
        k for k in keys
        if k not in verbatim and not any(k.startswith(p) for p in prefixes)
    ]
    assert not undocumented, (
        f"stats gauges not documented in README (name them or extend a "
        f"family wildcard): {sorted(undocumented)}")


def test_stats_gauges_all_exported_on_prometheus():
    """The generic Stats-gauge exposition loop: every gauge appears as
    rmqtt_<key> on a scrape (pinned so a future hand-rolled exporter
    can't silently drop the generic loop)."""
    from rmqtt_tpu.broker.http_api import HttpApi

    api = HttpApi(ServerContext(BrokerConfig()), port=0)
    text = api._prometheus()
    for k in Stats().to_json():
        assert f"rmqtt_{k}{{" in text, f"gauge {k} missing from exposition"


# ------------------------------------------------------- full-scrape pins

_COMMENT = re.compile(
    r"^# (TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
    r"(counter|gauge|histogram|summary|untyped)|HELP .*)$")
_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"\n]*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"\n]*\")*\})? "
    r"-?[0-9.eE+-]+(\s+[0-9]+)?$")


def _validate_scrape(text: str) -> None:
    """Promtool-style pass over one exposition body."""
    typed: dict = {}
    for line in text.strip().split("\n"):
        if line.startswith("#"):
            m = _COMMENT.match(line)
            assert m, f"bad comment line: {line!r}"
            if line.startswith("# TYPE "):
                _, _, name, typ = line.split(" ", 3)
                # the PR 7 bug class: two TYPE lines for one metric name
                # make the whole exposition invalid
                assert name not in typed, f"duplicate TYPE for {name}"
                typed[name] = typ
            continue
        assert _SAMPLE.match(line), f"bad sample line: {line!r}"
        name = re.split(r"[{ ]", line, 1)[0]
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in typed:
                base = name[: -len(suffix)]
                break
        assert base in typed, f"sample {name} has no TYPE declaration"
        typ = typed[base]
        if typ == "histogram":
            # histogram samples must be the declared family's
            # _bucket/_sum/_count series, never the bare name
            assert name != base, f"bare sample for histogram {base}"
        if typ == "counter":
            # exposition convention: counter sample names end in _total
            assert name.endswith("_total"), \
                f"counter {name} missing _total suffix"
    assert typed, "empty scrape"


def test_bench_trend_parses_all_artifact_generations(tmp_path):
    """scripts/bench_trend.py: the three BENCH_r*.json generations all
    parse (parsed dict, tail JSON line, head-truncated tail with an
    embedded last_tpu_run to be excluded), the trend pairs rounds per
    config, and the >10% goodput regression gate fires."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench_trend",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "bench_trend.py"))
    bt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bt)

    def cfg(tps, p99):
        return {"tpu_topics_per_sec": tps, "tpu_backend": "partitioned",
                "speedup": 1.0, "p99_ms": p99}

    # gen 1: parsed dict
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({
        "n": 1, "rc": 0, "tail": "",
        "parsed": {"metric": "m", "value": 1,
                   "configs": {"cfg1_exact_1k": cfg(1000.0, 5.0)}}}))
    # gen 2: parsed null, whole JSON line in the tail
    body = json.dumps({"metric": "m", "value": 2,
                       "configs": {"cfg1_exact_1k": cfg(2000.0, 4.0)}})
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({
        "n": 2, "rc": 0, "parsed": None, "tail": "noise\n" + body + "\n"}))
    # gen 3: truncated tail — config objects survive, the embedded
    # last_tpu_run's configs must NOT be picked up
    frag = ('_sec": 1, "configs": {"cfg1_exact_1k": '
            + json.dumps(cfg(1500.0, 6.0))
            + '}, "last_tpu_run": {"configs": {"cfg1_exact_1k": '
            + json.dumps(cfg(9_999_999.0, 1.0)) + "}}}")
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({
        "n": 3, "rc": 0, "parsed": None, "tail": frag}))

    rounds = bt.load_rounds(str(tmp_path / "BENCH_r*.json"))
    assert [r["round"] for r in rounds] == [1, 2, 3]
    assert rounds[2]["configs"]["cfg1_exact_1k"]["goodput"] == 1500.0
    rows, regressions = bt.trend(rounds, tolerance_pct=10.0)
    deltas = {(r["round"]): r["delta_pct"] for r in rows}
    assert deltas[2] == 100.0  # 1000 → 2000
    assert deltas[3] == -25.0  # 2000 → 1500: past the gate
    assert len(regressions) == 1 and regressions[0]["round"] == 3
    # within tolerance → gate silent
    _rows, none = bt.trend(rounds, tolerance_pct=30.0)
    assert none == []
    text = bt.render(rows, regressions, 10.0)
    assert "REGRESSIONS" in text and "cfg1_exact_1k" in text


def test_full_scrape_grammar_all_planes(tmp_path):
    """One live scrape with EVERY exporting plane enabled and active at
    once — telemetry (with samples), tracing, slo, devprof (synthetic
    activity), hostprof (live sampler), overload (enabled), durability
    (enabled, journaling), failpoints (armed) — validated against the
    exposition grammar. PR 7 caught a duplicate-TYPE bug on this surface
    by hand; this pins the whole scrape."""
    from tests.mqtt_client import TestClient
    from tests.test_http_plugins import http_get
    from rmqtt_tpu.broker.devprof import DEVPROF
    from rmqtt_tpu.broker.hostprof import HOSTPROF
    from rmqtt_tpu.broker.http_api import HttpApi
    from rmqtt_tpu.broker.server import MqttBroker
    from rmqtt_tpu.utils.failpoints import FAILPOINTS

    async def run():
        DEVPROF.reset()
        HOSTPROF.reset()
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0,
            telemetry_enable=True, telemetry_slow_ms=0.0,
            overload_enable=True,
            durability_enable=True,
            durability_path=str(tmp_path / "dur.db"),
            slo_enable=True,
            device_profile=True, host_profile=True,
        )))
        # synthetic device + failpoint activity so those families carry
        # nonzero samples on the wire
        DEVPROF.note_jit("match_global", ((4, 2), "k"), 1_000_000)
        DEVPROF.note_dispatch({"batch": 2, "padded": 4, "fused": True},
                              2_000_000)
        FAILPOINTS.configure({"device.dispatch": "off"})
        api = HttpApi(b.ctx, port=0)
        await b.start()
        await api.start()
        try:
            # real traffic: QoS1 pub/sub so telemetry, tracing, slo and
            # durability all record
            sub = await TestClient.connect(b.port, "scrape-sub",
                                           clean_start=False)
            await sub.subscribe("sc/#", qos=1)
            publ = await TestClient.connect(b.port, "scrape-pub")
            for i in range(5):
                await publ.publish(f"sc/{i}", b"x", qos=1)
                p = await sub.recv(timeout=10.0)
                assert p.topic.startswith("sc/")
            b.ctx.slo.tick()
            await asyncio.sleep(0.2)  # hostprof sampler ticks
            st, body = await http_get(api.bound_port, "/metrics/prometheus")
            assert st == 200
            text = body.decode()
            _validate_scrape(text)
            # the families from every plane are actually present
            for family in (
                "rmqtt_connections", "rmqtt_publish_received_total",
                "rmqtt_messages_delivered_total",
                "rmqtt_latency_publish_e2e_seconds_bucket",
                "rmqtt_tracing_", "rmqtt_slo_objective_state",
                "rmqtt_slo_events_total", "rmqtt_device_jit_traces_total",
                "rmqtt_host_loop_ticks_total",
                "rmqtt_host_loop_lag_seconds_bucket",
                "rmqtt_host_gc_pauses_total",
                "rmqtt_overload_state", "rmqtt_durability_appends",
                "rmqtt_failpoint_triggers_total",
                "rmqtt_hotkeys_topk", "rmqtt_hotkeys_top1_share",
                "rmqtt_hotkeys_alerts_total",
                "rmqtt_hotkeys_rotations_total",
                "rmqtt_uptime_seconds", "rmqtt_build_info",
            ):
                assert family in text, f"family {family} missing"
        finally:
            await api.stop()
            await b.stop()
            FAILPOINTS.configure({"device.dispatch": "off"})
            DEVPROF.reset()
            DEVPROF.configure(enabled=False)
            HOSTPROF.reset()
            HOSTPROF.configure(enabled=False)

    asyncio.run(asyncio.wait_for(run(), 60))


def test_roofline_peaks_are_keyed_by_device_kind():
    """The HBM model takes its peak from a table keyed by jax device_kind;
    a part that is not in the table is an error, never the v5e's number."""
    import pytest

    from rmqtt_tpu.bench.roofline_model import model_table, peak_hbm_gbps
    from rmqtt_tpu.ops.partitioned import PartitionedTable

    assert peak_hbm_gbps("TPU v5 lite") == 819.0
    with pytest.raises(ValueError, match="DEVICE_PEAKS"):
        peak_hbm_gbps("cpu")
    t = PartitionedTable()
    for f in ("a/+/c", "a/b/#", "x/y"):
        t.add(f)
    m = model_table(t, [1, 2, 3], "TPU v5 lite")
    assert m["device_kind"] == "TPU v5 lite" and m["hbm_gbps"] == 819.0
    with pytest.raises(ValueError):
        model_table(t, [1], "TPU v99")


# ------------------------------------------------- environment switches
#: every literal ``RMQTT_*`` variable the package reads. A new one has to be
#: argued for here (and named in README); ``RMQTT_<SECTION>__<KEY>`` is
#: conf.py's generic TOML override, built from a prefix, and is not one
ENV_SWITCHES = {
    "RMQTT_DELTA_UPLOADS", "RMQTT_DEVICE_PROFILE", "RMQTT_DEVPROF_DIR",
    "RMQTT_EGRESS_COALESCE", "RMQTT_FAILPOINTS", "RMQTT_FETCH_TIMEOUT",
    "RMQTT_FUSED", "RMQTT_HOSTPROF_DIR", "RMQTT_HOST_PROFILE",
    "RMQTT_HYBRID_ADAPT", "RMQTT_HYBRID_MAX", "RMQTT_KEEPALIVE_WHEEL",
    "RMQTT_PACKED", "RMQTT_PAD_FLOOR", "RMQTT_PROBE_EVERY", "RMQTT_SEG_BYTES",
}


def test_environment_switches_are_the_pinned_list_and_documented():
    import pathlib

    root = pathlib.Path(__file__).parent.parent
    read = set()
    for path in (root / "rmqtt_tpu").rglob("*.py"):
        read |= set(re.findall(
            r'(?:environ\.get\(|environ\[|getenv\()\s*"(RMQTT_[A-Z_]+)"',
            path.read_text()))
    assert read == ENV_SWITCHES, (
        f"read but not pinned: {sorted(read - ENV_SWITCHES)}; "
        f"pinned but not read: {sorted(ENV_SWITCHES - read)}")
    readme = (root / "README.md").read_text()
    missing = [v for v in sorted(ENV_SWITCHES) if f"`{v}" not in readme]
    assert not missing, f"switches README does not name: {missing}"
