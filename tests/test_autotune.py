"""Device-plane autotuner: policy oracle, canary discipline, pins.

Four tiers:

- **policy oracle** — synthetic signals drive ``tick(sig=...)`` against a
  registry over fake matchers: the pad-floor ladder converges on a
  pad-waste signal and STOPS, a failed canary rolls back (value AND
  provenance) and quarantines the knob, a boundary signal oscillating
  around the trigger never applies anything (hysteresis), and a retrace
  storm aborts exploration (idle → hold, mid-canary → rollback).
- **disabled pins** — [routing] autotune=false is zero behavior change:
  no task, ``tick()`` never reads a signal, no registry row ever says
  'autotune', surfaces shape-stable.
- **live e2e** — an in-proc xla broker with autotune on adapts the pad
  floor under real batch-1 traffic; the decision (with before/after
  metrics) is visible on ``/api/v1/autotune``, the slow-op ring and the
  stats gauges.
- **conf + catalog** — ``[routing] autotune*`` round-trips, unknown keys
  fail at load, and the README knob table matches ``KNOB_CATALOG`` and
  the live registry (the catalog-diff that keeps the docs honest).
"""

import asyncio
import json
import os
import re

import pytest

from rmqtt_tpu.broker.autotune import AutotuneService
from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.knobs import KNOB_CATALOG, build_registry


class _FakeMatcher:
    """The knob surface of PartitionedMatcher, no jax anywhere."""

    def __init__(self):
        self._pad_floor = 8
        self._fused = None
        self._packed_pref = True
        self.delta_enabled = True

    def set_pad_floor(self, floor):
        old = self._pad_floor
        self._pad_floor = max(1, int(floor))
        return old


class _Prof:
    """Zeroed profiler counter surface (baseline priming)."""

    traces = 0
    storms = 0
    dispatches = 0
    upload_counts = {}
    upload_bytes = {}


def _registry():
    shim = type("_Shim", (), {})()
    shim.matcher = _FakeMatcher()
    return build_registry(shim, None, environ={}), shim.matcher


def _service(reg, **kw):
    kw.setdefault("enabled", True)
    kw.setdefault("canary_k", 4)
    kw.setdefault("cooldown_s", 30.0)
    kw.setdefault("confirm_ticks", 2)
    kw.setdefault("devprof", _Prof())
    svc = AutotuneService(reg, **kw)
    svc.warmup_ticks = 0  # the oracle tests drive steady-state signals
    return svc


def _sig(total, **kw):
    base = dict(
        dispatches_total=total, traces_total=0, storms_total=0,
        dispatches=20, pad_waste=0.0, traces=0, p99_ms=1.0,
        batch_p50=2, batch_p99=2, delta_avg_bytes=0.0,
        full_avg_bytes=0.0, batch_ema=0.0, queue_frac=0.0)
    base.update(kw)
    return base


# ------------------------------------------------------------ policy oracle

def test_hill_climb_converges_on_pad_waste():
    """A sustained small-batch/pad-waste signal walks the floor ladder
    8→4→2→1 (one canaried step at a time) and then STOPS — converged
    means no further decisions, not perpetual exploration."""
    reg, m = _registry()
    svc = _service(reg)
    total = 0
    for _ in range(20):
        total += 20
        svc.tick(sig=_sig(total, pad_waste=0.875, batch_p99=2))
    assert m._pad_floor == 1
    assert svc.commits == 3 and svc.rollbacks == 0
    phases = [(e["phase"], e["from"], e["to"]) for e in svc.journal]
    assert ("commit", 8, 4) in phases and ("commit", 4, 2) in phases \
        and ("commit", 2, 1) in phases
    assert reg.source("pad_floor") == "autotune"
    # converged: further identical signals change nothing
    before = svc.decisions
    for _ in range(6):
        total += 20
        svc.tick(sig=_sig(total, pad_waste=0.875, batch_p99=2))
    assert svc.decisions == before


def test_floor_raises_on_retrace_churn():
    """Fresh small-shape compiles (traces) with no storm walk the floor
    UP so the shapes collapse onto one executable."""
    reg, m = _registry()
    m._pad_floor = 2
    svc = _service(reg)
    total = 0
    for _ in range(4):
        total += 20
        svc.tick(sig=_sig(total, traces=4, batch_p99=8))
    assert m._pad_floor == 4
    assert svc.commits == 1


def test_canary_failure_rolls_back_and_cools_down():
    reg, m = _registry()
    svc = _service(reg)
    # two confirm ticks start the canary (floor 8 -> 4)
    svc.tick(sig=_sig(20, pad_waste=0.875, batch_p99=2))
    svc.tick(sig=_sig(40, pad_waste=0.875, batch_p99=2))
    assert m._pad_floor == 4 and svc._canary is not None
    # canary window: enough dispatches, but p99 blew past the guard
    svc.tick(sig=_sig(60, pad_waste=0.875, batch_p99=2, p99_ms=50.0))
    assert m._pad_floor == 8  # rolled back
    assert svc.rollbacks == 1 and svc.commits == 0
    assert reg.source("pad_floor") == "default"  # provenance restored too
    last = list(svc.journal)[-1]
    assert last["phase"] == "rollback" and last["reason"] == "p99_regression"
    assert last["before"]["p99_ms"] == 1.0 and last["after"]["p99_ms"] == 50.0
    # quarantined: the same trigger signal cannot restart a canary
    total = 80
    for _ in range(5):
        total += 20
        svc.tick(sig=_sig(total, pad_waste=0.875, batch_p99=2))
    assert svc.decisions == 1 and m._pad_floor == 8
    # cooldown elapsed -> exploration resumes
    svc._cooldown_until["pad_floor"] = 0.0
    for _ in range(3):
        total += 20
        svc.tick(sig=_sig(total, pad_waste=0.875, batch_p99=2))
    assert svc.decisions == 2


def test_hysteresis_never_flaps_on_boundary_signal():
    """A signal oscillating around the trigger threshold proposes on
    alternate ticks and therefore NEVER survives the consecutive-tick
    confirmation — zero knob writes."""
    reg, m = _registry()
    svc = _service(reg)
    total = 0
    for i in range(24):
        total += 20
        waste = 0.6 if i % 2 == 0 else 0.3  # straddles the 0.5 band
        svc.tick(sig=_sig(total, pad_waste=waste, batch_p99=2))
    assert svc.decisions == 0 and m._pad_floor == 8
    assert reg.source("pad_floor") == "default"


def test_retrace_storm_holds_exploration_and_fails_canaries():
    reg, m = _registry()
    svc = _service(reg)
    # idle storm -> hold: the trigger signal is present but ignored
    svc.tick(sig=_sig(20, pad_waste=0.875, batch_p99=2, storms_total=1))
    assert svc.holds == 1 and svc.state_value() == svc.HOLD
    total = 40
    for _ in range(4):
        total += 20
        svc.tick(sig=_sig(total, pad_waste=0.875, batch_p99=2,
                          storms_total=1))
    assert svc.decisions == 0 and m._pad_floor == 8
    # hold expired -> canary starts; a storm DURING it rolls back
    svc._hold_until = 0.0
    svc.tick(sig=_sig(total + 20, pad_waste=0.875, batch_p99=2,
                      storms_total=1))
    svc.tick(sig=_sig(total + 40, pad_waste=0.875, batch_p99=2,
                      storms_total=1))
    assert svc._canary is not None and m._pad_floor == 4
    svc.tick(sig=_sig(total + 60, pad_waste=0.875, batch_p99=2,
                      storms_total=2))
    assert m._pad_floor == 8 and svc.rollbacks == 1
    assert list(svc.journal)[-1]["reason"] == "retrace_storm"


def test_dispatch_starved_canary_aborts_and_reverts():
    reg, m = _registry()
    svc = _service(reg)
    svc.canary_max_ticks = 3
    svc.tick(sig=_sig(20, pad_waste=0.875, batch_p99=2))
    svc.tick(sig=_sig(40, pad_waste=0.875, batch_p99=2))
    assert svc._canary is not None
    for i in range(3):  # traffic stopped: no dispatch progress
        svc.tick(sig=_sig(40, dispatches=0))
    assert svc._canary is None and svc.aborts == 1
    assert m._pad_floor == 8  # unverified settings never stick


def test_warmup_grace_ignores_boot_signals():
    """The first warmup_ticks observe only: prewarm/startup compile
    bursts must not start the ladder before the floor has latched."""
    reg, m = _registry()
    svc = _service(reg)
    svc.warmup_ticks = 2
    svc.tick(sig=_sig(20, pad_waste=0.875, batch_p99=2, traces=6))
    svc.tick(sig=_sig(40, pad_waste=0.875, batch_p99=2, traces=6))
    assert svc.decisions == 0 and m._pad_floor == 8
    # grace over: the persisting signal confirms and canaries normally
    svc.tick(sig=_sig(60, pad_waste=0.875, batch_p99=2))
    svc.tick(sig=_sig(80, pad_waste=0.875, batch_p99=2))
    assert svc.decisions == 1 and m._pad_floor == 4


def test_delta_gate_closes_when_scatter_outships_repack():
    reg, m = _registry()
    svc = _service(reg)
    total = 0
    for _ in range(4):
        total += 20
        svc.tick(sig=_sig(total, delta_avg_bytes=9e6, full_avg_bytes=1e6))
    assert m.delta_enabled is False
    assert svc.commits == 1
    assert reg.source("delta_uploads") == "autotune"


# ----------------------------------------------------------- disabled pins

def test_disabled_is_zero_behavior_change():
    ctx = ServerContext(BrokerConfig())  # autotune_enable defaults False
    at = ctx.autotune
    assert at.enabled is False and at._task is None
    # fire-never-entered: a disabled tick must not even read a signal
    at._signals = None  # would raise if entered
    at.tick()
    assert at.decisions == 0 and list(at.journal) == []
    snap = at.snapshot()
    for key in ("enabled", "state", "decisions", "commits", "rollbacks",
                "journal", "knobs", "canary", "cooldowns"):
        assert key in snap
    assert snap["enabled"] is False and snap["state"] == "idle"
    # no registry row carries an autotune fingerprint
    assert all(r["source"] != "autotune" for r in ctx.knobs.snapshot())
    stats = ctx.stats().to_json()
    assert stats["autotune_decisions"] == 0
    assert stats["autotune_commits"] == 0


def test_disabled_start_owns_no_task():
    async def run():
        ctx = ServerContext(BrokerConfig())
        ctx.start()
        try:
            assert ctx.autotune._task is None
        finally:
            await ctx.stop()

    asyncio.run(asyncio.wait_for(run(), 30))


# ----------------------------------------------------------------- live e2e

def test_live_adaptation_reaches_every_surface(tmp_path):
    """In-proc xla broker, autotune on, real batch-1 publishes: the pad
    floor ladder fires for real (canary + commit), and the decision is
    visible on /api/v1/autotune (before/after values), the slow-op ring,
    the knob registry and the stats gauges."""
    from tests.test_http_plugins import http_get
    from rmqtt_tpu.broker.devprof import DEVPROF
    from rmqtt_tpu.broker.http_api import HttpApi
    from rmqtt_tpu.broker.server import MqttBroker
    from rmqtt_tpu.router.base import Id, SubscriptionOptions

    async def run():
        DEVPROF.reset()
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, router="xla", route_cache=False,
            autotune_enable=True, autotune_interval_s=60.0,  # manual ticks
            autotune_canary_k=3, autotune_cooldown_s=0.2,
            autotune_confirm_ticks=2,
            device_profile=True, device_storm_n=100,
        )))
        ctx = b.ctx
        r = ctx.router
        r.set_hybrid_max(0)  # pin every batch to the device plane
        r._hybrid.probe_every = 0
        r.add("sens/+/temp", Id(1, "c1"), SubscriptionOptions(qos=0))
        DEVPROF.configure(interval_s=0.2)
        api = HttpApi(ctx, port=0)
        await b.start()
        await api.start()
        try:
            # wait for prewarm to latch the sticky floor (background thread)
            deadline = asyncio.get_running_loop().time() + 30
            while r.matcher._pad_floor < 8:
                assert asyncio.get_running_loop().time() < deadline, \
                    "prewarm never latched the pad floor"
                await asyncio.sleep(0.05)
            committed = False
            for i in range(400):
                await ctx.routing.matches(None, f"sens/{i % 3}/temp")
                if i % 5 == 4:
                    ctx.autotune.tick()
                if ctx.autotune.commits >= 1:
                    committed = True
                    break
            assert committed, "no adaptation committed under live traffic"
            assert r.matcher._pad_floor < 8
            assert ctx.knobs.source("pad_floor") == "autotune"
            st, body = await http_get(api.bound_port, "/api/v1/autotune")
            assert st == 200
            doc = json.loads(body)
            assert doc["enabled"] is True and doc["commits"] >= 1
            commit = next(e for e in doc["journal"]
                          if e["phase"] == "commit")
            assert commit["knob"] == "pad_floor"
            assert commit["from"] == 8 and commit["to"] == 4
            assert "p99_ms" in commit["before"] and "p99_ms" in commit["after"]
            knob_rows = {k["name"]: k for k in doc["knobs"]}
            assert knob_rows["pad_floor"]["source"] == "autotune"
            st, body = await http_get(api.bound_port,
                                      "/api/v1/routing/knobs")
            assert st == 200
            assert {k["name"] for k in json.loads(body)["knobs"]} \
                == set(ctx.knobs.names())
            # stats gauges + slow-op ring carry the same story
            assert ctx.stats().to_json()["autotune_commits"] >= 1
            assert any(e["op"].startswith("autotune.")
                       for e in ctx.telemetry.slow_ops)
        finally:
            await api.stop()
            await b.stop()
            DEVPROF.reset()
            DEVPROF.configure(enabled=False, interval_s=5.0)

    asyncio.run(asyncio.wait_for(run(), 120))


# ------------------------------------------------------------ conf + catalog

def test_conf_round_trip(tmp_path):
    from rmqtt_tpu import conf

    p = tmp_path / "rmqtt.toml"
    p.write_text(
        "[routing]\n"
        "autotune = true\n"
        "autotune_interval_s = 1.5\n"
        "autotune_canary_k = 4\n"
        "autotune_cooldown_s = 9.0\n"
        "autotune_p99_guard = 3.0\n"
        "autotune_confirm_ticks = 3\n"
        "autotune_journal_max = 64\n"
    )
    cfg = conf.load(str(p), environ={}).broker
    assert cfg.autotune_enable is True
    assert cfg.autotune_interval_s == 1.5
    assert cfg.autotune_canary_k == 4
    assert cfg.autotune_cooldown_s == 9.0
    assert cfg.autotune_p99_guard == 3.0
    assert cfg.autotune_confirm_ticks == 3
    assert cfg.autotune_journal_max == 64
    ctx = ServerContext(cfg)
    assert ctx.autotune.enabled and ctx.autotune.canary_k == 4
    p.write_text("[routing]\nautotune_bogus = 1\n")
    with pytest.raises(ValueError, match="autotune_bogus"):
        conf.load(str(p), environ={})


def test_knob_catalog_matches_readme_and_registry():
    """The catalog-diff that keeps the README knob table honest: the
    documented table, KNOB_CATALOG and a live xla registry must all name
    the same knobs (the registry in catalog order)."""
    readme = open(os.path.join(os.path.dirname(__file__), "..",
                               "README.md")).read()
    section = readme.split("### Self-tuning device plane", 1)[1] \
                    .split("\n### ", 1)[0]
    documented = re.findall(r"^\| `([a-z0-9_]+)` \|", section, re.M)
    assert documented, "README knob table not found"
    assert tuple(documented) == KNOB_CATALOG, (
        "README 'Self-tuning device plane' knob table out of sync with "
        "knobs.KNOB_CATALOG")
    ctx = ServerContext(BrokerConfig(router="xla"))
    assert tuple(ctx.knobs.names()) == KNOB_CATALOG, (
        "xla registry binds a different knob set than the catalog")


def test_knob_registry_sources_and_write_seams(monkeypatch):
    monkeypatch.setenv("RMQTT_FUSED", "0")
    monkeypatch.setenv("RMQTT_PAD_FLOOR", "16")
    ctx = ServerContext(BrokerConfig(router="xla", batch_max=2048))
    rows = {r["name"]: r for r in ctx.knobs.snapshot()}
    assert rows["fused"]["source"] == "env" and rows["fused"]["value"] is False
    assert rows["pad_floor"]["value"] == 16
    assert rows["pad_floor"]["source"] == "env"
    assert rows["max_batch"]["source"] == "conf"
    assert rows["max_batch"]["value"] == 2048
    assert rows["linger_ms"]["source"] == "default"
    # writes go through the live seams
    old = ctx.knobs.set("max_batch", 512)
    assert old == 2048 and ctx.routing.max_batch == 512
    assert ctx.knobs.source("max_batch") == "autotune"
    ctx.knobs.set("hybrid_max", 8)
    assert ctx.router._hybrid_max == 8 and ctx.router._hybrid.small_max == 8
    ctx.knobs.restore("max_batch", 2048, "conf")
    assert ctx.routing.max_batch == 2048
    assert ctx.knobs.source("max_batch") == "conf"
    # an explicit RMQTT_PAD_FLOOR seed survives prewarm's default latch
    # (the autotune-replay seeding workflow for live brokers)
    ctx.router.prewarm()
    assert ctx.router.matcher._pad_floor == 16


def test_autotune_replay_fits_knobs(tmp_path):
    """The offline fitter: a devprof dump whose rollups show batch-1
    traffic padded by a floor of 8 fits pad_floor=1 (+ the env seam)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "autotune_replay",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "autotune_replay.py"))
    ar = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ar)

    dump = {
        "schema": "rmqtt_tpu.devprof_dump/1",
        "snapshot": {
            "compile": {"storms": 0},
            "dispatch": {
                "items": 100, "padded_items": 800, "pad_floor": 8,
                "fused": 90, "fallback": 10,
                "rollups": [
                    {"dispatches": 50, "items": 50,
                     "batch_hist": {"2": 50}},
                    {"dispatches": 50, "items": 50,
                     "batch_hist": {"2": 50}},
                ],
            },
            "uploads": {"delta": 10, "full": 2,
                        "delta_bytes": 10_000, "full_bytes": 900_000},
        },
    }
    fit = ar.fit_knobs([dump])
    assert fit["knobs"]["pad_floor"] == 1
    assert fit["knobs"]["fused"] is True
    assert fit["knobs"]["delta_uploads"] is True
    assert fit["knobs"]["linger_ms"] == 0.5
    env = ar.knobs_to_env(fit["knobs"])
    assert env["RMQTT_PAD_FLOOR"] == "1"
    assert env["RMQTT_FUSED"] == "1"
    # bench artifacts with an embedded devprof snapshot parse too
    art = {"parsed": {"devprof": dump["snapshot"]}}
    assert ar.fit_knobs([art])["knobs"]["pad_floor"] == 1
