"""Hot-key attribution plane tests (broker/hotkeys.py + surfaces).

Tiers:
- Sketch math vs an exact oracle: Space-Saving brackets
  ``[count - err, count]`` contain the true count on a 100K-event zipf
  stream (k=64), the true heavy hitters are tracked, the Count-Min
  point estimate never underestimates, and the linear-counting distinct
  estimate lands near truth.
- Mergeability: sketch(A) ++ sketch(B) under the mergeable-summaries
  rule brackets the oracle of the concatenated stream; CMS merges
  cell-wise and rejects shape mismatches.
- Decay: epoch rotation ages a key out after two windows — "hot now",
  not since boot.
- Alerts: the top-1-share watchdog is transition-edged (one episode =
  one slow-ring row + one SERVER_HOTKEY fire), floored at
  ALERT_MIN_EVENTS, and clears when the share subsides.
- Live E2E: real MQTT traffic populates every space; /api/v1/hotkeys,
  the bounded Prometheus families, $SYS payload shapes, the history
  row, and ops_doctor's "who is hot" section all carry the same keys.
- Cluster: two REAL meshed nodes, /api/v1/hotkeys/sum over the
  what=hotkeys DATA path (totals sum, tops merge, nodes=2).
- Disabled pin: hotkeys=false spawns no task, nulls the routing seam,
  and every surface stays shape-stable.
- Conf: [observability] hotkeys* round-trip + unknown-key rejection.
"""

import asyncio
import functools
import importlib.util
import json
import pathlib
import random
import zlib
from collections import Counter

import pytest

from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
from rmqtt_tpu.broker.hooks import HookType
from rmqtt_tpu.broker.hotkeys import (
    ALERT_MIN_EVENTS,
    SPACES,
    CountMin,
    HotkeysService,
    SpaceSaving,
    _label_escape,
    first_segment,
    merge_topk,
)
from rmqtt_tpu.broker.http_api import HttpApi
from rmqtt_tpu.broker.server import MqttBroker

from tests.mqtt_client import TestClient
from tests.test_http_plugins import http_get


def _ctx(**kw):
    return ServerContext(BrokerConfig(port=0, **kw))


def _zipf_stream(rng, n, distinct, s=1.1):
    keys = [f"key{i}" for i in range(distinct)]
    weights = [1.0 / (i + 1) ** s for i in range(distinct)]
    return rng.choices(keys, weights=weights, k=n)


# ------------------------------------------------------------- sketch math
def test_first_segment():
    assert first_segment("tenant/dev/t") == "tenant"
    assert first_segment("flat") == "flat"
    assert first_segment("/leading/slash") == "/"


def test_space_saving_zipf_accuracy_vs_oracle():
    """100K zipf events, k=64: every tracked count brackets the truth
    within its per-entry error, err <= N/k, the floor bounds every
    untracked key, and the true top-16 are all tracked."""
    rng = random.Random(42)
    stream = _zipf_stream(rng, 100_000, 2_000)
    oracle = Counter(stream)
    ss = SpaceSaving(64)
    for key in stream:
        ss.offer(key)
    n = len(stream)
    floor = ss.floor()
    assert floor <= n // 64  # the classic Space-Saving bound
    tracked = {e["key"]: e for e in ss.entries()}
    assert len(tracked) == 64
    for key, ent in tracked.items():
        true = oracle[key]
        assert ent["err"] <= n // 64
        assert true <= ent["count"] <= true + ent["err"], key
    for key, true in oracle.items():
        if key not in tracked:
            assert true <= floor, key  # untracked ⇒ bounded by the floor
    top16 = [k for k, _ in oracle.most_common(16)]
    assert all(k in tracked for k in top16)
    # report order puts the real #1 first (its count dominates any error)
    assert ss.entries()[0]["key"] == top16[0]


@pytest.mark.parametrize("n", [4000, 1_000_000])
def test_hot_topics_of_the_benchmarks_zipf_stream(n):
    """The stream of the benchmark cell ``b1_1m_zipf.pub40`` (its generator,
    ``exact_zipf``: Zipf(0.99) over a table of exact device topics, the hot
    ranks scrambled over it), 200,000 publishes through the publish seam of
    the plane a broker builds (k at its default, 64). Space-Saving's promise:
    every topic whose true count passes total / k is tracked, its count
    bracketing the truth: the top 4 to 7 ranks here (rank 5 of 1,000,000
    draws ~1.3 % of publishes, rank 6 ~1.1 %, against 1 / 64 = 1.6 %). At
    the rehearsal's 4,000 topics the plane's top 10 also names at least 8 of
    the generator's 10 hottest; at the cell's 1,000,000 the ranks below the
    guarantee churn with the tail and are not named (PERF.md section 7)."""
    import itertools
    import sys

    bench = str(pathlib.Path(__file__).resolve().parent.parent / "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import generators

    gen = generators.load("exact_zipf")(2**31 + 36, {"subscriptions": n})
    hk = _ctx().hotkeys
    assert hk.enabled and hk.k == 64
    stream = list(itertools.islice(gen.topic_stream(2**33 + 1), 200_000))
    for topic in stream:
        hk.on_publish(topic, "pub-1", 8)
    top = hk.snapshot()["spaces"]["topics"]["top"]
    truth = Counter(stream)
    heavy = [t for t, c in truth.most_common() if c > len(stream) / hk.k]
    tracked = {e["key"]: e for e in top}
    for t in heavy:
        assert tracked[t]["count"] - tracked[t]["err"] <= truth[t] <= tracked[t]["count"]
    assert [e["key"] for e in top[:len(heavy)]] == heavy
    assert heavy == gen.hottest(len(heavy)) and len(heavy) >= 4
    if n == 4000:
        assert len({e["key"] for e in top[:10]} & set(gen.hottest(10))) >= 8


def test_count_min_never_underestimates():
    rng = random.Random(7)
    stream = _zipf_stream(rng, 20_000, 500)
    oracle = Counter(stream)
    cms = CountMin(1024, 4)
    for key in stream:
        cms.add_data(key.encode())
    for key, true in oracle.most_common(64):
        est = cms.query(key)
        assert est >= true
        assert est <= true + 20_000 // 256  # far inside the e*N/w bound
    assert cms.query("never-seen") <= 20_000 // 256


def test_merge_property_brackets_concatenated_stream():
    """sketch(A) ++ sketch(B) via the mergeable-summaries rule must
    bracket the oracle of A++B: count - err <= true <= count."""
    rng = random.Random(99)
    a_stream = _zipf_stream(rng, 30_000, 800)
    b_stream = _zipf_stream(rng, 30_000, 800, s=1.3)
    oracle = Counter(a_stream) + Counter(b_stream)
    sa, sb = SpaceSaving(64), SpaceSaving(64)
    for key in a_stream:
        sa.offer(key)
    for key in b_stream:
        sb.offer(key)
    merged, floor = merge_topk(sa.entries(), sa.floor(),
                               sb.entries(), sb.floor(), 64)
    assert len(merged) == 64 and floor == sa.floor() + sb.floor()
    for ent in merged:
        true = oracle[ent["key"]]
        assert ent["count"] - ent["err"] <= true <= ent["count"], ent["key"]
    # the combined heavy hitter survives the merge at rank 1
    assert merged[0]["key"] == oracle.most_common(1)[0][0]
    # CMS merge = cell-wise add: the merged estimate still upper-bounds
    ca, cb = CountMin(512, 4), CountMin(512, 4)
    for key in a_stream:
        ca.add_data(key.encode())
    for key in b_stream:
        cb.add_data(key.encode())
    ca.merge(cb)
    for key, true in oracle.most_common(16):
        assert ca.query(key) >= true


def test_cms_shape_mismatch_raises():
    try:
        CountMin(512, 4).merge(CountMin(256, 4))
    except ValueError:
        pass
    else:
        raise AssertionError("shape mismatch must raise")


# ------------------------------------------ the fold against the scan oracle
class _ScanSpaceSaving:
    """Space-Saving as the plane had it before the heap: the victim is
    found by scanning every tracked key (the oracle of the tests below)."""

    __slots__ = ("k", "counts", "errs")

    def __init__(self, k):
        self.k = max(1, int(k))
        self.counts = {}
        self.errs = {}

    def offer(self, key, inc=1):
        c = self.counts
        v = c.get(key)
        if v is not None:
            c[key] = v + inc
            return
        if len(c) < self.k:
            c[key] = inc
            self.errs[key] = 0
            return
        victim = min(c, key=c.get)
        floor = c.pop(victim)
        self.errs.pop(victim, None)
        c[key] = floor + inc
        self.errs[key] = floor

    floor = SpaceSaving.floor
    entries = SpaceSaving.entries


def _scan_offer(win, key, inc):
    """``_Window.offer`` as it was: one encode and five hashes a space."""
    win.total += inc
    win.ss.offer(key, inc)
    data = key.encode("utf-8", "surrogatepass")
    if win.cms is not None:
        w = win.cms.width
        for r, row in enumerate(win.cms.rows):
            row[zlib.crc32(data, (0x9E3779B9 * (r + 1)) & 0xFFFFFFFF) % w] \
                += inc
    h = zlib.crc32(data) % 4096
    win.bitmap[h >> 3] |= 1 << (h & 7)


class _ScanPlane(HotkeysService):
    """The plane with the scan-based Space-Saving and the per-space fold
    it had before the heap and the shared pass."""

    def __init__(self, ctx, cfg):
        super().__init__(ctx, cfg)
        for space in self.spaces.values():
            space.prev.ss = _ScanSpaceSaving(space.k)
            space.cur.ss = _ScanSpaceSaving(space.k)

    def rotate(self):
        super().rotate()
        for space in self.spaces.values():
            space.cur.ss = _ScanSpaceSaving(space.k)

    def _fold(self):
        sp = self.spaces
        pubs, self._pend_pub = self._pend_pub, []
        tc, tb, pc = {}, {}, {}
        for topic, cid, nbytes in pubs:
            tc[topic] = tc.get(topic, 0) + 1
            if nbytes > 0:
                tb[topic] = tb.get(topic, 0) + nbytes
            pc[cid] = pc.get(cid, 0) + 1
        for name, agg in (("topics", tc), ("topic_bytes", tb),
                          ("publishers", pc)):
            for key, n in agg.items():
                _scan_offer(sp[name].cur, key, n)
        disp, self._pend_disp = self._pend_disp, []
        fc = Counter(disp)
        pf = {}
        for topic, n in fc.items():
            seg = first_segment(topic)
            pf[seg] = pf.get(seg, 0) + n
        for key, n in pf.items():
            _scan_offer(sp["prefixes"].cur, key, n)
        subs, self._pend_sub = self._pend_sub, []
        for key, n in Counter(subs).items():
            _scan_offer(sp["subscribers"].cur, key, n)
        drops, self._pend_drop = self._pend_drop, []
        for key, n in Counter(drops).items():
            _scan_offer(sp["drops"].cur, key, n)


@functools.lru_cache(maxsize=None)
def _benchmark_zipf_topics(n):
    """``n`` publishes of the stream of ``b1_1m_zipf.pub40`` (its generator
    ``exact_zipf`` over the cell's 1,000,000 exact topics)."""
    import itertools
    import sys

    bench = str(pathlib.Path(__file__).resolve().parent.parent / "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import generators

    gen = generators.load("exact_zipf")(2**31 + 42,
                                        {"subscriptions": 1_000_000})
    return tuple(itertools.islice(gen.topic_stream(2**33 + 7), n))


def _fold_stream(case, rng):
    """Four folds of ``(topic, publisher, nbytes, subscriber, drop key)``
    events; the plane rotates after the second."""
    folds = []
    for f in range(4):
        if case == "uniform_1m":
            ns = [rng.randrange(1_000_000) for _ in range(3000)]
            ev = [(f"iot/{n}", f"pub-{n % 100}", 7, f"sub-{n % 4096}",
                   f"queue_full:sub-{n % 300}") for n in ns]
        elif case == "zipf_099":
            topics = _benchmark_zipf_topics(12_000)[f * 3000:(f + 1) * 3000]
            ev = [(t, f"pub-{i % 40}", 7, f"sub-{zlib.crc32(t.encode()) % 4096}",
                   f"queue_full:{t}") for i, t in enumerate(topics)]
        elif case == "all_tied":
            # every key of a fold twice, no key seen in two folds: every
            # count ties with every other
            keys = [f"f{f}/k{i}" for i in range(200)]
            ev = [(t, t, 5, t, t) for t in keys + keys]
        elif case == "evict_reinsert":
            # a cycle a little longer than k: every key is evicted and
            # comes back, with a few hot keys in between
            keys = [f"c{i % 71}/x" for i in range(700)]
            keys += [f"hot{i % 3}/x" for i in range(50)]
            rng.shuffle(keys)
            ev = [(t, t, 1, t, t) for t in keys]
        else:  # weighted: repeats, byte counts from 0 up, sizes > 1
            ns = [int(rng.paretovariate(1.2)) % 500 for _ in range(3000)]
            ev = [(f"w{n % 37}/t{n}", f"pub-{n % 90}", (n * 13) % 400,
                   f"sub-{n % 150}", f"drop:{n % 80}") for n in ns]
        folds.append(ev)
    return folds


def _sketches(hk):
    out = {}
    for name, space in hk.spaces.items():
        for side in ("cur", "prev"):
            win = getattr(space, side)
            out[name, side] = (
                list(win.ss.counts.items()), list(win.ss.errs.items()),
                win.cms.rows if win.cms is not None else None,
                bytes(win.bitmap), win.total)
    return out


@pytest.mark.parametrize("k", [8, 64])
@pytest.mark.parametrize("case", ["uniform_1m", "zipf_099", "all_tied",
                                  "evict_reinsert", "weighted"])
def test_fold_matches_scan_oracle(case, k):
    """The heap-evicting Space-Saving and the one-pass fold give every
    sketch of the plane exactly what the scan-based code gave on the same
    events: Space-Saving counts in dict order, errors, CMS rows, bitmaps
    and totals over four folds and one rotation, and so every view."""
    rng = random.Random(f"{case}/{k}")
    new = _ctx(hotkeys_k=k).hotkeys
    ctx = _ctx(hotkeys_k=k)
    old = _ScanPlane(ctx, ctx.cfg)
    assert new.k == old.k == k
    for f, events in enumerate(_fold_stream(case, rng)):
        for hk in (new, old):
            for topic, pub, nbytes, sub, drop in events:
                hk.on_publish(topic, pub, nbytes)
                hk.on_dispatch(topic)
                hk.on_deliver(sub)
                hk.on_drop("x", drop)
            hk.drain()
            if f == 1:
                hk.rotate()
        assert _sketches(new) == _sketches(old), f
        block, oracle = new.stats_block(), old.stats_block()
        for counter in ("hotkeys_fold_keys", "hotkeys_evictions"):
            block.pop(counter)
            oracle.pop(counter)
        assert block == oracle
        assert new.snapshot() == old.snapshot()
        assert new.history_summary() == old.history_summary()
    assert new.point("topics", events[0][0]) == old.point("topics",
                                                         events[0][0])


@pytest.mark.parametrize("stream", ["distinct", "repeats"])
def test_fold_counters(stream):
    """``hotkeys_fold_keys`` sums the distinct keys each fold offered each
    space; ``hotkeys_evictions`` counts what Space-Saving evicted: n
    distinct keys into a fresh plane of k = 8 evict n - 8 in every space,
    repeats of 8 keys evict nothing."""
    hk = _ctx(hotkeys_k=8).hotkeys
    n = 500 if stream == "distinct" else 8
    for rep in range(1 if stream == "distinct" else 20):
        for i in range(n):
            hk.on_publish(f"t{i}/x", f"pub-{i}", 3)
            hk.on_dispatch(f"t{i}/x")
            hk.on_deliver(f"sub-{i}")
            hk.on_drop("queue_full", f"sub-{i}")
    hk.drain()
    st = hk.stats_block()
    assert st["hotkeys_fold_keys"] == len(SPACES) * n
    assert st["hotkeys_evictions"] == len(SPACES) * (n - 8)
    assert all(len(hk.spaces[s].cur.ss.counts) == 8 for s in SPACES)
    hk.drain()  # nothing buffered: no fold, nothing counted
    assert hk.stats_block() == st


def test_distinct_estimate_near_truth():
    ctx = _ctx()
    hk = ctx.hotkeys
    for i in range(1000):
        hk.on_dispatch(f"ns{i}/dev")
    hk.drain()
    est = hk.spaces["prefixes"].view()["distinct_est"]
    assert abs(est - 1000) <= 150  # linear counting: ~15% at this load


# ------------------------------------------------------------------- decay
def test_rotation_ages_keys_out_after_two_windows():
    ctx = _ctx()
    hk = ctx.hotkeys
    for _ in range(10):
        hk.on_publish("old/topic", "old-client", 16)
    hk.drain()
    assert hk.spaces["topics"].view()["top"][0]["key"] == "old/topic"
    hk.rotate()
    # one rotation: still visible via the previous window
    view = hk.spaces["topics"].view()
    assert view["top"][0]["key"] == "old/topic" and view["total"] == 10
    hk.rotate()
    # two rotations with no fresh traffic: fully aged out
    view = hk.spaces["topics"].view()
    assert view["total"] == 0 and view["top"] == []
    assert hk.rotations == 2
    assert hk.stats_block()["hotkeys_rotations"] == 2


# ------------------------------------------------------------------ alerts
def test_alert_transition_edged_and_floored():
    ctx = _ctx(hotkeys_alert_share=0.5)
    hk = ctx.hotkeys
    # under the event floor: a 10-event window at 100% share is noise
    for _ in range(10):
        hk.on_publish("hot/t", "c1", 8)
    assert hk.check_alerts() == []
    # past the floor: one episode = exactly one fire
    for _ in range(ALERT_MIN_EVENTS):
        hk.on_publish("hot/t", "c1", 8)
    fired = hk.check_alerts()
    assert [r["space"] for r in fired] == ["topics", "publishers"]
    assert fired[0]["key"] == "hot/t" and fired[0]["share"] == 1.0
    assert hk.check_alerts() == []  # inside the episode: edge, not level
    assert hk.alerts_total == 2
    # the slow-op correlation ring carries the rows
    rows = [op for op in ctx.telemetry.slow_ops
            if op["op"] == "hotkeys.alert"]
    assert len(rows) == 2 and rows[0]["detail"]["key"] == "hot/t"
    # dilute the share below threshold: the episode clears ...
    for i in range(200):
        hk.on_publish(f"cold/t{i}", f"cc{i}", 8)
    assert hk.check_alerts() == []
    assert hk.spaces["topics"].alerting is False
    # ... and a new hot episode re-fires
    for _ in range(400):
        hk.on_publish("hot/t", "c1", 8)
    assert [r["space"] for r in hk.check_alerts()] == ["topics",
                                                       "publishers"]
    assert hk.alerts_total == 4


def test_forced_alert_end_to_end():
    """Real traffic drives one topic past hotkeys_alert_share: the
    SERVER_HOTKEY hook, the slow-ring row, the scrape counter, and the
    snapshot alerting flag must all land."""

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, hotkeys_alert_share=0.5, allow_anonymous=True)))
        api = HttpApi(b.ctx, port=0)
        await b.start()
        await api.start()
        fired = []

        async def on_hotkey(_ht, args, _prev):
            fired.append(args)
            return None

        b.ctx.hooks.register(HookType.SERVER_HOTKEY, on_hotkey)
        try:
            sub = await TestClient.connect(b.port, "hk-sub")
            await sub.subscribe("burn/#", qos=0)
            publ = await TestClient.connect(b.port, "hk-pub")
            for _ in range(ALERT_MIN_EVENTS + 10):
                await publ.publish("burn/one", b"payload", qos=0)
            for _ in range(ALERT_MIN_EVENTS + 10):
                await sub.recv()
            rows = b.ctx.hotkeys.check_alerts()
            await asyncio.sleep(0.05)  # let the hook task run
            assert any(r["space"] == "topics" and r["key"] == "burn/one"
                       for r in rows)
            assert fired, "SERVER_HOTKEY hook did not fire"
            space, key, row = fired[0]
            assert key == "burn/one" and row["share"] >= 0.5
            assert any(op["op"] == "hotkeys.alert"
                       for op in b.ctx.telemetry.slow_ops)
            # snapshot carries the episode flag + the hot key
            status, body = await http_get(api.bound_port, "/api/v1/hotkeys")
            assert status == 200
            snap = json.loads(body)
            assert snap["schema"] == "rmqtt_tpu.hotkeys/1"
            assert snap["spaces"]["topics"]["alerting"] is True
            assert snap["spaces"]["topics"]["top"][0]["key"] == "burn/one"
            assert snap["alerts_total"] >= 1
            # subscriber + publisher + prefix spaces saw the same episode
            assert snap["spaces"]["publishers"]["top"][0]["key"] == "hk-pub"
            assert snap["spaces"]["subscribers"]["top"][0]["key"] == "hk-sub"
            assert snap["spaces"]["prefixes"]["top"][0]["key"] == "burn"
            # scrape: bounded topk family + the alert counter
            status, body = await http_get(api.bound_port,
                                          "/metrics/prometheus")
            text = body.decode()
            assert "# TYPE rmqtt_hotkeys_topk gauge" in text
            assert ('rmqtt_hotkeys_topk{node="1",space="topics",'
                    'key="burn/one"}') in text
            assert ('rmqtt_hotkeys_alerts_total{node="1",space="topics"} 1'
                    in text)
            # ops_doctor renders the hot key in the "who is hot" section
            path = (pathlib.Path(__file__).parent.parent / "scripts"
                    / "ops_doctor.py")
            spec = importlib.util.spec_from_file_location("ops_doctor", path)
            od = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(od)
            joined = "\n".join(od.hotkey_lines(snap))
            assert "burn/one" in joined and "ALERTING" in joined
        finally:
            await api.stop()
            await b.stop()

    asyncio.run(run())


# ----------------------------------------------------------- live surfaces
def test_live_broker_populates_all_spaces():
    """Each delivered publish crosses every seam once: topics,
    topic_bytes, publishers, prefixes (dispatch), subscribers
    (deliver) — and a queue-class drop lands in the drops space."""

    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, allow_anonymous=True)))
        await b.start()
        try:
            hk = b.ctx.hotkeys
            sub = await TestClient.connect(b.port, "live-sub")
            await sub.subscribe("ns/#", qos=0)
            publ = await TestClient.connect(b.port, "live-pub")
            for i in range(12):
                await publ.publish(f"ns/t{i % 3}", b"x" * 32, qos=0)
            for _ in range(12):
                await sub.recv()
            snap = hk.snapshot()
            sp = snap["spaces"]
            assert sp["topics"]["total"] == 12
            assert sp["topic_bytes"]["total"] == 12 * 32
            assert sp["publishers"]["top"][0] == {
                "key": "live-pub", "count": 12, "err": 0, "share": 1.0}
            assert sp["subscribers"]["top"][0]["key"] == "live-sub"
            assert sp["prefixes"]["top"][0]["key"] == "ns"
            # the dispatch seam counts automaton work: the batcher dedups
            # repeated topics per batch, so >= one offer per distinct
            # topic but never more than the publish count
            assert 3 <= sp["prefixes"]["total"] <= sp["topics"]["total"]
            hk.on_drop("queue_full", "live-sub")
            assert (hk.snapshot()["spaces"]["drops"]["top"][0]["key"]
                    == "queue_full:live-sub")
            # stats gauges ride ctx.stats()
            st = b.ctx.stats().to_json()
            assert st["hotkeys_topics_tracked"] == 3
            assert st["hotkeys_publishers_tracked"] == 1
            # history row carries the share series for the annotator
            row = b.ctx.history.collect_once()
            assert row["hotkeys_top1_share"] >= 0.3
            assert "hotkeys.topics.top1_share" in row
            assert "hotkeys.prefixes.distinct" in row
            # $SYS payload shapes (bounded, three leaves)
            pay = hk.sys_payloads()
            assert set(pay) == {"topics", "clients", "prefixes"}
            assert pay["topics"]["by_count"]["total"] == 12
            assert pay["clients"]["publishers"]["top"][0]["key"] == "live-pub"
            assert pay["prefixes"]["drops"]["total"] == 1
        finally:
            await b.stop()

    asyncio.run(run())


def test_prometheus_export_bounded_and_escaped():
    ctx = _ctx()
    hk = ctx.hotkeys
    for i in range(40):  # 40 distinct topics >> the export bound
        hk.on_publish(f'evil"topic\n{i}', f"c{i}", 8)
    lines = hk.prometheus_lines('node="1"')
    topk = [ln for ln in lines if ln.startswith("rmqtt_hotkeys_topk{")]
    per_space = Counter(ln.split('space="')[1].split('"')[0] for ln in topk)
    assert all(v <= 8 for v in per_space.values())  # bounded cardinality
    assert all('\n' not in ln for ln in topk)  # escaping holds the grammar
    assert any('key="evil\\"topic\\n' in ln for ln in topk)
    assert _label_escape("x" * 300).startswith("x" * 120)
    assert _label_escape("x" * 300).endswith("...")
    assert _label_escape('a"b\\c\nd') == 'a\\"b\\\\c\\nd'


# ----------------------------------------------------------------- cluster
def test_hotkeys_sum_two_live_nodes():
    """Two REAL meshed nodes: /api/v1/hotkeys/sum fans the what=hotkeys
    DATA query to the peer and merges both sketch summaries."""
    from tests.test_cluster import link, make_node

    async def run():
        brokers = [await make_node(i + 1) for i in range(2)]
        clusters = await link(brokers)
        api = HttpApi(brokers[0].ctx, port=0)
        await api.start()
        try:
            for i, b in enumerate(brokers):
                hk = b.ctx.hotkeys
                for _ in range(20):
                    hk.on_publish("shared/topic", f"pub-node{i + 1}", 64)
                hk.on_publish(f"only/node{i + 1}", f"pub-node{i + 1}", 64)
            status, body = await http_get(
                api.bound_port, "/api/v1/hotkeys/sum")
            assert status == 200
            merged = json.loads(body)
            assert merged["nodes"] == 2
            topics = merged["spaces"]["topics"]
            assert topics["total"] == 42  # 21 events per node, summed
            top = {e["key"]: e for e in topics["top"]}
            # the shared key's counts added across nodes
            assert top["shared/topic"]["count"] == 40
            assert abs(top["shared/topic"]["share"] - 40 / 42) < 0.01
            # node-local keys both survive the merge
            assert "only/node1" in top and "only/node2" in top
            pubs = {e["key"] for e in merged["spaces"]["publishers"]["top"]}
            assert {"pub-node1", "pub-node2"} <= pubs
        finally:
            await api.stop()
            for c in clusters:
                await c.stop()
            for b in brokers:
                await b.stop()

    asyncio.run(run())


def test_merge_snapshots_recomputes_shares():
    a, b = _ctx(node_id=1), _ctx(node_id=2)
    for _ in range(30):
        a.hotkeys.on_publish("t/1", "c1", 8)
    for _ in range(10):
        b.hotkeys.on_publish("t/2", "c2", 8)
    merged = HotkeysService.merge_snapshots(
        a.hotkeys.snapshot(), [b.hotkeys.snapshot()])
    topics = merged["spaces"]["topics"]
    assert topics["total"] == 40
    assert topics["top"][0] == {"key": "t/1", "count": 30, "err": 0,
                                "share": 0.75}
    assert merged["enabled"] is True and merged["nodes"] == 2


# ---------------------------------------------------------------- disabled
def test_disabled_shape_stable_and_inert():
    async def run():
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, hotkeys_enable=False, allow_anonymous=True)))
        api = HttpApi(b.ctx, port=0)
        await b.start()
        await api.start()
        try:
            hk = b.ctx.hotkeys
            assert hk._task is None  # start() declined: no rotator task
            assert b.ctx.routing.hotkeys is None  # dispatch seam nulled
            # real traffic records NOTHING (the seams are gated off)
            sub = await TestClient.connect(b.port, "d-sub")
            await sub.subscribe("d/#", qos=0)
            publ = await TestClient.connect(b.port, "d-pub")
            for i in range(5):
                await publ.publish(f"d/{i}", b"x", qos=0)
            for _ in range(5):
                await sub.recv()
            snap = hk.snapshot()
            assert snap["enabled"] is False
            assert all(v["total"] == 0 and v["top"] == []
                       for v in snap["spaces"].values())
            assert hk.check_alerts() == []
            # shape-stable: identical key-set to an enabled snapshot
            ref = _ctx().hotkeys.snapshot()
            assert set(snap) == set(ref)
            assert set(snap["spaces"]) == set(ref["spaces"]) == set(SPACES)
            status, body = await http_get(api.bound_port, "/api/v1/hotkeys")
            assert status == 200 and json.loads(body)["enabled"] is False
            status, body = await http_get(api.bound_port,
                                          "/api/v1/hotkeys/sum")
            merged = json.loads(body)
            assert merged["nodes"] == 1 and merged["enabled"] is False
            # gauges present, zero; scrape families present, zero
            st = b.ctx.stats().to_json()
            assert st["hotkeys_topics_tracked"] == 0
            assert st["hotkeys_alerts"] == 0
            status, body = await http_get(api.bound_port,
                                          "/metrics/prometheus")
            text = body.decode()
            assert 'rmqtt_hotkeys_rotations_total{node="1"} 0' in text
            assert "# TYPE rmqtt_hotkeys_topk gauge" in text
            # history rows omit the hotkeys series when disabled
            row = b.ctx.history.collect_once()
            assert "hotkeys_top1_share" not in row
        finally:
            await api.stop()
            await b.stop()

    asyncio.run(run())


# -------------------------------------------------------------------- conf
def test_conf_hotkeys_knobs(tmp_path):
    from rmqtt_tpu import conf

    p = tmp_path / "hk.toml"
    p.write_text("""
[observability]
hotkeys = false
hotkeys_k = 128
hotkeys_cms_width = 2048
hotkeys_cms_depth = 5
hotkeys_window_s = 12.5
hotkeys_alert_share = 0.25
""")
    cfg = conf.load(str(p)).broker
    assert cfg.hotkeys_enable is False
    assert cfg.hotkeys_k == 128
    assert cfg.hotkeys_cms_width == 2048
    assert cfg.hotkeys_cms_depth == 5
    assert cfg.hotkeys_window_s == 12.5
    assert cfg.hotkeys_alert_share == 0.25
    # typos fail at load instead of silently defaulting
    p.write_text("[observability]\nhotkeys_topk = 9\n")
    try:
        conf.load(str(p))
    except ValueError as e:
        assert "hotkeys_topk" in str(e)
    else:
        raise AssertionError("unknown [observability] key must raise")
