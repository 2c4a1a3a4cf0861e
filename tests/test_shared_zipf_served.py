"""``$share`` groups on the served path under the Zipf stream, against the
benchmark's plain reference.

``RoutingService`` with the match cache on, over ``XlaRouter`` (JAX on the
CPU; small batches are the host mirror's, as on the chip), loaded with the
``exact_zipf_share`` generator's table at a small size: 2,000 exact rows and
four groups ``$share/g<k>/#`` of four members, owner rule ``i % 64``. 5,000
publishes of the generator's stream are routed one by one
(``matches_for_fanout``) and as runs of 16 (``matches_run``), so both cache
hits and misses collapse the groups. Every publish's receivers are held to
``harness/reference.py`` ``Table`` and ``harness/cell.py`` ``shared_groups``:
the plain owner once and one member of each group, nothing missed, nothing
extra. Besides:

- round robin's promise: within a group the members' counts differ by at
  most one, over the whole stream, and over a stretch of hits alone;
- a member that goes offline is skipped while others are online;
- the ``share_all`` / ``share_none`` faults of ``benchmark/tests/faulty_broker.py``
  are caught at this size, each through its own check alone;
- the ``shared.*`` counters count what the collapse met, and the
  ``routing.collapse`` stage counts each collapse that met a group.
"""

import asyncio
import importlib.util
import itertools
import sys
from collections import Counter, namedtuple
from pathlib import Path

import numpy as np
import pytest

from rmqtt_tpu.broker.routing import RoutingService
from rmqtt_tpu.broker.telemetry import Telemetry
from rmqtt_tpu.core.topic import parse_shared
from rmqtt_tpu.router.base import Id, Router, SubscriptionOptions

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from harness import cell, generators, reference  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "benchmark_tests_faulty_broker", BENCH / "tests" / "faulty_broker.py")
faulty = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faulty)

Msg = namedtuple("Msg", "from_id topic")
GROUPS, MEMBERS, EXACT = 4, 4, 2000
SUBSCRIBERS, PUBLISHES, RUN = 64, 5000, 16
SEED = 2**31 + 41
PUB = Id(1, "publisher")


def _generator():
    return generators.load("exact_zipf_share")(SEED, {
        "subscriptions": EXACT + GROUPS * MEMBERS,
        "shared_groups": GROUPS, "shared_members": MEMBERS})


@pytest.fixture(scope="module")
def table():
    g = _generator()
    filters = g.filters()
    topics = list(itertools.islice(g.topic_stream(SEED * 1009 + 17), PUBLISHES))
    return filters, topics


@pytest.fixture(scope="module")
def xla_router_cls():
    from rmqtt_tpu.router.xla import XlaRouter

    return XlaRouter


def _route(router_cls, filters, topics, mode, offline=(), after=0):
    """Route ``topics`` through a fresh service over a router loaded with
    ``filters`` (row i to client ``s<i % 64>``). From publish ``after`` on,
    the clients in ``offline`` are offline. → (receivers a publish as
    subscriber numbers, hit flags, the service's metrics, stage stats)."""
    down = set()
    router = router_cls(is_online=lambda cid: cid not in down)
    for i, f in enumerate(filters):
        group, inner = parse_shared(f)
        router.add(inner, Id(1, f"s{i % SUBSCRIBERS}"),
                   SubscriptionOptions(qos=1, shared_group=group))
    tele = Telemetry()
    svc = RoutingService(router, prewarm=False, telemetry=tele)

    async def go():
        svc.start()
        try:
            got = []
            for lo in range(0, len(topics), RUN):
                if lo >= after:
                    down.update(offline)
                chunk = topics[lo:lo + RUN]
                if mode == "one_by_one":
                    got += [await svc.matches_for_fanout(PUB, t) for t in chunk]
                else:
                    got += await svc.matches_run([Msg(PUB, t) for t in chunk],
                                                 [None] * len(chunk))
            return got
        finally:
            await svc.stop()

    got = asyncio.run(asyncio.wait_for(go(), 240))
    receivers = [[int(r.id.client_id[1:]) for rels in relmap.values() for r in rels]
                 for relmap, _hit in got]
    return receivers, [hit for _r, hit in got], svc.metrics.to_json(), tele.stage_stats()


def _judge(filters, topics, receivers) -> dict:
    """The harness's comparison of one stream: missing and unexpected
    pairs, and ``shared_groups``'s missed / extra / groups."""
    counts, subs, groups = reference.Table(filters, SUBSCRIBERS).expected(topics)
    W = np.arange(len(topics))
    want = np.unique(np.repeat(W, counts) * SUBSCRIBERS + subs)
    got = np.unique([p * SUBSCRIBERS + s for p, rs in enumerate(receivers) for s in rs])
    out = cell.shared_groups(groups, W, want, got, SUBSCRIBERS)
    out["missing"] = int(np.setdiff1d(want, got).size)
    return out


def _members(k: int) -> range:
    """Group k's members: its rows are k * MEMBERS.. of the sorted table."""
    return range(k * MEMBERS, (k + 1) * MEMBERS)


@pytest.mark.parametrize("mode", ["one_by_one", "runs_of_16"])
def test_every_publish_reaches_its_owner_and_one_member_of_each_group(
        mode, table, xla_router_cls):
    filters, topics = table
    receivers, hits, metrics, stages = _route(xla_router_cls, filters, topics, mode)
    judged = _judge(filters, topics, receivers)
    assert judged == {"unexpected": 0, "missed": 0, "extra": 0,
                      "groups": GROUPS * PUBLISHES, "missing": 0}
    # the owner once, and one member of each group: fan-out 1 + GROUPS
    # (a member that is the owner too is sent both copies)
    assert all(len(rs) == 1 + GROUPS for rs in receivers)
    n_hits = sum(hits)
    assert 0.4 * PUBLISHES < n_hits < PUBLISHES - 500  # hits and misses both
    # round robin: within a group the members' counts differ by at most one
    for k in range(GROUPS):
        mine = Counter(s for rs in receivers for s in rs[1:] if s in _members(k))
        chosen = [mine[m] for m in _members(k)]
        assert sum(chosen) == PUBLISHES and max(chosen) - min(chosen) <= 1, (k, chosen)
    assert metrics["shared.publishes"] == PUBLISHES
    assert metrics["shared.groups"] == GROUPS * PUBLISHES
    assert metrics["shared.candidates"] == GROUPS * MEMBERS * PUBLISHES
    assert metrics["shared.no_member"] == 0
    assert stages["stage_routing_collapse_count"] == PUBLISHES
    assert stages["stage_routing_cache_hit_count"] == n_hits


def test_round_robin_rotates_on_cache_hits_alone(table, xla_router_cls):
    """One hot topic, routed again and again: after its second miss every
    publish is a hit, and the hits alone keep each group balanced."""
    filters, topics = table
    hot = [topics[0]] * (3 * MEMBERS * 10)
    receivers, hits, _m, _s = _route(xla_router_cls, filters, hot, "one_by_one")
    assert hits[:2] == [False, False] and all(hits[2:])
    on_hits = receivers[2:2 + MEMBERS * 10]
    for k in range(GROUPS):
        mine = Counter(s for rs in on_hits for s in rs[1:] if s in _members(k))
        assert [mine[m] for m in _members(k)] == [10] * MEMBERS, k


@pytest.mark.parametrize("mode", ["one_by_one", "runs_of_16"])
def test_an_offline_member_is_skipped_while_others_are_online(
        mode, table, xla_router_cls):
    filters, topics = table
    gone, after = 5, RUN * (PUBLISHES // (2 * RUN))  # a member of group 1, mid-stream
    receivers, hits, _m, _s = _route(xla_router_cls, filters, topics, mode,
                                     offline={f"s{gone}"}, after=after)
    assert _judge(filters, topics, receivers)["missed"] == 0
    late = receivers[after:]
    assert sum(hits[after:]) > 0.4 * len(late)  # the cache re-flags liveness on hits
    assert not any(gone in rs[1:] for rs in late if rs[0] != gone)
    mine = Counter(s for rs in late for s in rs[1:] if s in _members(1))
    online = [mine[m] for m in _members(1) if m != gone]
    assert sum(online) == len(late) and max(online) - min(online) <= 1


@pytest.mark.parametrize("fault, check", [("share_all", "extra"), ("share_none", "missed")])
def test_the_share_faults_are_caught(fault, check, table, xla_router_cls, monkeypatch):
    filters, topics = table
    monkeypatch.setattr(Router, "collapse", Router.collapse)  # undone after the test
    faulty.install_share(fault)
    receivers, _hits, _m, _s = _route(xla_router_cls, filters, topics, "runs_of_16")
    judged = _judge(filters, topics, receivers)
    others = {k: v for k, v in judged.items() if k not in (check, "groups")}
    assert judged[check] > 0 and not any(others.values()), judged
