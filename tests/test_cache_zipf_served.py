"""The match cache on the served path under a Zipf-skewed stream.

``RoutingService`` over ``XlaRouter`` (JAX on the CPU; small batches are the
host mirror's, as on the chip) with a 2,000-row exact table plus 200 wildcard
rows, fed a seeded Zipf(0.99) stream of 20,000 publishes, once one by one
(``matches_for_fanout``) and once as runs of 16 (``matches_run``), with a
SUBSCRIBE or UNSUBSCRIBE every 500 publishes that changes what the hottest
topics match. Every publish's relations equal what an independent trie
(``benchmark/harness/trie.py``, the benchmark's reference) says of the rows
live at that instant; the cache hits on more than 40 % of publishes and is
invalidated by the mutations; the ``routing.cache_hit`` stage counts exactly
the hits.
"""

import asyncio
import importlib.util
import random
from collections import namedtuple
from pathlib import Path

import numpy as np
import pytest

from rmqtt_tpu.broker.routing import RoutingService
from rmqtt_tpu.broker.telemetry import Telemetry
from rmqtt_tpu.router.base import Id, SubscriptionOptions

_TRIE = Path(__file__).resolve().parent.parent / "benchmark" / "harness" / "trie.py"
_spec = importlib.util.spec_from_file_location("benchmark_reference_trie", _TRIE)
_trie = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_trie)

Msg = namedtuple("Msg", "from_id topic")
EXACT, WILD, PUBLISHES, EVERY, RUN = 2000, 200, 20_000, 500, 16
THETA = 0.99


def _table(rng):
    """→ ([(filter, client)], exact topics): ``EXACT`` rows ``iot/<n>`` and
    ``WILD`` wildcard rows, some over the exact topics and some beside them."""
    numbers = rng.sample(range(10_000_000), EXACT)
    topics = sorted(f"iot/{n}" for n in numbers)
    rows = [(t, f"c{i % 97}") for i, t in enumerate(topics)]
    for i in range(WILD):
        n = rng.choice(numbers)
        f = (f"+/{n}", f"iot/{n}/#", f"fleet/+/{i}/#", "iot/+")[i % 4]
        rows.append((f, f"w{i}"))
    return rows, topics


def _zipf_stream(topics, seed):
    """Ranks by inverse CDF of r**-THETA, scrambled over the topics."""
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(np.arange(1, len(topics) + 1, dtype=np.float64) ** -THETA)
    ranks = np.searchsorted(cdf / cdf[-1], rng.random(PUBLISHES), side="right")
    scramble = rng.permutation(len(topics))
    hot = [topics[i] for i in scramble[:4]]
    return [topics[scramble[min(r, len(topics) - 1)]] for r in ranks], hot


def _mutation(k: int, hot: list):
    """The k-th mutation (k from 0): a late client subscribes to the hottest
    topic exactly, then by a wildcard over the second hottest, and each is
    taken back two mutations later; every exact one bumps the ``iot``
    segment's epoch, every wildcard one the global epoch."""
    late = f"late{k // 4}"
    n2 = hot[1].split("/", 1)[1]
    return [("add", hot[0], late), ("add", f"+/{n2}", late),
            ("remove", hot[0], late), ("remove", f"+/{n2}", late)][k % 4]


def _relations(relmap) -> list:
    return sorted((r.id.client_id, r.topic_filter)
                  for rels in relmap.values() for r in rels)


@pytest.fixture(scope="module")
def xla_router_cls():
    from rmqtt_tpu.router.xla import XlaRouter

    return XlaRouter


@pytest.mark.parametrize("mode", ["one_by_one", "runs_of_16"])
def test_cache_serves_what_the_trie_says_under_zipf(mode, xla_router_cls):
    rng = random.Random(36)
    rows, topics = _table(rng)
    stream, hot = _zipf_stream(topics, 2**31 + 36)
    router = xla_router_cls()
    live = list(rows)
    for f, cid in live:
        router.add(f, Id(1, cid), SubscriptionOptions(qos=1))
    tele = Telemetry()
    svc = RoutingService(router, prewarm=False, telemetry=tele)
    pub = Id(1, "publisher")

    def reference():
        trie = _trie.Trie()
        for f, cid in live:
            trie.insert(f, (cid, f))
        return trie

    async def go():
        svc.start()
        try:
            trie, hits, k = reference(), 0, 0
            for lo in range(0, PUBLISHES, EVERY):
                chunk = stream[lo:lo + EVERY]
                if mode == "one_by_one":
                    got = [await svc.matches_for_fanout(pub, t) for t in chunk]
                else:
                    got = []
                    for i in range(0, len(chunk), RUN):
                        run = chunk[i:i + RUN]
                        got += await svc.matches_run(
                            [Msg(pub, t) for t in run], [None] * len(run))
                for t, (relmap, hit) in zip(chunk, got):
                    assert _relations(relmap) == sorted(trie.match(t)), (t, hit, k)
                    hits += hit
                op, f, cid = _mutation(k, hot)
                k += 1
                if op == "add":
                    router.add(f, Id(1, cid), SubscriptionOptions(qos=1))
                    live.append((f, cid))
                else:
                    assert router.remove(f, Id(1, cid))
                    live.remove((f, cid))
                trie = reference()
            return hits, svc.stats(), tele.stage_stats()
        finally:
            await svc.stop()

    hits, stats, stages = asyncio.run(asyncio.wait_for(go(), 240))
    assert stats["routing_cache_hits"] == hits
    assert hits + stats["routing_cache_misses"] == PUBLISHES
    assert hits / PUBLISHES > 0.4
    assert stats["routing_cache_invalidations"] > 0
    assert stages["stage_routing_cache_hit_count"] == hits
    assert stages["stage_routing_cache_hit_busy_ms_total"] > 0
    if mode == "runs_of_16":
        # each block of EVERY publishes ends in a short run
        assert stages["stage_ingress_run_count"] == -(-EVERY // RUN) * (PUBLISHES // EVERY)
