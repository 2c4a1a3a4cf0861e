"""Sharded matcher over a virtual 8-device CPU mesh must agree with single-device."""

import random

import jax
import numpy as np
import pytest

from rmqtt_tpu.core.topic import filter_valid, match_filter
from rmqtt_tpu.ops.encode import FilterTable
from rmqtt_tpu.ops.match import TpuMatcher, unpack_bitmap
from rmqtt_tpu.parallel.sharded import ShardedMatcher, make_mesh


def build_random_table(seed, nfilters=2000):
    rng = random.Random(seed)
    table = FilterTable()
    fids = {}
    words = ["a", "b", "c", "d", "", "+"]
    for _ in range(nfilters):
        n = rng.randint(1, 6)
        levels = [rng.choice(words) for _ in range(n)]
        if rng.random() < 0.3:
            levels[-1] = "#"
        f = "/".join(levels)
        if filter_valid(f):
            fids[table.add(f)] = f
    return table, fids, rng


@pytest.mark.parametrize("dp,fp", [(1, 8), (2, 4), (8, 1)])
def test_sharded_agrees_with_single(dp, fp):
    assert len(jax.devices()) == 8
    table, fids, rng = build_random_table(23)
    mesh = make_mesh(dp=dp, fp=fp)
    sharded = ShardedMatcher(table, mesh)
    single = TpuMatcher(table)

    topics = [
        "/".join(rng.choice(["a", "b", "c", "d", ""]) for _ in range(rng.randint(1, 6)))
        for _ in range(64)
    ]
    ttok, tlen, td = table.encode_topics(topics)
    packed_sh, counts = sharded.match_encoded(ttok, tlen, td)
    packed_sh = np.asarray(packed_sh)
    packed_1 = np.asarray(single.match_encoded(ttok, tlen, td))
    assert np.array_equal(packed_sh, packed_1)
    # psum'd counts equal the bitmap popcount and the oracle
    rows = unpack_bitmap(packed_1, nrows=table.capacity)
    for j, topic in enumerate(topics):
        expect = sorted(fid for fid, f in fids.items() if match_filter(f, topic))
        assert rows[j].tolist() == expect
        assert int(counts[j]) == len(expect)


def test_sharded_partitioned_matches_oracle():
    """Flagship partitioned matcher over the 8-device mesh (batch sharded,
    table replicated) agrees with the single-device matcher and the trie
    oracle."""
    import random

    from rmqtt_tpu.core.topic import filter_valid, match_filter
    from rmqtt_tpu.ops.partitioned import PartitionedMatcher, PartitionedTable
    from rmqtt_tpu.parallel.sharded import ShardedPartitionedMatcher, make_mesh

    rng = random.Random(77)
    table = PartitionedTable()
    fids = {}
    words = ["a", "b", "c", "d", "", "+"]
    while len(fids) < 1200:
        levels = [rng.choice(words) for _ in range(rng.randint(1, 6))]
        if rng.random() < 0.3:
            levels[-1] = "#"
        f = "/".join(levels)
        if filter_valid(f):
            fids[table.add(f)] = f
    mesh = make_mesh(dp=2, fp=4)
    sharded = ShardedPartitionedMatcher(table, mesh)
    single = PartitionedMatcher(table)
    topics = [
        "/".join(rng.choice(["a", "b", "c", "x", ""]) for _ in range(rng.randint(1, 6)))
        for _ in range(96)
    ] + ["$sys/x"]
    got = sharded.match(topics)
    ref = single.match(topics)
    for topic, row, srow in zip(topics, ref, got):
        expect = sorted(fid for fid, f in fids.items() if match_filter(f, topic))
        assert row.tolist() == expect, topic
        assert srow.tolist() == expect, topic


def test_broker_with_mesh_router():
    """A full broker whose XlaRouter runs the mesh-sharded partitioned
    matcher (explicit mesh — the 'auto' gate engages only on multi-chip
    TPU): pub/sub over real sockets routes through all 8 virtual devices."""
    import asyncio

    from rmqtt_tpu.broker.codec import packets as pk
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.broker.server import MqttBroker
    from rmqtt_tpu.parallel.sharded import ShardedPartitionedMatcher, make_mesh
    from rmqtt_tpu.router.xla import XlaRouter

    from tests.mqtt_client import TestClient

    async def run():
        ctx = ServerContext(BrokerConfig(port=0))
        router = XlaRouter(
            is_online=lambda cid: (
                ctx.registry.get(cid) is not None and ctx.registry.get(cid).connected
            ),
            mesh=make_mesh(dp=2, fp=4),
        )
        assert isinstance(router.matcher, ShardedPartitionedMatcher)
        ctx.router = router
        ctx.routing.router = router
        b = MqttBroker(ctx)
        await b.start()
        try:
            sub = await TestClient.connect(b.port, "mesh-sub")
            await sub.subscribe("m/+/t", "m/#", qos=1)
            pub = await TestClient.connect(b.port, "mesh-pub")
            await pub.publish("m/a/t", b"via-mesh", qos=1)
            got = [await sub.recv(timeout=30), await sub.recv(timeout=30)]
            assert all(p.payload == b"via-mesh" for p in got)
            await sub.disconnect_clean()
            await pub.disconnect_clean()
        finally:
            await b.stop()

    asyncio.run(asyncio.wait_for(run(), 120))


def test_sharded_global_compaction_and_regrow():
    """Sharded per-device global compaction == oracle, and a forced
    per-shard budget overflow regrows and still returns exact results."""
    import random

    from rmqtt_tpu.core.topic import filter_valid, match_filter
    from rmqtt_tpu.ops.partitioned import PartitionedTable
    from rmqtt_tpu.parallel.sharded import ShardedPartitionedMatcher, make_mesh

    rng = random.Random(91)
    table = PartitionedTable()
    fids = {}
    words = ["a", "b", "", "+"]
    while len(fids) < 600:
        levels = [rng.choice(words) for _ in range(rng.randint(1, 5))]
        if rng.random() < 0.35:
            levels[-1] = "#"
        f = "/".join(levels)
        if filter_valid(f):
            fids[table.add(f)] = f
    mesh = make_mesh(dp=2, fp=4)
    topics = [
        "/".join(rng.choice(["a", "b", "x", ""]) for _ in range(rng.randint(1, 5)))
        for _ in range(64)
    ]
    mg = ShardedPartitionedMatcher(table, mesh)
    got_g = mg.match(topics)
    for topic, g in zip(topics, got_g):
        expect = sorted(fid for fid, f in fids.items() if match_filter(f, topic))
        assert g.tolist() == expect, topic
    # force a per-shard overflow and re-match: sticky regrow, same results
    for key in list(mg._budgets):
        mg._budgets[key] = 2
    got_o = mg.match(topics)
    assert all(v >= 256 for v in mg._budgets.values())
    for g, o in zip(got_g, got_o):
        assert g.tolist() == o.tolist()
