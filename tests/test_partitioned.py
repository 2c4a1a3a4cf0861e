"""Partitioned matcher must agree with the direct matcher and dense kernel."""

import random

import numpy as np
import pytest

from rmqtt_tpu.core.topic import filter_valid, match_filter
from rmqtt_tpu.ops.partitioned import (
    CHUNK,
    PartitionedMatcher,
    PartitionedTable,
    partition_key,
    topic_partitions,
)


def test_partition_key_shapes():
    assert partition_key(["#"]) == ("#",)
    assert partition_key(["a"]) == ("1", "a")
    assert partition_key(["+"]) == ("1", "+")
    assert partition_key(["a", "#"]) == ("2", "a")
    assert partition_key(["+", "#"]) == ("2", "+")
    assert partition_key(["a", "b"]) == ("2E", "a", "b")
    assert partition_key(["", "+"]) == ("2E", "", "+")
    assert partition_key(["a", "+", "#"]) == ("H3", "a", "+")
    assert partition_key(["a", "b", "c"]) == ("4", "a", "b", "c")
    assert partition_key(["a", "+", "c", "d", "#"]) == ("4", "a", "+", "c")
    assert partition_key(["a", "b", "+"]) == ("4", "a", "b", "+")


def test_topic_partition_coverage_brute_force():
    """Every valid filter's partition must be in its matching topics' lists."""
    rng = random.Random(4)
    words = ["a", "b", "", "+"]
    filters = set()
    for _ in range(600):
        n = rng.randint(1, 4)
        levels = [rng.choice(words) for _ in range(n)]
        if rng.random() < 0.4:
            levels[-1] = "#"
        f = "/".join(levels)
        if filter_valid(f):
            filters.add(f)
    topics = set()
    for _ in range(300):
        n = rng.randint(1, 5)
        topics.add("/".join(rng.choice(["a", "b", "c", ""]) for _ in range(n)))
    for t in topics:
        tl = t.split("/")
        parts = set(topic_partitions(tl))
        for f in filters:
            if match_filter(f, t):
                assert partition_key(f.split("/")) in parts, (f, t)


def build_random(seed, n):
    rng = random.Random(seed)
    table = PartitionedTable()
    fids = {}
    words = ["a", "b", "c", "d", "", "+"]
    for _ in range(n):
        depth = rng.randint(1, 6)
        levels = [rng.choice(words) for _ in range(depth)]
        if rng.random() < 0.3:
            levels[-1] = "#"
        f = "/".join(levels)
        if filter_valid(f):
            fids[table.add(f)] = f
    return table, fids, rng


def test_partitioned_differential():
    table, fids, rng = build_random(31, 2500)
    matcher = PartitionedMatcher(table)
    topics = [
        "/".join(rng.choice(["a", "b", "c", "d", "e", "", "$s"]) for _ in range(rng.randint(1, 7)))
        for _ in range(128)
    ]
    got = matcher.match(topics)
    for topic, row in zip(topics, got):
        expect = sorted(fid for fid, f in fids.items() if match_filter(f, topic))
        assert sorted(row.tolist()) == expect, topic


def test_partitioned_churn():
    table, fids, rng = build_random(33, 800)
    matcher = PartitionedMatcher(table)
    for round_ in range(4):
        for fid in rng.sample(sorted(fids), len(fids) // 3):
            table.remove(fid)
            del fids[fid]
        for _ in range(150):
            depth = rng.randint(1, 5)
            levels = [rng.choice(["a", "b", "x", "", "+"]) for _ in range(depth)]
            if rng.random() < 0.3:
                levels[-1] = "#"
            f = "/".join(levels)
            if filter_valid(f):
                fids[table.add(f)] = f
        topics = ["/".join(rng.choice(["a", "b", "x", "y", ""]) for _ in range(rng.randint(1, 5))) for _ in range(48)]
        got = matcher.match(topics)
        for topic, row in zip(topics, got):
            expect = sorted(fid for fid, f in fids.items() if match_filter(f, topic))
            assert sorted(row.tolist()) == expect, f"round {round_}: {topic}"


def test_partitioned_overflow_rerun():
    table = PartitionedTable()
    fids = [table.add(f"a/s{i}/#") for i in range(300)]
    # all 300 share partition ("3","a",...)? no — distinct s{i} partitions;
    # use '+' to concentrate matches instead:
    table2 = PartitionedTable()
    fids2 = [table2.add("a/+/#") for _ in range(300)]
    m = PartitionedMatcher(table2)
    (row,) = m.match(["a/b/c"])
    assert len(row) == 300
    # a lone topic's shape starts at 256 slots: the budget regrew, sticky
    assert max(m._budgets.values()) >= 512


def test_deep_filter_and_topic():
    table = PartitionedTable()
    f1 = table.add("a/#")
    deep_filter = "/".join(["x"] * 12) + "/#"
    f2 = table.add(deep_filter)
    m = PartitionedMatcher(table)
    deep_topic = "/".join(["x"] * 14)
    (r1,) = m.match([deep_topic])
    assert r1.tolist() == [f2]
    (r2,) = m.match(["a/" + "/".join(str(i) for i in range(20))])
    assert r2.tolist() == [f1]


def test_jit_signature_stability_under_churn():
    """Table growth/churn must not thrash XLA compiles: device-array chunk
    counts are pow2-bucketed (floor 64) and NC/B/slot budgets are
    pow2-bucketed, so a steady add/remove workload pins a handful of jit
    signatures of the programs the matcher serves with."""
    import random

    from rmqtt_tpu.core.topic import filter_valid
    from rmqtt_tpu.ops.partitioned import _match_fused, _match_fused_grouped

    def programs():
        return _match_fused._cache_size() + _match_fused_grouped._cache_size()

    rng = random.Random(7)
    table = PartitionedTable()
    matcher = PartitionedMatcher(table)
    fids = []
    words = ["a", "b", "c", "d", "e", "+"]

    def add_some(n):
        while n:
            levels = [rng.choice(words) for _ in range(rng.randint(1, 5))]
            if rng.random() < 0.3:
                levels[-1] = "#"
            f = "/".join(levels)
            if filter_valid(f):
                fids.append(table.add(f))
                n -= 1

    add_some(200)
    topics = ["/".join(rng.choice(words[:5]) for _ in range(rng.randint(1, 5))) for _ in range(32)]
    matcher.match(topics)
    assert matcher._fused is True  # the first batch verified the pipeline
    base = programs()
    # churn: interleave adds/removes with matches across many rounds
    for round_ in range(30):
        add_some(40)
        for _ in range(15):
            fids.remove(f := rng.choice(fids))
            table.remove(f)
        matcher.match(
            ["/".join(rng.choice(words[:5]) for _ in range(rng.randint(1, 5))) for _ in range(32)]
        )
    assert matcher.fused_batches >= 30
    grown = programs() - base
    # buckets are sticky + pow2, so signatures grow log-bounded with table
    # size (the workload grows the table ~7x => a few nc/budget steps),
    # never per-round (30 rounds must NOT mean ~30 compiles)
    assert grown <= 4, f"churn thrashed XLA compiles: {grown} new signatures"


def _native_or_skip(table):
    """The table's native encoder (built on first encode), or skip."""
    table.encode_topics(["warm"])
    if not table._nenc:
        pytest.skip("native runtime unavailable")
    return table._nenc


def _encode_both(table, topics, pad=None):
    """Encode ``topics`` natively, then through ``_encode_py`` on the same
    table state (the plain reference); assert the encode tuples are
    bit-identical and return both (with groups)."""
    enc = table._nenc
    assert enc, "native encoder not serving"
    native = table.encode_topics(topics, pad_batch_to=pad, with_groups=True)
    table._nenc = False
    try:
        py = table.encode_topics(topics, pad_batch_to=pad, with_groups=True)
    finally:
        table._nenc = enc
    for a, b, name in zip(native[:4], py[:4], ["ttok", "tlen", "tdollar", "chunk_ids"]):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.array_equal(a, b), name
    assert native[4] == py[4]
    return native, py


def _mixed_stream():
    rng = random.Random(11)
    filters = set()
    words = ["a", "b", "c", "", "+", "sensor", "ünïcode"]
    while len(filters) < 500:
        levels = [rng.choice(words) for _ in range(rng.randint(1, 6))]
        if rng.random() < 0.25:
            levels[-1] = "#"
        f = "/".join(levels)
        if filter_valid(f):
            filters.add(f)
    topics = [
        "/".join(rng.choice(["a", "b", "c", "", "sensor", "ünïcode", "$sys"]) for _ in range(rng.randint(1, 6)))
        for _ in range(200)
    ] + ["$sys/x", "", "a"]
    return sorted(filters), topics


def _single_plus_stream():
    """cfg2's shape: depth 3-5 filters with one '+', and a publish stream in
    which (nearly) every topic has a 3-level prefix of its own — plus the
    short, '$', empty-level and unknown-token topics the walk must get
    right without a cache in front of it."""
    rng = random.Random(7)
    filters = set()
    while len(filters) < 1500:
        depth = rng.randint(3, 5)
        levels = [f"l{d}n{rng.randrange(40 * (d + 1))}" for d in range(depth)]
        levels[rng.randrange(depth)] = "+"
        filters.add("/".join(levels))
    filters = sorted(filters)
    # short / wildcard-first / '#' / '$' filters so every partition kind exists
    filters += ["#", "+", "l0n1", "l0n1/#", "+/#", "l0n2/l1n3", "+/l1n3", "l0n2/+",
                "l0n2/l1n3/#", "+/+/#", "$SYS/#", "$SYS/+/x", "/+/x", "//", "l0n1//l2n5"]
    topics = []
    for i in range(400):
        f = rng.choice(filters[:1500]).split("/")
        topics.append("/".join(
            f"l{d}n{rng.randrange(40 * (d + 1))}" if lev == "+" else lev
            for d, lev in enumerate(f)))
    topics += ["l0n1", "l0n2/l1n3", "nosuch", "nosuch/l1n3", "l0n2/nosuch/l2n1",
               "nosuch/never/seen/before", "$SYS/broker/x", "$SYS", "", "/", "//",
               "/l1n3/x", "l0n1//l2n5", "l0n1/l1n1/", "+/l1n3", "l0n2/+/x", "#",
               "l0n1/l1n2/l2n3/l3n4/l4n5/l5n6/l6n7/l7n8/l8n9/deeper/than/max_levels"]
    return filters, topics


@pytest.mark.parametrize("stream", [_mixed_stream, _single_plus_stream],
                         ids=["mixed", "single_plus_all_new_prefixes"])
def test_native_encode_matches_python_path(stream):
    """The C++ encoder (runtime/encode.cc) must agree bit-for-bit with the
    Python encode path on tokens, lengths, $-flags and candidate chunks —
    with nearly every topic a never-seen 3-level prefix as much as on a
    stream that repeats them."""
    filters, topics = stream()
    table = PartitionedTable()
    for f in filters:
        table.add(f)
    _native_or_skip(table)
    _encode_both(table, topics, pad=512)
    _encode_both(table, topics[:7])  # unpadded, second pass over seen prefixes
    table.compact()
    _encode_both(table, topics, pad=512)


def test_native_encode_follows_mutations():
    """The native partition mirror is synced lazily: an encode after every
    kind of mutation must see it (a stale mirror fails the parity)."""
    table = PartitionedTable()
    table.compact_async = False
    base = [table.add(f) for f in ("a/b/c", "a/+/c", "+/b/#", "x/y", "#")]
    _native_or_skip(table)
    topics = ["a/b/c", "a/q/c", "z/b/c/d", "x/y", "x", "big/k/7", "big/k/9/z", "n/e/w"]
    _encode_both(table, topics)
    # add: a brand-new partition, and one more row in an existing one
    new = [table.add("n/e/w"), table.add("a/b/c/d")]
    nat, _ = _encode_both(table, topics)
    assert nat[3][topics.index("n/e/w")].any()
    # remove: the partition's last shared row goes, its chunk list empties
    table.remove(new[0])
    table.remove(base[3])
    _encode_both(table, topics)
    # many new one-row partitions, then one partition that outgrows its
    # shared chunks and migrates to an exclusive one (_alloc_row)
    big = [table.add(f"big/k/{i}") for i in range(CHUNK + 3)]
    _encode_both(table, topics)
    wide = [table.add(f"w/w/w/{i}") for i in range(CHUNK + 3)]
    assert table._excl_chunks.get(("4", "w", "w", "w")), "no shared->exclusive migration"
    assert ("4", "w", "w", "w") not in table._shared_chunks_of
    _encode_both(table, topics + ["w/w/w/5", "w/w/w"])
    # frees inside the exclusive chunk, then re-adds
    for fid in wide[:40]:
        table.remove(fid)
    _encode_both(table, topics + ["w/w/w/5"])
    # compaction install: wholesale relayout, chunk ids all change
    epoch = table.layout_epoch
    table.compact()
    assert table.layout_epoch == epoch + 1
    _encode_both(table, topics + ["w/w/w/77"])
    for fid in big[:10]:
        table.remove(fid)
    table.add("after/compact/x")
    _encode_both(table, topics + ["after/compact/x"])
    # force_full_refresh bumps the epoch without moving a row
    table.force_full_refresh()
    _encode_both(table, topics)


def test_native_encode_groups():
    """``groups``: equal gid ⇒ equal candidate row; padded rows 0; real rows
    positive. (Native ids are batch-local, python's come from its cache, so
    only the contract is compared, not the ids.)"""
    filters, topics = _single_plus_stream()
    table = PartitionedTable()
    for f in filters:
        table.add(f)
    _native_or_skip(table)
    topics = topics + topics[:50] + ["l0n1/l1n1/l2n1/a", "l0n1/l1n1/l2n1/b", "nosuch/x", "other/x"]
    for enc in _encode_both(table, topics, pad=1024):
        chunk_ids, groups = np.asarray(enc[3]), np.asarray(enc[5])
        assert groups.shape == (1024,)
        assert (groups[: len(topics)] > 0).all() and (groups[len(topics):] == 0).all()
        first = {}
        for j, g in enumerate(groups[: len(topics)].tolist()):
            assert np.array_equal(chunk_ids[j], chunk_ids[first.setdefault(g, j)]), (j, g)
    nat = table.encode_topics(topics, with_groups=True)[5]
    # repeats of a prefix share a gid (the grouped upload's whole point)
    assert nat[len(topics) - 4] == nat[len(topics) - 3]
    for j in range(50):
        assert nat[400 + 18 + j] == nat[j]


def test_encode_counters_and_call_counts():
    """``encode_host_resolved`` stays 0 under the native encoder however
    many prefixes are new, and counts every topic under ``_encode_py``; a
    native encode makes a number of native calls that does not depend on
    the batch; a subscribe or unsubscribe makes none."""
    filters, topics = _single_plus_stream()
    table = PartitionedTable()
    for f in filters:
        table.add(f)
    real = _native_or_skip(table)

    class Counting:
        """Stub encoder: counts every call that reaches the native side."""

        def __init__(self):
            self.calls = []

        def __getattr__(self, name):
            attr = getattr(real, name)
            if not callable(attr):
                return attr

            def call(*a, **k):
                self.calls.append(name)
                return attr(*a, **k)

            return call

        def __setattr__(self, name, value):
            if name == "calls":
                object.__setattr__(self, name, value)
            else:
                setattr(real, name, value)

    stub = Counting()
    table._nenc = stub
    t0, h0 = table.encode_topics_total, table.encode_host_resolved
    table.encode_topics(topics, pad_batch_to=512)  # all-new prefixes
    assert stub.calls == ["encode"], stub.calls
    assert table.encode_topics_total - t0 == len(topics)
    assert table.encode_host_resolved == h0
    # mutations: no native call, whatever they touch
    stub.calls = []
    fids = [table.add(f"churn/{i}/+") for i in range(200)]
    for fid in fids[:100]:
        table.remove(fid)
    table.add("brand/new/token/levels")
    assert stub.calls == []
    # the next encode syncs the new tokens in one call and the dirty
    # partitions in two (exclusive chunks, then shared), whatever their number
    table.encode_topics(topics, pad_batch_to=512)
    assert stub.calls == ["add_tokens", "parts_put", "parts_put", "encode"], stub.calls
    stub.calls = []
    table.encode_topics(topics[:3])
    table.encode_topics(topics * 3)
    assert stub.calls == ["encode", "encode"], stub.calls
    assert table.encode_host_resolved == h0
    # the python fallback resolves every topic on the host
    table._nenc = False
    t1 = table.encode_topics_total
    table.encode_topics(topics)
    assert table.encode_topics_total - t1 == len(topics)
    assert table.encode_host_resolved - h0 == len(topics)


def test_native_encode_survives_nul_in_filter_levels():
    """Token and partition sync are length-delimited: a filter level holding
    a NUL (nothing validates it away) must not shift any other token's id."""
    table = PartitionedTable()
    table.add("a\x00b/c/d")
    table.add("x/y/z")
    _native_or_skip(table)
    nat, _ = _encode_both(table, ["x/y/z", "q/y/z"])
    assert nat[3][0].any() and not nat[3][1].any()


def test_default_matcher_matches_oracle_through_churn():
    """The default matcher (lax scan, fused tail) must produce exactly
    ``match_filter``'s matches across a full add/remove/rematch workload."""
    import random

    from rmqtt_tpu.core.topic import filter_valid, match_filter

    rng = random.Random(21)
    table = PartitionedTable()
    fids = {}
    words = ["a", "b", "c", "d", "", "+"]
    while len(fids) < 400:
        levels = [rng.choice(words) for _ in range(rng.randint(1, 5))]
        if rng.random() < 0.3:
            levels[-1] = "#"
        f = "/".join(levels)
        if filter_valid(f):
            fids[table.add(f)] = f
    m = PartitionedMatcher(table)
    topics = [
        "/".join(rng.choice(["a", "b", "c", "x", ""]) for _ in range(rng.randint(1, 5)))
        for _ in range(64)
    ] + ["$sys/a"]
    got = m.match(topics)
    for topic, row in zip(topics, got):
        expect = sorted(fid for fid, f in fids.items() if match_filter(f, topic))
        assert sorted(row.tolist()) == expect, topic
    # churn then rematch through the same matcher
    for fid in list(fids)[:150]:
        table.remove(fid)
        del fids[fid]
    got = m.match(topics[:16])
    assert m.fused_batches >= 1  # served on the device, not by the reference
    for topic, row in zip(topics[:16], got):
        expect = sorted(fid for fid, f in fids.items() if match_filter(f, topic))
        assert sorted(row.tolist()) == expect, topic

def test_global_compaction_matches_oracle():
    """The batch-global compaction with the host decode (the fused
    pipeline's reference and fallback) must produce the oracle's routing
    results."""
    table, fids, rng = build_random(47, 2000)
    topics = [
        "/".join(rng.choice(["a", "b", "c", "d", "", "$m"]) for _ in range(rng.randint(1, 6)))
        for _ in range(96)
    ]
    mg = PartitionedMatcher(table)
    mg._fused = False  # what a failed verify (or RMQTT_FUSED=0) leaves
    got_g = mg.match(topics)
    assert mg.fused_batches == 0
    for topic, g in zip(topics, got_g):
        expect = sorted(fid for fid, f in fids.items() if match_filter(f, topic))
        assert g.tolist() == expect, topic


def test_global_budget_regrow():
    """A too-small slot budget must regrow (sticky) and still return exact
    results — total is computed from the untruncated mask on device."""
    table = PartitionedTable()
    expect = sorted(table.add("a/+/#") for _ in range(200))
    m = PartitionedMatcher(table)
    m.match(["a/0/0", "a/0/1"])  # the fused verify consumes the first batch
    m.match(["a/0/0", "a/0/1"])  # settle the steady 2-topic bucket
    bucket = min(m._budgets)  # the smallest bucket = the 2-topic one
    m._budgets[bucket] = 4  # force overflow: 200 matches span many words
    rows = m.match(["a/b/c", "a/x/y"])
    assert m._budgets[bucket] >= 256  # regrown for this batch size
    for row in rows:
        assert row.tolist() == expect
    # next batch goes through without a rerun at the grown budget
    (row,) = m.match(["a/q/r"])
    assert row.tolist() == expect


def test_routes_decode_native_matches_numpy():
    """rt_match_decode_routes (C++) vs the numpy route-decode oracle on
    random route-level global-compaction entries (incl. padded topics)."""
    import numpy as np

    from rmqtt_tpu import runtime as rt
    from rmqtt_tpu.ops.partitioned import (
        CHUNK,
        WORDS_PER_CHUNK,
        _native_decode_routes,
        _numpy_decode_routes,
    )

    if rt.load() is None:
        import pytest

        pytest.skip("native runtime unavailable")
    rng = np.random.default_rng(17)
    b, padded, nc, nchunks = 61, 64, 4, 16
    w_total = nc * WORDS_PER_CHUNK
    # per-topic counts over the real topics; padded tail stays 0
    cn = np.zeros(padded, dtype=np.int64)
    cn[:b] = rng.integers(0, 12, size=b)
    n = int(cn.sum())
    # routes are flat topic-major; within a topic ascending (widx, bitpos)
    routes = np.concatenate([
        np.sort(rng.choice(w_total * 32, size=int(c), replace=False))
        for c in cn if c
    ]).astype(np.uint32)
    assert routes.shape[0] == n
    chunk_ids = rng.integers(0, nchunks, size=(padded, nc)).astype(np.int32)
    fid_map = rng.integers(0, 1 << 31, size=nchunks * CHUNK).astype(np.int64)
    got = _native_decode_routes(routes, cn, chunk_ids, b, fid_map)
    assert got is not None
    want = _numpy_decode_routes(routes, cn, chunk_ids, b, fid_map)
    assert len(got) == len(want) == b
    for g, w in zip(got, want):
        assert g.tolist() == w.tolist()


def test_upload_dtype_narrowing():
    """ttok uploads as int16 / chunk_ids as uint16 (tlen int16) while ids fit, widen
    stickily to int32, and both widths route identically."""
    table = PartitionedTable()
    fid = table.add("a/b/c")
    ttok, tlen, _td, cand, _nc = table.encode_topics(["a/b/c", "x/y"])
    assert ttok.dtype == np.int16 and cand.dtype == np.uint16
    assert tlen.dtype == np.int16
    m = PartitionedMatcher(table)
    r1, r2 = m.match(["a/b/c", "x/y"])
    assert r1.tolist() == [fid] and r2.tolist() == []
    table._tok_wide = True
    table._cand_wide = True  # as if vocab/chunk ids outgrew uint16
    ttok, tlen, _td, cand, _nc = table.encode_topics(["a/b/c"])
    assert ttok.dtype == np.int32 and cand.dtype == np.int32
    (r1,) = m.match(["a/b/c"])
    assert r1.tolist() == [fid]


def test_hostile_topic_depth_clamped():
    """A pathologically deep topic (thousands of levels) must not wrap the
    int16 tlen — it routes exactly like any topic deeper than max_levels."""
    table = PartitionedTable()
    f_hash = table.add("#")
    f_pfx = table.add("a/#")
    f_exact = table.add("a/b")
    m = PartitionedMatcher(table)
    deep = "a/" + "/".join(str(i) for i in range(40000))
    (row,) = m.match([deep])
    assert row.tolist() == sorted([f_hash, f_pfx]) and f_exact not in row.tolist()


def test_grouped_upload_dedup_parity():
    """A batch of repeated topics (live-traffic shape: U collapses) goes
    through the grouped candidate upload and routes identically to distinct
    topics; the no-dedup gate keeps unique batches on the plain path."""
    table, fids, rng = build_random(53, 1500)
    m = PartitionedMatcher(table)
    hot = ["a/b/c", "a/b", "x/y/z"]
    topics = [hot[i % 3] for i in range(64)]  # U=3 << B
    rows = m.match(topics)
    for topic, row in zip(topics, rows):
        expect = sorted(fid for fid, f in fids.items() if match_filter(f, topic))
        assert row.tolist() == expect, topic
    # gate: mostly-unique batch must return None from _group_inputs
    import numpy as np

    uniq_groups = np.arange(64, dtype=np.int32)
    fake_cand = np.zeros((64, 4), dtype=np.uint16)
    assert m._group_inputs(uniq_groups, fake_cand) is None


def test_prewarm_compiles_one_program_and_latches_the_floor():
    """The padding rule in one sentence: a batch pads to its pow2 bucket,
    and to the sticky floor if that is larger. A fresh matcher's 1-topic
    submit has batch dimension 1; ``prewarm`` compiles exactly one match
    program (a lone topic padded to the floor) and afterwards the 1-topic
    submit takes that program."""
    from rmqtt_tpu.broker.devprof import DEVPROF
    from rmqtt_tpu.ops.partitioned import PREWARM_FLOOR

    def batch_dim(h):
        assert h[0] == "f", h[0]  # fused handles carry the batch inside
        return h[3][5].shape[0]   # the rerun arguments

    table = PartitionedTable()
    fid = table.add("a/b")
    m = PartitionedMatcher(table)
    m._fused = True  # skip the first-use verify: count the served program
    assert m._pad_floor == 1
    h = m.match_submit(["a/b"])
    assert batch_dim(h) == 1
    assert m.match_complete(h)[0].tolist() == [fid]

    was = DEVPROF.enabled
    DEVPROF.reset()
    DEVPROF.enabled = True
    def traced():
        return {k: v["traces"]
                for k, v in DEVPROF.snapshot()["compile"]["kernels"].items()
                if v["traces"]}

    try:
        m.prewarm()
        # one topic of eight rows: the deduplicated-candidate form
        assert traced() == {"match_fused_grouped": 1}
        assert m._pad_floor == PREWARM_FLOOR
        h = m.match_submit(["a/b"])
        assert batch_dim(h) == PREWARM_FLOOR
        assert m.match_complete(h)[0].tolist() == [fid]
        assert traced() == {"match_fused_grouped": 1}  # the warmed program
    finally:
        DEVPROF.enabled = was
        DEVPROF.reset()


def test_nc_split_dispatch_parity():
    """The bucketed split-dispatch path must return exactly the unsplit
    path's per-topic fid sets, in original topic order (incl. pow2 batch
    padding, overflow regrow, and per-bucket chunk-column slicing)."""
    import numpy as np

    table = PartitionedTable()
    # three-level keys only: a split would prune the two fat partitions
    # below, and this case needs their skew
    table.split_rows = None
    fids = {}
    # skew candidate counts: two fat partitions (several exclusive chunks
    # each) that deep "fat/x/k/..." topics pull together, vs tiny cold ones
    for i in range(700):
        fids[table.add(f"fat/+/k/f{i}")] = f"fat/+/k/f{i}"
        fids[table.add(f"fat/x/+/g{i}")] = f"fat/x/+/g{i}"
    for i in range(200):
        fids[table.add(f"cold{i}/a")] = f"cold{i}/a"
    for f in ("#", "fat/#", "+/+/#"):
        fids[table.add(f)] = f
    topics = []
    for i in range(1200):
        if i % 3 == 0:
            topics.append(f"fat/x/k/f{i % 700}")
        elif i % 3 == 1:
            topics.append(f"cold{i % 200}/a")
        else:
            topics.append(f"miss{i}/y/z")
    m_split = PartitionedMatcher(table)
    m_split.SPLIT_MIN_BATCH = 64  # force the split path at test sizes
    enc = table.encode_topics(topics)
    plan = m_split._split_plan(np.asarray(enc[3]), len(topics))
    assert plan is not None, "test workload failed to trigger the split plan"
    assert len([s for s in plan[1] if s]) >= 2, "expected >=2 buckets"
    got = m_split.match(topics)
    m_plain = PartitionedMatcher(table)
    m_plain._split = False
    want = m_plain.match(topics)
    from rmqtt_tpu.core.topic import match_filter
    for t, g, w in zip(topics, got, want):
        assert g.tolist() == w.tolist(), t
    # spot-check a sample against the semantic oracle too
    for t, g in list(zip(topics, got))[::97]:
        expect = sorted(fid for fid, f in fids.items() if match_filter(f, t))
        assert sorted(g.tolist()) == expect, t


def test_segmented_table_parity():
    """A table split across multiple device arrays (RMQTT_SEG_BYTES exceeded)
    must match exactly like the single-array path: local chunk remapping,
    per-segment NC trim, affine fid decode, and cross-segment merge."""
    import numpy as np

    rng = random.Random(5)
    table = PartitionedTable()
    fids = {}
    # enough distinct partitions to spread rows over many chunks
    for i in range(4000):
        f = f"seg{i % 97}/+/x{i % 53}/f{i}"
        fids[table.add(f)] = f
    for i in range(300):
        fids[table.add(f"seg{i % 97}/lit/x{i % 53}")] = f"seg{i % 97}/lit/x{i % 53}"
    for f in ("#", "+/+/#"):
        fids[table.add(f)] = f
    table.compact()
    topics = [f"seg{rng.randrange(97)}/lit/x{rng.randrange(53)}/f{rng.randrange(4000)}"
              for _ in range(500)] + [f"seg{rng.randrange(97)}/lit/x{rng.randrange(53)}"
                                      for _ in range(200)]
    m_plain = PartitionedMatcher(table)
    m_plain._split = False
    want = m_plain.match(topics)
    m_seg = PartitionedMatcher(table)
    # force many segments at test scale (bit-packed tiles shrank the table
    # ~2.75x, so the budget must shrink with them to still trigger)
    m_seg._seg_bytes = 1 << 14
    got = m_seg.match(topics)
    assert m_seg._segments is not None and len(m_seg._segments) >= 2, \
        "test did not exercise segmentation"
    for t, g, w in zip(topics, got, want):
        assert g.tolist() == w.tolist(), t
    # and against the semantic oracle on a sample
    from rmqtt_tpu.core.topic import match_filter
    for t, g in list(zip(topics, got))[::71]:
        expect = sorted(fid for fid, f in fids.items() if match_filter(f, t))
        assert sorted(g.tolist()) == expect, t
    # churn across the segment boundary keeps working (device rebuild)
    for fid in list(fids)[:500]:
        table.remove(fid)
        del fids[fid]
    got2 = m_seg.match(topics[:64])
    for t, g in zip(topics[:64], got2):
        expect = sorted(fid for fid, f in fids.items() if match_filter(f, t))
        assert sorted(g.tolist()) == expect, t


@pytest.mark.parametrize("shape,tight", [
    ("one_row_partitions", True),    # b1_1m_exact's table, small
    ("one_partition_in_two_chunks", False),
    ("a_partition_with_chunks_of_its_own", False),
    ("holes_after_removals", False),
])
def test_a_tight_bulk_load_is_not_rebuilt(shape, tight):
    """A rebuild that could tighten nothing is skipped (``_layout_is_tight``):
    a bulk load of one-row partitions packs full shared chunks by itself, and
    its compaction would only land in the first minute of traffic. Any
    partition with chunks of its own, spread over two shared chunks, or holes
    in the packing keeps the rebuild."""
    t = PartitionedTable()
    fids = [t.add(f"iot/{n}") for n in range(3000)]  # 3,000 one-row partitions
    if shape == "one_partition_in_two_chunks":
        # the last shared chunk has 56 slots left: 80 rows of one partition
        # take them and open another
        for k in range(80):
            t.add(f"fleet/site/x/{k}")
    elif shape == "a_partition_with_chunks_of_its_own":
        for k in range(200):
            t.add(f"fleet/site/x/{k}")
    elif shape == "holes_after_removals":
        for fid in fids[:400]:
            t.remove(fid)
    assert t.needs_compact()
    with t._mu:
        assert t._layout_is_tight() is tight
    t.compact_async = True
    started = t.maybe_compact_async()
    assert started is (not tight)
    if tight:
        assert t.dirty_ops == 0 and not t.needs_compact() and t.compactions == 0
    else:
        t._compact_thread.join(30)
        assert t.compactions == 1
    m = PartitionedMatcher(t)
    rows = m.match(["iot/2999", "iot/17" if shape != "holes_after_removals" else "iot/17x",
                    "fleet/site/x/3", "iot/none"])
    assert len(rows[0]) == 1 and len(rows[3]) == 0
    assert len(rows[2]) == (0 if shape in ("one_row_partitions", "holes_after_removals") else 1)


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_the_spread_count_follows_the_occupancy_map_under_churn(seed):
    """``_layout_is_tight`` reads ``_spread`` instead of walking the partition
    map (1M entries under ``b1_1m_exact``, on the dispatch path): the count
    of partitions in more than one shared chunk has to equal a recount after
    adds, removals, migrations to chunks of their own and compactions."""
    import random

    rng = random.Random(seed)
    t = PartitionedTable()
    live = []

    def recount():
        return sum(len(occ) > 1 for occ in t._shared_chunks_of.values())

    seen = 0
    for phase in range(6):
        for step in range(1500):
            if step % 100 == 0:
                seen = max(seen, t._spread)
                assert t._spread == recount(), (phase, step)
            if live and rng.random() < 0.4:
                t.remove(live.pop(rng.randrange(len(live))))
            else:
                # 10 partitions that grow to a few hundred rows (they spread
                # over shared chunks, then migrate) among one-row partitions
                part = rng.randrange(10) if rng.random() < 0.6 else rng.randrange(10**6)
                live.append(t.add(f"fleet/site/p{part}/{rng.randrange(10**9)}"))
        assert t._spread == recount(), phase
        if phase % 2:
            t._compact()
            assert t._spread == recount(), ("compacted", phase)
    # the churn did spread partitions over shared chunks, and moved some out
    assert seen > 0 and t._excl_chunks
    for fid in live:
        t.remove(fid)
    assert t._spread == recount() == 0
