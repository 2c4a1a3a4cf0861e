"""Partitions that outgrow ``SPLIT_ROWS`` are keyed one level deeper.

- on seeded Unified-Namespace-shaped tables (``ent/site/area/line/cell/
  device/tag``, three nearly constant top levels) splits happen at levels
  3, 4 and 5, and the partitioned matcher answers what the program's trie
  (``rmqtt_tpu/core/trie.py``) answers;
- the Python encode path and the native encoder give the same candidate
  lists on those tables;
- churn across a split — adding past the size, removing below it,
  re-adding, a compaction install, and a split while a compaction build is
  in flight — keeps both equalities, and every fid names the filter it was
  given for;
- a compaction install brings the padded NC that a scattered load grew
  back to its floor;
- the existing generators' tables keep their three-level keys at small
  size, and a retained-topic table never splits.
"""

import random
import sys
from pathlib import Path

import numpy as np
import pytest

from rmqtt_tpu.core.topic import split_levels
from rmqtt_tpu.core.trie import TopicTree
from rmqtt_tpu.ops import partitioned as P
from rmqtt_tpu.ops.partitioned import (
    CHUNK,
    SPLIT_ROWS,
    PartitionedMatcher,
    PartitionedTable,
    partition_key,
    topic_partitions,
)
from rmqtt_tpu.ops.retained_part import RetainedTable

from tests.test_partitioned import _encode_both, _native_or_skip

BENCH = Path(__file__).resolve().parent.parent / "benchmark"

TAGS = ["temp", "pres", "vib", "flow", "lvl", "spd", "amp", "volt"]


def uns_filters(seed: int) -> list:
    """A small plant: one enterprise, two sites, one area each, three lines
    of 16 cells of 8 devices; the four consumer kinds (line ``#``, cell
    ``+``/tag, device ``#``, a seeded third of the exact tags), one cell
    with 150 devices (so a cell splits too), and the filters that end at a
    split level, put ``#`` there or ``+`` anywhere."""
    rng = random.Random(seed)
    rows = set()
    for s in range(2):
        for ln in range(3):
            line = f"ent/s{s}/a0/l{ln}"
            rows.add(line + "/#")
            for c in range(16):
                cell = f"{line}/c{c}"
                rows.update(f"{cell}/+/{t}" for t in TAGS)
                devices = 150 if (s, ln, c) == (0, 1, 3) else 8
                for d in range(devices):
                    rows.add(f"{cell}/d{d}/#")
                    rows.update(f"{cell}/d{d}/{t}" for t in TAGS if rng.random() < 0.35)
    rows.update(["ent/s0/a0", "ent/s0/a0/#", "ent/s0/a0/l1", "ent/s0/a0/l1/+",
                 "ent/s0/a0/+/c3/+/temp", "ent/s0/a0/+/#", "ent/+/a0/l0/c1/#",
                 "+/s0/a0/l1/c3/d5/temp", "ent/s0/a0/l1/c3/+/+", "ent/s0/a0/l1/c3/d9",
                 "ent/s0/a0/l1/c3/d9/temp/x", "#", "ent/#", "+/+/+/+/+/+/vib"])
    return sorted(rows)


def uns_topics(seed: int, n: int = 160) -> list:
    rng = random.Random(seed + 1)
    topics = []
    for _ in range(n):
        s, ln, c = rng.randrange(2), rng.randrange(3), rng.randrange(16)
        d = rng.randrange(150 if (s, ln, c) == (0, 1, 3) else 8)
        topics.append(f"ent/s{s}/a0/l{ln}/c{c}/d{d}/{rng.choice(TAGS)}")
    return topics + [
        "ent/s0/a0", "ent/s0/a0/l1", "ent/s0/a0/l1/c3", "ent/s0/a0/l1/c3/d9",
        "ent/s0/a0/l1/c3/d9/temp/x", "ent/s0/a0/l1/c3/d9/temp/x/y", "ent/s0/a0/lX/c3/d9/temp",
        "ent/s0/a0/l1/+/d9/temp", "ent/s1/a0/l2/c15/d7/volt", "$SYS/s0/a0/l1/c3/d5/temp",
        "ent/s0/a0/l1/c3//temp", "ent", "ent/s0"]


def build(filters, **attrs):
    table = PartitionedTable()
    for k, v in attrs.items():
        setattr(table, k, v)
    fids = {table.add(f): f for f in filters}
    return table, fids


def assert_oracle(table, fids, topics, matcher=None):
    """The partitioned matcher's fids per topic are the trie's, and every
    live fid still names the filter it was given for."""
    tree = TopicTree()
    for fid, f in fids.items():
        tree.insert(f, fid)
    got = (matcher or PartitionedMatcher(table)).match(topics)
    for t, row in zip(topics, got):
        want = sorted(v for _lv, vs in tree.matches(t) for v in vs)
        assert sorted(row.tolist()) == want, t
    assert {fid: "/".join(table._filter_of_fid(fid)) for fid in fids} == fids


def split_depths(table) -> set:
    return {len(k) - 1 for k in table._split_keys}


@pytest.mark.parametrize("seed", [3, 17, 2**31 + 5])
def test_splits_at_several_depths_match_the_trie(seed):
    table, fids = build(uns_filters(seed))
    assert split_depths(table) == {3, 4, 5}
    # every unsplit partition holds at most SPLIT_ROWS rows
    rows = {}
    for key in table._key_of_fid.values():
        rows[key] = rows.get(key, 0) + 1
    assert max(n for k, n in rows.items() if k not in table._split_keys) <= SPLIT_ROWS
    # a filter's key is partition_key's under the table's splits, and a
    # matching topic's walk names it
    for fid, f in fids.items():
        assert table._key_of_fid[fid] == partition_key(split_levels(f), table._split_keys)
    topics = uns_topics(seed)
    tree = TopicTree()
    for fid, f in fids.items():
        tree.insert(f, fid)
    for t in topics:
        parts = set(topic_partitions(split_levels(t), table._split_keys))
        for _lv, vs in tree.matches(t):
            for fid in vs:
                assert table._key_of_fid[fid] in parts, (fids[fid], t)
    assert_oracle(table, fids, topics)


@pytest.mark.parametrize("seed", [3, 17])
def test_python_and_native_encoders_agree_on_split_tables(seed):
    table, _fids = build(uns_filters(seed))
    _native_or_skip(table)
    topics = uns_topics(seed)
    _encode_both(table, topics, pad=256)
    _encode_both(table, topics[:9])
    # a split deep below a three-level prefix: a topic's cache key reads
    # as many levels as the deepest split key
    assert table._key_depth == 6
    assert table._nenc.splits_synced == len(table._split_keys)


def test_the_split_cuts_candidate_chunks_where_the_top_levels_select_nothing():
    table, _ = build(uns_filters(3))
    flat, _ = build(uns_filters(3), split_rows=None)
    topics = uns_topics(3)[:160]
    nc = [len(table._candidates_for(split_levels(t))) for t in topics]
    nc_flat = [len(flat._candidates_for(split_levels(t))) for t in topics]
    # a few wildcard partitions of their own stay beside the topic's cell
    assert 2 * max(nc) < min(nc_flat)
    assert not flat._split_keys


# ------------------------------------------------------------ churn
def _churn_filters(n: int, prefix: str = "ent/s0/a0") -> list:
    return [f"{prefix}/l{i % 3}/c{i // 3 % 40}/d{i // 120}/t" for i in range(n)]


STEPS = ["add_past", "remove_below", "readd", "compact", "split_during_build"]


@pytest.mark.parametrize("upto", STEPS)
def test_churn_across_a_split(upto):
    """Run the churn sequence up to ``upto``; after each step the matcher
    answers the trie's fids, the two encoders agree, and no fid moved to
    another filter."""
    table = PartitionedTable()
    table.compact_async = False
    fids = {table.add(f): f for f in ("#", "ent/s0/a0/#", "ent/s0/a0")}
    m = PartitionedMatcher(table)
    _native_or_skip(table)
    key = ("4", "ent", "s0", "a0")
    topics = [f"ent/s0/a0/l{i % 3}/c{i % 40}/d{i % 5}/t" for i in range(48)] + [
        "ent/s0/a0", "ent/s0/a0/l1", "ent/s0/a0/l9/c1/d1/t", "ent/s0/a0/l2/c7/d0/x3",
        "ent/s0/a0/l2/c7/d1/x500"]

    def check():
        assert_oracle(table, fids, topics, m)
        _encode_both(table, topics)

    # add past the size: the key holds SPLIT_ROWS rows, the next add splits it
    batch = _churn_filters(SPLIT_ROWS - 2)
    fids.update({table.add(f): f for f in batch})
    assert key not in table._split_keys and table._excl_rows[key] == SPLIT_ROWS
    check()
    before = dict(table._key_of_fid)
    fids[table.add("ent/s0/a0/l0/c0/d99/t")] = "ent/s0/a0/l0/c0/d99/t"
    assert key in table._split_keys and key not in table._excl_chunks
    moved = [fid for fid in before if table._key_of_fid[fid] != before[fid]]
    # every row of the key but the two that end at its split level
    assert len(moved) == SPLIT_ROWS - 2 and {before[fid] for fid in moved} == {key}
    assert table._key_of_fid[min(fids)] == ("#",)
    check()
    if upto == "add_past":
        return
    # remove below it: the split stays, the rows go
    for fid in list(fids)[3:3 + SPLIT_ROWS // 2 + 60]:
        table.remove(fid)
        del fids[fid]
    assert key in table._split_keys
    check()
    if upto == "remove_below":
        return
    # re-add: the same filters come back under the children
    again = [f for f in batch if f not in set(fids.values())]
    fids.update({table.add(f): f for f in again})
    assert all(len(table._key_of_fid[fid]) == 5 for fid, f in fids.items()
               if f.count("/") == 6)
    check()
    if upto == "readd":
        return
    # a compaction install: every row moves, fids and keys stay
    keys = dict(table._key_of_fid)
    table.compact()
    assert table._key_of_fid == keys
    check()
    if upto == "compact":
        return
    # a split while a compaction build is in flight: the install replays it
    # as a remove from the split key and an add under its child
    deep = [f"ent/s0/a0/l2/c7/d{d}/x{i}" for d in range(2) for i in range(SPLIT_ROWS)]
    line = ("5", "ent", "s0", "a0", "l2")
    held = sum(k == line for k in table._key_of_fid.values())
    fids.update({table.add(f): f for f in deep[:SPLIT_ROWS - held]})
    assert table._excl_rows[line] == SPLIT_ROWS and line not in table._split_keys
    build_state = P._build_compact_state

    def build_with_adds(*args):
        state = build_state(*args)
        fids.update({table.add(f): f for f in deep[SPLIT_ROWS - held:]})
        assert line in table._split_keys
        return state

    P._build_compact_state = build_with_adds
    done = table.compactions
    try:
        table.compact()
    finally:
        P._build_compact_state = build_state
    assert table.compactions == done + 1 and table._compact_journal is None
    check()


def test_a_compaction_install_lowers_the_padded_nc():
    """Rows loaded far apart (each cell's rows between every other cell's,
    as a fleet subscribing connection by connection sends them) scatter a
    partition over many shared chunks and grow the padded NC past its
    floor; the compaction that gathers them brings it back down, on both
    encoders, and the answers stay the trie's."""
    rows = sorted((d, t, ln, c) for ln in range(6) for c in range(16)
                  for d in range(12) for t in TAGS)
    table = PartitionedTable()
    table.compact_async = False
    fids = {table.add(f"ent/s0/a0/l{ln}/c{c}/d{d}/{t}"): f"ent/s0/a0/l{ln}/c{c}/d{d}/{t}"
            for d, t, ln, c in rows}
    _native_or_skip(table)
    topics = [f"ent/s0/a0/l{i % 6}/c{i % 16}/d{i % 12}/{TAGS[i % 8]}" for i in range(96)]
    native, _py = _encode_both(table, topics)
    assert native[4] > P.NC_FLOOR
    table.compact()
    native, _py = _encode_both(table, topics)
    assert native[4] == P.NC_FLOOR
    assert_oracle(table, fids, topics)


# ------------------------------------------------------------ no split
def _generator(name: str, n: int, seed: int = 5):
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    from harness import generators

    return generators.load(name)(seed, {"subscriptions": n})


@pytest.mark.parametrize("name, n", [("mixed_tree", 20000), ("single_plus", 20000),
                                     ("exact_one_each", 20000)])
def test_the_existing_generators_keep_their_keys(name, n):
    """At small size no partition of these tables reaches SPLIT_ROWS: every
    key is the three-level one, and every topic's candidates are those of
    a table that never splits."""
    gen = _generator(name, n)
    filters = gen.filters()
    table, fids = build(filters)
    flat, _ = build(filters, split_rows=None)
    assert not table._split_keys and table._key_depth == 3
    assert all(table._key_of_fid[fid] == partition_key(split_levels(f))
               for fid, f in fids.items())
    it = gen.topic_stream(11)
    for _ in range(2000):
        t = split_levels(next(it))
        assert np.array_equal(table._candidates_for(t), flat._candidates_for(t))


def test_a_retained_table_never_splits():
    """The retained-topic scan's inverse index names three-level keys: its
    table keeps them however large a partition grows."""
    table = RetainedTable()
    fids = [table.add(f"plant/a/b/dev{i}/temp") for i in range(SPLIT_ROWS + CHUNK)]
    assert not table._split_keys
    assert {table._key_of_fid[f] for f in fids} == {("4", "plant", "a", "b")}
    assert len(table.candidates_for_filter("plant/a/+/dev7/#")) >= 5
