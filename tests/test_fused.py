"""Fused on-device match→compact→decode pipeline + bit-packed tiles.

Tier-1 coverage for the fused device pipeline (ops/partitioned.py): an
interpret-mode smoke (chaos-matrix FAST_SUBSET style — fast enough to run
on every tier-1 pass), property tests pinning fused output == the lax
``scan_words_impl`` + ``compact_global_impl`` + host-decode reference
bit-exactly across randomized tables/topics in BOTH single-array and
segmented modes, the host-decode-never-entered pin, the verify+fallback
contract, and the bit-packed tile format's bitwise equivalence."""

import functools
import random

import numpy as np
import pytest

import rmqtt_tpu.ops.partitioned as P
from rmqtt_tpu.core.topic import filter_valid, match_filter
from rmqtt_tpu.ops.partitioned import (
    CHUNK,
    PartitionedMatcher,
    PartitionedTable,
    pack_device_rows,
    pack_device_rows_packed,
    scan_words_impl,
    scan_words_packed_impl,
)


def _random_table(rng, n, words=("a", "b", "c", "d", "", "+")):
    table = PartitionedTable()
    fids = {}
    while len(fids) < n:
        levels = [rng.choice(words) for _ in range(rng.randint(1, 5))]
        if rng.random() < 0.3:
            levels[-1] = "#"
        f = "/".join(levels)
        if filter_valid(f) and f not in set(fids.values()):
            fids[table.add(f)] = f
    return table, fids


def _random_topics(rng, n, words=("a", "b", "c", "x", "")):
    return ["/".join(rng.choice(words) for _ in range(rng.randint(1, 5)))
            for _ in range(n)] + ["$sys/a"]


def _oracle(fids, topic):
    return sorted(fid for fid, f in fids.items() if match_filter(f, topic))


def test_fused_smoke():
    """Fast tier-1 smoke: fused pipeline + packed tiles, one small batch
    against the semantic oracle."""
    rng = random.Random(2)
    table, fids = _random_table(rng, 120)
    m = PartitionedMatcher(table)
    topics = _random_topics(rng, 24)
    got = m.match(topics)
    assert m._fused is True, "fused pipeline did not pass its self-check"
    assert m._dev_playout is not None, "packed tiles did not engage"
    for topic, row in zip(topics, got):
        assert sorted(row.tolist()) == _oracle(fids, topic), topic
    assert m.fused_batches >= 1


@pytest.mark.parametrize("segmented", [False, True])
def test_fused_equals_reference_property(segmented):
    """Property: across randomized tables/topics (churn included), the
    fused matcher returns exactly what the forced-unfused reference
    (lax words → compact_global → host decode) and the semantic oracle
    return — single-array and segmented modes."""
    rng = random.Random(31 + segmented)
    for round_i in range(3):
        table, fids = _random_table(rng, 150 + 60 * round_i)
        m_fused = PartitionedMatcher(table)
        m_ref = PartitionedMatcher(table)
        m_ref._fused = False
        if segmented:
            m_fused._seg_bytes = 1 << 13
            m_ref._seg_bytes = 1 << 13
        topics = _random_topics(rng, 48)
        got = m_fused.match(topics)
        want = m_ref.match(topics)
        if segmented:
            assert m_fused._segments is not None and len(m_fused._segments) > 1
        else:
            assert m_fused._fused is True
        for topic, g, w in zip(topics, got, want):
            assert g.tolist() == w.tolist(), topic
            assert sorted(g.tolist()) == _oracle(fids, topic), topic
        # churn, then re-match through both (delta refresh incl. fid rows)
        for fid in list(fids)[: len(fids) // 3]:
            table.remove(fid)
            del fids[fid]
        got = m_fused.match(topics[:16])
        want = m_ref.match(topics[:16])
        for topic, g, w in zip(topics, got, want):
            assert g.tolist() == w.tolist(), topic
            assert sorted(g.tolist()) == _oracle(fids, topic), topic


def test_fused_never_enters_host_decode(monkeypatch):
    """THE pin: when the fused pipeline serves a batch, the host decode
    path (_decode_routes) is not entered at all."""
    rng = random.Random(4)
    table, fids = _random_table(rng, 100)
    m = PartitionedMatcher(table)
    topics = _random_topics(rng, 16)
    m.match(topics)  # first batch runs the verify (which DOES host-decode)
    assert m._fused is True

    def _boom(*a, **k):
        raise AssertionError("host decode entered on the fused path")

    monkeypatch.setattr(P, "_decode_routes", _boom)
    got = m.match(topics)
    for topic, row in zip(topics, got):
        assert sorted(row.tolist()) == _oracle(fids, topic), topic
    # sanity: the reference matcher DOES enter it (the pin means something)
    m_ref = PartitionedMatcher(table)
    m_ref._fused = False
    with pytest.raises(AssertionError, match="host decode entered"):
        m_ref.match(topics)


def test_fused_fallback_on_disagreement(monkeypatch):
    """The verify contract: a fused pipeline that disagrees with the
    reference is disabled and the batch is served from the reference."""
    rng = random.Random(5)
    table, fids = _random_table(rng, 80)
    real = P.match_fused_impl

    def corrupt(*args, **kw):
        out = real(*args, **kw)
        return out.at[0].add(1)  # flip one fid: must fail the self-check

    monkeypatch.setattr(P, "_match_fused",
                        functools.partial(corrupt))
    m = PartitionedMatcher(table)
    topics = _random_topics(rng, 12)
    got = m.match(topics)
    assert m._fused is False, "corrupted fused path was not disabled"
    for topic, row in zip(topics, got):
        assert sorted(row.tolist()) == _oracle(fids, topic), topic
    # later batches stay on the (correct) unfused path
    got = m.match(topics[:4])
    for topic, row in zip(topics[:4], got):
        assert sorted(row.tolist()) == _oracle(fids, topic), topic


def test_packed_words_bitwise_equal_legacy():
    """The bit-packed tile scan must produce BITWISE-identical packed
    words to the legacy int16 field-major scan on the same table state."""
    import jax

    rng = random.Random(6)
    table, _fids = _random_table(rng, 300, words=("a", "b", "c", "x1", "", "+"))
    topics = _random_topics(rng, 40)
    enc, _ = table.encode_topics_versioned(topics, pad_batch_to=48)
    ttok, tlen, td, cids, _nc = enc[:5]
    legacy = pack_device_rows(table)
    lay = table.packed_layout()
    assert lay is not None
    packed = pack_device_rows_packed(table, lay)
    lay2, tt = table.translate_packed(ttok)
    assert lay2 == lay
    w_legacy = np.asarray(jax.jit(scan_words_impl)(legacy, ttok, tlen, td, cids))
    w_packed = np.asarray(jax.jit(
        functools.partial(scan_words_packed_impl, layout=lay)
    )(packed, tt, tlen, td, cids))
    assert np.array_equal(w_legacy, w_packed)
    # and the packed tile really is smaller (the roofline claim's basis)
    legacy_tile = legacy.shape[1] * legacy.shape[2] * legacy.dtype.itemsize
    packed_tile = packed.shape[1] * packed.dtype.itemsize
    assert packed_tile * 2 <= legacy_tile


def test_packed_width_widening_and_depth_fallback():
    """A level's vocab crossing 252 widens that level to 2 bytes (layout
    change → full re-upload, results unchanged); filters deeper than 30
    levels disable the packed format and fall back to legacy tiles."""
    table = PartitionedTable()
    fids = {}
    for i in range(300):
        f = f"tok{i}/x"
        fids[table.add(f)] = f
    lay = table.packed_layout()
    assert lay is not None and lay.widths[0] == 2
    m = PartitionedMatcher(table)
    topics = [f"tok{i}/x" for i in range(0, 300, 7)] + ["nope/x"]
    got = m.match(topics)
    assert m._dev_playout is not None
    for topic, row in zip(topics, got):
        assert sorted(row.tolist()) == _oracle(fids, topic), topic
    # depth fallback: a 31-level filter makes the table unpackable
    deep = "/".join(["d"] * 31)
    fids[table.add(deep)] = deep
    assert table.packed_layout() is None
    got = m.match(topics[:4])
    assert m._dev_playout is None  # relayout to legacy tiles happened
    for topic, row in zip(topics[:4], got):
        assert sorted(row.tolist()) == _oracle(fids, topic), topic


def test_fused_budget_regrow_sticky():
    """Overflowing the route budget re-runs wider and stickies the new
    budget, exactly like the unfused wire."""
    table = PartitionedTable()
    fids = {}
    for i in range(48):
        f = f"a/b{i % 4}/c{i}/#"
        fids[table.add(f)] = f
    m = PartitionedMatcher(table)
    topics = [f"a/b{i % 4}/c{i}/deep" for i in range(16)]
    m.match(topics)  # learn shapes + verify fused
    assert m._fused is True
    for k in list(m._budgets):
        m._budgets[k] = 8  # far below the ~16 routes this batch produces
    got = m.match(topics)
    for topic, row in zip(topics, got):
        assert sorted(row.tolist()) == _oracle(fids, topic), topic
    assert all(g > 8 for g in m._budgets.values()), "regrow did not stick"


def test_fused_verify_not_latched_by_empty_batches():
    """A zero-match batch (empty table — the broker's prewarm probe) must
    NOT latch the fused verify on an empty-vs-empty comparison; the
    decision waits for a batch with real matches."""
    table = PartitionedTable()
    m = PartitionedMatcher(table)
    m.prewarm()  # the broker-start shape: prewarm before any sub
    assert m._fused is None, "vacuous empty-table batch latched the verify"
    fids = {table.add("a/b"): "a/b", table.add("a/+"): "a/+"}
    (row,) = m.match(["a/b"])
    assert m._fused is True  # first REAL matches decided it
    assert sorted(row.tolist()) == _oracle(fids, "a/b")


def test_prewarm_latches_pad_floor():
    """prewarm() compiles the small shape and latches the sticky pad
    floor; later tiny submits reuse the floor shape."""
    rng = random.Random(8)
    table, fids = _random_table(rng, 60)
    fids[table.add("a/b")] = "a/b"  # guarantee the decide batch has matches
    m = PartitionedMatcher(table)
    m.prewarm()
    assert m._pad_floor == 8
    m.match(["a/b"])  # decide fused on a real-match batch
    assert m._fused is True
    h = m.match_submit(["a/b"])
    cids = h[3][5] if h[0] == "f" else h[2]
    assert cids.shape[0] == 8  # padded up to the floor, not to 1
    (row,) = m.match_complete(h)
    assert sorted(row.tolist()) == _oracle(fids, "a/b")


def test_stage_timing_attribution():
    """stage_timing accumulates per-stage ns (cfg11's instrument) and is
    zero-cost / zero-filled when off."""
    rng = random.Random(9)
    table, fids = _random_table(rng, 80)
    m = PartitionedMatcher(table)
    topics = _random_topics(rng, 16)
    m.match(topics)
    assert all(v == 0 for v in m.stage_ns.values())
    m.stage_timing = True
    m.match(topics)
    assert m.stage_ns["encode"] > 0 and m.stage_ns["dispatch"] > 0
    assert m.stage_ns["fetch"] > 0


def test_oversize_upload_fails_soft_to_segments(monkeypatch):
    """A failed whole-table device upload retries as bounded segments
    (the cfg4 'pre NC-split table' compile-death fail-soft) instead of
    wedging the run."""
    import jax

    rng = random.Random(10)
    table, fids = _random_table(rng, 200)
    m = PartitionedMatcher(table)
    real_put = jax.device_put
    calls = {"n": 0}

    def flaky_put(x, *a, **k):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: simulated oversize table")
        return real_put(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", flaky_put)
    topics = _random_topics(rng, 12)
    got = m.match(topics)
    assert m._segments is not None, "fail-soft did not segment"
    for topic, row in zip(topics, got):
        assert sorted(row.tolist()) == _oracle(fids, topic), topic


def test_sharded_fused_matches_reference():
    """ShardedPartitionedMatcher's fused mirror returns exactly the
    unfused shard wire's results (single-device CPU mesh)."""
    import jax

    from rmqtt_tpu.parallel.sharded import (
        ShardedPartitionedMatcher,
        make_mesh,
    )

    rng = random.Random(12)
    table, fids = _random_table(rng, 150)
    mesh = make_mesh(devices=jax.devices("cpu")[:1], dp=1, fp=1)
    m = ShardedPartitionedMatcher(table, mesh)
    topics = _random_topics(rng, 24)
    got = m.match(topics)
    assert m._fused is True, "sharded fused mirror did not verify"
    for topic, row in zip(topics, got):
        assert sorted(np.asarray(row).tolist()) == _oracle(fids, topic), topic
    m_ref = ShardedPartitionedMatcher(table, mesh)
    m_ref._fused = False
    want = m_ref.match(topics)
    for g, w in zip(got, want):
        assert np.asarray(g).tolist() == np.asarray(w).tolist()
