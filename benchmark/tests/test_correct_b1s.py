"""``b1_1m_share.pub40``: its table is ``b1_1m_zipf``'s with four ``$share/g<k>/#``
groups of sixteen on it, its stream is ``b1_1m_zipf``'s, and the readers that
came with it read what they say.

- ``exact_zipf_share`` gives the group rows first, then exactly ``exact_zipf``'s
  table for the seed, sorted: 1,000,064 rows at the cell's size, and the same
  rule at a small one; the members are subscribers 0-63, a group's sixteen in
  a row; the plain reference takes the table and answers each topic with its
  one plain owner and all four groups;
- the stream is ``exact_zipf``'s for the seed, only exact subscribed topics,
  with Zipf's share at the top rank;
- each new reader gives a number on a made ``run``, 0 where no publish met
  a group (every other cell's table), and None where the broker has no such
  counter or stage (the parent);
- the cell is on every list it belongs in, and the two new metrics take no
  list: every cell that reports ``deliveries_per_s`` reads them.

The plain reference and the broker's routing service are compared on the CPU
in ``tests/test_shared_zipf_served.py``. Every case here starts no broker and
is counted with the tier-1 tests too (``tests/test_benchmark_b1s.py``). Run
with ``python -m pytest benchmark/tests``.
"""

import itertools
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from harness import generators, reference, spec  # noqa: E402

CELL = "b1_1m_share.pub40"
THETA = 0.99
BIG = 2**31 + 4141  # a run's seed may pass 32 signed bits
SUBSCRIBERS = 4096
NEW = ("shared.collapse_busy_pct", "shared.candidates_per_publish")


def share(seed: int, n: int, groups: int = 4, members: int = 16):
    return generators.load("exact_zipf_share")(seed, {
        "subscriptions": n + groups * members,
        "shared_groups": groups, "shared_members": members})


def zipf(seed: int, n: int):
    return generators.load("exact_zipf")(seed, {"subscriptions": n})


# ------------------------------------------------------------- the generator
@pytest.mark.parametrize("groups, members", [(4, 16), (2, 3)])
def test_the_table_is_the_groups_then_exact_zipfs(groups, members):
    g = share(BIG, 20000, groups, members)
    rows = g.filters()
    shared = groups * members
    assert len(rows) == 20000 + shared and rows == sorted(rows)
    assert rows[:shared] == [f"$share/g{k}/#" for k in range(groups) for _ in range(members)]
    assert rows[shared:] == zipf(BIG, 20000).filters()
    # by the owner rule, group k's members are subscribers k*members .. +members-1
    for k in range(groups):
        owners = [i % SUBSCRIBERS for i, f in enumerate(rows) if f == f"$share/g{k}/#"]
        assert owners == list(range(k * members, (k + 1) * members))


def test_the_configuration_is_the_table_at_full_size():
    config = spec.load_cell(CELL)["config"]
    assert (config["subscriptions"], config["shared_groups"], config["shared_members"]) \
        == (1_000_064, 4, 16)
    rows = generators.load(config["generator"])(BIG, config).filters()
    assert len(rows) == 1_000_064 and rows == sorted(rows)
    members = {i % SUBSCRIBERS for i, f in enumerate(rows) if f.startswith("$share/")}
    assert members == set(range(64))
    assert rows[64:] == zipf(BIG, 1_000_000).filters()


def test_the_reference_takes_the_table_and_answers_fan_out_five():
    g = share(BIG, 20000)
    table = reference.Table(g.filters(), SUBSCRIBERS)
    topics = list(itertools.islice(g.topic_stream(BIG + 7), 2000))
    counts, subs, (gcounts, gids, offsets, members) = table.expected(topics)
    assert (counts == 1).all() and (gcounts == 4).all()
    assert sorted(set(gids.tolist())) == [0, 1, 2, 3]
    assert [members[offsets[k]:offsets[k + 1]].tolist() for k in range(4)] \
        == [list(range(16 * k, 16 * (k + 1))) for k in range(4)]
    # the plain owner is the owner of the topic's own row
    rows = g.filters()
    assert subs.tolist() == [rows.index(t) % SUBSCRIBERS for t in topics]


# ---------------------------------------------------------------- the stream
def test_the_stream_is_exact_zipfs_over_the_exact_rows():
    g, z = share(BIG, 20000), zipf(BIG, 20000)
    stream_seed = BIG * 1009 + 17  # as fleet.py derives a publisher process's
    ours = list(itertools.islice(g.topic_stream(stream_seed), 20000))
    assert ours == list(itertools.islice(z.topic_stream(stream_seed), 20000))
    exact = set(g.filters()[64:])
    assert all(t in exact for t in ours)
    assert g.hottest(10) == z.hottest(10)


@pytest.mark.parametrize("n", [4000, 1_000_000])
def test_the_top_ranks_share_is_zipfs(n):
    draws = 200_000
    g = share(BIG, n)
    counts = Counter(itertools.islice(g.topic_stream(BIG + 5), draws))
    want = 1.0 / float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -THETA))
    top, got = counts.most_common(1)[0]
    assert top == g.hottest(1)[0]
    assert abs(got / draws - want) <= 0.10 * want
    assert not any(t.startswith("$share") for t in counts)


# ------------------------------------------------- the readers, on made runs
def _run(stats0: dict, stats1: dict, metrics0: dict, metrics1: dict,
         t0: float = 100.0, t1: float = 110.0) -> dict:
    snap = lambda t, s, m: {"t": t, "stats": s, "metrics": m,  # noqa: E731
                            "device": {"backend": {}}}
    return {"before": snap(t0, stats0, metrics0), "after": snap(t1, stats1, metrics1),
            "trace": None}


WITH = _run(
    {"stage_routing_collapse_count": 1000, "stage_routing_collapse_busy_ms_total": 40.0},
    {"stage_routing_collapse_count": 31000, "stage_routing_collapse_busy_ms_total": 240.0},
    {"shared.publishes": 1000, "shared.candidates": 64000},
    {"shared.publishes": 31000, "shared.candidates": 64 * 31000})
# the parent: neither the stage nor the counters
PARENT = _run({"routing_dispatches": 10}, {"routing_dispatches": 20}, {}, {})
READ = {"shared.collapse_busy_pct": 2.0, "shared.candidates_per_publish": 64.0}


@pytest.mark.parametrize("name", NEW)
def test_new_reader_reads_its_counter_and_is_silent_without_it(name):
    reader = spec.load_reader(name)
    assert reader.read(WITH) == pytest.approx(READ[name])
    assert reader.read(PARENT) is None
    still = _run(WITH["before"]["stats"], WITH["before"]["stats"],
                 WITH["before"]["metrics"], WITH["before"]["metrics"])
    # the stage and the counters are there, and no publish met a group
    assert reader.read(still) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_new_reader_is_held_to_its_per_layer_entry(name):
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    reader = spec.load_reader(name)
    assert {k: entry[k] for k in reader.SPEC} == reader.SPEC
    assert "workloads" not in entry  # every cell reads it; the groupless ones read 0
    assert entry["moves"] == "deliveries_per_s"


def test_the_cell_is_on_every_list_it_belongs_in():
    """The cell is on the list of every metric that has one and moves an
    end-to-end metric the cell reports; its configuration is whole."""
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "pub40"
    reported = {m["name"] for m in bench["end_to_end"]
                if spec.metric_applies(m, CELL)}
    assert {"deliveries_per_s", "setup_s", "puback_p99_ms"} <= reported
    for m in bench["per_layer"]:
        if "workloads" in m:
            assert (CELL in m["workloads"]) == (m["moves"] in reported), m["name"]
    config = spec.load_cell(CELL)["config"]
    zipf_config = spec.load_cell("b1_1m_zipf.pub40")["config"]
    assert config["generator"] == "exact_zipf_share"
    for key in ("subscriber_connections", "publisher_connections", "match_programs",
                "reduced"):
        assert config[key] == zipf_config[key], key
    assert set(config["guarantees"]) == set(zipf_config["guarantees"]) | {"shared"}
    assert config["hbm_bytes_per_topic"] > zipf_config["hbm_bytes_per_topic"]
