"""``b1_1m_zipf.pub40``: its table is ``b1_1m_exact``'s, its stream is the
Zipf one it says, its ``correct`` has been shown to fail, and the readers
that came with it read what they say.

- ``exact_zipf`` gives exactly ``exact_one_each``'s table for a seed, the same
  stream in two processes for one seed, and only subscribed topics;
- the top rank's share of 200,000 draws is within 10 % of 1 / H(N, 0.99), at
  the rehearsal's N and at the cell's; the hot ranks are spread over the
  subscriber connections by the scramble;
- the ``--cpu`` rehearsal of the cell reads ``correct`` true on a sound broker
  and false under the ``drop`` control (``faulty_broker.py``: a spoiled row is
  cached like a sound one, so the cache serves the fault again and again);
- each new reader gives a number on a fabricated ``run`` and None where the
  broker has no such counter or stage (the parent, for ``routing.cache_busy_pct``).

The cases without a broker are counted with the tier-1 tests too
(``tests/test_benchmark_b1z.py``). Run with ``python -m pytest benchmark/tests``.
"""

import hashlib
import itertools
import subprocess
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from harness import cell, generators, spec  # noqa: E402

CELL = "b1_1m_zipf.pub40"
THETA = 0.99
BIG = 2**31 + 4242  # the driver's seeds pass 32 signed bits


def zipf(seed: int, n: int):
    return generators.load("exact_zipf")(seed, {"subscriptions": n})


# ------------------------------------------------------------- the generator
def test_the_table_is_exact_one_each():
    g = zipf(BIG, 20000)
    assert g.filters() == generators.load("exact_one_each")(
        BIG, {"subscriptions": 20000}).filters()
    assert g.filters() != zipf(BIG + 1, 20000).filters()


_DIGEST = (
    "import sys, hashlib, itertools; sys.path.insert(0, sys.argv[1]);"
    "from harness import generators;"
    "g = generators.load('exact_zipf')(int(sys.argv[2]), {'subscriptions': 20000});"
    "s = list(itertools.islice(g.topic_stream(int(sys.argv[3])), 5000));"
    "print(hashlib.sha256('\\n'.join(s).encode()).hexdigest())")


def _stream_in_a_process(seed: int, stream_seed: int) -> str:
    return subprocess.run(
        [sys.executable, "-c", _DIGEST, str(HERE.parent), str(seed), str(stream_seed)],
        check=True, capture_output=True, text=True).stdout.strip()


def test_the_stream_is_the_seeds_in_every_process():
    stream_seed = BIG * 1009 + 17  # as fleet.py derives a publisher process's
    a, b = (_stream_in_a_process(BIG, stream_seed) for _ in range(2))
    here = list(itertools.islice(zipf(BIG, 20000).topic_stream(stream_seed), 5000))
    assert a == b == hashlib.sha256("\n".join(here).encode()).hexdigest()
    # another stream seed draws other ranks; another table seed scrambles them
    assert _stream_in_a_process(BIG, stream_seed + 1) != a
    assert zipf(BIG + 1, 20000).hottest(10) != zipf(BIG, 20000).hottest(10)


def test_every_topic_is_subscribed():
    g = zipf(BIG, 20000)
    members = set(g.filters())
    stream = list(itertools.islice(g.topic_stream(7), 20000))
    assert all(t in members for t in stream)
    assert stream[:3] != list(itertools.islice(g.topic_stream(8), 3))


@pytest.mark.parametrize("n", [4000, 1_000_000])
def test_the_top_ranks_share_is_zipfs(n):
    draws = 200_000
    g = zipf(BIG, n)
    counts = Counter(itertools.islice(g.topic_stream(BIG + 5), draws))
    want = 1.0 / float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -THETA))
    top, got = counts.most_common(1)[0]
    assert top == g.hottest(1)[0]
    assert abs(got / draws - want) <= 0.10 * want
    # and the ranks below it fall off as r**-0.99 (rank 10 against rank 1)
    tenth = counts[g.hottest(10)[9]]
    assert 0.8 * 10 ** -THETA <= tenth / got <= 1.25 * 10 ** -THETA


def test_hot_ranks_are_spread_over_owners():
    g = zipf(BIG, 1_000_000)
    owners = {int(row) % 4096 for row in g.scramble()[:16]}
    assert len(owners) >= 12
    rows = g.scramble()
    assert sorted(rows[:1000].tolist()) != list(range(1000))  # scrambled, not sorted


# ------------------------------------------------- the rehearsal, and its control
@pytest.fixture
def short_run(monkeypatch):
    monkeypatch.setattr(cell, "WARMUP_MIN_S", 2.0)
    monkeypatch.setattr(cell, "WARMUP_CAP_S", 6.0)
    monkeypatch.setattr(cell, "SETTLE_LIMIT_S", 5.0)


def run(monkeypatch, fault=None):
    launcher = cell.brokermod.LAUNCHER
    if fault:
        monkeypatch.setenv("BENCHMARK_FAULT", fault)
        launcher = HERE / "faulty_broker.py"
    return cell.run_cell(CELL, 20261015, 3.0, False, time.perf_counter(),
                         cpu=True, launcher=launcher)


def test_sound_broker_is_correct(short_run, monkeypatch):
    r = run(monkeypatch)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 100
    assert r["device"]["platform"] == "cpu"
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    reported = {m["name"] for m in bench["end_to_end"]
                if spec.metric_applies(m, CELL)}
    assert {"deliveries_per_s", "setup_s", "puback_p99_ms"} <= reported == set(r["metrics"])


def test_control_dropped_delivery_is_not_correct(short_run, monkeypatch):
    r = run(monkeypatch, "drop")
    assert r["correct"] is False
    assert r["checks"]["missing_pairs"]["value"] > 0
    assert r["checks"]["unacked_qos1"]["value"] == 0  # every PUBACK still came


# ------------------------------------------------- the readers, on made runs
def _run(stats0: dict, stats1: dict, t0: float = 100.0, t1: float = 110.0) -> dict:
    snap = lambda t, s: {"t": t, "stats": s, "metrics": {},  # noqa: E731
                         "device": {"backend": {}}}
    return {"before": snap(t0, stats0), "after": snap(t1, stats1), "trace": None}


WITH = _run(
    {"routing_cache_hits": 1000, "routing_cache_misses": 4000,
     "routing_cache_door_rejects": 3000, "stage_routing_cache_hit_count": 1000,
     "stage_routing_cache_hit_busy_ms_total": 50.0},
    {"routing_cache_hits": 7000, "routing_cache_misses": 8000,
     "routing_cache_door_rejects": 4000, "stage_routing_cache_hit_count": 7000,
     "stage_routing_cache_hit_busy_ms_total": 350.0})
# the parent: the cache's counters, no routing.cache_hit stage
PARENT = _run({k: v for k, v in WITH["before"]["stats"].items() if not k.startswith("stage_")},
              {k: v for k, v in WITH["after"]["stats"].items() if not k.startswith("stage_")})
# a broker without a match cache's counters
NONE = _run({"routing_dispatches": 10}, {"routing_dispatches": 20})
READ = {"routing.cache_hit_share_pct": 60.0,
        "routing.cache_busy_pct": 3.0,
        "routing.cache_door_reject_share_pct": 25.0}


@pytest.mark.parametrize("name", sorted(READ))
def test_new_reader_reads_its_counter_and_is_silent_without_it(name):
    reader = spec.load_reader(name)
    assert reader.read(WITH) == pytest.approx(READ[name])
    assert reader.read(NONE) is None
    same = _run(WITH["before"]["stats"], WITH["before"]["stats"])
    if name == "routing.cache_busy_pct":
        # the stage is there and made no pass: 0, a reading in every cell
        # whose stream never repeats a topic (cfg2, cfg3)
        assert reader.read(same) == 0.0
    else:
        assert reader.read(same) is None  # nothing looked up, hit or missed
    parent = reader.read(PARENT)
    if name == "routing.cache_busy_pct":
        assert parent is None  # no stage before PR 36
    else:
        assert parent == pytest.approx(READ[name])


@pytest.mark.parametrize("name", sorted(READ))
def test_new_reader_is_held_to_its_per_layer_entry(name):
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    reader = spec.load_reader(name)
    assert {k: entry[k] for k in reader.SPEC} == reader.SPEC
    assert "workloads" not in entry  # every cell reads it; the uniform ones are the control
    assert entry["moves"] == "deliveries_per_s"


def test_the_cell_is_on_every_list_it_belongs_in():
    """As ``b1_1m_exact.pub40``: the cell is on the list of every metric that
    has one and moves an end-to-end metric the cell reports."""
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
    b1 = spec.load_cell("b1_1m_exact.pub40")["config"]
    assert config["generator"] == "exact_zipf"
    for key in ("subscriptions", "subscriber_connections", "publisher_connections",
                "hbm_bytes_per_topic", "match_programs", "reduced"):
        assert config[key] == b1[key], key
    assert set(config["guarantees"]) == set(b1["guarantees"])
