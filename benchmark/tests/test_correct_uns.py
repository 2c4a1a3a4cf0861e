"""``uns_1m_isa95.pub40``: its table is the seed's ISA-95 Unified Namespace,
its stream draws only the namespace's tags, its ``correct`` has been shown to
fail, and the readers that came with it read what they say.

- ``isa95_uns`` gives the same sorted table in two processes for one seed and
  another for another seed: at the cell's size 1,000,000 rows, 1,024
  ``line/#``, 262,144 ``cell/+/tag``, 262,144 ``device/#`` and 474,688 exact
  tags; at the ``--cpu`` rehearsal's 4,000 rows all four kinds still;
- the stream draws only from the 4,194,304 tags, uniformly;
- the plain reference takes the table and answers about 3.11 (subscriber,
  publish) pairs a publish;
- the two ``matcher.*`` readers give a number on a made run and None where the
  broker has no such counter (the parent, with these files laid over it);
- the cell is on every list it belongs in;
- the ``--cpu`` rehearsal reads ``correct`` true on a sound broker and false
  under the ``drop`` control (``faulty_broker.py``).

Every case but the two rehearsals is counted with the tier-1 tests too
(``tests/test_benchmark_uns.py``). Run with ``python -m pytest benchmark/tests``
from the checkout's root.
"""

import hashlib
import itertools
import subprocess
import sys
import time
from collections import Counter
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from harness import cell, generators, reference, spec  # noqa: E402

CELL = "uns_1m_isa95.pub40"
BIG = 2**31 + 4343  # a run's seed may pass 32 signed bits
SUBSCRIBERS = 4096
NEW = ("matcher.candidate_chunks_mean", "matcher.scan_pad_share_pct")
KINDS = {"line": 1024, "cell_tag": 262_144, "device": 262_144, "exact": 474_688}


def uns(seed: int, n: int = 1_000_000):
    config = dict(spec.load_cell(CELL)["config"], subscriptions=n)
    return generators.load("isa95_uns")(seed, config)


def kind(row: str) -> str:
    levels = row.split("/")
    if len(levels) == 5 and levels[-1] == "#":
        return "line"
    if len(levels) == 7 and levels[5] == "+":
        return "cell_tag"
    if len(levels) == 7 and levels[6] == "#":
        return "device"
    assert len(levels) == 7 and "+" not in levels and "#" not in levels, row
    return "exact"


# ------------------------------------------------------------- the generator
_DIGEST = (
    "import sys, hashlib, json; sys.path.insert(0, sys.argv[1]);"
    "from harness import generators;"
    "c = json.load(open(sys.argv[3]));"
    "f = generators.load('isa95_uns')(int(sys.argv[2]), c).filters();"
    "print(len(f), hashlib.sha256('\\n'.join(f).encode()).hexdigest())")


def _digest_in_a_process(seed: int) -> str:
    conf = spec.ROOT / "benchmark" / "configs" / "uns_1m_isa95.json"
    return subprocess.run(
        [sys.executable, "-c", _DIGEST, str(HERE.parent), str(seed), str(conf)],
        check=True, capture_output=True, text=True).stdout.strip()


def test_the_table_is_the_seeds_in_every_process_with_its_four_kinds():
    a, b, other = (_digest_in_a_process(s) for s in (BIG, BIG, BIG + 1))
    assert a == b != other and a.startswith("1000000 ")
    table = uns(BIG).filters()
    assert f"1000000 {hashlib.sha256(chr(10).join(table).encode()).hexdigest()}" == a
    assert table == sorted(set(table))
    assert Counter(map(kind, table)) == KINDS
    # seven levels, top three nearly constant: 1, 8 and 64 values
    exact = [r.split("/") for r in table if kind(r) == "exact"]
    assert [len({tuple(r[:k + 1]) for r in exact}) for k in range(3)] == [1, 8, 64]


def test_the_rehearsal_table_keeps_every_kind():
    rows = uns(BIG, 4000).filters()
    kinds = Counter(map(kind, rows))
    assert len(rows) == 4000 and set(kinds) == set(KINDS)
    assert kinds["line"] == 4 and kinds["cell_tag"] == kinds["device"] == 4 * 256


# ---------------------------------------------------------------- the stream
def test_the_stream_draws_only_the_namespaces_tags_uniformly():
    g = uns(BIG)
    assert g.n_tags == 4_194_304
    stream_seed = BIG * 1009 + 17  # as fleet.py derives a publisher process's
    topics = list(itertools.islice(g.topic_stream(stream_seed), 40000))
    assert topics == list(itertools.islice(g.topic_stream(stream_seed), 40000))
    assert all(kind(t) == "exact" for t in topics)
    tags = {g.tag_topic(i) for i in (0, 1, 4095, 4096, g.n_tags - 1)}
    assert "acme/site7/area7/line15/cell15/dev15/runtime" in tags
    # uniform: about 40,000 of 4,194,304 tags repeat almost never, and every
    # line and every tag name is drawn about equally
    assert len(set(topics)) > 39_700
    lines = Counter(t.rsplit("/", 3)[0] for t in topics)
    assert len(lines) == 1024 and max(lines.values()) < 3 * 40000 / 1024
    names = Counter(t.rsplit("/", 1)[1] for t in topics)
    assert len(names) == 16 and min(names.values()) > 0.8 * 40000 / 16


def test_the_reference_answers_three_point_one_one_a_publish():
    g = uns(BIG)
    table = reference.Table(g.filters(), SUBSCRIBERS)
    topics = list(itertools.islice(g.topic_stream(BIG + 7), 20000))
    counts = table.expected(topics)[0]
    # line, cell-tag and device rows always; the exact row for 11.3 % of tags
    # (a pair counts once where two of them share an owner)
    assert counts.min() >= 2 and counts.max() <= 4
    assert 3.08 < counts.mean() < 3.14


# ------------------------------------------------- the readers, on made runs
def _run(stats0: dict, stats1: dict) -> dict:
    snap = lambda t, s: {"t": t, "stats": s, "metrics": {},  # noqa: E731
                         "device": {"backend": {}}}
    return {"before": snap(100.0, stats0), "after": snap(110.0, stats1), "trace": None}


WITH = _run({"routing_encode_topics": 1000, "routing_encode_candidates": 2300,
             "routing_encode_scanned": 32000},
            {"routing_encode_topics": 11000, "routing_encode_candidates": 25300,
             "routing_encode_scanned": 352000})
PARENT = _run({"routing_encode_topics": 1000}, {"routing_encode_topics": 11000})
READ = {"matcher.candidate_chunks_mean": 2.3, "matcher.scan_pad_share_pct": 92.8125}


@pytest.mark.parametrize("name", NEW)
def test_new_reader_reads_its_counter_and_is_silent_without_it(name):
    reader = spec.load_reader(name)
    assert reader.read(WITH) == pytest.approx(READ[name])
    assert reader.read(PARENT) is None
    # the counters are there and the device encoded nothing in the window
    assert reader.read(_run(WITH["before"]["stats"], WITH["before"]["stats"])) is None


@pytest.mark.parametrize("name", NEW)
def test_new_reader_is_held_to_its_per_layer_entry(name):
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    reader = spec.load_reader(name)
    assert {k: entry[k] for k in reader.SPEC} == reader.SPEC
    assert "workloads" not in entry  # every cell's device encodes topics
    assert entry["moves"] == "deliveries_per_s"


def test_the_cell_is_on_every_list_it_belongs_in():
    """The cell is on the list of every metric that has one and moves an
    end-to-end metric the cell reports, and on every list that names every
    cell; its configuration is whole."""
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
    exact_config = spec.load_cell("b1_1m_exact.pub40")["config"]
    assert config["generator"] == "isa95_uns" and config["subscriptions"] == 1_000_000
    for key in ("subscriber_connections", "publisher_connections", "match_programs",
                "reduced", "guarantees"):
        assert config[key] == exact_config[key], key
    assert set(config["assumed"]) >= {"hierarchy", "tags", "consumers", "stream"}
    assert 0 < config["hbm_bytes_per_topic"] < exact_config["hbm_bytes_per_topic"]


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
    return cell.run_cell(CELL, 20261019, 3.0, False, time.perf_counter(),
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
