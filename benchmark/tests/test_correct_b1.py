"""``b1_1m_exact.pub40``: its ``correct`` has been shown to fail, its table is
the seed's, and the readers that came with it read what they say.

- the ``--cpu`` rehearsal of the cell (tiny sizes of ``harness/rehearsal.json``)
  reads ``correct`` true on a sound broker, false under the ``drop`` control
  (``faulty_broker.py``: the delivery guarantee broken, every PUBACK still
  sent) and false through ``acks_out_of_order`` alone under ``reorder`` (the
  cell pipelines 16 deep by itself: [MQTT-4.6.0-2] broken, everything still
  delivered and acked);
- ``exact_one_each`` gives the same sorted table in two processes for one seed
  and another for another seed, and every topic of its stream is subscribed;
- on this table the plain trie (``harness/trie.py``, the reference that
  decides ``correct``) answers what a dictionary lookup of the topic answers;
- each new reader gives a number on a fabricated ``run`` and None where the
  broker has no such counter (a program from before this cell: the parent,
  with these files laid over it).

Run with ``python -m pytest benchmark/tests`` from the checkout's root.
"""

import hashlib
import itertools
import subprocess
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from harness import cell, generators, spec  # noqa: E402

CELL = "b1_1m_exact.pub40"
FOUR = ["cfg2_100k_plus.fleet_sat", "cfg2_100k_plus.fleet_sat_q0",
        "cfg3_1m_mixed.fleet_sat", CELL]


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
    return cell.run_cell(CELL, 20261002, 3.0, False, time.perf_counter(),
                         cpu=True, launcher=launcher)


def test_sound_broker_is_correct(short_run, monkeypatch):
    r = run(monkeypatch)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 100
    assert r["checks"]["acks_out_of_order"] == {"value": 0, "limit": 0}
    assert r["device"]["platform"] == "cpu"  # a rehearsal names its platform
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    reported = {m["name"] for m in bench["end_to_end"]
                if spec.metric_applies(m, CELL)}
    assert {"deliveries_per_s", "setup_s"} <= reported == set(r["metrics"])


def test_control_dropped_delivery_is_not_correct(short_run, monkeypatch):
    r = run(monkeypatch, "drop")
    assert r["correct"] is False
    assert r["checks"]["missing_pairs"]["value"] > 0
    assert r["checks"]["unacked_qos1"]["value"] == 0  # every PUBACK still came


def test_control_reordered_acks_are_not_correct(short_run, monkeypatch):
    r = run(monkeypatch, "reorder")
    values = {k: v["value"] for k, v in r["checks"].items()}
    assert values.pop("expected_pairs") > 100
    out_of_order = values.pop("acks_out_of_order")
    assert not any(values.values())  # everything delivered, everything acked
    assert out_of_order > 0 and r["correct"] is False and r["failed"] == out_of_order


# ------------------------------------------------------------- the generator
_DIGEST = (
    "import sys, hashlib; sys.path.insert(0, sys.argv[1]);"
    "from harness import generators;"
    "f = generators.load('exact_one_each')(int(sys.argv[2]),"
    " {'subscriptions': 20000}).filters();"
    "print(len(f), hashlib.sha256('\\n'.join(f).encode()).hexdigest())")


def _digest_in_a_process(seed: int) -> str:
    return subprocess.run(
        [sys.executable, "-c", _DIGEST, str(HERE.parent), str(seed)],
        check=True, capture_output=True, text=True).stdout.strip()


def test_the_table_is_the_seeds_in_every_process():
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    a, b, other = (_digest_in_a_process(s) for s in (big, big, big + 1))
    assert a == b != other and a.startswith("20000 ")
    g = generators.load("exact_one_each")(big, {"subscriptions": 20000})
    table = g.filters()
    assert f"20000 {hashlib.sha256(chr(10).join(table).encode()).hexdigest()}" == a
    assert table == sorted(set(table)) and len(table) == 20000
    assert all(f.startswith("iot/") and f[4:].isdigit()
               and 0 <= int(f[4:]) < 10_000_000 for f in table)
    # every publish has exactly one subscriber: its topic is a table entry
    members = set(table)
    stream = list(itertools.islice(g.topic_stream(7), 2000))
    assert all(t in members for t in stream) and len(set(stream)) > 1500
    assert stream == list(itertools.islice(g.topic_stream(7), 2000))
    assert stream != list(itertools.islice(g.topic_stream(8), 2000))


def test_the_plain_trie_is_a_dictionary_lookup_on_this_table():
    """Fan-out exactly 1: the reference's answer for a published topic is the
    one owner ``i % subscribers`` of that topic's filter, and nobody for a
    topic beside the table (a neighbour number, a level more, a level less)."""
    from harness.trie import Trie

    subscribers = 37
    g = generators.load("exact_one_each")(2**31 + 99, {"subscriptions": 20000})
    table = g.filters()
    trie, owner = Trie(), {}
    for i, f in enumerate(table):
        trie.insert(f, i % subscribers)
        owner[f] = i % subscribers
    for t in itertools.islice(g.topic_stream(11), 3000):
        assert trie.match(t) == [owner[t]]
    beside = [t for t in (f"iot/{n}" for n in range(3000)) if t not in owner]
    assert len(beside) > 2000
    for t in beside[:500] + ["iot", f"{table[0]}/x", "iot/", "/" + table[0]]:
        assert trie.match(t) == []


# ------------------------------------------------- the readers, on made runs
def _run(metrics0: dict, metrics1: dict) -> dict:
    """Two snapshots ten seconds apart, as ``cell.run_cell`` hands them on."""
    snap = lambda t, m: {"t": t, "stats": {}, "metrics": m,  # noqa: E731
                         "device": {"backend": {}}}
    return {"before": snap(100.0, metrics0), "after": snap(110.0, metrics1),
            "trace": None}


WITH = _run(
    {"ingress.runs": 100, "ingress.run_publishes": 100, "fanout.enqueues": 1000,
     "deliver.cold_enqueues": 900, "net.egress_frames": 500,
     "net.egress_flushes": 400},
    {"ingress.runs": 1100, "ingress.run_publishes": 6600, "fanout.enqueues": 7500,
     "deliver.cold_enqueues": 6750, "net.egress_frames": 10500,
     "net.egress_flushes": 8400})
# a program from before this cell: no run counters, no cold-enqueue counter;
# one from before PR 28 has no egress counters either
WITHOUT = _run({"fanout.enqueues": 1000, "publish.received": 1000},
               {"fanout.enqueues": 7500, "publish.received": 7500})


@pytest.mark.parametrize("name,value", [
    ("ingress.run_publishes_mean", 6.5),
    ("deliver.cold_enqueue_share_pct", 90.0),
    ("egress.frames_per_write", 1.25),
])
def test_new_reader_reads_its_counter_and_is_silent_without_it(name, value):
    reader = spec.load_reader(name)
    assert reader.read(WITH) == pytest.approx(value)
    assert reader.read(WITHOUT) is None
    assert reader.read(_run(WITH["before"]["metrics"],
                            WITH["before"]["metrics"])) is None  # nothing moved
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert {k: entry[k] for k in reader.SPEC} == reader.SPEC
    # membership, not equality: a later PR appends its cell to the list
    assert set(FOUR) <= set(entry["workloads"])


def test_the_cell_is_on_the_per_layer_metrics_and_its_configuration_is_whole():
    """The cell is on the list of every per-layer metric that has one and
    moves an end-to-end metric the cell reports, its shares of the roofline
    among them."""
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    reported = {m["name"] for m in bench["end_to_end"]
                if spec.metric_applies(m, CELL)}
    for m in bench["per_layer"]:
        if "workloads" in m:
            assert (CELL in m["workloads"]) == (m["moves"] in reported), m["name"]
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert {"ingress.run_publishes_mean", "deliver.cold_enqueue_share_pct",
            "egress.frames_per_write", "kernel.scan_roofline",
            "kernel.tail_share_pct"} <= listed
    config = spec.load_cell(CELL)["config"]
    assert config["subscriptions"] == 1_000_000 and config["hbm_bytes_per_topic"] > 0
    assert {"ack_order", "delivery_order"} <= set(config["guarantees"])
