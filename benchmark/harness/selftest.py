"""The yardstick's own checks; no chip, no broker.

    python3 benchmark/run.py --selftest

1. every file under ``configs/``, ``traffic/``, ``cells/`` and
   ``layer_metrics/`` loads, agrees with ``BENCHMARK.json``, and every name
   and unit keeps to the character rules;
2. the frozen trie agrees with the program's ``rmqtt_tpu/core/trie.py`` on
   2,000 seeded filters and topics of each generator (the one place the
   benchmark imports the program);
3. the MQTT splitter finds the same packets however the stream is cut;
4. the trace reduction gives, on the recorded fixture, the numbers written
   beside it;
5. an open mix: the schedule of a mix repeats, a burst is one instant in
   every publisher process, and the ``open_loop`` line (due, sent, never
   sent, full-window share, lateness) reads what hand-made records say.
"""

from __future__ import annotations

import itertools
import json
import os
import random
import sys

from harness import generators, mqtt, spec
from harness.trie import Trie


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"selftest: FAILED: {what}")


def files_and_names() -> int:
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    names = [bench[k][i]["name"] for k in ("configs", "workloads", "end_to_end",
                                           "per_layer") for i in range(len(bench[k]))]
    for w in bench["workloads"]:
        names += [w["config"], w["traffic"]]
    for c in bench["configs"]:
        names += c["reduced"]
    for n in names:
        check(spec.NAME.match(n) is not None, f"name {n!r} breaks the character rules")
    for m in bench["end_to_end"] + bench["per_layer"]:
        check(spec.UNIT.match(m["unit"]) is not None, f"unit {m['unit']!r} of {m['name']}")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        reader = spec.load_reader(m["name"])
        for k in ("layer", "unit", "moves", "source"):
            check(reader.SPEC[k] == m[k],
                  f"layer_metrics/{m['name']}.py SPEC[{k!r}] differs from BENCHMARK.json")
        check(m["moves"] in e2e, f"{m['name']} moves an unknown metric")
    for f in (spec.BENCH_DIR / "layer_metrics").glob("[!_]*.py"):
        check(f.stem in {m["name"] for m in bench["per_layer"]},
              f"layer_metrics/{f.name} is not in BENCHMARK.json")
    for w in bench["workloads"]:
        generators.load(spec.load_cell(w["name"])["config"]["generator"])
    for f in (spec.BENCH_DIR / "cells").glob("*.json"):
        check(any(w["name"] == f.stem for w in bench["workloads"]),
              f"cells/{f.name} is not in BENCHMARK.json")
    for f in (spec.BENCH_DIR / "traffic").glob("*.json"):
        spec.load_traffic(f.stem, {})
    for c in bench["configs"]:
        conf = spec.load_json(spec.ROOT / c["file"])
        check(conf["name"] == c["name"] and conf["source"] == c["source"]
              and conf["reduced"] == c["reduced"],
              f"{c['file']} and BENCHMARK.json disagree")
    for f in spec.BENCH_DIR.rglob("*"):
        rel = str(f.relative_to(spec.BENCH_DIR))
        if "__pycache__" not in rel:
            check(all(ch.isascii() and (ch.isalnum() or ch in "_.-/") for ch in rel),
                  f"file name {rel!r} breaks the character rules")
    return len(names)


def trie_agrees() -> int:
    sys.path.insert(0, str(spec.ROOT))
    from rmqtt_tpu.core.trie import TopicTree  # the program's trie

    n = 0
    for name, cls in sorted(generators.REGISTRY.items()):
        gen = cls(20240930, {"subscriptions": 2000})
        filters = gen.filters()
        ours, theirs = Trie(), TopicTree()
        for i, f in enumerate(filters):
            ours.insert(f, i)
            theirs.insert(f, i)
        topics = list(itertools.islice(gen.topic_stream(7), 2000))
        # and topics the wildcard-first rule is about
        topics += ["$SYS/brokers", "$SYS/a/b/c/d/e", "v0_1", "l0n1/l1n1"]
        for t in topics:
            want = sorted(v for _lv, vs in theirs.matches(t) for v in vs)
            check(sorted(ours.match(t)) == want, f"{name}: tries disagree on {t!r}")
            n += len(want)
        check(n > 0, f"{name}: no topic matched any filter")
    return n


def mqtt_splits() -> int:
    rng = random.Random(5)
    packets = [mqtt.connect("c"), mqtt.subscribe(7, ["a/+", "b/#"], 1),
               mqtt.publish("t/" + "x" * 200, b"12345", 1, 65535),
               mqtt.publish("t", b"9", 0), mqtt.puback(513), mqtt.DISCONNECT]
    stream = b"".join(packets)
    whole = mqtt.Parser().feed(stream)
    check(len(whole) == len(packets), "the splitter lost a packet")
    check(mqtt.publish_fields(*whole[2][1:]) == (b"12345", 1, 65535), "PUBLISH fields")
    check(mqtt.publish_fields(*whole[3][1:]) == (b"9", 0, 0), "QoS0 PUBLISH fields")
    for _ in range(200):
        p, got, i = mqtt.Parser(), [], 0
        while i < len(stream):
            j = i + rng.randint(1, 40)
            got += p.feed(stream[i:j])
            i = j
        check(got == whole, "the splitter depends on how the stream is cut")
    return len(whole)


def trace_fixture() -> dict:
    """``trace_small.expected.json`` comes from ``fixtures/handcheck.py``: a
    reading of the protobuf that shares no code with the reduction and adds
    durations up in picoseconds. ``ProfileData`` gives whole nanoseconds, so
    a sum over n events may fall short by n ns, and a gap by 2."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from harness import trace_reduce

    fixtures = spec.BENCH_DIR / "fixtures"
    want = spec.load_json(fixtures / "trace_small.expected.json")
    got = trace_reduce.reduce(fixtures / "trace_small.xplane.pb")
    check(got["device_planes"] == want["device_planes"], "device planes of the fixture")
    check(abs(got["busy_s"] - want["busy_s"]) <= 1e-9 * want["operations"],
          f"busy_s {got['busy_s']} != {want['busy_s']}")
    check({k: v[0] for k, v in got["modules"].items()}
          == {k: v[0] for k, v in want["modules"].items()}, "module runs of the fixture")
    for k, (n, s) in want["modules"].items():
        check(abs(got["modules"][k][1] - s) <= 1e-9 * n, f"module seconds of {k}")
    check(len(got["gaps"]) == len(want["gaps_s"]) and all(
        abs(g - w) <= 2e-9 for g, w in zip(got["gaps"], want["gaps_s"])),
        "idle gaps of the fixture")
    check(len(got["ops"]) == len(want["ops_s"]), "longest operations of the fixture")
    for (name, s), (wname, ws) in zip(got["ops"], want["ops_s"]):
        check(wname.startswith(name.removesuffix("...")) and abs(s - ws) <= 1e-9 * 6,
              f"operation {name!r}: {s} != {ws}")
    return {"busy_s": got["busy_s"], "modules": len(got["modules"])}


def open_loop_lines() -> dict:
    """Four publishes of one process, window [10, 12): the first was due
    before it and sent inside it after a wait on a full window, the second
    due and sent inside it 100 ms late with a free slot, the third due
    inside and sent after the close, and a fourth (due at 11.5 by the
    schedule) still queued at the close and never sent."""
    import types

    import numpy as np

    from harness import cell, fleet

    t = spec.load_traffic("fleet_sat", {  # no cell runs an open mix yet: one made here
        "loop": "open", "rate_publishes_per_s": 3200, "arrival": "burst",
        "burst_size": 256, "inflight": 16})
    heads = [[list(itertools.islice(fleet.schedule(t, k, 2, 256), 600))
              for k in (0, 1)] for _ in (0, 1)]
    check(heads[0] == heads[1], "the schedule of a mix does not repeat")
    check({x for x, _c in heads[0][0][:128]} == {x for x, _c in heads[0][1][:128]}
          == {0.0}, "a burst is not one instant in both processes")
    f8 = lambda *v: np.array(v, np.float64).tobytes()  # noqa: E731
    replies = {"subs": [{"ids": np.array([0, 1], np.int64).tobytes(),
                         "times": f8(10.8, 10.7), "cpu": f8(), "lost": 0,
                         "subs": np.zeros(2, np.dtype("l")).tobytes()}],
               "pubs": [{"topics": ["a"] * 3, "t_send": f8(10.4, 10.6, 12.1),
                         "t_due": f8(9.9, 10.5, 11.9), "t_ack": f8(10.7, 10.65, 12.2),
                         "waited": np.array([1, 0, 1], np.int8).tobytes(),
                         "inflight": 0,
                         "out_of_order": 0, "cpu": f8(), "lost": 0}]}
    made = types.SimpleNamespace(subs="subs", pubs="pubs",
                                 ask=lambda procs, _cmd: replies[procs])
    ref = types.SimpleNamespace(expected=lambda topics: (
        np.ones(len(topics), np.int64), np.zeros(len(topics), np.int64)))
    rec = cell.Records(1)
    rec.drain(made)
    cmp = cell.compare(rec, ref, 1, 10.0, 12.0)
    check(not any(cmp["checks"].values()) and cmp["publishes"] == 2,
          "window membership is not by send instant")
    check(np.allclose(cmp["deliver_ms"], [900.0, 200.0]), "deliver_ms is not from the due instant")
    line = cell.open_loop(rec, cmp, 3, 10.0, 12.0)  # the schedule had three due in it
    want = {"due": 3, "sent": 2, "never_sent": 1,
            "sent_of_due_pct": 200 / 3, "window_full_share_pct": 200 / 3,
            "late_p50_ms": 100.0, "late_p99_ms": 100.0, "late_max_ms": 100.0}
    for k, v in want.items():
        check(abs(line[k] - v) < 1e-6, f"open_loop line: {k} reads {line[k]}, not {v}")
    return {k: line[k] for k in ("due", "sent", "never_sent")}


def main() -> int:
    out = {"names": files_and_names(), "trie_matches_compared": trie_agrees(),
           "mqtt_packets": mqtt_splits(), "trace_fixture": trace_fixture(),
           "open_loop": open_loop_lines()}
    print(json.dumps({"selftest": "ok", **out}))
    return 0
