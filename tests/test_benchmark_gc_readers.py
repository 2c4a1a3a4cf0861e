"""The two per-layer readers of the collector, counted with the tier-1 tests.

``benchmark/layer_metrics/host.gc_pause_pct.py`` and
``host.gc_full_pause_pct.py`` each read one cumulative counter of
``/api/v1/stats`` at the two ends of a stretch and give its growth as a share
of the stretch. Each is fed two scripted snapshots: the share it should read,
the traced span's own snapshots in place of the window's where a run has them,
and ``None`` where the program has no such counter (the parent of the PR that
brought it), so that the result line leaves the metric out.
"""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from harness import spec  # noqa: E402

READERS = {"host.gc_pause_pct": ("host_gc_pause_ms_total", "deliveries_per_s"),
           "host.gc_full_pause_pct": ("host_gc_full_pause_ms_total", "puback_p99_ms")}


def _run(key, t0, ms0, t1, ms1, trace=None):
    def snap(t, ms):
        stats = {"host_loop_cpu_ms_total": 1.0}
        if ms is not None:
            stats[key] = ms
        return {"t": t, "stats": stats}
    return {"before": snap(t0, ms0), "after": snap(t1, ms1), "trace": trace}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_the_counters_growth_as_a_share_of_the_window(name):
    key, _ = READERS[name]
    read = spec.load_reader(name).read
    # 5,100 ms stopped in 51 s: a tenth of the window, whatever came before
    assert read(_run(key, 100.0, 40_000.0, 151.0, 45_100.0)) == pytest.approx(10.0)
    assert read(_run(key, 100.0, 40_000.0, 151.0, 40_000.0)) == 0.0
    # a traced run is read between the snapshots inside its span
    span = {"before": {"t": 120.0, "stats": {key: 41_000.0}},
            "after": {"t": 130.0, "stats": {key: 41_150.0}}}
    assert read(_run(key, 100.0, 40_000.0, 151.0, 45_100.0, trace=span)) \
        == pytest.approx(1.5)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_silent_on_a_program_without_the_counter(name):
    key, _ = READERS[name]
    read = spec.load_reader(name).read
    assert read(_run(key, 100.0, None, 151.0, None)) is None
    assert read(_run(key, 100.0, None, 151.0, 45_100.0)) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_held_to_its_per_layer_entry(name):
    _, moves = READERS[name]
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    reader = spec.load_reader(name)
    assert {k: entry[k] for k in ("layer", "unit", "moves", "source")} == reader.SPEC
    assert entry["moves"] == moves and entry["better"] == "lower"
    assert entry["layer"] == "broker event loop (one Python thread)"
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
