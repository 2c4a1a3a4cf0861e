"""The per-layer readers of the event loop's wait and of the broker's own
stats and hot-key planes, counted with the tier-1 tests.

``benchmark/layer_metrics/host.loop_lag_p99_ms.py`` takes the 99th percentile
of the lag sampler's ticks between two snapshots from the deltas of the
cumulative buckets ``hist_host_loop_lag_b<i>``; ``host.telemetry_busy_pct.py``
gives the busy share of the window of the ``stats.snapshot`` and
``hotkeys.fold`` stages. Each is fed scripted snapshots: the number it should
read, the traced span's own snapshots in place of the window's where a run
has them, and ``None`` where the program has no such keys (the parent of the
change that brought them), so that the result line leaves the metric out.
"""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from harness import spec  # noqa: E402

NAMES = ("host.loop_lag_p99_ms", "host.telemetry_busy_pct")
LAG = [f"hist_host_loop_lag_b{i:02d}" for i in range(40)]
BUSY = ("stage_stats_snapshot_count", "stage_stats_snapshot_busy_ms_total",
        "stage_hotkeys_fold_count", "stage_hotkeys_fold_busy_ms_total")


def _snap(t, lag=None, busy=None):
    stats = {"host_loop_cpu_ms_total": 1.0}
    if lag is not None:
        stats.update({k: lag.get(i, 0) for i, k in enumerate(LAG)})
    if busy is not None:
        stats.update(zip(BUSY, busy))
    return {"t": t, "stats": stats}


def _run(before, after, trace=None):
    return {"before": before, "after": after, "trace": trace}


def test_the_lag_reader_takes_the_windows_quantile_from_the_buckets():
    read = spec.load_reader("host.loop_lag_p99_ms").read
    # 1,000 ticks before the window, all 1 s late; in it 990 on time (bucket
    # 20: ~1 ms) and 10 at 300 ms (bucket 28)
    before = _snap(100.0, lag={29: 1000})
    after = _snap(151.0, lag={29: 1000, 20: 990, 28: 10})
    assert read(_run(before, after)) == pytest.approx(2 ** 21 / 1e6)
    after = _snap(151.0, lag={29: 1000, 20: 980, 28: 20})
    assert read(_run(before, after)) == pytest.approx(2 ** 29 / 1e6)
    # a traced run is read between the snapshots inside its span
    span = {"before": _snap(120.0, lag={29: 1000}),
            "after": _snap(130.0, lag={29: 1000, 25: 200})}
    assert read(_run(before, after, trace=span)) == pytest.approx(2 ** 26 / 1e6)


def test_the_telemetry_reader_gives_both_stages_share_of_the_window():
    read = spec.load_reader("host.telemetry_busy_pct").read
    before = _snap(100.0, busy=(40, 900.0, 35, 8_000.0))
    after = _snap(151.0, busy=(51, 1_000.0, 45, 10_450.0))
    # (100 + 2,450) ms of 51 s: 5 %
    assert read(_run(before, after)) == pytest.approx(5.0)
    span = {"before": _snap(120.0, busy=(44, 950.0, 39, 9_000.0)),
            "after": _snap(130.0, busy=(46, 960.0, 41, 9_490.0))}
    assert read(_run(before, after, trace=span)) == pytest.approx(5.0)


@pytest.mark.parametrize("name", NAMES)
def test_reader_is_silent_on_a_program_without_its_keys(name):
    read = spec.load_reader(name).read
    assert read(_run(_snap(100.0), _snap(151.0))) is None
    # and where nothing was sampled in the window
    assert read(_run(_snap(100.0, lag={}, busy=(3, 1.0, 2, 1.0)),
                     _snap(151.0, lag={}, busy=(3, 1.0, 2, 1.0)))) is None


@pytest.mark.parametrize("name", NAMES)
def test_reader_is_held_to_its_per_layer_entry(name):
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    reader = spec.load_reader(name)
    assert {k: entry[k] for k in ("layer", "unit", "moves", "source")} == reader.SPEC
    assert entry["moves"] == "puback_p99_ms" and entry["better"] == "lower"
    assert entry["layer"] == "broker event loop (one Python thread)"
    assert entry["source"] == "program_counter" and "workloads" not in entry
    # no list: every cell reports it, and every cell reports the tail it moves
    for cell in bench["workloads"]:
        assert name in [m["name"] for m in spec.load_cell(cell["name"])["per_layer"]]
