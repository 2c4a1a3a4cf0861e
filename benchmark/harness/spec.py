"""The benchmark's data files, loaded and checked.

``BENCHMARK.json`` names cells, configurations and metrics; everything that
belongs to one of them is a file of its own under ``benchmark/``, found by
that name. An unknown key in a traffic mix or a cell is an error, never
ignored: a mix that asks for something the generator does not do must not
run as something else.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# every key a mix has, and what it means; null where a key does not apply
TRAFFIC_KEYS = {
    "loop": "closed (the broker sets the rate) | open (the mix offers one)",
    "publishers": "publisher connections",
    "subscribers": "subscriber connections; filter i belongs to subscriber i % subscribers",
    "inflight": "the connection's window: QoS1 publishes it has unacknowledged, "
                "always (closed loop) or at the most (open loop)",
    "rate_publishes_per_s": "open loop: publishes due per second over all connections",
    "arrival": "open loop: burst, the only arrival a cell asks for yet",
    "burst_size": "open loop, burst: publishes due at one instant",
    "qos1_share": "share of publishes sent at QoS1; 1.0, the only one a cell asks for yet",
    "subscribe_qos": "QoS the subscribers ask for",
    "filters_per_subscribe": "filters in one SUBSCRIBE packet of the table load",
    "publisher_procs": "load-generator processes holding the publishers",
    "subscriber_procs": "load-generator processes holding the subscribers",
    "pregen_publishes_per_s": "topics drawn ahead per second of run (more are drawn on the fly)",
    "link": "loopback (the only link a one-machine run has)",
    "why": "what the mix stands for",
}
CELL_KEYS = {"config", "traffic", "overrides", "why"}


def fail(msg: str):
    raise SystemExit(f"benchmark: {msg}")


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        fail(f"no file {path.relative_to(ROOT)}")
    except json.JSONDecodeError as e:
        fail(f"{path.relative_to(ROOT)}: {e}")


def load_traffic(name: str, overrides: dict) -> dict:
    t = load_json(BENCH_DIR / "traffic" / f"{name}.json")
    t.update(overrides)
    unknown = set(t) - set(TRAFFIC_KEYS)
    missing = set(TRAFFIC_KEYS) - set(t) - {"why"}
    if unknown or missing:
        fail(f"traffic {name!r}: unknown keys {sorted(unknown)}, "
             f"missing keys {sorted(missing)}")
    if t["loop"] not in ("closed", "open"):
        fail(f"traffic {name!r}: loop must be closed or open")
    if not t["inflight"] or t["inflight"] < 1:
        fail(f"traffic {name!r}: a connection's window needs inflight >= 1")
    if t["qos1_share"] != 1.0:
        fail(f"traffic {name!r}: qos1_share must be 1.0: a QoS0 publish "
             "carries no guarantee that the comparison could hold exactly, "
             "and no cell asks for one yet")
    rate, arrival, burst = (t[k] for k in (
        "rate_publishes_per_s", "arrival", "burst_size"))
    if t["loop"] == "closed":
        if any(v is not None for v in (rate, arrival, burst)):
            fail(f"traffic {name!r}: a closed loop offers no rate: "
                 "rate_publishes_per_s, arrival and burst_size must be null")
    elif not rate or rate <= 0:
        fail(f"traffic {name!r}: an open loop needs rate_publishes_per_s > 0")
    elif arrival != "burst":
        fail(f"traffic {name!r}: an open loop's arrival must be burst: no cell "
             f"asks for another yet (it names {arrival!r}), and the PR that "
             "adds the first such cell brings the generator's branch with its "
             "chip run")
    else:
        if not burst or burst < 1:
            fail(f"traffic {name!r}: arrival burst needs burst_size >= 1")
        if t["publishers"] < burst:
            fail(f"traffic {name!r}: a burst of {burst} needs as many "
                 f"publishers (no connection gets two publishes of one burst); "
                 f"the mix has {t['publishers']}")
    if t["link"] != "loopback":
        fail(f"traffic {name!r}: only the loopback link exists here")
    return t


def metric_applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> dict:
    """→ {name, config, traffic, end_to_end, per_layer}: the cell's files,
    and the metrics of ``BENCHMARK.json`` that it reports."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        fail(f"BENCHMARK.json has no workload {name!r} "
             f"(it has {[w['name'] for w in bench['workloads']]})")
    cell = load_json(BENCH_DIR / "cells" / f"{name}.json")
    if set(cell) - CELL_KEYS:
        fail(f"cell {name!r}: unknown keys {sorted(set(cell) - CELL_KEYS)}")
    if (cell["config"], cell["traffic"]) != (entry["config"], entry["traffic"]):
        fail(f"cell {name!r}: cells/{name}.json and BENCHMARK.json disagree")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT / conf["file"])
    return {
        "name": name, "chips": entry["chips"], "config": config,
        "traffic": load_traffic(cell["traffic"], cell.get("overrides", {})),
        "end_to_end": [m for m in bench["end_to_end"] if metric_applies(m, name)],
        "per_layer": [m for m in bench["per_layer"] if metric_applies(m, name)],
    }


def load_reader(metric_name: str):
    """The per-layer metric's own file: ``layer_metrics/<name>.py`` with
    ``SPEC`` (layer, unit, moves, source) and ``read(run)``."""
    path = BENCH_DIR / "layer_metrics" / f"{metric_name}.py"
    if str(path.parent) not in sys.path:  # readers share _deltas.py
        sys.path.insert(0, str(path.parent))
    if not path.exists():
        fail(f"no reader layer_metrics/{metric_name}.py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + re.sub(r"\W", "_", metric_name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
