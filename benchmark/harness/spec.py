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
    "loop": "closed (open is in the format, and refused until a cell brings it)",
    "publishers": "publisher connections",
    "subscribers": "subscriber connections; filter i belongs to subscriber i % subscribers",
    "inflight": "closed loop: QoS1 publishes each connection keeps outstanding",
    "rate_publishes_per_s": "open loop: publishes due per second over all connections",
    "arrival": "open loop: poisson | burst",
    "burst_size": "open loop, burst: publishes due at one instant",
    "qos1_share": "open loop: share of publishes sent at QoS1 (closed loop is all QoS1)",
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
    if t["loop"] == "closed":
        if not t["inflight"] or t["inflight"] < 1:
            fail(f"traffic {name!r}: a closed loop needs inflight >= 1")
        if t["qos1_share"] != 1.0:
            fail(f"traffic {name!r}: a closed loop publishes at QoS1 alone")
    elif t["loop"] == "open":
        fail(f"traffic {name!r}: the generator has no open loop yet; the PR "
             "that adds the first open-loop cell brings it, with its chip run")
    else:
        fail(f"traffic {name!r}: loop must be closed or open")
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
