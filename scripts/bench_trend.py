#!/usr/bin/env python
"""Bench trajectory: consume the accumulated BENCH_r*.json artifacts.

Every driver round leaves a ``BENCH_rNN.json`` artifact, but nothing has
ever read them together — the "bench trajectory" was empty by neglect,
not by lack of data. This script reads all rounds, extracts each round's
per-config numbers (tolerating the three artifact generations: a
``parsed`` dict, a JSON line inside ``tail``, or a tail whose head was
truncated — per-config objects are regex-recovered from the fragment),
renders a per-config trend table (goodput / p99 / speedup, with the
delta vs the previous round that has the config), and **exits non-zero
on a >tolerance%% goodput regression** between the last two comparable
rounds — the CI gate that turns the artifact pile into a trajectory.

Reduced-size (CPU fallback) rounds and full-size rounds are never
compared against each other: the marker rides each config entry.

Usage:
  python scripts/bench_trend.py                 # ./BENCH_r*.json
  python scripts/bench_trend.py --dir /path --tolerance 10
  python scripts/bench_trend.py --json          # machine-readable
  python scripts/bench_trend.py --from-history /var/lib/rmqtt/history

``--from-history <dir>`` gates against a live broker's RECORDED timeline
instead of bench artifacts: the telemetry-history segments
(broker/history.py) are split into equal time windows, each window's
delivered-message rate becomes a pseudo-round's goodput (p99 rides
along from ``publish_e2e_p99_ms``), and the same regression gate fires
on a >tolerance%% drop between the last two windows — production traffic
as the trend, no bench run required.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

#: goodput keys probed per config entry, most-representative first (the
#: router-level number is what a broker user gets; raw device otherwise)
_GOODPUT_KEYS = ("router_topics_per_sec", "device_topics_per_sec",
                 "tpu_topics_per_sec",  # rounds 1-5: CPU runs under this name
                 "cpu_topics_per_sec")


def _extract_json_objects(text: str) -> List[dict]:
    """Balanced-brace scan: every top-level-parseable {...} in ``text``."""
    out = []
    i = 0
    n = len(text)
    while i < n:
        if text[i] != "{":
            i += 1
            continue
        depth = 0
        in_str = False
        esc = False
        for j in range(i, n):
            c = text[j]
            if in_str:
                if esc:
                    esc = False
                elif c == "\\":
                    esc = True
                elif c == '"':
                    in_str = False
                continue
            if c == '"':
                in_str = True
            elif c == "{":
                depth += 1
            elif c == "}":
                depth -= 1
                if depth == 0:
                    try:
                        out.append(json.loads(text[i:j + 1]))
                    except ValueError:
                        pass
                    i = j
                    break
        i += 1
    return out


def parse_round(path: str) -> Optional[dict]:
    """→ {"round": n, "configs": {name: entry}, "metric": ..., "value": ...}
    or None when the artifact carries no usable config data."""
    with open(path) as f:
        art = json.load(f)
    n = art.get("n")
    if n is None:
        m = re.search(r"r(\d+)", os.path.basename(path))
        n = int(m.group(1)) if m else 0
    def usable(b) -> bool:
        # a round is trendable with a configs table OR a special-shape
        # block we synthesize a config entry from (cfg15 standalone runs)
        return isinstance(b, dict) and bool(
            b.get("configs") or b.get("autotune_paired")
            or b.get("egress_paired") or b.get("history_overhead")
            or b.get("hotkeys_overhead"))

    body = art.get("parsed")
    if not usable(body):
        body = None
        tail = art.get("tail") or ""
        # newest-first: the last parseable whole-line JSON object wins
        for line in reversed(tail.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    cand = json.loads(line)
                except ValueError:
                    continue
                if usable(cand):
                    body = cand
                    break
        if body is None and tail:
            # truncated tail (the artifact keeps only the stream's last
            # bytes): recover per-config objects from the fragment —
            # `"cfgN_...": {...}` pairs survive truncation individually.
            # Scan only UP TO any embedded last_tpu_run block: its configs
            # are a prior round's on-chip numbers, not this round's.
            scan = tail.split('"last_tpu_run"', 1)[0]
            configs: Dict[str, dict] = {}
            for m in re.finditer(r'"(cfg\d+[a-z0-9_]*)"\s*:\s*\{', scan):
                name = m.group(1)
                objs = _extract_json_objects(scan[m.end() - 1:][:4000])
                if objs:
                    # keep the FIRST occurrence (truncation can only cut
                    # the table's head, never interleave duplicates)
                    configs.setdefault(name, objs[0])
            if configs:
                body = {"configs": configs, "metric": None, "value": None,
                        "recovered_from_tail": True}
    if body is None:
        return None
    # special-shape configs that ride the artifact OUTSIDE the configs
    # table get synthesized entries so the trend (and the regression
    # gate) track them like any other config. cfg15: the autotune leg's
    # goodput is the tracked number, the pair ratio rides as "speedup".
    body_configs = dict(body.get("configs") or {})
    ap = body.get("autotune_paired")
    if isinstance(ap, dict) and isinstance(ap.get("autotune"), dict):
        body_configs.setdefault("cfg15_autotune_paired", {
            "tpu_topics_per_sec":
                ap["autotune"].get("goodput_topics_per_sec"),
            "p99_ms": ap["autotune"].get("p99_small_ms"),
            "speedup": ap.get("pair_ratio"),
            **({"reduced_sizes": True} if ap.get("reduced_sizes") else {}),
        })
    # cfg16: the coalesced leg's fan-out goodput is the tracked number,
    # the coalesced-over-legacy goodput ratio rides as "speedup"
    ep = body.get("egress_paired")
    if isinstance(ep, dict):
        body_configs.setdefault("cfg16_egress_paired", {
            "tpu_topics_per_sec": ep.get("fanout_goodput_coalesced"),
            "speedup": ep.get("goodput_ratio"),
            "syscall_reduction_x": ep.get("syscall_reduction_x"),
            **({"reduced_sizes": True} if ep.get("reduced_sizes") else {}),
        })
    # cfg17: the collector-on goodput is the tracked number; the pair
    # ratio (on/off) rides as "speedup" so a creeping collector cost
    # shows up on the trend even inside the 2% bound
    hp = body.get("history_overhead")
    if isinstance(hp, dict):
        lat = hp.get("latency_ms") if isinstance(
            hp.get("latency_ms"), dict) else {}
        body_configs.setdefault("cfg17_history_overhead", {
            "tpu_topics_per_sec": hp.get("msgs_per_sec_on"),
            "p99_ms": lat.get("e2e_p99"),
            "speedup": hp.get("median_pair_ratio"),
            "overhead_pct": hp.get("overhead_pct"),
            **({"reduced_sizes": True} if hp.get("reduced_sizes") else {}),
        })
    # cfg18: same contract for the hot-key attribution plane — track the
    # armed goodput and let the pair ratio expose creeping sketch cost
    ho = body.get("hotkeys_overhead")
    if isinstance(ho, dict):
        lat = ho.get("latency_ms") if isinstance(
            ho.get("latency_ms"), dict) else {}
        body_configs.setdefault("cfg18_sketch_overhead", {
            "tpu_topics_per_sec": ho.get("msgs_per_sec_on"),
            "p99_ms": lat.get("e2e_p99"),
            "speedup": ho.get("median_pair_ratio"),
            "overhead_pct": ho.get("overhead_pct"),
            **({"reduced_sizes": True} if ho.get("reduced_sizes") else {}),
        })
    configs = {}
    for name, entry in body_configs.items():
        if not isinstance(entry, dict):
            continue
        goodput = None
        for key in _GOODPUT_KEYS:
            if isinstance(entry.get(key), (int, float)):
                goodput = float(entry[key])
                break
        configs[name] = {
            "goodput": goodput,
            "p99_ms": entry.get("p99_ms"),
            "speedup": entry.get("router_speedup", entry.get("speedup")),
            "reduced": bool(entry.get("reduced_sizes", False)),
        }
    return {
        "round": int(n),
        "path": os.path.basename(path),
        "metric": body.get("metric"),
        "value": body.get("value"),
        "configs": configs,
        **({"recovered_from_tail": True}
           if body.get("recovered_from_tail") else {}),
    }


def load_rounds(pattern: str) -> List[dict]:
    rounds = []
    for path in sorted(glob.glob(pattern)):
        try:
            r = parse_round(path)
        except (ValueError, OSError) as e:
            print(f"warning: {path}: {e}", file=sys.stderr)
            continue
        if r is not None:
            rounds.append(r)
    rounds.sort(key=lambda r: r["round"])
    return rounds


def rounds_from_history(dirpath: str, windows: int = 6) -> List[dict]:
    """Recorded history segments → pseudo-rounds for the same trend/gate
    machinery: the timeline splits into ``windows`` equal spans, each
    span's average ``messages.delivered.rate`` is that round's goodput
    (series key ``history_delivered``), its average
    ``publish_e2e_p99_ms`` the p99."""
    from rmqtt_tpu.broker.history import load_dir

    rows, _anomalies, _torn = load_dir(dirpath)
    rows = [r for r in rows if isinstance(r.get("t"), (int, float))]
    if len(rows) < 2:
        return []
    t0, span = rows[0]["t"], max(1e-9, rows[-1]["t"] - rows[0]["t"])
    buckets: List[List[dict]] = [[] for _ in range(windows)]
    for r in rows:
        buckets[min(windows - 1,
                    int((r["t"] - t0) / span * windows))].append(r)

    def _avg(grp: List[dict], key: str) -> Optional[float]:
        vals = [g[key] for g in grp
                if isinstance(g.get(key), (int, float))]
        return round(sum(vals) / len(vals), 3) if vals else None

    rounds = []
    for i, grp in enumerate(buckets):
        if not grp:
            continue
        goodput = _avg(grp, "messages.delivered.rate")
        if goodput is None:
            continue
        rounds.append({
            "round": i,
            "path": f"history[{i}]",
            "metric": None,
            "value": None,
            "configs": {"history_delivered": {
                "goodput": goodput,
                "p99_ms": _avg(grp, "publish_e2e_p99_ms"),
                "speedup": None,
                "reduced": False,
            }},
        })
    return rounds


def trend(rounds: List[dict], tolerance_pct: float
          ) -> Tuple[List[dict], List[dict]]:
    """→ (rows, regressions). One row per (config, round) with the delta
    vs the previous round carrying the same config at the same size
    class; regressions = rows of the LATEST transition per config whose
    goodput dropped more than tolerance."""
    rows: List[dict] = []
    last_seen: Dict[Tuple[str, bool], dict] = {}
    latest_delta: Dict[str, dict] = {}
    for r in rounds:
        for name, entry in sorted(r["configs"].items()):
            if entry["goodput"] is None:
                continue
            key = (name, entry["reduced"])
            prev = last_seen.get(key)
            delta_pct = None
            if prev and prev["goodput"]:
                delta_pct = round(
                    100.0 * (entry["goodput"] - prev["goodput"])
                    / prev["goodput"], 1)
            row = {
                "round": r["round"],
                "config": name,
                "reduced": entry["reduced"],
                "goodput": entry["goodput"],
                "p99_ms": entry["p99_ms"],
                "speedup": entry["speedup"],
                "delta_pct": delta_pct,
            }
            rows.append(row)
            last_seen[key] = entry
            if delta_pct is not None:
                latest_delta[name] = row
    regressions = [row for row in latest_delta.values()
                   if row["delta_pct"] is not None
                   and row["delta_pct"] < -tolerance_pct]
    return rows, regressions


def render(rows: List[dict], regressions: List[dict],
           tolerance_pct: float) -> str:
    out = ["bench trend — per-config goodput/p99 across rounds",
           f"(delta vs previous round with the config; gate: "
           f">{tolerance_pct:.0f}% goodput drop on the latest transition)",
           ""]
    headers = ["config", "round", "goodput/s", "p99_ms", "speedup",
               "delta", "size"]
    table: List[List[str]] = []
    for row in sorted(rows, key=lambda r: (r["config"], r["round"])):
        table.append([
            row["config"], f"r{row['round']:02d}",
            f"{row['goodput']:.0f}" if row["goodput"] else "-",
            str(row["p99_ms"]) if row["p99_ms"] is not None else "-",
            str(row["speedup"]) if row["speedup"] is not None else "-",
            (f"{row['delta_pct']:+.1f}%" if row["delta_pct"] is not None
             else "·"),
            "reduced" if row["reduced"] else "full",
        ])
    widths = [max(len(h), *(len(t[i]) for t in table)) if table else len(h)
              for i, h in enumerate(headers)]
    out.append("  ".join(h.ljust(w) for h, w in zip(headers, widths)))
    out.append("  ".join("-" * w for w in widths))
    for t in table:
        out.append("  ".join(c.ljust(w) for c, w in zip(t, widths)))
    out.append("")
    if regressions:
        out.append(f"REGRESSIONS (> {tolerance_pct:.0f}% goodput drop):")
        for row in regressions:
            out.append(f"  {row['config']} r{row['round']:02d}: "
                       f"{row['delta_pct']:+.1f}% "
                       f"({row['goodput']:.0f}/s)")
    else:
        out.append("no goodput regressions past the gate")
    return "\n".join(out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="directory holding BENCH_r*.json (default: repo root)")
    ap.add_argument("--tolerance", type=float, default=10.0,
                    help="goodput regression gate in percent (default 10)")
    ap.add_argument("--from-history", metavar="DIR",
                    help="gate against recorded telemetry-history "
                         "segments instead of BENCH_r*.json artifacts")
    ap.add_argument("--history-windows", type=int, default=6,
                    help="time windows the history timeline splits into "
                         "(default 6; each window is one pseudo-round)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    if args.from_history:
        rounds = rounds_from_history(args.from_history,
                                     max(2, args.history_windows))
        if not rounds:
            print(f"no usable history samples in {args.from_history}",
                  file=sys.stderr)
            return 2
    else:
        rounds = load_rounds(os.path.join(args.dir, "BENCH_r*.json"))
    if not rounds:
        print("no parseable BENCH_r*.json artifacts found", file=sys.stderr)
        return 2
    rows, regressions = trend(rounds, args.tolerance)
    if args.json:
        print(json.dumps({"rounds": [r["round"] for r in rounds],
                          "rows": rows, "regressions": regressions},
                         indent=1))
    else:
        print(render(rows, regressions, args.tolerance))
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
