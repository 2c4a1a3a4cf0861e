#!/usr/bin/env python
"""Render a device-plane flight-recorder dump to a human-readable report.

The profiler (`rmqtt_tpu/broker/devprof.py`) writes dump artifacts —
``{"schema": "rmqtt_tpu.devprof_dump/1", "snapshot": ..., "flight": [...]}``
— on failover trips, fused-verify disagreement, retrace storms and failed
bench configs (``bench.py`` guarded handler, ``.devprof/``).
This script turns one into the tables an operator reads first:

  * top shape keys by trace (compile) time, per kernel — the "what kept
    recompiling" table for retrace-storm postmortems;
  * stage-time breakdown (encode / dispatch / fetch / decode) aggregated
    over the flight ring — where the dispatch path actually spends;
  * the pad-waste / dispatch-latency timeline from the interval rollups;
  * the tail of the flight ring itself.

Usage:  python scripts/devprof_report.py .devprof/cfg4_shared_10m_zipf.json
        python scripts/devprof_report.py --flight 20 dump.json
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List


def _table(headers: List[str], rows: List[List[str]]) -> str:
    widths = [max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
              for i, h in enumerate(headers)]
    out = ["  ".join(h.ljust(w) for h, w in zip(headers, widths))]
    out.append("  ".join("-" * w for w in widths))
    for r in rows:
        out.append("  ".join(c.ljust(w) for c, w in zip(r, widths)))
    return "\n".join(out)


def render(dump: dict, flight_tail: int = 10) -> str:
    snap = dump.get("snapshot") or {}
    comp = snap.get("compile") or {}
    disp = snap.get("dispatch") or {}
    hbm = snap.get("hbm") or {}
    up = snap.get("uploads") or {}
    flight = dump.get("flight") or []
    out: List[str] = []
    out.append(f"devprof dump — reason: {dump.get('reason', '?')} "
               f"ts: {dump.get('ts', '?')}")
    out.append(
        f"compile: {comp.get('traces', 0)} traces "
        f"({comp.get('trace_ms_total', 0)} ms total), "
        f"{comp.get('cache_hits', 0)} cache hits, "
        f"{comp.get('storms', 0)} retrace storms")
    if comp.get("last_storm"):
        s = comp["last_storm"]
        out.append(f"  last storm: {s.get('traces_in_window')} traces in "
                   f"{s.get('window_s')}s (last kernel {s.get('kernel')})")
    out.append(
        f"dispatch: {disp.get('dispatches', 0)} batches, "
        f"{disp.get('items', 0)} topics over {disp.get('padded_items', 0)} "
        f"padded rows (waste {disp.get('pad_waste', 0):.1%}, floor "
        f"{disp.get('pad_floor', 1)}), fused {disp.get('fused', 0)} / "
        f"fallback {disp.get('fallback', 0)}")
    out.append(
        f"uploads: {up.get('delta', 0)} delta ({up.get('delta_bytes', 0)} B) "
        f"/ {up.get('full', 0)} full ({up.get('full_bytes', 0)} B)")
    out.append(
        f"hbm: modeled {hbm.get('modeled_bytes', 0)} B "
        f"({hbm.get('layout', 'n/a')} tiles {hbm.get('tiles_bytes', 0)} B, "
        f"fid map {hbm.get('fid_map_bytes', 0)} B, "
        f"{hbm.get('segments', 0)} segments); "
        f"live arrays {hbm.get('live_arrays_bytes', 'n/a')} B")

    # top shape keys by trace time, flattened across kernels
    rows = []
    for kernel, kinfo in sorted((comp.get("kernels") or {}).items()):
        for key in kinfo.get("keys", []):
            rows.append((key.get("trace_ms", 0), kernel, key.get("key", "")))
    rows.sort(reverse=True)
    out.append("\n== top shape keys by trace (compile) time ==")
    out.append(_table(
        ["trace_ms", "kernel", "shape key"],
        [[f"{ms:.1f}", k, key[:100]] for ms, k, key in rows[:15]])
        if rows else "(no traces recorded)")

    # stage-time breakdown over the flight ring
    stage_tot = {"encode": 0, "dispatch": 0, "fetch": 0, "decode": 0}
    staged = 0
    for rec in flight:
        sn = rec.get("stage_ns")
        if sn:
            staged += 1
            for k in stage_tot:
                stage_tot[k] += sn.get(k, 0)
    out.append("\n== stage-time breakdown (flight ring) ==")
    if staged:
        total = max(1, sum(stage_tot.values()))
        out.append(_table(
            ["stage", "total_ms", "share"],
            [[k, f"{v / 1e6:.2f}", f"{v / total:.1%}"]
             for k, v in stage_tot.items()]))
        out.append(f"({staged} of {len(flight)} records carry stage timing)")
    else:
        out.append("(no stage timing in the ring — enable stage_timing / "
                   "device_profile)")

    # pad-waste / latency timeline
    out.append("\n== dispatch timeline (interval rollups) ==")
    rollups = disp.get("rollups") or []
    out.append(_table(
        ["t", "disp", "items", "pad_waste", "p50_ms", "p99_ms",
         "delta_B", "full_B", "traces"],
        [[str(r.get("t")), str(r.get("dispatches")), str(r.get("items")),
          f"{r.get('pad_waste', 0):.1%}", str(r.get("p50_ms")),
          str(r.get("p99_ms")), str(r.get("delta_bytes")),
          str(r.get("full_bytes")), str(r.get("traces"))]
         for r in rollups[-20:]]) if rollups else "(no rollups)")

    out.append(f"\n== flight ring tail (last {flight_tail} of "
               f"{len(flight)}) ==")
    for rec in flight[-flight_tail:]:
        out.append(json.dumps(rec, sort_keys=True))
    return "\n".join(out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dump", help="path to a devprof dump JSON")
    ap.add_argument("--flight", type=int, default=10,
                    help="flight-ring records to print (default 10)")
    args = ap.parse_args()
    with open(args.dump) as f:
        dump = json.load(f)
    if dump.get("schema") != "rmqtt_tpu.devprof_dump/1":
        print(f"warning: unexpected schema {dump.get('schema')!r}",
              file=sys.stderr)
    print(render(dump, args.flight))
    return 0


if __name__ == "__main__":
    sys.exit(main())
