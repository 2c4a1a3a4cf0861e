#!/usr/bin/env python
"""Offline autotune: fit STARTING knobs from recorded device-plane data.

The live autotuner (rmqtt_tpu/broker/autotune.py) adapts knobs from
devprof rollups as traffic flows — but every process still STARTS from
the static defaults and re-learns the workload from scratch. This script
closes the offline half of the loop: it replays recorded evidence —
devprof flight-recorder dumps (``rmqtt_tpu.devprof_dump/1``), bench
artifacts (``BENCH_r*.json``, which embed a
``devprof`` snapshot), or raw ``/api/v1/device`` bodies — and fits the
knob vector a broker should START from:

- **pad_floor** from the merged per-interval batch-size histogram: the
  pow2 cover of the p50 batch when small batches dominate, pulled down
  to 1 when pad-waste shows the floor itself is the waste.
- **fused / packed** kept ON unless the evidence shows fallback-dominant
  dispatch (a fused pipeline that keeps disagreeing re-verifies forever).
- **delta_uploads** from the observed per-upload byte averages: scatter
  only pays while a delta ships fewer bytes than the repack it replaces.
- **linger_ms** raised one notch when rollups show high dispatch rates
  of near-empty batches (the micro-batch window the cfg1 regime wants).

Output is the fitted knob dict plus (``--env``) the matching ``RMQTT_*``
environment, so a process starts from what the last one learned instead
of from the defaults.

Usage:
  python scripts/autotune_replay.py .devprof/*.json
  python scripts/autotune_replay.py bench_out.json --json   # a bench.py artifact
  python scripts/autotune_replay.py dumps/*.json --env   # shell-ready
  python scripts/autotune_replay.py --history /var/lib/rmqtt/history

``--history <dir>`` replays a broker's recorded telemetry-history
segments (broker/history.py): the per-sample ``device.*`` window
summaries — including the mergeable sparse batch histograms — are
re-assembled into a devprof-snapshot-shaped document and fitted exactly
like a flight-recorder dump, so a production timeline seeds the next
process without anyone having saved a dump.
"""

from __future__ import annotations

import argparse
import glob
import json
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _pow2_cover(n: int, cap: int = 64) -> int:
    """Smallest power of two >= n, clamped to [1, cap]."""
    n = max(1, int(n))
    p = 1
    while p < n and p < cap:
        p <<= 1
    return min(p, cap)


def extract_snapshots(doc: dict) -> List[dict]:
    """Pull every devprof snapshot-shaped dict out of one artifact,
    whatever its generation: a flight-recorder dump (``snapshot`` key +
    schema), a bench artifact (``devprof`` embed), a raw ``/api/v1/device``
    body (has ``compile``+``dispatch`` at top level), or a checkpoint
    wrapping any of those."""
    out: List[dict] = []
    if not isinstance(doc, dict):
        return out
    if isinstance(doc.get("snapshot"), dict):  # devprof dump artifact
        out.append(doc["snapshot"])
    if isinstance(doc.get("devprof"), dict):  # bench artifact embed
        out.append(doc["devprof"])
    if isinstance(doc.get("compile"), dict) and isinstance(
            doc.get("dispatch"), dict):
        out.append(doc)  # raw /api/v1/device body (or snapshot itself)
    # BENCH driver artifacts nest the bench stdout under "parsed"
    if isinstance(doc.get("parsed"), dict):
        out.extend(extract_snapshots(doc["parsed"]))
    return out


def _merged_batch_hist(snaps: List[dict]) -> Dict[int, int]:
    """Merge every rollup's sparse batch histogram (upper-bound key →
    count) across snapshots — the mergeable-by-addition property the
    log2 buckets exist for."""
    hist: Dict[int, int] = {}
    for snap in snaps:
        for roll in (snap.get("dispatch") or {}).get("rollups") or []:
            for k, c in (roll.get("batch_hist") or {}).items():
                try:
                    hist[int(k)] = hist.get(int(k), 0) + int(c)
                except (TypeError, ValueError):
                    continue
    return hist


def _hist_quantile(hist: Dict[int, int], q: float) -> Optional[int]:
    """q-th batch-size bucket LOWER bound (the conservative estimate for
    a pad floor: upper bounds are exclusive)."""
    total = sum(hist.values())
    if not total:
        return None
    rank = max(1, int(q * total + 0.999999))
    acc = 0
    for upper in sorted(hist):
        acc += hist[upper]
        if acc >= rank:
            return max(1, upper // 2)
    return max(1, max(hist) // 2)


def fit_knobs(docs: List[dict]) -> dict:
    """→ {"knobs": {...}, "evidence": {...}} fitted over every devprof
    snapshot found in ``docs``. Knobs omitted from the result carry no
    evidence either way (the caller keeps its defaults for them)."""
    snaps: List[dict] = []
    for doc in docs:
        snaps.extend(extract_snapshots(doc))
    knobs: Dict[str, Any] = {}
    evidence: Dict[str, Any] = {"snapshots": len(snaps)}
    if not snaps:
        return {"knobs": knobs, "evidence": evidence}

    # --- pad floor: cover the p50 batch; drop to 1 when the floor IS the
    # waste (pad-waste high while batches concentrate below the floor)
    bhist = _merged_batch_hist(snaps)
    b50 = _hist_quantile(bhist, 0.50)
    b99 = _hist_quantile(bhist, 0.99)
    disp = [s.get("dispatch") or {} for s in snaps]
    items = sum(d.get("items", 0) for d in disp)
    padded = sum(d.get("padded_items", 0) for d in disp)
    pad_waste = (1.0 - items / padded) if padded else 0.0
    floors = [d.get("pad_floor", 1) for d in disp if d.get("pad_floor")]
    floor_seen = max(floors) if floors else 1
    if b50 is not None:
        fitted = _pow2_cover(b50)
        if pad_waste >= 0.5 and b99 is not None and b99 <= floor_seen:
            # the recorded floor padded essentially every batch: start low
            fitted = _pow2_cover(b99 if b99 > 1 else 1)
        knobs["pad_floor"] = fitted
        evidence["batch_p50"] = b50
        evidence["batch_p99"] = b99
        evidence["pad_waste"] = round(pad_waste, 4)
        evidence["pad_floor_seen"] = floor_seen

    # --- fused: keep unless the record shows fallback-dominant dispatch
    fused = sum(d.get("fused", 0) for d in disp)
    fallback = sum(d.get("fallback", 0) for d in disp)
    if fused + fallback >= 16:
        knobs["fused"] = fused >= fallback
        evidence["fused_share"] = round(fused / (fused + fallback), 4)

    # --- delta gate: scatter must ship fewer bytes than the repack
    up = [s.get("uploads") or {} for s in snaps]
    d_count = sum(u.get("delta", 0) for u in up)
    f_count = sum(u.get("full", 0) for u in up)
    d_bytes = sum(u.get("delta_bytes", 0) for u in up)
    f_bytes = sum(u.get("full_bytes", 0) for u in up)
    if d_count >= 4 and f_count >= 1:
        d_avg, f_avg = d_bytes / d_count, f_bytes / f_count
        knobs["delta_uploads"] = d_avg <= f_avg
        evidence["delta_avg_bytes"] = int(d_avg)
        evidence["full_avg_bytes"] = int(f_avg)

    # --- micro-batch window: sustained near-empty batches at high
    # dispatch rates want a small linger
    rolls = [r for s in snaps
             for r in (s.get("dispatch") or {}).get("rollups") or []]
    busy = [r for r in rolls if r.get("dispatches", 0) >= 16]
    if busy:
        tiny = [r for r in busy
                if r.get("items", 0) / max(1, r["dispatches"]) <= 2.0]
        if len(tiny) >= max(2, len(busy) // 2):
            knobs["linger_ms"] = 0.5
            evidence["tiny_batch_intervals"] = len(tiny)

    # --- retrace storms recorded → a higher floor is safer than compiles
    storms = sum((s.get("compile") or {}).get("storms", 0) for s in snaps)
    evidence["storms"] = storms
    if storms and "pad_floor" in knobs and b99 is not None:
        knobs["pad_floor"] = max(knobs["pad_floor"], _pow2_cover(b99))
    return {"knobs": knobs, "evidence": evidence}


#: fitted knob → the env seam that seeds a fresh process with it.
#: linger_ms rides the conf env override ([routing] linger_ms); the rest
#: are the matcher/router construction-time kill-switches.
ENV_SEAMS = {
    "pad_floor": ("RMQTT_PAD_FLOOR", str),
    "fused": ("RMQTT_FUSED", lambda v: "1" if v else "0"),
    "packed": ("RMQTT_PACKED", lambda v: "1" if v else "0"),
    "delta_uploads": ("RMQTT_DELTA_UPLOADS", lambda v: "1" if v else "0"),
    "hybrid_max": ("RMQTT_HYBRID_MAX", str),
    "linger_ms": ("RMQTT_ROUTING__LINGER_MS", str),
}


def knobs_to_env(knobs: Dict[str, Any]) -> Dict[str, str]:
    env: Dict[str, str] = {}
    for name, value in knobs.items():
        seam = ENV_SEAMS.get(name)
        if seam is not None and value is not None:
            env[seam[0]] = seam[1](value)
    return env


def history_to_doc(dirpath: str) -> Optional[dict]:
    """Recorded history segments → one devprof-snapshot-shaped doc the
    fitter consumes unchanged. Each history sample's ``device.*`` block
    is a disjoint window summary (rollup_summary since the previous
    sample), so summing across samples — and key-adding the sparse batch
    histograms — reconstructs the recording's dispatch totals."""
    from rmqtt_tpu.broker.history import load_dir

    rows, _anomalies, _torn = load_dir(dirpath)
    rollups: List[dict] = []
    items = padded = traces = dispatches = 0
    for r in rows:
        dv = {k[len("device."):]: v for k, v in r.items()
              if k.startswith("device.")}
        if not dv:
            continue
        rollups.append({
            "batch_hist": dv.get("batch_hist") or {},
            "dispatches": dv.get("dispatches", 0),
            "items": dv.get("items", 0),
        })
        dispatches += int(dv.get("dispatches", 0) or 0)
        items += int(dv.get("items", 0) or 0)
        padded += int(dv.get("padded", 0) or 0)
        traces += int(dv.get("traces", 0) or 0)
    if not rollups:
        return None
    return {
        "schema": "rmqtt_tpu.history_replay/1",
        "compile": {"traces": traces, "storms": 0},
        "dispatch": {"rollups": rollups, "dispatches": dispatches,
                     "items": items, "padded_items": padded},
    }


def load_docs(paths: List[str]) -> List[dict]:
    docs: List[dict] = []
    for pattern in paths:
        for path in sorted(glob.glob(pattern)) or [pattern]:
            try:
                with open(path) as f:
                    docs.append(json.load(f))
            except (OSError, ValueError) as e:
                print(f"warning: {path}: {e}", file=sys.stderr)
    return docs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*",
                    help="devprof dumps / bench artifacts / device bodies")
    ap.add_argument("--history", action="append", default=[],
                    metavar="DIR",
                    help="recorded telemetry-history segment dir(s) "
                         "(broker/history.py) to fit from")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable {knobs, evidence, env}")
    ap.add_argument("--env", action="store_true",
                    help="print shell-ready KEY=VALUE lines only")
    args = ap.parse_args()
    if not args.paths and not args.history:
        ap.error("need artifact paths and/or --history <dir>")
    docs = load_docs(args.paths)
    for d in args.history:
        doc = history_to_doc(d)
        if doc is not None:
            docs.append(doc)
        else:
            print(f"warning: {d}: no device samples in history",
                  file=sys.stderr)
    if not docs:
        print("no readable artifacts", file=sys.stderr)
        return 2
    fit = fit_knobs(docs)
    env = knobs_to_env(fit["knobs"])
    if args.env:
        for k, v in sorted(env.items()):
            print(f"{k}={v}")
        return 0
    if args.json:
        print(json.dumps({**fit, "env": env}, indent=1))
        return 0
    print("fitted starting knobs "
          f"({fit['evidence'].get('snapshots', 0)} snapshot(s)):")
    for k, v in sorted(fit["knobs"].items()):
        print(f"  {k:>14} = {v}")
    if not fit["knobs"]:
        print("  (no knob has enough evidence; defaults stand)")
    print("evidence:", json.dumps(fit["evidence"]))
    if env:
        print("env:", " ".join(f"{k}={v}" for k, v in sorted(env.items())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
