#!/usr/bin/env python
"""Standalone churn-soak driver for the partitioned device table.

The bench's cfg9_churn_soak (bench.py run_churn_config) proves the delta
path at one fixed shape; this script sweeps it: table size, mutation rate
and duration are CLI knobs, so a real-chip session can chart per-mutation
upload bytes and p99-under-churn across scales (the 10M north-star regime)
without editing bench.py.

Per leg it reports match p50/p99, mutation rate, delta/full upload counts,
upload bytes per mutation, and background-compaction activity — the same
counters the broker surfaces through RoutingService.stats().

Usage:
  python scripts/churn_bench.py --subs 200000 --rate 500 --seconds 20
  python scripts/churn_bench.py --subs 50000 --no-delta   # the old cliff
  RMQTT_SEG_BYTES=$((64<<20)) python scripts/churn_bench.py --subs 2000000
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--subs", type=int, default=100_000, help="table size")
    ap.add_argument("--rate", type=int, default=200,
                    help="target subscribe+unsubscribe ops/sec")
    ap.add_argument("--seconds", type=float, default=15.0, help="soak length")
    ap.add_argument("--batch", type=int, default=1024, help="publish batch size")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu", action="store_true", help="force the CPU platform")
    ap.add_argument("--no-delta", action="store_true",
                    help="disable delta uploads (measure the full-refresh cliff)")
    ap.add_argument("--no-compact", action="store_true",
                    help="disable background compaction")
    args = ap.parse_args()

    if args.cpu:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"
    from rmqtt_tpu.utils.jaxenv import device_identity, setup_compile_cache

    setup_compile_cache()
    device_identity()  # raises when no accelerator answers and no --cpu

    import bench  # reuses the generators + table builders

    rng = random.Random(args.seed)
    filters = bench.gen_mixed(rng, args.subs)
    topics = bench.gen_topics_uniform(rng, max(args.batch * 8, 4096))
    table, fids = bench.build_tpu_table(filters, "partitioned")
    matcher = bench.make_matcher(table)
    matcher.delta_enabled = not args.no_delta
    table.compact_async = not args.no_compact
    fset = set(filters)
    reserve = [f for f in bench.gen_mixed(rng, args.subs // 10)
               if f not in fset]
    fid_pool = list(fids)  # O(1) swap-pop removal inside the soak loop
    batches = [topics[i: i + args.batch]
               for i in range(0, len(topics) - args.batch + 1, args.batch)]

    for b in batches[:2]:  # compile
        matcher.match(b)

    lat = []
    mutations = 0
    bytes0, d0, f0, c0 = (matcher.upload_bytes, matcher.delta_uploads,
                          matcher.full_uploads, table.compactions)
    deadline = time.perf_counter() + args.seconds
    t_start = time.perf_counter()
    next_mut = t_start
    i = 0
    while time.perf_counter() < deadline:
        now = time.perf_counter()
        while next_mut <= now and reserve:
            # one add + one remove per tick at --rate ops/sec total
            f = reserve.pop()
            fid_pool.append(table.add(f))
            fids[fid_pool[-1]] = f
            j = rng.randrange(len(fid_pool))
            fid_pool[j], fid_pool[-1] = fid_pool[-1], fid_pool[j]
            fid = fid_pool.pop()
            reserve.append(fids.pop(fid))
            table.remove(fid)
            mutations += 2
            next_mut += 2.0 / max(1, args.rate)
        t1 = time.perf_counter()
        matcher.match(batches[i % len(batches)])
        lat.append(time.perf_counter() - t1)
        i += 1
    wall = time.perf_counter() - t_start
    lat.sort()
    out = {
        "metric": "churn_soak",
        "subs": len(fids),
        "delta_enabled": matcher.delta_enabled,
        "batches": len(lat),
        "match_p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
        "match_p99_ms": round(
            lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3, 2),
        "topics_per_sec": round(len(lat) * args.batch / wall, 1),
        "mutations": mutations,
        "mutation_rate_per_sec": round(mutations / wall, 1),
        "upload_bytes": matcher.upload_bytes - bytes0,
        "upload_bytes_per_mutation": round(
            (matcher.upload_bytes - bytes0) / max(1, mutations), 1),
        "delta_uploads": matcher.delta_uploads - d0,
        "full_uploads": matcher.full_uploads - f0,
        "compactions": table.compactions - c0,
        "compact_ms": round(table.compact_ms, 1),
        "nchunks": table.nchunks,
    }
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
