#!/usr/bin/env python
"""Partitioned-matcher roofline: analytic bytes-moved vs HBM bandwidth.

The achievable ceiling as a NUMBER. This
script builds the bench's filter tables (reduced or full), measures the
real candidate-chunk distribution of the bench's topic streams, and
computes the per-batch HBM traffic of the scan kernel from the actual
device-tile layouts — BOTH of them:

- legacy int16/int32 field-major tiles (``ops.partitioned.pack_device_rows``)
- bit-packed int32 byte-plane tiles (``pack_device_rows_packed``): per-level
  local token ids at 1-2 bytes each + one metadata byte, grouped four byte
  planes per int32 lane

and the fused-pipeline deltas (the ``[B, NC*WPC]`` words array that no
longer round-trips between two dispatches, and the route wire moving from
2 B + host decode to 4 B final fids). The model itself lives in
``rmqtt_tpu/bench/roofline_model.py`` so ``bench.py`` embeds the SAME
numbers next to each measured config (modeled-vs-measured per run).

The peak comes from ``roofline_model.DEVICE_PEAKS`` by ``--device-kind``
(default the v5e, "TPU v5 lite"); an unknown part is an error. The
printout compares the ceiling with the standing measured rates so the
gap names what actually binds (dispatch round trip, scan step overhead,
compaction) — see NOTES.md "Roofline" for the analysis.

Usage: python scripts/roofline.py [--full] [--device-kind KIND]
"""

from __future__ import annotations

import argparse
import json
import random
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # model only — no device needed

import numpy as np  # noqa: E402


def build(name, filters, topics, batch, device_kind):
    from rmqtt_tpu.bench.roofline_model import model_table
    from rmqtt_tpu.core.topic import parse_shared, split_levels
    from rmqtt_tpu.ops.partitioned import CHUNK, PartitionedTable

    t = PartitionedTable()
    for f in filters:
        _, stripped = parse_shared(f)
        t.add(stripped)
    t.compact()
    # measured candidate distribution over the real topic stream
    ncs = [len(t._candidates_for(split_levels(topic)))
           for topic in topics[:4096]]
    model = model_table(t, ncs, device_kind)
    layout = t.packed_layout()
    model.update({
        "config": name,
        "filters": len(filters),
        "nchunks": t.nchunks,
        "batch": batch,
        "packed_layout": list(layout.widths) if layout is not None else None,
        "table_mb_legacy": round(
            t.nchunks * model["tile_bytes_legacy"] / 1e6, 1),
        "table_mb_packed": (
            round(t.nchunks * model["tile_bytes_packed"] / 1e6, 1)
            if layout is not None else None),
    })
    return model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="build the full-size tables (cfg3 1M; slow)")
    ap.add_argument("--device-kind", default="TPU v5 lite",
                    help="jax device_kind to model (peaks table in "
                         "rmqtt_tpu/bench/roofline_model.py)")
    args = ap.parse_args()

    sys.path.insert(0, str(REPO))
    import bench

    rng = random.Random(0)
    rows = []
    n1 = 1000
    f1 = bench.gen_exact(rng, n1)
    t1 = [rng.choice(f1) if rng.random() < 0.5 else bench._tree_topic(rng, 4)
          for _ in range(4096)]
    rows.append(build("cfg1_exact_1k", f1, t1, 4096, args.device_kind))
    n2, nt2 = (100_000, 8192) if args.full else (20_000, 8192)
    f2 = bench.gen_single_plus(rng, n2)
    t2 = ["/".join(f"l{d}n{rng.randrange(400)}" for d in range(rng.randint(3, 5)))
          for _ in range(nt2)]
    rows.append(build("cfg2_plus_100k", f2, t2, 8192, args.device_kind))
    n3 = 1_000_000 if args.full else 100_000
    f3 = bench.gen_mixed(rng, n3)
    t3 = bench.gen_topics_uniform(rng, 8192)
    rows.append(build("cfg3_mixed_1m", f3, t3, 16384, args.device_kind))
    n4 = 10_000_000 if args.full else 200_000
    f4 = bench.gen_mixed(rng, n4, shared_frac=0.1)
    t4 = bench.gen_topics_zipf(rng, 8192)
    rows.append(build("cfg4_shared_10m_zipf", f4, t4, 8192, args.device_kind))

    print(f"\nHBM roofline of {args.device_kind} @ {rows[0]['hbm_gbps']:.0f} GB/s "
          f"({'full' if args.full else 'reduced'} tables):")
    for r in rows:
        print(
            f"  {r['config']:22s} "
            f"tiles {r['tile_bytes_legacy']:5d}→{r['tile_bytes_packed'] or 0:5d} B "
            f"({r['packed_tile_reduction_x'] or 0:.2f}x)  "
            f"nc_mean {r['nc_mean']:6.2f}  "
            f"{r['bytes_per_topic_legacy']:>8d}→{r['bytes_per_topic']:>7d} B/topic "
            f"({r['hbm_bytes_reduction_x']:.2f}x)  "
            f"ceiling {r['ceiling_topics_per_sec_legacy'] / 1e6:6.2f}→"
            f"{r['ceiling_topics_per_sec'] / 1e6:.2f}M topics/s"
        )
    print("\nfused pipeline (per topic, modeled): words round-trip "
          "eliminated; wire 2B/route + host decode → 4B/route final fids")
    out = REPO / "ROOFLINE.json"
    out.write_text(json.dumps(
        {"device_kind": args.device_kind, "hbm_gbps": rows[0]["hbm_gbps"],
         "full_tables": args.full, "configs": rows},
        indent=1))
    print(f"\n→ {out}")


if __name__ == "__main__":
    main()
