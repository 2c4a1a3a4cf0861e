"""Analytic HBM-traffic model of the partitioned match kernel.

One source of truth for the roofline numbers: ``scripts/roofline.py``
builds tables offline and prints ceilings; ``bench.py`` calls
``model_table`` against the LIVE table of each measured config and embeds
the model next to the measured rate, so every bench artifact carries its
own modeled-vs-measured delta (the "is the bandwidth claim holding?"
check the ISSUE asked to make per-run).

The model (see ``ops/partitioned.pack_device_rows`` /
``pack_device_rows_packed`` for the layouts):

    tile_bytes_legacy  = (L+3) * CHUNK * dtype_size      # int16 field-major
    tile_bytes_packed  = groups * CHUNK * 4              # int32 byte planes
    batch_bytes        = B * NC_eff * tile_bytes         # the scan's gathers
                       + B * NC_eff * WPC * 4            # packed words out
    ceiling            = HBM_BW / bytes_per_topic        # topics/s if bound

plus the fused-pipeline deltas: the words array no longer round-trips
between two dispatches, the device→host wire carries 4 B/route (final
fids) instead of 2 B/route + a host-side chunk-gather + fid-map + sort.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from rmqtt_tpu.ops.partitioned import CHUNK, WORDS_PER_CHUNK

#: published peaks, keyed by ``jax.devices()[0].device_kind``. A device that
#: is not here is an error, not a default: a roofline against the wrong
#: part's bandwidth is a wrong number with a right-looking name.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "hbm_gbps": 819.0,
        "source": "Google Cloud documentation, \"TPU v5e\": 16 GB of HBM "
                  "at 819 GB/s per chip",
    },
}


def peak_hbm_gbps(device_kind: str) -> float:
    try:
        return DEVICE_PEAKS[device_kind]["hbm_gbps"]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"roofline_model.DEVICE_PEAKS (known: {sorted(DEVICE_PEAKS)}); "
            "add the part with its source before modelling it") from None


def tile_bytes_legacy(max_levels: int, tok_wide: bool = False) -> int:
    """One gathered tile in the legacy int16/int32 field-major layout."""
    return (max_levels + 3) * CHUNK * (4 if tok_wide else 2)


def tile_bytes_packed(layout) -> int:
    """One gathered tile in the bit-packed int32 byte-plane layout."""
    return layout.groups * CHUNK * 4


def model_table(table, ncs: Sequence[int], device_kind: str,
                measured_topics_per_sec: Optional[float] = None) -> dict:
    """HBM roofline of one table on ``device_kind`` against a MEASURED
    candidate-count sample ``ncs`` (one entry per topic of the real publish
    stream). When ``measured_topics_per_sec`` is given, the
    modeled-vs-measured fraction is included so regressions in either
    direction are visible per run."""
    bw_gbps = peak_hbm_gbps(device_kind)
    ncs = np.asarray(ncs, dtype=np.float64)
    nc_eff = float(ncs.mean()) if ncs.size else 1.0
    layout = table.packed_layout()
    legacy = tile_bytes_legacy(table.max_levels, table._tok_wide)
    ptile = tile_bytes_packed(layout) if layout is not None else None
    out_bytes = nc_eff * WORDS_PER_CHUNK * 4
    bpt_legacy = nc_eff * legacy + out_bytes
    bpt = nc_eff * ptile + out_bytes if ptile is not None else bpt_legacy
    bw = bw_gbps * 1e9
    out = {
        "device_kind": device_kind,
        "hbm_gbps": bw_gbps,
        "nc_mean": round(nc_eff, 2),
        "nc_p99": int(np.percentile(ncs, 99)) if ncs.size else 0,
        "tile_bytes_legacy": legacy,
        "tile_bytes_packed": ptile,
        "packed_tile_reduction_x": (
            round(legacy / ptile, 2) if ptile else None),
        "bytes_per_topic_legacy": int(bpt_legacy),
        "bytes_per_topic": int(bpt),
        "hbm_bytes_reduction_x": round(bpt_legacy / bpt, 2),
        "ceiling_topics_per_sec": int(bw / bpt),
        "ceiling_topics_per_sec_legacy": int(bw / bpt_legacy),
        # what the fused pipeline removes per topic: the intermediate
        # [B, NC*WPC] words array written by dispatch 1 and re-read by
        # dispatch 2, and the host decode (chunk gather + fid map + sort);
        # what it costs: 4 B/route on the wire instead of 2
        "fused": {
            "words_roundtrip_bytes_per_topic": int(
                2 * nc_eff * WORDS_PER_CHUNK * 4),
            "wire_bytes_per_route": 4,
            "unfused_wire_bytes_per_route": 2,
            "host_decode_on_wire": False,
        },
    }
    if measured_topics_per_sec is not None:
        out["measured_topics_per_sec"] = round(measured_topics_per_sec, 1)
        out["measured_fraction_of_ceiling"] = round(
            measured_topics_per_sec / max(1.0, out["ceiling_topics_per_sec"]),
            4)
    return out
