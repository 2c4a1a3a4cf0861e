"""The least time the chip could take for the matches it served.

The match kernel is bound by HBM traffic: for every topic it gathers the
candidate tiles of the packed table and writes the packed match words. A
configuration freezes its bytes per topic (``hbm_bytes_per_topic``, with how
it was got) and the chip's peak is in ``peaks.json``, so the share reads the
same work whatever implements the kernel:

    bytes_per_topic = nc_mean * (tile_bytes + words_per_chunk * 4)
    least_s         = topics * bytes_per_topic / (hbm_gbps * 1e9)
    share           = least_s / device seconds of the match programs

The arithmetic is ``rmqtt_tpu/bench/roofline_model.py``'s (``model_table``:
``bytes_per_topic``), copied so that later PRs cannot move the yardstick.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent.parent / "peaks.json"


def peak_hbm_bytes_per_s(device_kind: str) -> float:
    peaks = json.loads(PEAKS.read_text())
    if device_kind not in peaks:
        raise SystemExit(f"benchmark: no published peaks for device_kind "
                         f"{device_kind!r} in {PEAKS.name} (known: {sorted(peaks)})")
    return peaks[device_kind]["hbm_gbps"] * 1e9


def bytes_per_topic(nc_mean: float, tile_bytes: int, words_per_chunk: int) -> float:
    return nc_mean * (tile_bytes + words_per_chunk * 4)


def match_roofline_pct(topics: int, hbm_bytes_per_topic: float,
                       device_kind: str, program_seconds: float) -> float:
    least_s = topics * hbm_bytes_per_topic / peak_hbm_bytes_per_s(device_kind)
    return 100.0 * least_s / program_seconds
