"""Share of the scan's chunk steps that gather a padding chunk:
100 x (1 - Δ``routing_encode_candidates`` / Δ``routing_encode_scanned``)
over the window (``/api/v1/stats``, ``ops/partitioned.py``). A topic's scan
runs the batch's padded NC (a sticky power of two, at least 32) however few
candidates it has: this is the work the padding costs. Absent where the
broker has no such counter (a program from before partitions split) or
scanned nothing in the window."""

from _deltas import stat

SPEC = {"layer": "device matcher ops/partitioned.py", "unit": "%",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    try:
        candidates = stat(run, "routing_encode_candidates")
        scanned = stat(run, "routing_encode_scanned")
    except KeyError:  # a program from before partitions split
        return None
    return 100.0 * (1.0 - candidates / scanned) if scanned else None
