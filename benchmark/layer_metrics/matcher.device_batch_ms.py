"""Host-clock milliseconds per batch the device matcher served: encode,
dispatch, fetch and decode as the matcher's stage clock sums them. Absent
(not 0) where the device served no batch in the window."""

from _deltas import served, stat

SPEC = {"layer": "device matcher ops/partitioned.py", "unit": "ms/batch",
        "source": "program_span", "moves": "deliveries_per_s"}


def read(run: dict):
    batches = served(run["before"], run["after"], "device", 0)
    if not batches:
        return None
    ms = sum(stat(run, f"routing_stage_{s}_ms_total")
             for s in ("encode", "dispatch", "fetch", "decode"))
    return ms / batches
