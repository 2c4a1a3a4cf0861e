"""Candidate chunks a topic of the window's device encodes:
Δ``routing_encode_candidates`` over Δ``routing_encode_topics``
(``/api/v1/stats``, ``ops/partitioned.py``). Every topic encoded for the
device counts, the hybrid's probes and the batches run off the path to
compile too, so it reads the configuration's CPU-counted ``nc_mean`` within
a few percent, not exactly. Absent where the broker has no such counter (a
program from before partitions split) or encoded no topic in the window."""

from _deltas import stat

SPEC = {"layer": "device matcher ops/partitioned.py", "unit": "chunks/topic",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    try:
        candidates = stat(run, "routing_encode_candidates")
        topics = stat(run, "routing_encode_topics")
    except KeyError:  # a program from before partitions split
        return None
    return candidates / topics if topics else None
