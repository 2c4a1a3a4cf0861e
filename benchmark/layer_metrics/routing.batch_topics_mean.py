"""Mean topics per batch the routing service handed to the router."""

from _deltas import stat

SPEC = {"layer": "routing service broker/routing.py", "unit": "topics/batch",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    batches = stat(run, "routing_dispatches")
    return stat(run, "routing_dispatched_items") / batches if batches else None
