"""Share of the window's routed topics that the device matcher served."""

from _deltas import served

SPEC = {"layer": "hybrid ops/hybrid.py router/xla.py", "unit": "%",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    dev = served(run["before"], run["after"], "device", 1)
    both = dev + served(run["before"], run["after"], "side", 1)
    return 100.0 * dev / both if both else None
