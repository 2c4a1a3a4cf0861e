"""CPU share of the busiest load-generator process over the window.

A starved generator must not be read as a slow broker: near 100 the fleet,
not the broker, sets the rate.
"""

SPEC = {"layer": "load generator (benchmark)", "unit": "%",
        "source": "host_clock", "moves": "deliveries_per_s"}


def read(run: dict):
    shares = run["loadgen_cpu_busy_pct"]
    return max(shares.values()) if shares else None
