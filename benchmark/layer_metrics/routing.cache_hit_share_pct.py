"""Share of the window's match-cache lookups that hit (``routing_cache_hits``
over hits + ``routing_cache_misses`` on ``/api/v1/stats``,
``rmqtt_tpu/router/cache.py``): the publishes that resolved from the cached
relations without entering the batcher, one lookup a publish. Absent where the
broker has no such counters or looked nothing up."""

from _stages import delta

SPEC = {"layer": "routing service broker/routing.py", "unit": "%",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    hits, misses = delta(run, "routing_cache_hits"), delta(run, "routing_cache_misses")
    if hits is None or misses is None or not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
