"""Share of the window's match-cache misses whose entry the doorkeeper did
not admit (``routing_cache_door_rejects`` over ``routing_cache_misses``,
``rmqtt_tpu/router/cache.py``): a topic is stored on its second miss since
the doorkeeper was last cleared, so a high share says the misses are a tail
of topics seen once. A miss whose topic is matched in the same dispatch as
another miss of it is put once, so the share is of misses, not of puts.
Absent where the broker has no such counters or nothing missed."""

from _stages import delta

SPEC = {"layer": "routing service broker/routing.py", "unit": "%",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    rejects, misses = (delta(run, "routing_cache_door_rejects"),
                       delta(run, "routing_cache_misses"))
    if rejects is None or not misses:
        return None
    return 100.0 * rejects / misses
