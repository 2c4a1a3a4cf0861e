"""Loop-thread busy share of the window in ``routing.cache_hit``: a match-cache
hit's lookup, ``derive`` and ``collapse`` (``RoutingService.matches_for_fanout``
and ``matches_run``; in a run the section is left out of ``ingress.run``).
Where the window held no hit (a uniform stream over a large topic space) the
stage made no pass and its share is 0, which is a reading and not silence.
Absent only where the broker has no such stage (a program from before PR 36)."""

from _stages import delta, window_s

SPEC = {"layer": "routing service broker/routing.py", "unit": "%",
        "source": "program_span", "moves": "deliveries_per_s"}


def read(run: dict):
    ms = delta(run, "stage_routing_cache_hit_busy_ms_total")
    if ms is None or delta(run, "stage_routing_cache_hit_count") is None:
        return None
    return 100.0 * ms / (window_s(run) * 1e3)
