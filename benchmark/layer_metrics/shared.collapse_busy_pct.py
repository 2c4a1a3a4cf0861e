"""Loop-thread busy share of the window in ``routing.collapse``: ``Router.collapse``
where a relation holds at least one ``$share`` group (the group choice: one
member of each group, by the broker's strategy), on a match-cache hit and on a
miss alike. Its time is also inside ``routing.cache_hit`` and
``routing.resolve``, whose readers keep counting it. Where the window held no
collapse that met a group the stage made no pass and its share is 0; absent
where the broker has no such stage (a program older than it)."""

from _stages import delta, window_s

SPEC = {"layer": "shared-subscription choice router/relations.py", "unit": "%",
        "source": "program_span", "moves": "deliveries_per_s"}


def read(run: dict):
    ms = delta(run, "stage_routing_collapse_busy_ms_total")
    if ms is None or delta(run, "stage_routing_collapse_count") is None:
        return None
    return 100.0 * ms / (window_s(run) * 1e3)
