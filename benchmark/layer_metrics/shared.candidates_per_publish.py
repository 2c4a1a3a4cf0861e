"""Candidates offered to the ``$share`` strategy a publish that met a group:
``shared.candidates`` over ``shared.publishes`` (``/api/v1/metrics``), both
counted where ``Router.collapse`` runs on a relation that holds a group. On
a table whose every publish meets G groups of M members it reads G x M: a
guard that the traffic reaches the groups. 0 where the counters are there
and no publish met a group (a table without groups); absent where the
broker has no such counters (a program older than them)."""

from _counters import metric

SPEC = {"layer": "shared-subscription choice router/relations.py", "unit": "candidates/pub",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    candidates, publishes = metric(run, "shared.candidates"), metric(run, "shared.publishes")
    if candidates is None or publishes is None:
        return None
    return candidates / publishes if publishes else 0.0
