"""Publishes a run the sessions handed to the routing service
(``ingress.run_publishes`` over ``ingress.runs``): the consecutive PUBLISH
packets one connection had sent by the time its read chunk was served enter
routing together, as one run. A lone publish is a run of one, so a fleet with
one publish outstanding a connection reads 1.0. Absent where the broker has no
such counters (a program from before PR 33) or handed nothing on."""

from _counters import metric

SPEC = {"layer": "ingress codec + admission broker/session.py", "unit": "pubs/run",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    pubs, runs = metric(run, "ingress.run_publishes"), metric(run, "ingress.runs")
    return pubs / runs if pubs is not None and runs else None
