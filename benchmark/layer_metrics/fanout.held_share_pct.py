"""Share of the window's publishes whose PUBACK / PUBREC was held at all for
deliver-queue room (``fanout.held`` over ``publish.received``). 0 where the
queues never filled; absent where the broker has no such counter or received
nothing."""

from _counters import metric

SPEC = {"layer": "fan-out backpressure broker/shared.py session.py", "unit": "%",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    held, received = metric(run, "fanout.held"), metric(run, "publish.received")
    return 100.0 * held / received if held is not None and received else None
