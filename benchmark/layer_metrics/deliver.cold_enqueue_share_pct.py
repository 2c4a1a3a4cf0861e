"""Share of the window's fan-out enqueues that found their subscriber's deliver
queue empty, its deliver loop parked on it (``deliver.cold_enqueues`` over
``fanout.enqueues``): each such delivery pays a task wake-up, a QoS1 window
opened and closed for one message, and a write of one frame. Absent where the
broker has no such counters (a program from before PR 33) or enqueued
nothing."""

from _counters import metric

SPEC = {"layer": "session deliver queue broker/queue.py", "unit": "%",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    cold, all_ = metric(run, "deliver.cold_enqueues"), metric(run, "fanout.enqueues")
    return 100.0 * cold / all_ if cold is not None and all_ else None
