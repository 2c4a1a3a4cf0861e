"""Share of the window's fan-out enqueues that found their subscriber's deliver
queue more than half full (``deliver.queue_over_half`` over
``fanout.enqueues``): the compare ``Session.enqueue`` makes anyway, counted on
its far side. Absent where the broker has no such counters or enqueued
nothing."""

from _counters import metric

SPEC = {"layer": "session deliver queue broker/queue.py", "unit": "%",
        "source": "program_counter", "moves": "puback_p99_ms"}


def read(run: dict):
    over, all_ = metric(run, "deliver.queue_over_half"), metric(run, "fanout.enqueues")
    return 100.0 * over / all_ if over is not None and all_ else None
