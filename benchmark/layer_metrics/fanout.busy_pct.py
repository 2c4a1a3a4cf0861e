"""Loop-thread busy share of the window in ``fanout.enqueue``: the per-subscriber
``_deliver_local`` loop of ``SessionRegistry.forwards``.
Absent where the broker has no such counters or none of the stages ran."""

from _stages import busy_pct

SPEC = {"layer": "fan-out broker/shared.py", "unit": "%",
        "source": "program_span", "moves": "deliveries_per_s"}


def read(run: dict):
    return busy_pct(run, ('fanout.enqueue',))
